"""Converted-parameter cache: convert HF weights once, restore fast after.

SURVEY.md §5 (checkpoint/resume): the reference re-downloads and even
deletes each model's HF cache per sweep (compare_base_vs_instruct.py:79-86);
our design converts safetensors -> JAX pytree once and caches the result
with orbax, so a 12-model sweep pays the layout conversion once per model
ever, and restores go straight to (sharded) device buffers.

Layout per entry:
  <cache_root>/<name>/params/   orbax checkpoint (the pytree)
  <cache_root>/<name>/cfg.json  the ModelConfig/T5Config + kind marker
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Optional, Tuple

import jax

from ..utils.logging import get_logger
from ..utils.manifest import atomic_write_text
from .registry import ModelConfig, T5Config

log = get_logger(__name__)

# Batch axis of every KV-cache leaf. decoder.init_cache lays the cache out
# (L, K, T, B, hd) — and int8 scale leaves (L, K, T, B) — so the batch is
# axis 3 in both flavors, which is what makes the row gather below one
# uniform tree_map.
KV_BATCH_AXIS = 3
# A model with a state-space mixer carries, after the K/V pair, the SSM
# state (L, B, Hs, P, N) and the conv tail (L, B, taps - 1, C): batch is
# axis 1 there.
REC_BATCH_AXIS = 1


def gather_rows(cache: Any, row_idx: jax.Array) -> Any:
    """Broadcast/reorder KV-cache rows: leaf[..., row_idx, ...] along the
    batch axis, for every leaf of either cache flavor (bf16 pair or int8
    payload+scale pairs).

    This is the cross-cell prefix-reuse primitive: the prefix-group decode
    prefills one cache row per *distinct* shared prefix (G rows), then
    gathers it out to one row per member prompt (M rows, ``row_idx`` maps
    member -> group) before the per-member suffix extension. The gather is
    a copy — the M-row cache is the same size the ungrouped path allocates
    anyway — but the quadratic prefill ran over G <= M rows.
    """
    import jax.numpy as jnp

    kv = jax.tree.map(
        lambda a: jnp.take(a, row_idx, axis=KV_BATCH_AXIS), tuple(cache[:2]))
    return kv + tuple(jnp.take(a, row_idx, axis=REC_BATCH_AXIS)
                      for a in cache[2:])


def kv_cache_bytes(cfg, batch: int, max_len: int, dtype_bytes: int = 2) -> int:
    """HBM bytes of one decode KV cache at (batch, max_len) — the number
    the scheduler's batch-ladder sizing and DEPLOY.md's bucket-tuning
    notes reason about. int8 caches store a 1-byte payload plus an fp32
    scale per (head, position, row) vector."""
    per_side = cfg.n_layers * cfg.n_kv_heads * max_len * batch
    if getattr(cfg, "kv_cache_int8", False):
        return 2 * (per_side * cfg.head_dim + per_side * 4)
    return 2 * per_side * cfg.head_dim * dtype_bytes

_CFG_KINDS = {"decoder": ModelConfig, "t5": T5Config}


def _cfg_to_json(cfg) -> str:
    kind = "t5" if isinstance(cfg, T5Config) else "decoder"
    return json.dumps({"kind": kind, "fields": dataclasses.asdict(cfg)},
                      indent=2)


def _cfg_from_json(text: str):
    obj = json.loads(text)
    cls = _CFG_KINDS[obj["kind"]]
    fields = obj["fields"]
    # Tuples serialize as lists; dataclass fields that expect tuples accept
    # sequences at runtime, so pass through unchanged.
    return cls(**fields)


def cache_entry_dir(cache_root: Path, name: str) -> Path:
    return Path(cache_root) / name.replace("/", "__")


def has_cached(cache_root: Path, name: str) -> bool:
    entry = cache_entry_dir(cache_root, name)
    return (entry / "cfg.json").exists() and (entry / "params").exists()


def save_params(cache_root: Path, name: str, params: Any, cfg) -> Path:
    """Write the converted pytree + config. Overwrites an existing entry."""
    import orbax.checkpoint as ocp

    entry = cache_entry_dir(cache_root, name)
    entry.mkdir(parents=True, exist_ok=True)
    ckpt_dir = entry / "params"
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(ckpt_dir.resolve(), params, force=True)
    atomic_write_text(entry / "cfg.json", _cfg_to_json(cfg))
    log.info("cached converted params for %s at %s", name, entry)
    return entry


def load_params(
    cache_root: Path, name: str, shardings: Optional[Any] = None
) -> Tuple[Any, Any]:
    """Restore (params, cfg). With `shardings` (a pytree of NamedSharding
    matching the params tree), buffers restore directly into their sharded
    placement — no host-memory detour."""
    import orbax.checkpoint as ocp

    entry = cache_entry_dir(cache_root, name)
    cfg = _cfg_from_json((entry / "cfg.json").read_text())
    with ocp.StandardCheckpointer() as ckptr:
        if shardings is None:
            params = ckptr.restore((entry / "params").resolve())
        else:
            # Restore straight into the sharded placement: abstract targets
            # built from saved metadata + the caller's NamedShardings.
            metadata = ckptr.metadata((entry / "params").resolve())
            abstract = jax.tree.map(
                lambda meta, sh: jax.ShapeDtypeStruct(
                    meta.shape, meta.dtype, sharding=sh),
                metadata, shardings,
            )
            params = ckptr.restore((entry / "params").resolve(), abstract)
    log.info("restored cached params for %s", name)
    return params, cfg
