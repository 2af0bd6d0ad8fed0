"""High-level model loading: HF checkpoint directory -> ScoringEngine.

The reference loads each model with ``AutoModelForCausalLM.from_pretrained
(device_map="auto", 8-bit)`` (compare_base_vs_instruct.py:423-455) and
routes t5/t0/tk-instruct through the Seq2Seq class
(compare_instruct_models.py:471-475). Here the flow is:

  local checkpoint dir -> AutoConfig/AutoTokenizer -> state dict
  (safetensors preferred, torch .bin fallback) -> loader.convert_* ->
  jax pytree (bf16 on TPU) -> optional Mesh sharding -> ScoringEngine

Zero-egress discipline: everything is ``local_files_only`` — weights must
already be on disk; nothing here talks to a hub.
"""

from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Any, Dict, Mapping, Optional

import jax.numpy as jnp
import numpy as np

from ..config import MeshConfig, RuntimeConfig
from ..engine.runner import ScoringEngine
from ..utils.logging import get_logger
from . import loader

log = get_logger(__name__)

# Routing rule "t5|t0|tk-instruct -> Seq2Seq" (compare_instruct_models.py:471-475).
_ENCDEC_PATTERN = re.compile(r"(^|/)(t5|flan-t5|t0|tk-instruct)", re.IGNORECASE)


def is_encoder_decoder(name_or_path: str, hf_cfg=None) -> bool:
    if hf_cfg is not None and getattr(hf_cfg, "is_encoder_decoder", False):
        return True
    return bool(_ENCDEC_PATTERN.search(str(name_or_path)))


class _LazyStateDict(Mapping[str, Any]):
    """Read tensors straight from safetensors shards on demand — one tensor
    resident at a time instead of a second full copy of a 7B checkpoint."""

    def __init__(self, model_dir: Path):
        from safetensors import safe_open

        self._open = safe_open
        self._index: Dict[str, Path] = {}
        index_file = model_dir / "model.safetensors.index.json"
        if index_file.exists():
            weight_map = json.loads(index_file.read_text())["weight_map"]
            for key, shard in weight_map.items():
                self._index[key] = model_dir / shard
        else:
            single = model_dir / "model.safetensors"
            if not single.exists():
                raise FileNotFoundError(f"no safetensors found in {model_dir}")
            with safe_open(single, framework="np") as f:
                for key in f.keys():
                    self._index[key] = single

    def __getitem__(self, key: str) -> np.ndarray:
        path = self._index[key]
        with self._open(path, framework="np") as f:
            return f.get_tensor(key)

    def __iter__(self):
        return iter(self._index)

    def __len__(self) -> int:
        return len(self._index)


def load_state_dict(model_dir: Path) -> Mapping[str, Any]:
    """safetensors (lazy) preferred; torch .bin fallback (full load)."""
    model_dir = Path(model_dir)
    try:
        return _LazyStateDict(model_dir)
    except FileNotFoundError:
        pass
    import torch

    bins = sorted(model_dir.glob("pytorch_model*.bin"))
    if not bins:
        raise FileNotFoundError(
            f"no safetensors or pytorch_model*.bin in {model_dir}"
        )
    state: Dict[str, Any] = {}
    for b in bins:
        state.update(torch.load(b, map_location="cpu", weights_only=True))
    return state


def load_engine(
    model_dir: Path,
    runtime: Optional[RuntimeConfig] = None,
    mesh_cfg: Optional[MeshConfig] = None,
    dtype=None,
    cache_root: Optional[Path] = None,
    quantize_int8: bool = False,
    int8_dynamic: bool = False,
    kv_cache_int8: bool = False,
    spec_config=None,
    governor_config=None,
    cascade_config=None,
    devices=None,
) -> ScoringEngine:
    """Build a ready ScoringEngine from a local HF checkpoint directory.

    With `cache_root`, the converted pytree is cached via models.cache: the
    HF-layout conversion happens once per model ever, subsequent loads
    restore orbax buffers directly (sharded, when a mesh is given).
    ``devices`` pins the engine: the mesh is built over them, or (no
    mesh) the params are committed to the first — how each in-process
    serving replica gets a chip of its own
    (parallel.sharding.replica_devices)."""
    import jax
    import transformers

    model_dir = Path(model_dir)
    hf_cfg = transformers.AutoConfig.from_pretrained(
        model_dir, local_files_only=True, trust_remote_code=False
    )
    tokenizer = transformers.AutoTokenizer.from_pretrained(
        model_dir, local_files_only=True, trust_remote_code=False
    )
    if dtype is None:
        dtype = (jnp.bfloat16 if jax.devices()[0].platform != "cpu"
                 else jnp.float32)

    encdec = is_encoder_decoder(model_dir.name, hf_cfg)

    from . import cache as cache_mod

    if cache_root is not None and cache_mod.has_cached(cache_root, model_dir.name):
        params, cfg = cache_mod.load_params(cache_root, model_dir.name)
    else:
        state = load_state_dict(model_dir)
        if encdec:
            cfg = loader.t5_config_from_hf(hf_cfg)
            params = loader.convert_t5(state, cfg, dtype=dtype)
        else:
            cfg, family = loader.config_from_hf(hf_cfg)
            params = loader.convert_decoder(state, cfg, family, dtype=dtype)
        if cache_root is not None:
            cache_mod.save_params(cache_root, model_dir.name, params, cfg)

    if kv_cache_int8:
        if encdec:
            # ≤50-token decodes re-run the tiny decoder stack instead of
            # keeping a cache (generate.t5_greedy_decode), so there is no
            # cache to quantize — say so instead of silently ignoring the
            # flag (ADVICE r2 #4).
            log.warning(
                "%s: --kv-cache-int8 has no effect on encoder-decoder "
                "models (no KV cache in the seq2seq decode path); "
                "proceeding without it", model_dir.name)
        else:
            import dataclasses

            cfg = dataclasses.replace(cfg, kv_cache_int8=True)
    if quantize_int8:
        from . import quant

        before = quant.param_bytes(params)
        qfn = (quant.quantize_encdec_params if encdec
               else quant.quantize_decoder_params)
        params = qfn(params, dynamic=int8_dynamic)
        log.info(
            "int8-quantized %s: %.2f GB -> %.2f GB", model_dir.name,
            before / 2**30, quant.param_bytes(params) / 2**30,
        )

    seq_mesh = None
    if mesh_cfg is not None and mesh_cfg.n_devices > 1:
        from ..parallel import sharding

        if encdec and mesh_cfg.seq > 1:
            # Ring/Ulysses prefill is a decoder-path feature; refuse the
            # seq axis loudly rather than silently serving a different
            # sharding than the user asked for (ADVICE r2 #4).
            raise ValueError(
                f"--mesh with seq={mesh_cfg.seq} > 1 is not supported for "
                f"encoder-decoder checkpoints ({model_dir.name}); use a "
                f"DATAxMODEL mesh (e.g. "
                f"{mesh_cfg.data}x{mesh_cfg.model * mesh_cfg.seq})")
        mesh = sharding.build_mesh(mesh_cfg, devices)
        params = sharding.shard_params(params, cfg, mesh)
        if mesh_cfg.seq > 1:
            # Long-context: engine prefills seq-sharded (ring attention)
            # and decodes dense from the gathered cache.
            seq_mesh = mesh
        log.info(
            "sharded %s over mesh %s", model_dir.name,
            dict(zip(mesh.axis_names, mesh.devices.shape)),
        )

    elif devices is not None:
        # Committed placement: every dispatch of this engine follows its
        # params to this device instead of the process default.
        params = jax.device_put(params, devices[0])

    log.info("loaded %s (%s, %s)", model_dir.name,
             "enc-dec" if encdec else "decoder", np.dtype(dtype).name)
    return ScoringEngine(
        params, cfg, tokenizer, runtime or RuntimeConfig(),
        encoder_decoder=encdec, seq_mesh=seq_mesh,
        spec_config=spec_config, governor_config=governor_config,
        cascade_config=cascade_config,
    )


def engine_factory(
    checkpoint_root: Path,
    runtime: Optional[RuntimeConfig] = None,
    mesh_cfg: Optional[MeshConfig] = None,
    cache_root: Optional[Path] = None,
    quantize_int8: bool = False,
    int8_dynamic: bool = False,
    kv_cache_int8: bool = False,
    spec_config=None,
    governor_config=None,
    cascade_config=None,
):
    """EngineFactory for engine.multi: maps an HF repo id to
    ``checkpoint_root/<org>__<name>`` or ``checkpoint_root/<name>``."""
    checkpoint_root = Path(checkpoint_root)

    def factory(model_name: str, devices=None) -> ScoringEngine:
        candidates = [
            checkpoint_root / model_name.replace("/", "__"),
            checkpoint_root / model_name.split("/")[-1],
            checkpoint_root / model_name,
        ]
        for cand in candidates:
            if cand.is_dir():
                return load_engine(cand, runtime, mesh_cfg,
                                   cache_root=cache_root,
                                   quantize_int8=quantize_int8,
                                   int8_dynamic=int8_dynamic,
                                   kv_cache_int8=kv_cache_int8,
                                   spec_config=spec_config,
                                   governor_config=governor_config,
                                   cascade_config=cascade_config,
                                   devices=devices)
        raise FileNotFoundError(
            f"no local checkpoint for {model_name} under {checkpoint_root} "
            f"(tried {[str(c) for c in candidates]})"
        )

    return factory
