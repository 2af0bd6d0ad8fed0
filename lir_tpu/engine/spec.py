"""Speculative scoring decode — host-side drafting orchestration.

The paper's workload (PAPER.md axis 1: thousands of rephrasings of ~5
legal prompts, all ending in near-identical ``"confidence: NN"`` tails
and yes/no preambles) is uniquely speculation-friendly: the remaining
decode cost after the PR-7 kernels is the ≤10-token SEQUENTIAL scan
itself, and speculative decoding (Leviathan et al. 2023) collapses it —
draft k tokens cheaply, verify them in ONE multi-query forward
(generate._spec_tail over decoder.verify_extend), accept greedily so
every emitted token is bitwise what the sequential scan would have
produced.

This module owns the HOST half: building one dispatch's
:class:`SpecPlan` —

- **radix-tree continuation drafts** (prompt-lookup, Saxena-style, with
  the lookup table being the engine's own radix prefix tree token
  history): ``prefix_tree.continuation(bucket, ids, k)`` predicts each
  row's whole continuation from previously cached longer prompts and
  recorded completion tails — no draft model, no extra HBM;
- **compacted context buffers** for the in-scan n-gram fallback drafter
  (the dispatch's own prompt tokens + accepted emissions);
- **fleet draft-model arming** (the engine holds the small model's
  params/cfg, acquired through the PR-10 WeightCache by the fleet
  layer so drafting can never evict the verifier mid-dispatch);

plus the readout side: folding the dispatch's device-side SpecOut
counters into profiling.SpecStats without forcing a host sync on the
dispatch thread (``flush_pending``), and recording observed completions
back into the tree (``record_tails``) so repeat visits draft the whole
reply. Draft quality is strictly a SPEED knob — a corrupted draft
(faults/plan.py ``draft_corrupt``) only costs re-verification.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from ..utils.logging import get_logger

log = get_logger(__name__)


@dataclasses.dataclass
class SpecPlan:
    """One shared dispatch's drafting inputs (engine-internal). Arrays
    are host numpy; the runner lifts them to device with the dispatch.
    ``ctx_*`` are each branch's compacted full prompts right-padded to
    bucket + suffix-bucket + decode-budget; ``draft_*`` the tree-probed
    continuations padded to the decode budget. ``fleet`` is True when a
    draft model (engine._spec_draft) drafts instead of the self-lookup
    pair."""

    k: int
    ngram: int
    ctx_a: np.ndarray
    ctx_a_len: np.ndarray
    draft_a: np.ndarray
    draft_a_len: np.ndarray
    ctx_b: np.ndarray
    ctx_b_len: np.ndarray
    draft_b: np.ndarray
    draft_b_len: np.ndarray
    fleet: bool = False
    tree_rows: int = 0

    def host_arrays(self) -> dict:
        """The eight drafting arrays under the names
        compile_plan.dispatch_args reads them by."""
        names = ("ctx_a", "ctx_a_len", "draft_a", "draft_a_len",
                 "ctx_b", "ctx_b_len", "draft_b", "draft_b_len")
        return {n: getattr(self, n) for n in names}


def _ctx_arrays(ids_rows: Sequence[Sequence[int]], width: int,
                pad_id: int) -> Tuple[np.ndarray, np.ndarray]:
    B = len(ids_rows)
    ctx = np.full((B, width), pad_id, np.int32)
    lens = np.zeros((B,), np.int32)
    for r, ids in enumerate(ids_rows):
        n = min(len(ids), width)
        ctx[r, :n] = np.asarray(ids[:n], np.int32)
        lens[r] = n
    return ctx, lens


def _tree_drafts(tree, bucket: int, ids_rows: Sequence[Sequence[int]],
                 budget: int) -> Tuple[np.ndarray, np.ndarray, int]:
    B = len(ids_rows)
    toks = np.zeros((B, budget), np.int32)
    lens = np.zeros((B,), np.int32)
    hit_rows = 0
    for r, ids in enumerate(ids_rows):
        cont = tree.continuation(bucket, ids, budget)
        if cont:
            n = min(len(cont), budget)
            toks[r, :n] = np.asarray(cont[:n], np.int32)
            lens[r] = n
            hit_rows += 1
    return toks, lens, hit_rows


def build_plan(engine, bin_ids: Sequence[Sequence[int]],
               conf_ids: Sequence[Sequence[int]], bucket: int,
               ba: int, bb: int, new_tokens: int,
               conf_tokens: int) -> Optional[SpecPlan]:
    """Build one shared dispatch's SpecPlan, or None when speculation is
    off / unsupported for this engine (the runner then dispatches the
    sequential executable and counts a fallback only for spec-eligible
    engines)."""
    rt = engine.rt
    if not engine.spec_supported():
        return None
    spec_cfg = engine.spec_cfg
    k = int(rt.spec_k)
    from ..engine import tokens as tok

    pad_id = tok.pad_token_id(engine.tokenizer)
    ctx_a, len_a = _ctx_arrays(bin_ids, bucket + ba + new_tokens, pad_id)
    ctx_b, len_b = _ctx_arrays(conf_ids, bucket + bb + conf_tokens, pad_id)
    B = len(bin_ids)
    draft_a = np.zeros((B, new_tokens), np.int32)
    dlen_a = np.zeros((B,), np.int32)
    draft_b = np.zeros((B, conf_tokens), np.int32)
    dlen_b = np.zeros((B,), np.int32)
    fleet = engine._spec_draft is not None
    tree_rows = 0
    if (not fleet and spec_cfg.tree_probe
            and engine.prefix_cache is not None):
        draft_a, dlen_a, hits_a = _tree_drafts(
            engine.prefix_cache, bucket, bin_ids, new_tokens)
        draft_b, dlen_b, hits_b = _tree_drafts(
            engine.prefix_cache, bucket, conf_ids, conf_tokens)
        tree_rows = hits_a + hits_b
    plan = SpecPlan(k=k, ngram=int(spec_cfg.ngram),
                    ctx_a=ctx_a, ctx_a_len=len_a,
                    draft_a=draft_a, draft_a_len=dlen_a,
                    ctx_b=ctx_b, ctx_b_len=len_b,
                    draft_b=draft_b, draft_b_len=dlen_b,
                    fleet=fleet, tree_rows=tree_rows)
    fault = getattr(engine, "spec_fault_plan", None)
    if fault is not None:
        vocab = int(engine.cfg.vocab_size)
        fault.corrupt_draft([(plan.draft_a, plan.draft_a_len),
                             (plan.draft_b, plan.draft_b_len)], vocab)
    return plan


def record_tails(engine, bucket: int,
                 prompt_ids: Sequence[Sequence[int]],
                 gen_rows: Any, n_real: int,
                 max_tails: int = 32) -> int:
    """Record each real row's observed continuation (its raw generated
    ids) into the radix tree's token history, so a repeat visit of the
    same prompt drafts the whole reply. No-op without a tree. Returns
    rows recorded."""
    tree = engine.prefix_cache
    if tree is None or not engine.spec_supported():
        return 0
    if not engine.spec_cfg.tree_probe:
        return 0
    gen = np.asarray(gen_rows)
    done = 0
    for r in range(min(n_real, gen.shape[0], len(prompt_ids))):
        if tree.record_tail(bucket, prompt_ids[r], gen[r].tolist(),
                            max_tails=max_tails):
            done += 1
    return done


def flush_pending(engine) -> None:
    """Fold every pending device-side SpecOut pair into
    profiling.SpecStats. Deferred off the dispatch path on purpose — a
    device_get at dispatch time would serialize the host against the
    in-flight computation; callers flush at readout boundaries (the
    serve batcher after its payload device_get, the sweep at stats
    time)."""
    import jax

    pending = engine._spec_pending
    if not pending:
        return
    engine._spec_pending = []
    host = jax.device_get(pending)
    for spec_a, spec_b in host:
        for so in (spec_a, spec_b):
            engine.spec_stats.add_branch(so.drafted, so.accepted,
                                         int(so.chunks),
                                         int(so.seq_steps))
