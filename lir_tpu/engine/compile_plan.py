"""Compile plan: parallel AOT precompilation of the sweep's executables.

The ragged scheduler plans every dispatch shape up front, so nothing about
compilation needs to be lazy: this module turns a dispatch plan into the
exact set of (bucket, batch, suffix, variant) executables the sweep will
call, lowers and compiles them CONCURRENTLY in background threads (XLA
compilation releases the GIL) while the first bucket streams, and hands
the engine an :class:`ExecutableRegistry` the dispatch path consults
instead of triggering trace-on-first-call inside the timed loop.

Three layers cooperate:

1. **Persistent cache** (utils/compile_cache.py): every AOT compile goes
   through JAX's disk cache, so a restarted worker deserializes instead
   of recompiling — and because the lazy jit path hashes to the SAME HLO,
   precompiled-vs-lazy results are not merely numerically equal but the
   same executable.
2. **This registry**: keyed by (engine manifest key, shape spec). The
   manifest key covers model config, quant mode, mesh, and bucket ladder
   (utils/compile_cache.manifest_key), so an executable compiled for one
   configuration can never be looked up by another.
3. **Observability** (utils/profiling.CompileStats): per-shape compile
   seconds, registry hit / lazy-miss counts, how many planned programs
   were ever dispatched, the wall seconds spent loading, persistent-cache
   hit/miss deltas — logged per sweep and surfaced in bench.py's
   headline. Trace spans (observe/tracing): ``engine/compile_load`` per
   executable on the pool threads, ``engine/compile_wait`` where a
   dispatch blocks on one (``engine/compile_lazy``, a compile outside
   the plan, comes from utils/compile_cache's listener).
4. **Scope tables** (:func:`scope_table`, ``ExecutableRegistry.
   scope_tables``): for each executable handed out, which
   ``lir.<phase>`` scope each optimized HLO instruction was built
   under — the route from a device operation in a profiler trace
   (keyed by instruction name) back to prefill / extend / decode /
   readout. Built only when asked.

The registry is an OPTIMIZATION: every lookup miss (unplanned shape, the
runner's shared-prefix fallback path, a failed compile) falls through to
the ordinary jitted call, which is always correct.
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..observe import tracing
from ..utils.logging import get_logger
from ..utils.profiling import CompileStats

log = get_logger(__name__)

TOPK = 20  # the D6 top-20 logprob map — fixed across every sweep caller


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """Everything that selects one compiled executable, shape-wise.

    ``kind`` is "shared" (decode_fused_shared), "grouped"
    (decode_fused_grouped), or their prefix-cache-resume variants
    "shared_paged"/"grouped_paged" (generate.*_paged — the block-table
    executables, selected additionally by ``window``, the remainder-
    window edge each row recomputes while the rest of its prefix
    gathers from the page pool). ``batch`` is the PADDED member-row
    count the runner will dispatch (shared: the padded batch; grouped:
    m_pad); ``groups`` the padded prefill-row count (grouped only, else
    0). ``sfx_a``/``sfx_b`` are the right-pad suffix bucket edges
    (grouped uses a single merged edge in ``sfx_a``). ``stops_armed``
    records whether the stop-mask arguments are arrays or None — that
    changes the traced pytree, hence the executable. ``scratch``
    selects the donated-KV-cache variant (every dispatch after the
    first of a bucket queue donates the previous cache —
    runner._CacheHandoff; paged and unpaged variants of one shape
    return the same cache aval, so the chain crosses them freely).
    ``spec_k`` > 0 selects the SPECULATIVE-decode executable for that
    verify-window size (generate.greedy_decode_fused_shared_spec /
    _paged_spec — the verify executables are planned per (bucket,
    batch, k)); ``spec_draft`` its fleet-draft-model variant (the
    draft model's params ride the traced pytree). ``trunk`` > 0 selects
    a CASCADE-prefill executable (kinds "shared_cascade"/
    "shared_cascade_paged" — generate.greedy_decode_fused_shared_cascade
    and its paged-trunk sibling) at that static shared-trunk extent, and
    ``cascade_int8`` its in-kernel int8-QK^T variant; both change the
    lowered program, so keying them here is what guarantees an
    executable can never serve the wrong mode (a dense lookup can't
    return a cascade program or vice versa). For the paged cascade kind,
    ``window`` is the TRUNK's recompute-window edge (the (1, W) chunk
    the radix resume teacher-forces), not a per-row window.
    ``decode_trunk`` > 0 selects the CASCADE-DECODE variant of the
    plain "shared"/"shared_paged" kinds (and their spec siblings): the
    decode scans' trunk splits run trunk-aware
    (ops/flash_decode.flash_decode_trunk — bitwise the flat kernels)
    at that static trunk extent. The cascade kinds don't carry it:
    their decode trunk IS ``trunk`` (generate._cascade_branches), so
    ``trunk`` already keys the lowering."""

    kind: str
    bucket: int
    batch: int
    groups: int
    sfx_a: int
    sfx_b: int
    new_tokens: int
    conf_tokens: int
    stops_armed: bool
    scratch: bool
    window: int = 0
    spec_k: int = 0
    spec_draft: bool = False
    trunk: int = 0
    cascade_int8: bool = False
    decode_trunk: int = 0

    @property
    def label(self) -> str:
        sfx = (f"{self.sfx_a}+{self.sfx_b}"
               if self.kind.startswith(("shared", "piggy"))
               else str(self.sfx_a))
        var = "donated" if self.scratch else "fresh"
        win = f"/win{self.window}" if self.window else ""
        spec = ""
        if self.spec_k:
            spec = f"/spec{self.spec_k}" + ("+draft" if self.spec_draft
                                            else "")
        casc = ""
        if self.trunk:
            casc = f"/trunk{self.trunk}" + ("+i8" if self.cascade_int8
                                            else "")
        if self.decode_trunk:
            casc += f"/dtrunk{self.decode_trunk}"
        return (f"{self.kind}/b{self.bucket}x{self.batch}/sfx{sfx}"
                f"/new{self.new_tokens}-{self.conf_tokens}{win}{spec}"
                f"{casc}/{var}")


def shared_spec(bucket: int, batch: int, sfx_a: int, sfx_b: int,
                new_tokens: int, conf_tokens: int, stops_armed: bool,
                scratch: bool, spec_k: int = 0,
                spec_draft: bool = False,
                decode_trunk: int = 0) -> ShapeSpec:
    return ShapeSpec("shared", int(bucket), int(batch), 0, int(sfx_a),
                     int(sfx_b), int(new_tokens), int(conf_tokens),
                     bool(stops_armed), bool(scratch),
                     spec_k=int(spec_k), spec_draft=bool(spec_draft),
                     decode_trunk=int(decode_trunk))


def grouped_spec(bucket: int, groups: int, batch: int, sfx: int,
                 max_new: int, stops_armed: bool,
                 scratch: bool) -> ShapeSpec:
    return ShapeSpec("grouped", int(bucket), int(batch), int(groups),
                     int(sfx), 0, int(max_new), 0, bool(stops_armed),
                     bool(scratch))


def shared_paged_spec(bucket: int, batch: int, window: int, sfx_a: int,
                      sfx_b: int, new_tokens: int, conf_tokens: int,
                      stops_armed: bool, scratch: bool,
                      spec_k: int = 0,
                      decode_trunk: int = 0) -> ShapeSpec:
    return ShapeSpec("shared_paged", int(bucket), int(batch), 0,
                     int(sfx_a), int(sfx_b), int(new_tokens),
                     int(conf_tokens), bool(stops_armed), bool(scratch),
                     int(window), spec_k=int(spec_k),
                     decode_trunk=int(decode_trunk))


def shared_cascade_spec(bucket: int, batch: int, trunk: int, sfx_a: int,
                        sfx_b: int, new_tokens: int, conf_tokens: int,
                        stops_armed: bool, scratch: bool,
                        int8_qk: bool = False) -> ShapeSpec:
    """Cold cascade-prefill executable (generate.greedy_decode_fused_
    shared_cascade): batch-1 trunk prefill at the static ``trunk``
    extent + per-row cascade remainder extension."""
    return ShapeSpec("shared_cascade", int(bucket), int(batch), 0,
                     int(sfx_a), int(sfx_b), int(new_tokens),
                     int(conf_tokens), bool(stops_armed), bool(scratch),
                     trunk=int(trunk), cascade_int8=bool(int8_qk))


def shared_cascade_paged_spec(bucket: int, batch: int, trunk: int,
                              window: int, sfx_a: int, sfx_b: int,
                              new_tokens: int, conf_tokens: int,
                              stops_armed: bool, scratch: bool,
                              int8_qk: bool = False) -> ShapeSpec:
    """Warm cascade executable (generate.greedy_decode_fused_shared_
    cascade_paged): the trunk resumes from the radix page pool through a
    (1, ``window``) recompute chunk instead of prefilling."""
    return ShapeSpec("shared_cascade_paged", int(bucket), int(batch), 0,
                     int(sfx_a), int(sfx_b), int(new_tokens),
                     int(conf_tokens), bool(stops_armed), bool(scratch),
                     int(window), trunk=int(trunk),
                     cascade_int8=bool(int8_qk))


def grouped_paged_spec(bucket: int, groups: int, batch: int, window: int,
                       sfx: int, max_new: int, stops_armed: bool,
                       scratch: bool) -> ShapeSpec:
    return ShapeSpec("grouped_paged", int(bucket), int(batch), int(groups),
                     int(sfx), 0, int(max_new), 0, bool(stops_armed),
                     bool(scratch), int(window))


def stream_fold_spec(n_prompts: int, n_rephrase: int, batch: int,
                     guard: bool) -> ShapeSpec:
    """Streaming-statistics accumulator update (engine/stream_stats.
    fold_update) for one fold width: ``bucket`` carries the prompt
    count, ``groups`` the rephrase-slot count, ``batch`` the dispatch's
    fold width (shared: padded member rows; grouped: one branch's row
    count), and ``stops_armed`` the numerics-guard bit — the guard is a
    STATIC of the fold program (it changes the lowered predicate), so
    guarded and unguarded sinks can never share an executable."""
    return ShapeSpec("stream_fold", int(n_prompts), int(batch),
                     int(n_rephrase), 0, 0, 0, 0, bool(guard), False)


def piggy_prefill_spec(bucket: int, batch: int, sfx_a: int, sfx_b: int,
                       new_tokens: int, conf_tokens: int) -> ShapeSpec:
    """Chain opener (generate.shared_piggyback_prefill): prefill + suffix
    extensions into the disjoint-region carry, decode scans parked. Stop
    tables don't appear until the scans run, so stops_armed is always
    False here."""
    return ShapeSpec("piggy_prefill", int(bucket), int(batch), 0,
                     int(sfx_a), int(sfx_b), int(new_tokens),
                     int(conf_tokens), False, False)


def piggy_step_spec(bucket: int, batch: int, sfx_a: int, sfx_b: int,
                    new_tokens: int, conf_tokens: int,
                    stops_armed: bool) -> ShapeSpec:
    """One piggybacked call: parked decode scans + the next dispatch's
    prefill in one program (generate.shared_piggyback_step)."""
    return ShapeSpec("piggy_step", int(bucket), int(batch), 0, int(sfx_a),
                     int(sfx_b), int(new_tokens), int(conf_tokens),
                     bool(stops_armed), False)


def piggy_drain_spec(bucket: int, batch: int, sfx_a: int, sfx_b: int,
                     new_tokens: int, conf_tokens: int,
                     stops_armed: bool) -> ShapeSpec:
    """Chain closer: the last parked dispatch's decode scans alone
    (generate.shared_piggyback_drain)."""
    return ShapeSpec("piggy_drain", int(bucket), int(batch), 0, int(sfx_a),
                     int(sfx_b), int(new_tokens), int(conf_tokens),
                     bool(stops_armed), False)


def plan_specs(dispatches: Sequence[Any], batch_size: int, new_tokens: int,
               conf_tokens: int, stops_armed: bool,
               prefix_page_size: int = 0,
               piggyback: bool = False,
               stream_shape: Optional[Tuple[int, int, bool]] = None,
               spec_k: int = 0, spec_draft: bool = False,
               cascade_trunk=None, cascade_int8: bool = False,
               decode_trunk=None,
               ) -> List[ShapeSpec]:
    """Distinct executables a dispatch plan will call, in first-use order
    (the precompile pool works the list front-to-back, so the first
    bucket's executable compiles first and the dispatch loop rarely
    waits). Mirrors the runner's padding/handoff behavior exactly:
    the first dispatch of each handoff key runs the scratchless variant,
    every consecutive same-key dispatch the donated one. A spec's
    ``bucket`` is the prefix extent the dispatch RUNS at — the plan's
    tight ``Dispatch.edge`` — since that is what the runner is handed.

    ``prefix_page_size`` > 0 (an engine whose cross-request prefix cache
    is enabled) additionally plans the block-table executables: for each
    dispatch shape, one paged variant per remainder-window edge the
    runner may pick (models/paged.window_edges) — which window a warm
    dispatch runs depends on what the radix tree holds at dispatch
    time, so the plan covers them all.

    ``piggyback`` (an engine whose chunked prefill/decode piggybacking is
    on) plans the chain executables for every run of CONSECUTIVE
    same-shape shared dispatches — the exact chains the sweep forms:
    opener (prefill-only), step (parked decode + next prefill), and
    drain. Plain specs stay planned regardless (the runtime memory gate
    may refuse a chain, and the recovery path re-dispatches plainly).

    ``stream_shape`` = (n_prompts, n_rephrase, numerics_guard) plans the
    streaming-statistics accumulator-update executable for every
    distinct fold width the plan's dispatches will use (shared: the
    padded member-row count; grouped: one branch's row count), so the
    sink's per-dispatch fold never pays trace-on-first-call inside the
    timed loop either. Planned FIRST — the very first dispatch folds.

    ``cascade_trunk`` (a cascade-prefill engine) maps a shared dispatch
    to its snapped shared-trunk extent (0 = ineligible — the runner's
    own eligibility rule, so the plan covers exactly the cascade
    executables the loop will call); eligible dispatches plan the
    cascade executable (plus its paged-trunk variants when the prefix
    cache is on — the trunk's recompute window depends on what the
    radix tree holds at dispatch time, so every trunk window edge is
    covered). The plain shared spec stays planned regardless: a dense
    fallback re-dispatches through it.

    ``decode_trunk`` (a cascade-DECODE engine) maps a shared dispatch to
    the static trunk extent its decode scans dedup at (0 = flat
    kernels); eligible dispatches plan the trunk-aware variant of every
    plain shared/paged/spec executable ALONGSIDE the flat one — which
    variant the runner calls depends on the same per-dispatch rule, and
    the flat specs cover the --no-cascade-decode engine and the dense
    fallback."""
    from ..models import paged as paged_mod

    specs: List[ShapeSpec] = []
    seen = set()
    prev_key: Optional[Tuple] = None

    def add(spec: ShapeSpec) -> None:
        if spec not in seen:
            seen.add(spec)
            specs.append(spec)

    if stream_shape is not None:
        n_prompts, n_rephrase, guard = stream_shape
        for d in dispatches:
            _, m_pad = d.padded_rows(batch_size)
            width = m_pad if d.kind == "shared" else len(d.items)
            add(stream_fold_spec(n_prompts, n_rephrase, width, guard))
    for d in dispatches:
        g_pad, m_pad = d.padded_rows(batch_size)
        if d.kind == "shared":
            key = ("shared", d.edge, m_pad, d.sfx_bucket_a,
                   d.sfx_bucket_b, new_tokens, conf_tokens)
            scratch = key == prev_key
            trunk = int(cascade_trunk(d)) if cascade_trunk else 0
            # Cascade-decode extent for the PLAIN kinds: a cascade-
            # prefill-eligible dispatch never reaches them (the cascade
            # path takes precedence), so its dtrunk variants would be
            # dead compiles.
            dt = (int(decode_trunk(d))
                  if (decode_trunk is not None and not trunk) else 0)
            add(shared_spec(d.edge, m_pad, d.sfx_bucket_a,
                            d.sfx_bucket_b, new_tokens, conf_tokens,
                            stops_armed, scratch=scratch,
                            decode_trunk=dt))
            if spec_k:
                # Speculative verify executables, planned per
                # (bucket, batch, k) alongside the sequential shape
                # (the runner falls back to it on a spec-ineligible
                # dispatch).
                add(shared_spec(d.edge, m_pad, d.sfx_bucket_a,
                                d.sfx_bucket_b, new_tokens, conf_tokens,
                                stops_armed, scratch=scratch,
                                spec_k=spec_k, spec_draft=spec_draft,
                                decode_trunk=dt))
            if trunk:
                add(shared_cascade_spec(d.edge, m_pad, trunk,
                                        d.sfx_bucket_a, d.sfx_bucket_b,
                                        new_tokens, conf_tokens,
                                        stops_armed, scratch=scratch,
                                        int8_qk=cascade_int8))
                if prefix_page_size:
                    for w in paged_mod.window_edges(trunk,
                                                    prefix_page_size):
                        add(shared_cascade_paged_spec(
                            d.edge, m_pad, trunk, w, d.sfx_bucket_a,
                            d.sfx_bucket_b, new_tokens, conf_tokens,
                            stops_armed, scratch=scratch,
                            int8_qk=cascade_int8))
            if piggyback and scratch and not trunk:
                # A repeat of the previous shared shape — the sweep will
                # chain these dispatches: plan all three chain stages.
                add(piggy_prefill_spec(d.edge, m_pad, d.sfx_bucket_a,
                                       d.sfx_bucket_b, new_tokens,
                                       conf_tokens))
                add(piggy_step_spec(d.edge, m_pad, d.sfx_bucket_a,
                                    d.sfx_bucket_b, new_tokens,
                                    conf_tokens, stops_armed))
                add(piggy_drain_spec(d.edge, m_pad, d.sfx_bucket_a,
                                     d.sfx_bucket_b, new_tokens,
                                     conf_tokens, stops_armed))
            if prefix_page_size:
                for w in paged_mod.window_edges(d.edge, prefix_page_size):
                    add(shared_paged_spec(
                        d.edge, m_pad, w, d.sfx_bucket_a, d.sfx_bucket_b,
                        new_tokens, conf_tokens, stops_armed,
                        scratch=scratch, decode_trunk=dt))
                    if spec_k and not spec_draft:
                        # Paged + speculative composes for self-drafting
                        # only (the paged front binds slot tables, not
                        # prefix tokens — nothing for a draft model to
                        # prefill from).
                        add(shared_paged_spec(
                            d.edge, m_pad, w, d.sfx_bucket_a,
                            d.sfx_bucket_b, new_tokens, conf_tokens,
                            stops_armed, scratch=scratch, spec_k=spec_k,
                            decode_trunk=dt))
        else:
            sfx = max(d.sfx_bucket_a, d.sfx_bucket_b)
            max_new = max(new_tokens, conf_tokens)
            key = ("grouped", d.edge, g_pad, m_pad, sfx, max_new)
            scratch = key == prev_key
            add(grouped_spec(d.edge, g_pad, m_pad, sfx, max_new,
                             stops_armed, scratch=scratch))
            if prefix_page_size:
                for w in paged_mod.window_edges(d.edge, prefix_page_size):
                    add(grouped_paged_spec(
                        d.edge, g_pad, m_pad, w, sfx, max_new,
                        stops_armed, scratch=scratch))
        prev_key = key
    return specs


# ---------------------------------------------------------------------------
# Lowering: exact aval reconstruction of the runner's call sites
# ---------------------------------------------------------------------------

def _spec_avals(engine, spec: ShapeSpec):
    """The eight drafting-array avals (SpecPlan.dyn_args order) appended
    to a speculative executable's argument list."""
    import jax
    import jax.numpy as jnp

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    B = spec.batch
    return (i32(B, spec.bucket + spec.sfx_a + spec.new_tokens), i32(B),
            i32(B, spec.new_tokens), i32(B),
            i32(B, spec.bucket + spec.sfx_b + spec.conf_tokens), i32(B),
            i32(B, spec.conf_tokens), i32(B))


def _spec_statics(engine, spec: ShapeSpec) -> dict:
    out = dict(spec_k=spec.spec_k, ngram=int(engine.spec_cfg.ngram))
    return out


def _spec_draft_kwargs(engine, spec: ShapeSpec):
    """(dynamic kwargs, statics) arming the fleet draft model in a
    speculative executable's signature."""
    if not spec.spec_draft:
        return {"draft_params": None}, {"draft_cfg": None}
    draft_params, draft_cfg, _ = engine._spec_draft
    import jax

    avals = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype),
        draft_params)
    return {"draft_params": avals}, {"draft_cfg": draft_cfg}


def _avals_shared(engine, spec: ShapeSpec):
    """(args, kwargs) ShapeDtypeStructs matching runner.decode_fused_shared's
    call into generate.greedy_decode_fused_shared (or its speculative
    sibling when ``spec.spec_k``) — one canonical layout shared with
    :func:`_registry_call` so lowering and dispatch can never drift
    apart."""
    import jax
    import jax.numpy as jnp

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    B = spec.batch
    digit_ids, digit_vals = engine.digit_table
    args = (engine.params, i32(B, spec.bucket), i32(B, spec.bucket),
            i32(B, spec.sfx_a), i32(B, spec.sfx_a),
            i32(B, spec.sfx_b), i32(B, spec.sfx_b),
            i32(B), i32(B), i32(len(digit_ids)), f32(len(digit_vals)))
    V = engine.cfg.vocab_size
    kwargs = dict(
        stop_mask_a=(i32(V) if spec.stops_armed else None),
        stop_mask_b=(i32(V) if spec.stops_armed else None),
        eos_id=(i32() if spec.stops_armed else None),
    )
    statics = dict(max_new_a=spec.new_tokens, max_new_b=spec.conf_tokens,
                   topk=TOPK, prefill_fn=engine._prefill_fn,
                   return_cache=True, decode_trunk=spec.decode_trunk)
    if spec.spec_k:
        args = args + _spec_avals(engine, spec)
        dk, ds = _spec_draft_kwargs(engine, spec)
        kwargs.update(dk)
        statics.update(_spec_statics(engine, spec), **ds)
    return args, kwargs, statics


def _avals_grouped(engine, spec: ShapeSpec):
    import jax
    import jax.numpy as jnp

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    G, M = spec.groups, spec.batch
    digit_ids, digit_vals = engine.digit_table
    args = (engine.params, i32(G, spec.bucket), i32(G, spec.bucket),
            i32(M, spec.sfx_a), i32(M, spec.sfx_a), i32(M),
            i32(M), i32(M), i32(len(digit_ids)), f32(len(digit_vals)))
    V = engine.cfg.vocab_size
    armed = spec.stops_armed
    kwargs = dict(
        stop_mask=(i32(V) if armed else None),
        stop_mask2=(i32(V) if armed else None),
        stop_sel=(jax.ShapeDtypeStruct((M,), jnp.bool_) if armed else None),
        eos_id=(i32() if armed else None),
    )
    statics = dict(max_new=spec.new_tokens, topk=TOPK,
                   prefill_fn=engine._prefill_fn, return_cache=True)
    return args, kwargs, statics


def _pool_avals(engine):
    """ShapeDtypeStruct tree of the engine's page-pool leaves (the paged
    executables bind the pool as an ordinary pytree argument)."""
    import jax

    pool = engine.prefix_cache.pool
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype),
        pool.leaves)


def _avals_shared_paged(engine, spec: ShapeSpec):
    """Avals for runner.decode_fused_shared's PAGED call into
    generate.greedy_decode_fused_shared_paged (prefix-cache resume):
    (params, pool, slot_src, win_start, prefix_mask, rem, rem_mask,
    sfx..x4, yes, no, digit_ids, digit_vals)."""
    import jax
    import jax.numpy as jnp

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    B, W = spec.batch, spec.window
    digit_ids, digit_vals = engine.digit_table
    args = (engine.params, _pool_avals(engine),
            i32(B, spec.bucket), i32(), i32(B, spec.bucket),
            i32(B, W), i32(B, W),
            i32(B, spec.sfx_a), i32(B, spec.sfx_a),
            i32(B, spec.sfx_b), i32(B, spec.sfx_b),
            i32(B), i32(B), i32(len(digit_ids)), f32(len(digit_vals)))
    V = engine.cfg.vocab_size
    kwargs = dict(
        stop_mask_a=(i32(V) if spec.stops_armed else None),
        stop_mask_b=(i32(V) if spec.stops_armed else None),
        eos_id=(i32() if spec.stops_armed else None),
    )
    statics = dict(max_new_a=spec.new_tokens, max_new_b=spec.conf_tokens,
                   topk=TOPK, return_cache=True,
                   decode_trunk=spec.decode_trunk)
    if spec.spec_k:
        args = args + _spec_avals(engine, spec)
        statics.update(_spec_statics(engine, spec))
    return args, kwargs, statics


def _avals_shared_cascade(engine, spec: ShapeSpec):
    """Avals for runner.decode_fused_shared's cascade call into
    generate.greedy_decode_fused_shared_cascade: the dense shared
    layout with the trunk extent baked static (``spec.trunk``)."""
    import jax
    import jax.numpy as jnp

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    B = spec.batch
    digit_ids, digit_vals = engine.digit_table
    args = (engine.params, i32(B, spec.bucket), i32(B, spec.bucket),
            i32(B, spec.sfx_a), i32(B, spec.sfx_a),
            i32(B, spec.sfx_b), i32(B, spec.sfx_b),
            i32(B), i32(B), i32(len(digit_ids)), f32(len(digit_vals)))
    V = engine.cfg.vocab_size
    kwargs = dict(
        stop_mask_a=(i32(V) if spec.stops_armed else None),
        stop_mask_b=(i32(V) if spec.stops_armed else None),
        eos_id=(i32() if spec.stops_armed else None),
    )
    statics = dict(max_new_a=spec.new_tokens, max_new_b=spec.conf_tokens,
                   trunk_len=spec.trunk, topk=TOPK,
                   int8_qk=spec.cascade_int8, return_cache=True)
    return args, kwargs, statics


def _avals_shared_cascade_paged(engine, spec: ShapeSpec):
    """Avals for the warm-trunk cascade call into
    generate.greedy_decode_fused_shared_cascade_paged: a batch-1 paged
    front (slot table + recompute window over the TRUNK extent) ahead
    of the dense shared layout."""
    import jax
    import jax.numpy as jnp

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    B, W, Tt = spec.batch, spec.window, spec.trunk
    digit_ids, digit_vals = engine.digit_table
    args = (engine.params, _pool_avals(engine),
            i32(1, Tt), i32(), i32(1, Tt),
            i32(1, W), i32(1, W),
            i32(B, spec.bucket), i32(B, spec.bucket),
            i32(B, spec.sfx_a), i32(B, spec.sfx_a),
            i32(B, spec.sfx_b), i32(B, spec.sfx_b),
            i32(B), i32(B), i32(len(digit_ids)), f32(len(digit_vals)))
    V = engine.cfg.vocab_size
    kwargs = dict(
        stop_mask_a=(i32(V) if spec.stops_armed else None),
        stop_mask_b=(i32(V) if spec.stops_armed else None),
        eos_id=(i32() if spec.stops_armed else None),
    )
    statics = dict(max_new_a=spec.new_tokens, max_new_b=spec.conf_tokens,
                   trunk_len=spec.trunk, topk=TOPK,
                   int8_qk=spec.cascade_int8, return_cache=True)
    return args, kwargs, statics


def _avals_grouped_paged(engine, spec: ShapeSpec):
    import jax
    import jax.numpy as jnp

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    G, M, W = spec.groups, spec.batch, spec.window
    digit_ids, digit_vals = engine.digit_table
    args = (engine.params, _pool_avals(engine),
            i32(G, spec.bucket), i32(), i32(G, spec.bucket),
            i32(G, W), i32(G, W),
            i32(M, spec.sfx_a), i32(M, spec.sfx_a), i32(M),
            i32(M), i32(M), i32(len(digit_ids)), f32(len(digit_vals)))
    V = engine.cfg.vocab_size
    armed = spec.stops_armed
    kwargs = dict(
        stop_mask=(i32(V) if armed else None),
        stop_mask2=(i32(V) if armed else None),
        stop_sel=(jax.ShapeDtypeStruct((M,), jnp.bool_) if armed else None),
        eos_id=(i32() if armed else None),
    )
    statics = dict(max_new=spec.new_tokens, topk=TOPK, return_cache=True)
    return args, kwargs, statics


def _avals_piggy(engine, spec: ShapeSpec):
    """Avals for the three piggyback-chain entry points. The step and
    drain bind the CARRY aval — recovered from the opener via eval_shape
    (tracing only, no device work)."""
    import jax
    import jax.numpy as jnp

    from . import generate

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    B = spec.batch
    dispatch_args = (i32(B, spec.bucket), i32(B, spec.bucket),
                     i32(B, spec.sfx_a), i32(B, spec.sfx_a),
                     i32(B, spec.sfx_b), i32(B, spec.sfx_b))
    budgets = dict(max_new_a=spec.new_tokens, max_new_b=spec.conf_tokens)
    if spec.kind == "piggy_prefill":
        return dispatch_args, {}, dict(**budgets, prefill_fn=None)
    carry = generate.shared_piggyback_prefill.eval_shape(
        engine.params, engine.cfg, *dispatch_args, **budgets,
        prefill_fn=None)
    digit_ids, digit_vals = engine.digit_table
    readout = (i32(B), i32(B), i32(len(digit_ids)), f32(len(digit_vals)))
    V = engine.cfg.vocab_size
    kwargs = dict(
        stop_mask_a=(i32(V) if spec.stops_armed else None),
        stop_mask_b=(i32(V) if spec.stops_armed else None),
        eos_id=(i32() if spec.stops_armed else None),
    )
    if spec.kind == "piggy_step":
        return ((carry,) + dispatch_args + readout, kwargs,
                dict(**budgets, topk=TOPK, prefill_fn=None))
    # piggy_drain: carry + readout args, slot offsets derived from the
    # spec exactly as the runner derives them.
    statics = dict(slot0_a=spec.bucket + spec.sfx_a,
                   slot0_b=(spec.bucket + spec.sfx_a + spec.new_tokens
                            + spec.sfx_b),
                   **budgets, topk=TOPK)
    return (carry,) + readout, kwargs, statics


def _lower_compile(engine, spec: ShapeSpec):
    """Lower + compile one spec; returns the jax Compiled executable."""
    return _lower(engine, spec).compile()


def _lower(engine, spec: ShapeSpec):
    """Lower one spec; returns the jax Lowered program.

    The donated variant needs the KV-cache aval, which is exactly the
    scratchless variant's returned cache — recovered via eval_shape
    (tracing only, no device work)."""
    from . import generate

    if spec.kind == "stream_fold":
        from . import stream_stats

        return stream_stats.lower_fold(
            spec.bucket, spec.groups, spec.batch, TOPK,
            spec.stops_armed)
    if spec.kind.startswith("piggy"):
        fn = {"piggy_prefill": generate.shared_piggyback_prefill,
              "piggy_step": generate.shared_piggyback_step,
              "piggy_drain": generate.shared_piggyback_drain}[spec.kind]
        args, kwargs, statics = _avals_piggy(engine, spec)
        return fn.lower(engine.params, engine.cfg, *args, **kwargs,
                        **statics)
    if spec.kind == "shared":
        fn = (generate.greedy_decode_fused_shared_spec if spec.spec_k
              else generate.greedy_decode_fused_shared)
        args, kwargs, statics = _avals_shared(engine, spec)
    elif spec.kind == "shared_cascade":
        fn = generate.greedy_decode_fused_shared_cascade
        args, kwargs, statics = _avals_shared_cascade(engine, spec)
    elif spec.kind == "shared_cascade_paged":
        fn = generate.greedy_decode_fused_shared_cascade_paged
        args, kwargs, statics = _avals_shared_cascade_paged(engine, spec)
    elif spec.kind == "shared_paged":
        fn = (generate.greedy_decode_fused_shared_paged_spec
              if spec.spec_k else generate.greedy_decode_fused_shared_paged)
        args, kwargs, statics = _avals_shared_paged(engine, spec)
    elif spec.kind == "grouped_paged":
        fn = generate.greedy_decode_fused_grouped_paged
        args, kwargs, statics = _avals_grouped_paged(engine, spec)
    else:
        fn = generate.greedy_decode_fused_grouped
        args, kwargs, statics = _avals_grouped(engine, spec)
    scratch = None
    if spec.scratch:
        out_shape = fn.eval_shape(args[0], engine.cfg, *args[1:],
                                  scratch_cache=None, **kwargs, **statics)
        scratch = out_shape[-1]  # the returned final cache's aval tree
    return fn.lower(args[0], engine.cfg, *args[1:],
                    scratch_cache=scratch, **kwargs, **statics)


# Process-wide executable cache: the AOT analogue of jit's in-memory
# executable cache. `.lower().compile()` bypasses the pjit cache, so
# without this every sweep (bench warmup -> timed, back-to-back grids on
# one engine, repeated tests) would re-pay its AOT compiles; with it, a
# (manifest key, spec) pair compiles at most once per process. Safe by
# keying: the manifest key covers model config, runtime knobs, quant
# mode, mesh, ladder AND a params-aval fingerprint (runner), and the
# compiled program binds only shapes/dtypes — params values are runtime
# arguments, so engines sharing a key may share executables.
_EXEC_CACHE: Dict[Tuple[str, ShapeSpec], Any] = {}
_EXEC_CACHE_LOCK = threading.Lock()


def exec_cache_clear() -> None:
    """Drop the process-wide executable cache (tests; pairs with
    jax.clear_caches() when simulating a cold restart in-process)."""
    with _EXEC_CACHE_LOCK:
        _EXEC_CACHE.clear()


class ExecutableRegistry:
    """Futures of compiled executables, keyed by ShapeSpec under one
    engine manifest key.

    ``get`` blocks only when the wanted shape is still compiling (the
    pool works specs in dispatch order, so in the steady state the
    executable is ready before its first dispatch); a missing or failed
    spec returns None and the caller falls back to the lazily-jitted
    path. Thread-safe: the sweep's dispatch thread reads while pool
    threads write results."""

    def __init__(self, manifest_key: str,
                 stats: Optional[CompileStats] = None,
                 compile_timeout_s: Optional[float] = None,
                 guard_stats=None):
        self.manifest_key = manifest_key
        self.stats = stats if stats is not None else CompileStats()
        # Watchdog bound on how long a dispatch may wait for a still-
        # compiling executable (guard layer): a wedged compile thread
        # then costs one lazy-jit fallback, not the sweep. None = wait
        # unbounded (legacy).
        self.compile_timeout_s = compile_timeout_s
        self.guard_stats = guard_stats
        self._futures: Dict[ShapeSpec, "Future"] = {}
        self._handed: List[ShapeSpec] = []   # executables get() returned
        self._lock = threading.Lock()
        self._warned = False

    def __len__(self) -> int:
        return len(self._futures)

    def submit(self, spec: ShapeSpec, engine, executor) -> None:
        with self._lock:
            if spec in self._futures:
                return
            cache_key = (self.manifest_key, spec)
            with _EXEC_CACHE_LOCK:
                cached = _EXEC_CACHE.get(cache_key)
            if cached is not None:
                fut: "Future" = Future()
                fut.set_result(cached)
                self._futures[spec] = fut
                return

            def task():
                with self.stats.loading(), tracing.span(
                        "engine/compile_load", label=spec.label):
                    t0 = time.perf_counter()
                    compiled = _lower_compile(engine, spec)
                    self.stats.record_shape(spec.label,
                                            time.perf_counter() - t0)
                with _EXEC_CACHE_LOCK:
                    _EXEC_CACHE[cache_key] = compiled
                return compiled

            self._futures[spec] = executor.submit(task)

    def get(self, spec: ShapeSpec):
        with self._lock:
            fut = self._futures.get(spec)
        if fut is None:
            self.stats.lazy_misses += 1
            return None
        waiting = (contextlib.nullcontext() if fut.done() else
                   tracing.span("engine/compile_wait", label=spec.label))
        try:
            with waiting:
                compiled = fut.result(timeout=self.compile_timeout_s)
        except FuturesTimeout:
            # Stalled compile: abandon the wait (the pool thread keeps
            # the future; a late success still lands in _EXEC_CACHE for
            # the next sweep) and dispatch lazily.
            if self.guard_stats is not None:
                self.guard_stats.site("stalls", "compile")
            log.warning("AOT compile for %s exceeded its %.1fs watchdog "
                        "deadline; falling back to lazy jit for this "
                        "dispatch", spec.label, self.compile_timeout_s)
            self.stats.lazy_misses += 1
            return None
        except Exception as err:  # noqa: BLE001 — fall back to lazy jit
            if not self._warned:
                self._warned = True
                log.warning("AOT compile failed for %s (%r); falling back "
                            "to lazy jit for unserved shapes", spec.label,
                            err)
            self.stats.lazy_misses += 1
            return None
        self.stats.hit(spec.label)
        with self._lock:
            if spec not in self._handed:
                self._handed.append(spec)
        return compiled

    def scope_tables(self, engine) -> List[Dict[str, Any]]:
        """For each dispatch program this registry handed out:
        ``{"label", "module", "scopes", "instructions", "recompiled"}``
        with ``scopes`` = :func:`scope_table` of its optimized HLO.
        Built only when asked (after a traced window), never on the
        dispatch path.

        The persistent cache's key leaves metadata out, so an entry
        written by a build of this code with other scopes (or none) is
        handed back with ITS op_names: an executable whose text names no
        ``lir.`` scope is compiled once more from this process's own
        lowering with metadata in the key (a separate cache entry; the
        optimized instruction names do not depend on metadata), and
        ``recompiled`` says so. The compiler option passed there changes
        nothing in the program: an explicit option is what makes jit
        compile again instead of handing back the executable it
        remembers for this lowering."""
        with self._lock:
            handed = [s for s in self._handed if s.kind != "stream_fold"]
        out = []
        for spec in handed:
            compiled = self._futures[spec].result()
            module, scopes, n = scope_table(compiled.as_text())
            stale = not scopes
            if stale:
                import jax

                flag = "jax_compilation_cache_include_metadata_in_key"
                before = getattr(jax.config, flag)
                jax.config.update(flag, True)
                try:
                    module, scopes, n = scope_table(
                        _lower(engine, spec).compile(compiler_options={
                            "xla_hlo_graph_addresses": False}).as_text())
                finally:
                    jax.config.update(flag, before)
            out.append({"label": spec.label, "module": module,
                        "scopes": scopes, "instructions": n,
                        "recompiled": stale})
        return out

    def wait(self) -> int:
        """Block until every submitted compile finishes; returns the count
        of successful executables (the precompile CLI's synchronous exit)."""
        ok = 0
        with self._lock:
            futures = list(self._futures.items())
        for spec, fut in futures:
            try:
                fut.result()
                ok += 1
            except Exception as err:  # noqa: BLE001
                log.warning("precompile failed for %s: %r", spec.label, err)
        return ok


_HLO_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|/)(lir\.\w+)")


def scope_table(hlo_text: str) -> Tuple[str, Dict[str, str], int]:
    """(module name, {instruction name: scope}, instructions seen) of
    one optimized HLO module's text (``compiled.as_text()``).

    An instruction's scope is the OUTERMOST ``lir.<phase>`` component
    of its ``metadata={op_name=...}`` (``verify_extend`` inside the
    speculative decode loop is decode); instructions under no scope are
    left out. A profiler trace names a device operation by the same
    instruction name (``%fusion.1081``) under the ``XLA Modules`` event
    of this module. A fusion carries its root's op_name, so the phases
    need not cover a program's whole device time: a reader reports the
    cover beside them."""
    found = _HLO_MODULE.search(hlo_text)
    scopes: Dict[str, str] = {}
    n = 0
    for line in hlo_text.splitlines():
        head = _HLO_INSTRUCTION.match(line)
        if head is None:
            continue
        n += 1
        op_name = _OP_NAME.search(line)
        scope = _SCOPE.search(op_name.group(1)) if op_name else None
        if scope is not None:
            scopes[head.group(1)] = scope.group(1)
    return (found.group(1) if found else ""), scopes, n


def precompile_async(engine, specs: Sequence[ShapeSpec],
                     max_workers: int = 0) -> ExecutableRegistry:
    """Kick off background compilation of every spec (dispatch order) and
    return the registry immediately — the sweep's first dispatches stream
    while later buckets' executables compile concurrently. The pool's
    threads outlive this call; registry futures own the results."""
    stats = getattr(engine, "compile_stats", None) or CompileStats()
    rt = getattr(engine, "rt", None)
    timeout = None
    if rt is not None and getattr(rt, "watchdog_multiple", 0) > 0:
        # The compile deadline mirrors the dispatch watchdog's shape:
        # floor * multiple — generous enough for a real 7B executable,
        # bounded enough that a wedged compiler thread costs one lazy
        # fallback instead of parking the dispatch loop forever.
        timeout = rt.watchdog_floor_s * max(rt.watchdog_multiple, 1.0)
    registry = ExecutableRegistry(engine.cache_manifest_key, stats,
                                  compile_timeout_s=timeout,
                                  guard_stats=getattr(engine,
                                                      "guard_stats", None))
    if not specs:
        return registry
    from ..utils import compile_cache

    compile_cache.write_manifest(engine.cache_manifest_key, {
        "model": engine.cfg, "runtime": engine.rt,
        "buckets": engine.buckets,
        "quant": compile_cache.quant_mode(engine.params),
        "shapes": [s.label for s in specs]})
    import os

    workers = max_workers or min(len(specs), max(2, (os.cpu_count() or 4)))
    executor = ThreadPoolExecutor(
        max_workers=workers,
        thread_name_prefix=compile_cache.COMPILE_PLAN_THREADS)
    for spec in specs:
        registry.submit(spec, engine, executor)
    executor.shutdown(wait=False)
    return registry


def registry_call(compiled, args: Tuple, kwargs: Dict[str, Any],
                  scratch_cache):
    """Invoke a registry executable with the canonical argument layout.

    AOT-compiled functions take only the DYNAMIC arguments (static
    cfg/budgets/flags were baked in at lower time), with the same
    positional/keyword split the lowering used — args positional minus
    cfg, stop args + scratch_cache by keyword."""
    return compiled(*args, scratch_cache=scratch_cache, **kwargs)


def sweep_specs_for_ladder(engine, sfx_buckets: Sequence[int] = (8, 16),
                           batches: Optional[Sequence[int]] = None,
                           ) -> List[ShapeSpec]:
    """The warm-ahead-of-serving spec set (`lir_tpu precompile` and the
    serving layer's boot precompile): for every bucket-ladder edge x
    candidate suffix edge x batch size, both handoff variants of the
    shared-prefix executable at the engine's sweep budgets.

    ``batches`` defaults to the engine's configured batch alone (the
    offline sweep dispatches full batches except one tail); the online
    server additionally warms the power-of-two TAIL batches
    (serve_batches) because continuous batching dispatches partial
    batches whenever the queue runs shallow. Grouped-dispatch shapes
    depend on the realized prefix groups, so those still compile lazily
    (into the persistent cache) the first time a grid forms them."""
    rt = engine.rt
    new_tokens = (rt.max_new_tokens if rt.sweep_full_completions
                  else min(rt.sweep_decode_tokens, rt.max_new_tokens))
    conf_tokens = (rt.max_new_tokens if rt.sweep_full_completions
                   else min(rt.sweep_confidence_tokens, rt.max_new_tokens))
    stops_armed = (rt.sweep_early_stop and not rt.sweep_full_completions
                   and engine.digit_stop_mask is not None)
    windows = ()
    if getattr(engine, "prefix_cache", None) is not None:
        from ..models import paged as paged_mod

        windows = lambda b: paged_mod.window_edges(  # noqa: E731
            b, engine.prefix_cache.page_size)
    sk = 0
    sdraft = False
    if getattr(engine, "spec_supported", lambda: False)():
        sk = rt.spec_k
        sdraft = getattr(engine, "_spec_draft", None) is not None
    specs = []
    for bucket in engine.buckets:
        for sfx in sfx_buckets:
            for batch in (batches if batches is not None
                          else (rt.batch_size,)):
                for scratch in (False, True):
                    specs.append(shared_spec(
                        bucket, batch, sfx, sfx, new_tokens,
                        conf_tokens, stops_armed, scratch))
                    if sk:
                        specs.append(shared_spec(
                            bucket, batch, sfx, sfx, new_tokens,
                            conf_tokens, stops_armed, scratch,
                            spec_k=sk, spec_draft=sdraft))
                    if windows:
                        # Block-table variants: one per remainder-window
                        # edge, so a warm serve dispatch resuming from
                        # the radix cache never pays a trace either.
                        for w in windows(bucket):
                            specs.append(shared_paged_spec(
                                bucket, batch, w, sfx, sfx, new_tokens,
                                conf_tokens, stops_armed, scratch))
                            if sk and not sdraft:
                                specs.append(shared_paged_spec(
                                    bucket, batch, w, sfx, sfx,
                                    new_tokens, conf_tokens, stops_armed,
                                    scratch, spec_k=sk))
    return specs


def serve_batches(batch_size: int) -> Tuple[int, ...]:
    """Every padded batch shape the continuous batcher can dispatch at a
    configured batch size: the full batch plus each power-of-two tail
    below it (runner._tail_batch pads partial batches onto this grid)."""
    out = []
    b = 1
    while b < batch_size:
        out.append(b)
        b *= 2
    out.append(batch_size)
    return tuple(out)
