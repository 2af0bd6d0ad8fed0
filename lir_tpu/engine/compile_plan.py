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

    ``kind`` is "shared" (a pair of format branches per row:
    runner.decode_fused_shared) or "grouped" (member rows gathered from
    prefix groups: decode_fused_grouped) — both the ONE dispatch program
    (generate.greedy_decode_dispatch), whose front and tail follow from
    the fields below (:func:`dispatch_program`) — or "trunk" (the trunk
    program, generate.greedy_decode_trunk: ``bucket`` its extent, one
    row), or one of the three piggyback stages, or "stream_fold".
    ``batch`` is the PADDED
    member-row count the runner will dispatch (shared: the padded batch;
    grouped: m_pad); ``groups`` the padded prefill-row count (grouped
    only, else 0). ``sfx_a``/``sfx_b`` are the right-pad suffix bucket
    edges (grouped uses a single merged edge in ``sfx_a``).
    ``stops_armed`` records whether the stop tables are arrays or absent
    — that changes the traced pytree, hence the executable. ``scratch``
    selects the donated-KV-cache variant (every dispatch after the first
    of a bucket queue donates the previous cache — paged.CacheHandoff).

    ``window`` > 0 selects a PAGED front at that recompute-window edge:
    each row recomputes its last ``window`` real prefix tokens and
    gathers the rest from the page pool; with ``trunk`` it is the
    TRUNK's window (a (1, W) chunk), not a per-row one. ``trunk`` > 0
    selects the CASCADE front at that static shared-trunk extent (which
    is then also the decode steps' trunk), ``cascade_int8`` its
    in-kernel int8-QK^T variant and ``held`` the front that takes the
    trunk's cache as an argument instead of prefilling it (the engine
    holds it: runner.ScoringEngine.route).
    ``decode_trunk`` > 0 runs the decode
    steps of a NON-cascade front trunk-aware at that extent
    (ops/flash_decode.flash_decode_trunk — bitwise the flat kernels).
    ``spec_k`` > 0 selects the speculative tail at that verify-window
    size and ``spec_draft`` its fleet-draft-model variant (the draft
    model's params ride the traced pytree). Every one of them changes
    the lowered program, so keying them here is what guarantees an
    executable can never serve the wrong mode."""

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
    held: bool = False

    @property
    def label(self) -> str:
        if self.kind == "trunk":
            return f"trunk/t{self.bucket}x{self.batch}"
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
            casc = (f"/trunk{self.trunk}" + ("+i8" if self.cascade_int8
                                             else "")
                    + ("+held" if self.held else ""))
        if self.decode_trunk:
            casc += f"/dtrunk{self.decode_trunk}"
        return (f"{self.kind}/b{self.bucket}x{self.batch}/sfx{sfx}"
                f"/new{self.new_tokens}-{self.conf_tokens}{win}{spec}"
                f"{casc}/{var}")

    @property
    def cache_key(self) -> "ShapeSpec":
        """What every program returning this one's cache aval shares:
        the donation chain's key. The front, the trunks and the stop
        tables leave the aval alone, so cold, warm, cascade and dense
        dispatches of one shape chain unbroken; a speculative cache is
        LONGER (spec_k slots a decode window) and chains on its own."""
        return dataclasses.replace(
            self, scratch=False, window=0, trunk=0, cascade_int8=False,
            decode_trunk=0, held=False)


def handoff_key(spec: ShapeSpec, kinds: bool = False) -> ShapeSpec:
    """What consecutive dispatches must share for one to donate the
    other's returned cache: ``spec.cache_key``, and for a model whose
    layers differ in kind (``kinds``) the trunk too — there a cascade
    front returns the trunk at one row with the rows' own slots behind
    it and a dense front every row's whole prefix (models/mixed.py):
    two avals, two chains. The runner keys the handoff on it and
    :func:`plan_specs` follows it, so the plan's donated variants are
    the ones that run."""
    key = spec.cache_key
    return dataclasses.replace(key, trunk=spec.trunk) if kinds else key


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


def trunk_spec(trunk: int) -> ShapeSpec:
    """The trunk program (generate.greedy_decode_trunk) at ``trunk``
    tokens: one row, no suffix, no tail, nothing donated."""
    return ShapeSpec("trunk", int(trunk), 1, 0, 0, 0, 0, 0, False, False)


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


def plan_specs(dispatches: Sequence[Any], routes: Sequence[Any],
               stream_shape: Optional[Tuple[int, int, bool]] = None,
               after: Any = None) -> List[ShapeSpec]:
    """Distinct executables a dispatch plan will call, in first-use order
    (the precompile pool works the list front-to-back, so the first
    bucket's executable compiles first and the dispatch loop rarely
    waits). ``routes[i]`` is the engine's routing of ``dispatches[i]``
    (runner.ScoringEngine.route_dispatch); WHICH programs a dispatch may
    run is the route's to say (``Route.planned``) — this function only
    follows the handoff (``Route.handoff_key``): the first dispatch of a
    shape runs the scratchless variant, every consecutive repeat the
    donated one, and a
    repeat is also what the sweep chains through the piggyback stages.
    ``after``: the route of the dispatch that runs before the first of
    these (the last of the plan window before this one), None at the
    start of a call.

    ``stream_shape`` = (n_prompts, n_rephrase, numerics_guard) plans the
    streaming-statistics accumulator-update executable for every
    distinct fold width the plan's dispatches will use (shared: the
    padded member-row count; grouped: one branch's row count), so the
    sink's per-dispatch fold never pays trace-on-first-call inside the
    timed loop either. Planned FIRST — the very first dispatch folds."""
    specs: List[ShapeSpec] = []
    seen = set()

    def add(spec: ShapeSpec) -> None:
        if spec not in seen:
            seen.add(spec)
            specs.append(spec)

    if stream_shape is not None:
        n_prompts, n_rephrase, guard = stream_shape
        for d, route in zip(dispatches, routes):
            width = (route.shape.batch if d.kind == "shared"
                     else len(d.items))
            add(stream_fold_spec(n_prompts, n_rephrase, width, guard))
    prev = None if after is None else after.handoff_key
    for route in routes:
        repeat = route.handoff_key == prev
        for spec in route.planned(scratch=repeat, chain=repeat):
            add(spec)
        prev = route.handoff_key
    return specs


# ---------------------------------------------------------------------------
# The dispatch program's statics and arguments, from a ShapeSpec
# ---------------------------------------------------------------------------

def dispatch_program(engine, spec: ShapeSpec,
                     return_cache: bool = True):
    """The static description (generate.Program) of the dispatch program
    ``spec`` keys: the front follows from ``window`` and ``trunk``, the
    layout from ``kind``, the tail from ``spec_k``."""
    from . import generate

    if spec.trunk:
        front = ("cascade_held" if spec.held
                 else "cascade_paged" if spec.window else "cascade")
    else:
        front = "paged" if spec.window else "prefill"
    grouped = spec.kind == "grouped"
    tail = {}
    if spec.spec_k:
        tail = dict(spec_k=spec.spec_k, ngram=int(engine.spec_cfg.ngram),
                    draft_cfg=(engine._spec_draft[1] if spec.spec_draft
                               else None))
    return generate.Program(
        front=front, layout="grouped" if grouped else "pair",
        max_new=((spec.new_tokens,) if grouped
                 else (spec.new_tokens, spec.conf_tokens)),
        topk=TOPK, trunk=spec.trunk or spec.decode_trunk,
        int8_qk=spec.cascade_int8,
        prefill_fn=engine._prefill_fn if front == "prefill" else None,
        return_cache=return_cache, **tail)


def dispatch_args(engine, spec: ShapeSpec,
                  host: Optional[Dict[str, Any]] = None):
    """The dispatch program's dynamic arguments (generate.DispatchArgs)
    for ``spec``: from the dispatch's own arrays, ``host`` by name, when
    the runner dispatches; from ``jax.ShapeDtypeStruct``s when the plan
    lowers (``host`` None). ONE function for both, so a lowering can
    never disagree with its call site; an array whose shape is not the
    spec's raises here, before a program built for another shape could
    be asked for. What the engine holds (stop tables, digit table, page
    pool, a draft model's weights) it hands over itself; the held
    trunk's cache comes with the dispatch (``host["trunk_cache"]``: the
    runner made sure these rows start with it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import generate

    def leaf(name, shape, dtype=jnp.int32):
        if host is None:
            return jax.ShapeDtypeStruct(shape, dtype)
        arr = jnp.asarray(host[name], dtype)
        if arr.shape != shape:
            raise ValueError(f"{spec.label}: argument {name!r} has shape "
                             f"{arr.shape}, the program takes {shape}")
        return arr

    def held(tree):
        if host is not None:
            return tree
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype), tree)

    grouped = spec.kind == "grouped"
    M, S = spec.batch, spec.bucket
    G = spec.groups if grouped else M
    sfx = ((("sfx_a", spec.sfx_a),) if grouped
           else (("sfx_a", spec.sfx_a), ("sfx_b", spec.sfx_b)))
    digit_ids, digit_vals = engine.digit_table
    paged = None
    if spec.window:
        # Over a cascade trunk the front resumes ONE row at the trunk's
        # extent; otherwise every prefix row at the bucket's.
        rows, ext = (1, spec.trunk) if spec.trunk else (G, S)
        paged = generate.PagedFront(
            pool=held(engine.prefix_cache.pool.leaves),
            slot_src=leaf("slot_src", (rows, ext)),
            win_start=leaf("win_start", ()),
            rem=leaf("rem", (rows, spec.window)),
            rem_mask=leaf("rem_mask", (rows, spec.window)))
    stops = None
    if spec.stops_armed:
        # Member rows of a grouped batch lie [bin, conf] per cell: the
        # odd rows read the digit table.
        stops = generate.Stops(
            binary=held(engine.eos_stop_mask),
            digits=held(engine.digit_stop_mask),
            eos_id=held(jnp.int32(engine.eos_id)),
            sel=(held(jnp.asarray(np.arange(M) % 2 == 1)) if grouped
                 else None))
    drafts = None
    if spec.spec_k:
        budgets = (("a", spec.sfx_a, spec.new_tokens),
                   ("b", spec.sfx_b, spec.conf_tokens))
        drafts = generate.Drafts(
            ctx=tuple(leaf(f"ctx_{b}", (M, S + w + n))
                      for b, w, n in budgets),
            ctx_len=tuple(leaf(f"ctx_{b}_len", (M,)) for b, _, _ in budgets),
            tokens=tuple(leaf(f"draft_{b}", (M, n)) for b, _, n in budgets),
            lens=tuple(leaf(f"draft_{b}_len", (M,)) for b, _, _ in budgets),
            params=(held(engine._spec_draft[0]) if spec.spec_draft
                    else None))
    return generate.DispatchArgs(
        prefix_mask=leaf("prefix_mask", (G, S)),
        sfx=tuple(leaf(name, (M, w)) for name, w in sfx),
        sfx_mask=tuple(leaf(f"{name}_mask", (M, w)) for name, w in sfx),
        yes_ids=leaf("yes_ids", (M,)), no_ids=leaf("no_ids", (M,)),
        digit_ids=held(jnp.asarray(digit_ids, jnp.int32)),
        digit_vals=held(jnp.asarray(digit_vals, jnp.float32)),
        # The paged front binds slot tables, not tokens; a cascade front
        # still extends the rows' remainders from them.
        prefix=(leaf("prefix", (G, S)) if spec.trunk or not spec.window
                else None),
        paged=paged,
        trunk_cache=((trunk_cache_avals(engine, spec.trunk) if host is None
                      else host["trunk_cache"]) if spec.held else None),
        group_idx=leaf("group_idx", (M,)) if grouped else None,
        stops=stops, drafts=drafts)


def trunk_cache_avals(engine, trunk: int):
    """What the trunk program returns at ``trunk`` tokens (tracing only,
    no device work): the avals a ``"cascade_held"`` front is lowered
    over."""
    import jax
    import jax.numpy as jnp

    from . import generate

    return generate.greedy_decode_trunk.eval_shape(
        engine.params, engine.cfg,
        jax.ShapeDtypeStruct((1, int(trunk)), jnp.int32))


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
    cache the program returns — generate.dispatch_cache_avals (tracing
    only, of the front alone; no device work)."""
    from . import generate

    if spec.kind == "stream_fold":
        from . import stream_stats

        return stream_stats.lower_fold(
            spec.bucket, spec.groups, spec.batch, TOPK,
            spec.stops_armed)
    if spec.kind == "trunk":
        import jax
        import jax.numpy as jnp

        return generate.greedy_decode_trunk.lower(
            engine.params, engine.cfg,
            jax.ShapeDtypeStruct((spec.batch, spec.bucket), jnp.int32))
    if spec.kind.startswith("piggy"):
        fn = {"piggy_prefill": generate.shared_piggyback_prefill,
              "piggy_step": generate.shared_piggyback_step,
              "piggy_drain": generate.shared_piggyback_drain}[spec.kind]
        args, kwargs, statics = _avals_piggy(engine, spec)
        return fn.lower(engine.params, engine.cfg, *args, **kwargs,
                        **statics)
    fn = generate.greedy_decode_dispatch
    program = dispatch_program(engine, spec)
    args = dispatch_args(engine, spec)
    scratch = None
    if spec.scratch:
        scratch = generate.dispatch_cache_avals(engine.params, engine.cfg,
                                                program, args)
    return fn.lower(engine.params, engine.cfg, program, args,
                    scratch_cache=scratch)


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

    def loaded(self) -> bool:
        """True when no submitted executable is still compiling or
        loading."""
        with self._lock:
            return all(f.done() for f in self._futures.values())

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
                     max_workers: int = 0,
                     registry: Optional[ExecutableRegistry] = None,
                     ) -> ExecutableRegistry:
    """Kick off background compilation of every spec (dispatch order) and
    return the registry immediately — the sweep's first dispatches stream
    while later buckets' executables compile concurrently. The pool's
    threads outlive this call; registry futures own the results.
    ``registry``: the one an earlier plan window of the same call made;
    the specs it does not hold yet are added to it (``submit``: a shape
    planned in two windows is compiled, and counted, once)."""
    from ..utils import compile_cache

    if registry is None:
        stats = getattr(engine, "compile_stats", None) or CompileStats()
        rt = getattr(engine, "rt", None)
        timeout = None
        if rt is not None and getattr(rt, "watchdog_multiple", 0) > 0:
            # The compile deadline mirrors the dispatch watchdog's shape:
            # floor * multiple — generous enough for a real 7B executable,
            # bounded enough that a wedged compiler thread costs one lazy
            # fallback instead of parking the dispatch loop forever.
            timeout = rt.watchdog_floor_s * max(rt.watchdog_multiple, 1.0)
        registry = ExecutableRegistry(
            engine.cache_manifest_key, stats, compile_timeout_s=timeout,
            guard_stats=getattr(engine, "guard_stats", None))
        if specs:
            compile_cache.write_manifest(engine.cache_manifest_key, {
                "model": engine.cfg, "runtime": engine.rt,
                "buckets": engine.buckets,
                "quant": compile_cache.quant_mode(engine.params),
                "shapes": [s.label for s in specs]})
    if not specs:
        return registry
    import os

    workers = max_workers or min(len(specs), max(2, (os.cpu_count() or 4)))
    executor = ThreadPoolExecutor(
        max_workers=workers,
        thread_name_prefix=compile_cache.COMPILE_PLAN_THREADS)
    for spec in specs:
        registry.submit(spec, engine, executor)
    executor.shutdown(wait=False)
    return registry


def empty_scratch(run):
    """A cache for the compiled dispatch program ``run`` to donate where
    the chain holds none yet (RuntimeConfig.donate_first): zeros of what it
    takes as ``scratch_cache``, placed where it takes them. The program
    never reads it. None where ``run`` is the lazily jitted function, which
    then traces its scratchless signature."""
    import jax
    import jax.numpy as jnp

    info = getattr(run, "args_info", None)
    if info is None or info[1].get("scratch_cache") is None:
        return None
    return jax.tree.map(
        lambda a, where: jnp.zeros(a.shape, a.dtype, device=where),
        info[1]["scratch_cache"], run.input_shardings[1]["scratch_cache"])


def registry_call(run, params, args, scratch_cache):
    """Invoke a dispatch program — a registry executable, or the lazily
    jitted function with its statics bound — on the canonical argument
    layout. ``scratch_cache`` is DONATED: the caller's binding is dead
    afterwards (lint/donation.py knows this call by name)."""
    return run(params, args, scratch_cache=scratch_cache)


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
    specs: List[ShapeSpec] = []
    for bucket in engine.buckets:
        for sfx in sfx_buckets:
            for batch in (batches if batches is not None
                          else (rt.batch_size,)):
                # No rows yet, so no trunk: the route plans the dense
                # program, its speculative sibling, and one block-table
                # variant per remainder-window edge — a warm serve
                # dispatch resuming from the radix cache never pays a
                # trace either.
                route = engine.route("shared", bucket, batch, 0, sfx, sfx,
                                     new_tokens, conf_tokens, stops_armed)
                for scratch in (False, True):
                    specs.extend(route.planned(scratch))
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
