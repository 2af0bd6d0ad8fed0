"""Batched scoring engine: the TPU replacement for both reference backends.

Where the reference loops prompts one at a time through
``model.generate(output_scores=True)`` (compare_base_vs_instruct.py:458-492)
or ships them to the OpenAI Batch API (perturb_prompts.py:551-726), this
engine packs ragged prompts into fixed-shape left-padded batches, runs ONE
jitted greedy-decode-with-capture per batch (sharded over the device mesh),
and applies the C13 readout vectorized over the batch.

Static-shape discipline: prompts are bucketed by token length and the batch
axis padded to ``batch_size``, so XLA compiles once per (bucket, batch_size)
pair and every subsequent batch reuses the cache.
"""

from __future__ import annotations

import math

import dataclasses
import threading
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import CascadeConfig, GovernorConfig, RuntimeConfig, SpecConfig
from ..guard.watchdog import DispatchWatchdog
from ..models import decoder, paged, quant
from ..observe import tracing
from ..utils.logging import get_logger
from ..utils.profiling import (CascadeStats, CompileStats, FaultStats,
                               FillStats,
                               GuardStats, KernelStats, PrefixCacheStats,
                               RecurrentStats, SparseStats, SpecStats,
                               cascade_decode_bytes_saved,
                               cascade_prefill_flops_saved)
from . import (compile_plan, generate, hbm, prefix_tree,
               scheduler as scheduler_mod, score, spec as spec_mod,
               tokens as tok)


log = get_logger(__name__)


class PiggybackIneligible(RuntimeError):
    """A dispatch can't ride the piggyback chain (layout fallback, memory
    headroom, learned-position ceiling) — the caller dispatches it through
    the plain path instead. Deliberate control flow, never an error."""


def _params_span(params: Any) -> int:
    """Devices the widest-sharded param leaf lives on (1 for host/aval
    trees and single-device engines)."""
    n = 1
    for leaf in jax.tree.leaves(params):
        sh = getattr(leaf, "sharding", None)
        if sh is not None:
            n = max(n, len(sh.device_set))
    return n


def _tail_batch(n: int, cap: int) -> int:
    """Smallest power of two >= n, capped at the configured batch size."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


# Cross-dispatch donation chain for the dense dispatch caches. The class
# moved to models/paged.py so all three KV ownership schemes — the page
# pool, the radix index, and the dispatch-scratch donation chain — live
# under the one allocator module; this alias keeps the historical name.
_CacheHandoff = paged.CacheHandoff


def _tree_bytes(tree: Any) -> int:
    """Bytes of a pytree's array leaves, from shape METADATA alone
    (.size / .itemsize are host ints on an async jax array): no device
    round-trip."""
    nbytes = 0
    for leaf in jax.tree.leaves(tree):
        size, dtype = getattr(leaf, "size", None), getattr(leaf, "dtype", None)
        if size is not None and dtype is not None:
            nbytes += int(size) * int(jnp.dtype(dtype).itemsize)  # lint: allow(host-sync)
    return nbytes


@dataclasses.dataclass
class _PrefixPlan:
    """One dispatch's radix-cache resume decision (engine-internal).

    ``window`` is the remainder-window edge the paged front will run
    (each row recomputes its last ``window`` real prefix tokens and
    gathers everything earlier from the page pool), or 0 when nothing
    useful is cached — the dispatch then runs the plain unpaged prefill
    (whose executable already exists) and only INSERTS pages afterward.
    ``matches`` hold the dispatch's page pins; every plan MUST pass
    through ScoringEngine._finish_prefix_resume, which inserts the new
    pages and releases the pins."""

    bucket: int
    prefix_ids: List[Sequence[int]]
    matches: List[Any]
    n_real: int
    window: int = 0
    w0: int = 0
    slot_src: Optional[np.ndarray] = None
    rem: Optional[np.ndarray] = None
    rem_mask: Optional[np.ndarray] = None

    def host_arrays(self) -> dict:
        """What a paged front binds, under the names
        compile_plan.dispatch_args reads them by; nothing when cold."""
        if not self.window:
            return {}
        return dict(slot_src=self.slot_src, win_start=self.w0,
                    rem=self.rem, rem_mask=self.rem_mask)


@dataclasses.dataclass(frozen=True)
class Route:
    """Where one dispatch goes: THE routing rule of the dispatch
    programs, held once. :meth:`ScoringEngine.route` fills in what the
    engine and the dispatch's rows decide before anything is looked up;
    :meth:`spec` then names the program that runs given what the
    dispatch finds (a radix window, a draft plan that survived the
    governor), and :meth:`planned` the programs it MAY run, which is
    what the compile plan compiles. The sweep's chain keys, the
    watchdog's price and the batcher's read ``trunk`` / ``dtrunk``.

    Precedence: the cascade front (``trunk``; ``held`` where the trunk
    is worth holding across dispatches) before the speculative
    tail before the piggyback chain — the latter two optimize around
    the very prefill the cascade removes. A paged front whenever the
    radix tree holds a window. The decode steps of a dense or paged
    front run trunk-aware at ``dtrunk``; a cascade front fixes the
    decode trunk itself."""

    shape: compile_plan.ShapeSpec   # the dense, fresh program at this shape
    trunk: int = 0        # cascade-prefill trunk the rows share (0: none)
    dtrunk: int = 0       # trunk the decode steps dedup at (0: flat)
    int8: bool = False    # the cascade kernel's int8-QK^T variant
    spec_k: int = 0       # the engine speculates at this window (0: no)
    fleet: bool = False   # ... with a fleet draft model
    page_size: int = 0    # radix prefix cache page (0: no cache)
    piggyback: bool = False
    # False: beside a cascade front the dense program is not planned. It
    # would run every row's whole prefix in one pass, which the engine's
    # token cap (RuntimeConfig.dispatch_tokens) forbids: at 40 rows of
    # 16k tokens the compiler itself refuses it.
    plan_dense: bool = True
    # True: the first dispatch of a shape donates an empty cache
    # (RuntimeConfig.donate_first), so only donated variants are planned.
    donate_first: bool = False
    # True: the cascade front takes the trunk's cache as an argument
    # ("cascade_held"); the engine holds it from dispatch to dispatch.
    held: bool = False
    # ... and this dispatch must have the trunk program run first: the
    # engine does not hold these tokens' trunk when it starts.
    trunk_run: bool = False
    # The trunk the engine holds once this dispatch has run (its token
    # ids; None: none): the next dispatch's route is made from it, in
    # the plan as at dispatch time. A value carried, not a fact compared.
    held_ids: Optional[Tuple[int, ...]] = dataclasses.field(
        default=None, compare=False, repr=False)
    # The model's layers differ in kind: a cascade front's cache is not
    # a dense front's (compile_plan.handoff_key).
    kinds: bool = False

    @property
    def handoff_key(self) -> compile_plan.ShapeSpec:
        """What the dispatch chains its cache on (the donation chain)."""
        return compile_plan.handoff_key(self.spec(), self.kinds)

    def spec(self, window: int = 0, speculate: bool = False,
             scratch: bool = False) -> compile_plan.ShapeSpec:
        """The program to run. ``window``: the radix plan's recompute
        window over the trunk's namespace (cascade) or the bucket's, 0
        when cold; ``speculate``: a draft plan is at hand; ``scratch``:
        the handoff holds a cache to donate. A fleet draft model cannot
        ride a paged front (it binds slot tables, not prefix tokens:
        nothing to prefill the draft cache from), so that pair runs the
        sequential tail."""
        shape = dataclasses.replace(self.shape, window=window,
                                    scratch=scratch)
        if self.trunk:
            return dataclasses.replace(shape, trunk=self.trunk,
                                       cascade_int8=self.int8,
                                       held=self.held)
        k = self.spec_k if speculate and not (self.fleet and window) else 0
        return dataclasses.replace(shape, decode_trunk=self.dtrunk,
                                   spec_k=k,
                                   spec_draft=bool(k) and self.fleet)

    @property
    def chain_key(self) -> Optional[Tuple[int, int, int, int]]:
        """What consecutive dispatches must share to ride one piggyback
        chain, or None when this one never chains: the engine chains
        nothing, or the cascade front takes it (that front has no
        parked-decode carry slot)."""
        if not self.piggyback or self.trunk:
            return None
        s = self.shape
        return s.bucket, s.batch, s.sfx_a, s.sfx_b

    def planned(self, scratch: bool,
                chain: bool = False) -> List[compile_plan.ShapeSpec]:
        """Every program the dispatch may run, in first-use order, at
        the handoff variant ``scratch``: which window a warm dispatch
        runs depends on what the radix tree holds by then, so every
        window edge is covered; the governor may shed speculation and a
        chain may be refused, so the sequential program stays planned
        beside them. A cascade-eligible dispatch also keeps the dense
        program and its speculative sibling, which it never reaches
        (ROADMAP S7 prunes them here). ``chain``: the sweep will chain
        this dispatch to the previous one (a repeat of its shape), so
        the three piggyback stages are planned."""
        scratch = scratch or self.donate_first
        if self.held:
            # The trunk's own program where it has to run, then the one
            # dispatch program behind it: a held trunk is never behind a
            # radix window, and the dense program is not its fallback.
            return ([compile_plan.trunk_spec(self.trunk)]
                    if self.trunk_run else []) + [
                        self.spec(0, False, scratch)]
        dense = dataclasses.replace(self, trunk=0,
                                    dtrunk=0 if self.trunk else self.dtrunk)
        edges = lambda extent: (  # noqa: E731
            paged.window_edges(extent, self.page_size)
            if self.page_size else ())
        out = []
        if self.plan_dense or not self.trunk:
            out.append(dense.spec(0, False, scratch))
            if self.spec_k:
                out.append(dense.spec(0, True, scratch))
        if self.trunk:
            out += [self.spec(w, False, scratch)
                    for w in (0,) + tuple(edges(self.trunk))]
        elif chain and self.chain_key is not None:
            s = self.shape
            stage = (s.bucket, s.batch, s.sfx_a, s.sfx_b, s.new_tokens,
                     s.conf_tokens)
            out += [compile_plan.piggy_prefill_spec(*stage),
                    compile_plan.piggy_step_spec(*stage, s.stops_armed),
                    compile_plan.piggy_drain_spec(*stage, s.stops_armed)]
        for w in edges(self.shape.bucket):
            out.append(dense.spec(w, False, scratch))
            if self.spec_k and not self.fleet:
                out.append(dense.spec(w, True, scratch))
        return out


@dataclasses.dataclass
class PromptScore:
    """One prompt's raw measurement. Sweep drivers wrap this into
    data/schemas.py records (which add model identity and D1/D2 semantics)."""

    prompt: str
    completion: str
    yes_prob: float
    no_prob: float
    yes_logprob: float
    no_logprob: float
    odds_ratio: float
    relative_prob: float
    position_found: int
    yes_no_found: bool


@dataclasses.dataclass
class SampledScore:
    """n-run count-averaged measurement (reasoning-model mode,
    perturb_prompts.py:412-446): probabilities are answer-count fractions,
    not logit softmaxes."""

    prompt: str
    response: str               # most common run text
    all_responses: List[str]
    token_1_prob: float
    token_2_prob: float
    odds_ratio: float


class ScoringEngine:
    """Holds (params, cfg, tokenizer) and the jitted decode path.

    ``encoder_decoder=True`` routes through the T5 branch (reference routing
    rule compare_instruct_models.py:471-475).
    """

    def __init__(self, params: Any, cfg: Any, tokenizer: Any,
                 runtime: Optional[RuntimeConfig] = None,
                 encoder_decoder: bool = False,
                 yes_text: str = "Yes", no_text: str = "No",
                 seq_mesh: Any = None, seq_impl: str = "ring",
                 spec_config: Optional[SpecConfig] = None,
                 governor: Optional["hbm.HbmGovernor"] = None,
                 governor_config: Optional[GovernorConfig] = None,
                 cascade_config: Optional[CascadeConfig] = None):
        self.params = params
        self.cfg = cfg
        self.tokenizer = tokenizer
        self.rt = runtime or RuntimeConfig()
        # Speculative scoring decode (engine/spec.py): drafting policy,
        # per-dispatch SpecOut readouts pending their deferred host
        # fold, the optional fleet draft model (set_spec_draft), and
        # the fault hook a wrapped plan uses to corrupt drafts
        # (faults/plan.wrap_engine).
        self.spec_cfg = spec_config or SpecConfig()
        self.spec_stats = SpecStats()
        # Shared-prefix cascade prefill (ops/cascade_prefill): eligibility
        # policy + the dedup counters bench.py's "cascade" key reads.
        self.cascade_cfg = cascade_config or CascadeConfig()
        self.cascade_stats = CascadeStats()
        self.fill_stats = FillStats()
        # Recurrent state beside K/V (models with a state-space mixer):
        # bytes per dispatch cache, forks, kernel calls, trunk states
        # shared (profiling.RecurrentStats; metrics source "recurrent").
        self.recurrent_stats = RecurrentStats()
        # Block-sparse attention with a selection step (models/mixed.py):
        # blocks offered and kept, dense queries, pooled-key bytes
        # (profiling.SparseStats; metrics source "sparse").
        self.sparse_stats = SparseStats()
        self._spec_draft = None
        self._spec_pending: List[Any] = []
        self.spec_fault_plan = None
        self.encoder_decoder = encoder_decoder
        # Static rule for SHARDED engines: a Mosaic (Pallas TPU) kernel
        # cannot be partitioned by GSPMD — the TPU compiler refuses the
        # program ("Mosaic kernels cannot be automatically partitioned.
        # Please wrap the call in a shard_map") — so an engine whose
        # params span more than one device runs every attention route
        # dense until the kernels are wrapped in shard_map over the
        # `model` axis (ROADMAP S3/W2). Decided here, from the params'
        # own shardings, never by catching the compiler's error.
        span = _params_span(params)
        if span > 1:
            log.info("params span %d devices: Pallas kernels off (fused "
                     "decode, cascade prefill/decode, flash prefill) — "
                     "GSPMD cannot partition a Mosaic call", span)
            self.rt = dataclasses.replace(
                self.rt, fused_decode=False, cascade_prefill=False,
                cascade_decode=False)
            if getattr(cfg, "use_flash_attention", False):
                self.cfg = cfg = dataclasses.replace(
                    cfg, use_flash_attention=False)
        # Fused decode kernels are a RUNTIME choice surfaced through the
        # static model config (the decode executables specialize on it):
        # --no-fused-decode restores the dense decode lowering exactly,
        # and the manifest key shifts with the cfg so a registry or
        # warmed compile cache can never serve the other mode's
        # executables.
        if (not encoder_decoder
                and getattr(cfg, "fused_decode", None) is not None
                and cfg.fused_decode != self.rt.fused_decode):
            self.cfg = cfg = dataclasses.replace(
                cfg, fused_decode=self.rt.fused_decode)
        # Cascade decode + fused-suffix cascade prefill follow the same
        # discipline: runtime choices mirrored into the static model
        # config, so --no-cascade-decode / --no-cascade-fused-suffix
        # re-key every affected executable and the manifest can never
        # serve the other mode's lowering.
        if (not encoder_decoder
                and getattr(cfg, "cascade_decode", None) is not None
                and cfg.cascade_decode != self.rt.cascade_decode):
            self.cfg = cfg = dataclasses.replace(
                cfg, cascade_decode=self.rt.cascade_decode)
        if (not encoder_decoder
                and getattr(cfg, "cascade_fused_suffix", None) is not None
                and cfg.cascade_fused_suffix != self.rt.cascade_fused_suffix):
            self.cfg = cfg = dataclasses.replace(
                cfg, cascade_fused_suffix=self.rt.cascade_fused_suffix)
        # Sequence-parallel prefill (long-context path): with a mesh whose
        # `seq` axis > 1, the quadratic prompt phase runs seq-sharded
        # through ring/Ulysses attention (parallel/seq_forward) and hands
        # the KV cache back unsharded for ordinary dense decode. Built ONCE
        # here so the jitted decode fns cache on a stable static callable.
        self._prefill_fn = None
        if seq_mesh is not None and not encoder_decoder:
            from ..parallel.seq_forward import prefill_seq_parallel

            @jax.named_scope("lir.prefill")
            def _seq_prefill(p, c, t, m, T, *, _mesh=seq_mesh,
                             _impl=seq_impl):
                return prefill_seq_parallel(p, c, t, m, T, mesh=_mesh,
                                            impl=_impl)

            self._prefill_fn = _seq_prefill
        self.yes_id, self.no_id = tok.yes_no_ids(
            tokenizer, encoder_decoder=encoder_decoder,
            yes_text=yes_text, no_text=no_text)
        self.eos_id = getattr(tokenizer, "eos_token_id", None)
        # The pipelined sweep tokenizes bucket N+1 on the main thread while
        # its writer thread decodes bucket N's completions. HF fast (Rust)
        # tokenizers are NOT safe under concurrent encode/decode (encode
        # takes a write borrow for truncation/padding state -> intermittent
        # "Already borrowed" RuntimeError), so every tokenizer touch goes
        # through this lock. Contention is negligible: encode/decode are
        # each ~ms per bucket vs ~1.5 s of device work.
        self._tok_lock = threading.Lock()
        # Length buckets. With the ragged scheduler: a ~sqrt(2) ladder
        # (tokens.bucket_ladder) so short prompts prefill short shapes —
        # each edge compiles once and the scheduler keeps dispatches
        # bucket-pure. Legacy mode keeps the powers-of-two set whose
        # per-batch pick_bucket pads every mixed-length batch to its
        # longest row (the bench's single-bucket baseline).
        if self.rt.ragged_scheduler:
            self.buckets = list(tok.bucket_ladder(self.rt.max_seq_len))
        else:
            self.buckets = [b for b in (64, 128, 256, 512, 1024)
                            if b <= self.rt.max_seq_len] or [self.rt.max_seq_len]
        if getattr(cfg, "pos_embedding", None) == "learned":
            # A bucket + generation budget past the learned-position table
            # would read beyond pos_embed (gpt2/opt tables are exactly
            # max_seq_len rows): trim such buckets so a ~1000-token prompt
            # fails loudly into a smaller bucket's truncation semantics
            # instead of decoding at clipped positions.
            limit = cfg.max_seq_len - self.rt.max_new_tokens
            fitting = [b for b in self.buckets if b <= limit]
            if not fitting:
                raise ValueError(
                    f"{cfg.name}: no length bucket fits the learned-"
                    f"position table ({cfg.max_seq_len} rows) minus the "
                    f"generation budget ({self.rt.max_new_tokens}) — "
                    f"reduce max_new_tokens or max_seq_len")
            self.buckets = fitting
        self._digit_table: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._digit_stop_mask: Any = False  # False = not resolved yet
        self._eos_stop_mask: Optional[jax.Array] = None
        # Cross-dispatch KV-cache buffer reuse (donation) + the last
        # sweep's scheduler counters (profiling.OccupancyStats) — set by
        # sweep.run_perturbation_sweep, read by bench.py.
        self._handoff = _CacheHandoff()
        # The ONE held trunk (ids, cache): what generate.
        # greedy_decode_trunk returned for these tokens, read by every
        # consecutive dispatch that starts with them (:meth:`route`).
        self._trunk: Optional[Tuple[Tuple[int, ...], Any]] = None
        self.occupancy = None
        # In-flight piggyback chain (chunked prefill/decode piggybacking):
        # the parked dispatch whose decode scans ride the next same-shape
        # dispatch's prefill call (generate.PiggybackCarry + the statics
        # needed to drain it). One chain at a time by construction — the
        # sweep drains before switching shapes.
        self._piggy: Optional[dict] = None
        # Per-phase kernel accounting + piggyback counters
        # (profiling.KernelStats; bench.py fills the phase rows).
        self.kernel_stats = KernelStats()
        # Unified HBM governor (engine/hbm.py): one ledger every HBM
        # consumer registers with, the pressure-driven degradation
        # ladder, and reclaim-and-retry OOM routing. With no configured
        # budget and no device memory stats (CPU) the ladder never
        # engages — behavior is identical to pre-governor. Built BEFORE
        # the prefix cache so the pool reservation lands in the ledger.
        if governor is not None:
            self.governor: Optional[hbm.HbmGovernor] = governor
        else:
            self.governor = hbm.HbmGovernor(governor_config)
        # Ledger keys are namespaced by model so engines sharing one
        # fleet governor never collide (and the fleet can hand params
        # accounting over to the weight cache — release_params_ledger).
        self._ledger_key = f"params:{getattr(cfg, 'name', 'model')}"
        if self.governor is not None and params is not None:
            try:
                self.governor.register(self._ledger_key,
                                       quant.param_bytes(params))
            except Exception:  # noqa: BLE001 — ledger accounting must
                # never block engine construction (exotic test params)
                pass
            self.governor.set_action(
                "evict_pages",
                engage=lambda: self._evict_cold_pages())
            self.governor.set_action(
                "no_piggyback",
                engage=lambda: self._drop_handoff_scratch())
        # Cross-request radix prefix cache (engine/prefix_tree.py) over
        # the paged KV allocator (models/paged.py): a dispatch resumes
        # each row's prefix from the deepest cached radix node and pays
        # prefill only for the unshared remainder — across requests,
        # batches, and sweeps. Built by enable_prefix_cache() (the serve
        # layer turns it on by default, ServeConfig.prefix_cache;
        # offline sweeps opt in via RuntimeConfig.prefix_cache).
        self.prefix_cache: Optional[prefix_tree.RadixPrefixCache] = None
        self.prefix_stats = PrefixCacheStats()
        if self.rt.prefix_cache:
            self.enable_prefix_cache()
        # Compile plan (engine/compile_plan.py): the sweep precompiles its
        # planned shapes into this registry; the decode entry points below
        # consult it and fall back to lazy jit on any miss. Stats record
        # per-shape compile seconds + registry/persistent-cache hit rates.
        self.compile_stats = CompileStats()
        self.exec_registry = None
        # Failure-path accounting (lir_tpu/faults): the sweep's dispatch
        # recovery and any wrapping FaultPlan count into this.
        self.fault_stats = FaultStats()
        # Guard layer (lir_tpu/guard): the dispatch watchdog (stall
        # detection priced by scheduler.bucket_cost, calibrated against
        # this engine's own dispatch rate) and the counters it shares
        # with the numerics guard and the multihost liveness barrier.
        self.guard_stats = GuardStats()
        # Speculating engines price dispatches at the spec decode floor,
        # so their watchdog seeds with the wider UNFUSED/SPEC headroom
        # (a zero-accept dispatch degenerating to sequential cost must
        # never trip a spec-calibrated deadline — scheduler.
        # watchdog_seed_headroom).
        # A cascade engine additionally multiplies in the cascade/dense
        # prefill spread: deadlines calibrate on trunk-discounted
        # dispatches, and an ineligible dispatch legitimately falls back
        # to the full dense prefill.
        self.watchdog = DispatchWatchdog(
            multiple=self.rt.watchdog_multiple,
            floor_s=self.rt.watchdog_floor_s, stats=self.guard_stats,
            seed_headroom=scheduler_mod.watchdog_seed_headroom(
                self.rt.spec_decode and self.rt.spec_k >= 2,
                cascade=self.cascade_supported()))
        self._seq_mesh_note = (
            None if seq_mesh is None
            else (repr(getattr(seq_mesh, "shape", seq_mesh)), seq_impl))
        self._manifest_key: Optional[str] = None

    def fresh_handoff(self) -> None:
        """Reset the cross-dispatch KV-cache donation chain. Call at the
        start of every dispatch stream (a sweep, a serving session): the
        first dispatch of each bucket then always runs the scratchless
        jit signature and later ones the donated-cache signature — the
        same two executables a warmup over the same shapes compiles, so
        steady-state timing never hits a fresh compile mid-stream."""
        self._handoff = _CacheHandoff()
        if getattr(self, "governor", None) is not None:
            self.governor.unregister(
                f"handoff:{getattr(self.cfg, 'name', 'model')}")
        self._drop_trunk()

    def _drop_trunk(self) -> None:
        """Let go of the held trunk (its buffers are freed once the last
        dispatch reading them is done) and of its ledger entry."""
        self._trunk = None
        if getattr(self, "governor", None) is not None:
            self.governor.unregister(
                f"trunk:{getattr(self.cfg, 'name', 'model')}")

    def _hold_trunk(self, ids: Tuple[int, ...]) -> None:
        """Run the trunk program over ``ids`` and hold what it returns.
        The trunk held before is dropped FIRST, so two are never alive."""
        self._drop_trunk()
        spec = compile_plan.trunk_spec(len(ids))
        tokens = jnp.asarray(np.asarray(ids, np.int32)[None, :])
        with tracing.span("sweep/trunk", tokens=len(ids)):
            run = self._hit_or_lazy(
                spec, lambda params, toks: generate.greedy_decode_trunk(
                    params, self.cfg, toks))
            cache = run(self.params, tokens)
        self._trunk = (ids, cache)
        if self.governor is not None:
            self.governor.register(
                f"trunk:{getattr(self.cfg, 'name', 'model')}",
                _tree_bytes(cache))
        self.cascade_stats.count("trunk_programs")
        self._note_prefilled(len(ids), (), (), (), cache, 0, 0)
        # One chunked scan a recurrent layer, no dispatch of rows.
        self.recurrent_stats.count("scan_calls", self._recurrent_layers())

    @property
    def held_trunk(self) -> Optional[Tuple[int, ...]]:
        """Token ids of the trunk this engine holds now, or None."""
        return None if self._trunk is None else self._trunk[0]

    def release_params_ledger(self) -> None:
        """The fleet weight cache now owns this engine's param bytes —
        drop the engine-level ledger entry so a shared governor never
        double-counts them under both ``params:<model>`` and the
        cache's ``weights`` entry (engine/fleet.py calls this when a
        model moves under cache ownership)."""
        if self.governor is not None:
            self.governor.unregister(self._ledger_key)

    def _drop_handoff_scratch(self) -> bool:
        """Governor no_piggyback rung: beyond refusing new chains, give
        back the donation-chain scratch buffer the handoff retains
        between dispatches — real HBM freed NOW (the next dispatch
        simply runs the scratchless executable signature, which every
        bucket already compiled as its first dispatch). True when a
        parked buffer was actually released."""
        had = self._handoff.pending
        self.fresh_handoff()
        return had

    def _evict_cold_pages(self) -> bool:
        """Governor evict_pages rung: drop the coldest radix pages
        (tree-driven LRU — models/paged refcounts keep in-flight pages
        unevictable). Returns True when any page was actually freed.
        With a tier store attached (:meth:`attach_tiers`) the rung
        DEMOTES instead: the coldest leaves export to the host tier
        before their pages leave HBM, and plain eviction remains the
        fallback when nothing was demotable."""
        if self.prefix_cache is None:
            return False
        store = getattr(self, "_tier_store", None)
        if store is not None and store.demote(self):
            return True
        n = self.prefix_cache.evict(
            self.governor.cfg.evict_pages_per_step
            if self.governor is not None else paged.DEFAULT_PAGE_SIZE)
        return n > 0

    def attach_tiers(self, store) -> None:
        """Point the ``evict_pages`` reclaim rung at a
        serve/tiers.TieredPageStore: HBM pressure then demotes the
        coldest radix leaves down the host/disk ladder (reversible —
        a later promote re-enters through the paged-warm import path
        bitwise) instead of deleting them. The rung's engage callback
        is unchanged — demotion frees the same HBM pages eviction
        would, so the governor's reclaim accounting holds."""
        decoder.refuse_recurrent(self.cfg, "the tiered page store")
        self._tier_store = store

    def _note_handoff(self, cache: Any) -> None:
        """Ledger the donation-chain scratch cache the engine keeps
        live between dispatches (shape metadata only — no device
        sync). One entry: the chain holds at most one parked cache."""
        if self.governor is None or cache is None:
            return
        nbytes = _tree_bytes(cache)
        self.governor.register(
            f"handoff:{getattr(self.cfg, 'name', 'model')}", nbytes)

    def _note_prefilled(self, trunk: int, prefix_lens: Sequence[int],
                        sfx_a: Sequence[Sequence[int]],
                        sfx_b: Sequence[Sequence[int]], cache: Any,
                        new_tokens: int, conf_tokens: int,
                        trunk_inside: bool = True) -> None:
        """Count one shared dispatch's real prompt tokens (a trunk once)
        and, for a model whose softmax layers select blocks, what its
        queries were offered and kept: host ints from the rows' lengths
        and the decode budgets (each branch runs its budget of steps
        unless every row stops first). ``trunk_inside`` False: the
        dispatch program took the trunk as a value, and the trunk
        program's run counted its tokens and its queries' blocks
        (:meth:`_hold_trunk`: a trunk and no rows)."""
        rows = list(zip(prefix_lens, sfx_a, sfx_b))
        own = trunk if trunk_inside else 0
        prefilled = own + sum(n - trunk + len(a) + len(b)
                              for n, a, b in rows)
        stats = self.cascade_stats
        stats.count("trunk_tokens_prefilled", own)
        stats.count("tokens_prefilled", prefilled)
        cfg = self.cfg
        if not getattr(cfg, "layer_kinds", ()):
            return
        from ..ops import sparse_attention as sparse

        sizes = dict(block=cfg.sparse_block, topk=cfg.sparse_topk,
                     init_blocks=cfg.sparse_init_blocks,
                     window=cfg.sparse_window, dense_len=cfg.sparse_dense_len)
        total = np.zeros(3, np.int64)
        if own:
            total += sparse.kept_blocks(np.arange(trunk), trunk, **sizes)
        for n, a, b in rows:
            pos = [np.arange(trunk, n)]
            pos += [np.arange(n, n + len(s) + steps)
                    for s, steps in ((a, new_tokens), (b, conf_tokens))]
            total += sparse.kept_blocks(np.concatenate(pos), trunk or n,
                                        **sizes)
        layers = cfg.kind_layers("sparse")
        queries = prefilled + len(rows) * (new_tokens + conf_tokens)
        sp = self.sparse_stats
        sp.count("blocks_kept", int(total[0]) * layers)
        sp.count("blocks_offered", int(total[1]) * layers)
        sp.count("dense_queries", int(total[2]) * layers)
        sp.count("queries", queries * layers)
        sp.count("pooled_key_bytes", _tree_bytes(cache[3]))

    def _recurrent_layers(self) -> int:
        """Layers that carry a recurrent state: every layer of a model
        with a mixer, the lightning layers where layers differ in kind
        (K/V for the softmax layers only; only the latter scan), none
        otherwise."""
        if not getattr(self.cfg, "carries_state", False):
            return 0
        if self.cfg.layer_kinds:
            return self.cfg.kind_layers("lightning")
        return self.cfg.n_layers

    def _note_recurrent(self, cache: Any, rows: int, steps: int,
                        windows: int, trunk_rows: int = 0,
                        forks: Optional[int] = None) -> None:
        """Count one dispatch's recurrent state (host ints from shape
        metadata; nothing for a model without a mixer). In a shared
        dispatch both format branches start from the state of the
        prefix's end: two forks a row, unless ``forks`` says otherwise.
        ``windows`` is the number of chunked-scan windows the program
        runs per layer (prefix [+ trunk], the suffix extends), ``steps``
        the decode budget, each step a single-token update per layer."""
        if not getattr(self.cfg, "carries_state", False):
            return
        L = self._recurrent_layers()
        if self.cfg.layer_kinds:
            from ..models import mixed

            kv, state = mixed.cache_kinds(cache)
        else:
            kv, state = cache[:2], cache[2:]
        stats = self.recurrent_stats
        stats.count("dispatches")
        stats.count("kv_bytes", _tree_bytes(kv))
        stats.count("state_bytes", _tree_bytes(state))
        stats.count("forks", 2 * rows if forks is None else forks)
        stats.count("scan_calls", windows * L)
        stats.count("step_calls", steps * L)
        stats.count("trunk_states_shared", trunk_rows)

    def enable_prefix_cache(self) -> None:
        """Build the paged KV pool + radix index (idempotent). The pool
        leaves materialize immediately at their full configured size
        (rt.prefix_cache_pages x rt.prefix_page_size token positions,
        models/paged.kv_page_bytes each) so serving never allocates HBM
        mid-traffic; disable by sizing prefix_cache_pages < 2.
        Sequence-parallel engines keep the unpaged path (the paged
        window extension is a dense chunked prefill — resharding it
        through ring/Ulysses attention is not worth R tokens)."""
        if (self.prefix_cache is not None or self.encoder_decoder
                or self._prefill_fn is not None):
            return
        if self.rt.prefix_cache_pages < 2:
            return
        decoder.refuse_recurrent(self.cfg, "the radix prefix cache")
        pool = paged.KVPagePool(self.rt.prefix_cache_pages,
                                self.rt.prefix_page_size)
        pool.ensure(self._cache_aval())
        self.prefix_cache = prefix_tree.RadixPrefixCache(
            pool, stats=self.prefix_stats)
        if self.governor is not None:
            # The pool materializes at full size up front — the ledger
            # carries the whole reservation, not current occupancy.
            self.governor.register(
                f"kv_pages:{getattr(self.cfg, 'name', 'model')}",
                pool.nbytes)

    # -- speculative decode (engine/spec.py) --------------------------------

    def spec_supported(self) -> bool:
        """Engine-level gate for speculative decode: on by config with a
        verify window of at least 2, plain decoder engines only (T5 and
        seq-parallel prefills keep their own paths). Per-dispatch
        eligibility (layout fallbacks, fleet-draft x paged exclusion)
        is decided where the dispatch forms."""
        return (self.rt.spec_decode and self.rt.spec_k >= 2
                and not self.encoder_decoder
                and self._prefill_fn is None
                # no roll-back of recurrent state to the last accepted
                # token yet (decoder.refuse_recurrent)
                and not getattr(self.cfg, "carries_state", False))

    def set_spec_draft(self, params: Any, cfg: Any, name: str = "") -> None:
        """Arm fleet-model drafting: the small model's (params, cfg)
        draft for this engine's verifier. The caller owns the weights'
        lifetime — the fleet layer acquires them through the PR-10
        WeightCache around every dispatch window so drafting can never
        evict the verifier mid-dispatch. Same tokenizer/vocab as the
        verifier is the caller's contract (enforced here by vocab)."""
        if cfg.vocab_size != self.cfg.vocab_size:
            raise ValueError(
                f"spec draft model {name or cfg.name!r} vocab "
                f"{cfg.vocab_size} != verifier vocab "
                f"{self.cfg.vocab_size} — draft and verifier must share "
                f"a tokenizer")
        self._spec_draft = (params, cfg, name)
        if self.governor is not None:
            try:
                self.governor.register(
                    f"spec_draft:{name or cfg.name}",
                    quant.param_bytes(params))
            except Exception:  # noqa: BLE001 — accounting only
                pass

    def clear_spec_draft(self) -> None:
        if self._spec_draft is not None and self.governor is not None:
            _, dcfg, dname = self._spec_draft
            self.governor.unregister(f"spec_draft:{dname or dcfg.name}")
        self._spec_draft = None

    def spec_record(self, bucket: int,
                    prompt_ids: Sequence[Sequence[int]], gen_rows: Any,
                    n_real: Optional[int] = None) -> int:
        """Record observed completions into the radix tree's token
        history (prompt-lookup drafting warms itself — spec.py)."""
        return spec_mod.record_tails(
            self, bucket, prompt_ids, gen_rows,
            len(prompt_ids) if n_real is None else n_real,
            max_tails=self.spec_cfg.tree_tails_per_node)

    def spec_flush(self) -> None:
        """Fold pending device-side SpecOut counters into spec_stats
        (deferred off the dispatch path — spec.flush_pending)."""
        spec_mod.flush_pending(self)

    # -- shared-prefix cascade prefill (ops/cascade_prefill) ----------------

    def cascade_supported(self) -> bool:
        """Engine-level gate for cascade prefill: on by config, plain
        decoder engines only (T5 and seq-parallel prefills keep their
        own paths), float KV cache only (the cascade extension writes
        float k/v into the broadcast trunk cache — int8 KV engines keep
        the dense path), and only where the prefix-leg Pallas kernel
        runs: the TPU backend, or CPU under the interpreter when
        decoder.CASCADE_INTERPRET_ON_CPU is armed (tier-1 and the
        cascade smoke; production CPU stays dense). Per-dispatch
        eligibility (trunk length, row count) is :meth:`shared_trunk`'s."""
        if not (self.rt.cascade_prefill and not self.encoder_decoder
                and self._prefill_fn is None
                and not getattr(self.cfg, "kv_cache_int8", False)):
            return False
        return (jax.default_backend() == "tpu"
                or decoder.CASCADE_INTERPRET_ON_CPU)

    def _lcp_trunk(self, prefix_ids: Sequence[Sequence[int]],
                   n_real: Optional[int], bucket: Optional[int]) -> int:
        """The quantized shared-trunk extent both cascade phases key on:
        all-rows LCP, snapped DOWN to the trunk_quantum grid, clamped
        strictly inside the bucket, floored at min_trunk; 0 when the
        dispatch is too small (min_rows) or the trunk too short."""
        cc = self.cascade_cfg
        rows_real = len(prefix_ids) if n_real is None else n_real
        if rows_real < max(cc.min_rows, 2):
            return 0
        q = max(int(cc.trunk_quantum), 1)
        if getattr(self.cfg, "layer_kinds", ()):
            # The selection's blocks and pooling kernels sit on whole
            # blocks of main keys (models/mixed.py).
            q = math.lcm(q, int(self.cfg.sparse_block))
        trunk = (tok.common_prefix_len(prefix_ids) // q) * q
        if bucket is not None and trunk >= bucket:
            trunk = ((bucket - 1) // q) * q
        if trunk < max(int(cc.min_trunk), q):
            return 0
        return trunk

    # -- cascade decode (ops/flash_decode trunk-aware splits) ---------------

    def cascade_decode_supported(self) -> bool:
        """Engine-level gate for cascade DECODE: on by config, plain
        decoder engines only, float KV only, and only where the fused
        decode kernels run at all (cfg.fused_decode on the TPU backend,
        or CPU under the interpreter when
        decoder.FUSED_DECODE_INTERPRET_ON_CPU is armed) — the
        trunk-aware split dedup lives inside flash_decode/flash_decode_mq,
        so without the fused kernels there is nothing to dedup. The
        decoder gates once more on cfg.cascade_decode (belt and braces:
        --no-cascade-decode zeroes the trunk here AND flips the static
        cfg, so stale executables can never serve the other mode)."""
        if not (self.rt.cascade_decode and not self.encoder_decoder
                and getattr(self.cfg, "fused_decode", True)
                and not getattr(self.cfg, "kv_cache_int8", False)):
            return False
        return (jax.default_backend() == "tpu"
                or decoder.FUSED_DECODE_INTERPRET_ON_CPU)

    def shared_trunk(self, prefix_ids: Optional[Sequence[Sequence[int]]],
                     n_real: Optional[int] = None,
                     bucket: Optional[int] = None) -> Tuple[int, int]:
        """(cascade-prefill trunk, decode trunk) of a dispatch whose rows
        carry these shared prefixes; 0 where that phase runs dense / flat.

        Both are the one quantized LCP (:meth:`_lcp_trunk`: the longest
        common token prefix across EVERY row — pad rows repeat a real
        row, so the all-rows LCP equals the real-rows LCP, and the
        broadcast-trunk cache layout requires the trunk to lead every
        batch row), each behind its own gate: a dispatch can cascade its
        decode steps even when the prefill runs dense (cascade prefill
        off; a paged-warm prefix), and vice versa. The extent is a
        static compiled shape — compile_plan keys executables on it.
        ``n_real`` gates the min_rows dedup check: padding repeats dedup
        for free but buy nothing."""
        prefill_ok = self.cascade_supported()
        decode_ok = self.cascade_decode_supported()
        if not prefix_ids or not (prefill_ok or decode_ok):
            return 0, 0
        trunk = self._lcp_trunk(prefix_ids, n_real, bucket)
        return (trunk if prefill_ok else 0), (trunk if decode_ok else 0)

    # -- routing: which dispatch program a dispatch runs --------------------

    def route(self, kind: str, edge: int, rows: int, groups: int,
              sfx_a: int, sfx_b: int, new_tokens: int, conf_tokens: int,
              stops_armed: bool,
              prefix_rows: Optional[Sequence[Sequence[int]]] = None,
              n_real: Optional[int] = None,
              held: Optional[Sequence[int]] = None) -> Route:
        """The :class:`Route` of one dispatch, from its facts: ``kind``
        ("shared" | "grouped"), the prefix ``edge`` it runs at, its
        PADDED member ``rows`` (and prefill ``groups``, grouped only),
        suffix bucket edges, token budgets, whether the stops are armed,
        the rows' shared prefixes (None: no rows yet, as when the
        serving ladder is warmed — no trunk then), and ``held``: the
        trunk the engine holds when the dispatch starts (its token ids;
        the plan carries ``Route.held_ids`` from each route to the next,
        a dispatch reads :attr:`held_trunk`). Everything that asks
        which program a dispatch runs or may run — the dispatch itself,
        the compile plan, the sweep's chains and prices, the batcher —
        asks here.

        A trunk is HELD (its cache an argument, the trunk program run
        once for every consecutive dispatch that starts with it) where
        it is at least half of the tokens the dispatch would prefill:
        ``trunk >= rows * (edge - trunk)``, the scheduler's grouping rule
        turned on a trunk. A dispatch too small to split at a trunk of
        its own (under ``min_rows``) takes the held one where every row
        starts with it. Under a radix cache the paged fronts own the
        trunk; across a device mesh nothing lays such a value out."""
        page = (self.prefix_cache.page_size
                if self.prefix_cache is not None else 0)
        held = None if held is None else tuple(held)
        if kind == "grouped":
            # Both formats ride one suffix edge and one decode budget.
            return Route(compile_plan.ShapeSpec(
                "grouped", int(edge), int(rows), int(groups),
                int(max(sfx_a, sfx_b)), 0,
                int(max(new_tokens, conf_tokens)), 0, bool(stops_armed),
                False), page_size=page,
                donate_first=bool(self.rt.donate_first), held_ids=held)
        trunk, dtrunk = self.shared_trunk(prefix_rows, n_real, edge)
        can_hold = (self.cascade_supported() and not page
                    and _params_span(self.params) <= 1)
        taken = bool(
            can_hold and not trunk and held is not None and prefix_rows
            and len(held) < int(edge)
            and all(tuple(r[:len(held)]) == held for r in prefix_rows))
        if taken:
            # Too few rows for a trunk of their own, and each starts
            # with the one the dispatch before them left.
            trunk = len(held)
            dtrunk = trunk if self.cascade_decode_supported() else 0
        if trunk and getattr(self.cfg, "layer_kinds", ()):
            # Behind a trunk every row's own slots must lie inside the
            # selection's local window (models/mixed.py); where they do
            # not, each row's whole prefix is its own main part.
            from ..models import mixed

            extent = generate.dispatch_extent(
                self.cfg, int(edge), (int(sfx_a), int(sfx_b)),
                (int(new_tokens), int(conf_tokens)), int(rows))
            if not mixed.tail_fits(self.cfg, trunk, extent - trunk):
                trunk = dtrunk = 0
        hold = bool(trunk) and (taken or (
            can_hold and trunk >= int(rows) * (int(edge) - trunk)))
        ids = tuple(prefix_rows[0][:trunk]) if hold else None
        return Route(
            compile_plan.ShapeSpec(
                "shared", int(edge), int(rows), 0, int(sfx_a), int(sfx_b),
                int(new_tokens), int(conf_tokens), bool(stops_armed),
                False),
            trunk=trunk, dtrunk=dtrunk,
            int8=bool(self.cascade_cfg.int8_qk),
            spec_k=int(self.rt.spec_k) if self.spec_supported() else 0,
            fleet=self._spec_draft is not None, page_size=page,
            piggyback=self.piggyback_supported(),
            plan_dense=not (self.rt.dispatch_tokens
                            and int(rows) * int(edge)
                            > self.rt.dispatch_tokens),
            donate_first=bool(self.rt.donate_first),
            held=hold, trunk_run=hold and ids != held,
            held_ids=ids if hold else held,
            kinds=bool(getattr(self.cfg, "layer_kinds", ())))

    def route_dispatch(self, d, new_tokens: int, conf_tokens: int,
                       stops_armed: bool,
                       held: Optional[Sequence[int]] = None) -> Route:
        """:meth:`route` of one scheduler.Dispatch, padded as
        decode_fused_shared / decode_fused_grouped will pad it, behind
        the trunk ``held`` by then."""
        g_pad, m_pad = d.padded_rows(self.rt.batch_size)
        shared = d.kind == "shared"
        return self.route(
            d.kind, d.edge, m_pad, 0 if shared else g_pad, d.sfx_bucket_a,
            d.sfx_bucket_b, new_tokens, conf_tokens, stops_armed,
            [it.bin_ids[:it.lcp] for it in d.items] if shared else None,
            len(d.items), held)

    def route_plan(self, dispatches, new_tokens: int, conf_tokens: int,
                   stops_armed: bool,
                   held: Optional[Sequence[int]] = None) -> List[Route]:
        """The routes of a call's dispatches, in the order they will run:
        each is made behind the trunk its predecessor leaves held, which
        is what the dispatch itself will find (:attr:`held_trunk`).
        ``held``: what the dispatch before the first of them leaves (the
        last ``Route.held_ids`` of the plan window before this one); None
        on a fresh chain (:meth:`fresh_handoff`: nothing held)."""
        routes: List[Route] = []
        for d in dispatches:
            routes.append(self.route_dispatch(d, new_tokens, conf_tokens,
                                              stops_armed, held))
            held = routes[-1].held_ids
        return routes

    def _note_cascade_decode(self, dtrunk: int, rows: int, cache,
                             new_tokens: int, conf_tokens: int) -> None:
        """Fold one trunk-aware decode dispatch into the cascade
        counters: the analytic HBM bytes the trunk dedup did NOT stream
        (trunk K/V tiles load once per decode step instead of once per
        row — profiling.cascade_decode_bytes_saved), over both format
        branches' full decode budgets, at the extent of the dispatch's
        own ``cache`` (slots are its leaves' axis 2)."""
        if not dtrunk or rows <= 1:
            return
        t0 = jax.tree.leaves(cache)[0].shape[2]
        self.cascade_stats.count("cascade_decode_dispatches")
        self.cascade_stats.count(
            "trunk_bytes_deduped",
            int(cascade_decode_bytes_saved(
                self.cfg, rows, dtrunk, t0, new_tokens + conf_tokens)))

    def _cache_aval(self):
        """ShapeDtypeStruct tree of this engine's decode cache (leaf
        structure + dtypes — bf16 vs int8 payload+scale — exactly as
        prefill produces them), the authoritative template the page
        pool materializes from. Tracing only; no device work."""
        tok_aval = jax.ShapeDtypeStruct((1, 8), jnp.int32)
        _, cache, _ = jax.eval_shape(
            lambda p, t, m: decoder.prefill(p, self.cfg, t, m, 8),
            self.params, tok_aval, tok_aval)
        return cache

    # -- cross-request prefix resume (engine/prefix_tree over models/paged) --

    def _plan_prefix_resume(self, bucket: int,
                            prefix_ids: List[Sequence[int]],
                            n_real: int) -> "_PrefixPlan":
        """Pin the deepest cached prefix of every row and decide the
        dispatch's remainder window (the exact-layout scheme —
        generate._paged_prefix): window = the smallest planned edge
        covering every row's uncached tail, anchored at the dispatch's
        longest real row; rows recompute the window's slice of their
        prefix and gather the rest from the pool at the very slots the
        right-padded prefill would use, so results stay bitwise
        identical to the unpaged path. No coverable window means the
        cache holds nothing useful — the plan degrades to the unpaged
        prefill (still inserting pages afterward, which is how the
        cache warms up in the first place)."""
        tree = self.prefix_cache
        ps = tree.page_size
        matches = [tree.lookup(bucket, ids, record=(r < n_real))
                   for r, ids in enumerate(prefix_ids)]
        plan = _PrefixPlan(bucket=bucket, prefix_ids=list(prefix_ids),
                           matches=matches, n_real=n_real)
        for r in range(n_real):
            self.prefix_stats.count("prefill_tokens_total",
                                    len(prefix_ids[r]))
        # The canonical layout is RIGHT-padded (slot = token position),
        # so the recompute window is anchored at the dispatch's LONGEST
        # REAL ROW: slots [w0, w0 + window) with w0 = max_n - window (a
        # traced scalar into the paged executable, so the anchor moves
        # per dispatch without retracing). Every row's uncached tail
        # must start at or after w0 — the window covers the WORST row
        # (a fully-paged row needs none) — and anchoring at max_n
        # instead of the bucket edge means rows shorter than the bucket
        # never recompute pad slots.
        max_n = max(len(ids) for ids in prefix_ids)
        needed = max(max((max_n - m.tokens
                          for ids, m in zip(prefix_ids, matches)
                          if m.tokens < len(ids)), default=1), 1)
        window = paged.pick_window(needed, bucket, ps)
        if window is None:
            return plan                      # cold: unpaged prefill
        w0 = max(max_n - window, 0)
        B = len(prefix_ids)
        slot_src = np.zeros((B, bucket), np.int32)
        rem_ids = []
        for r, (ids, m) in enumerate(zip(prefix_ids, matches)):
            n = len(ids)
            keep = min(m.tokens, w0, n)      # tokens resumed from pages
            for t in range(keep):
                page = m.pages[t // ps]
                slot_src[r, t] = page * ps + t % ps
            rem_ids.append(list(ids[w0:]))   # recompute [w0, n)
            if r < n_real:
                self.prefix_stats.count("hit_tokens", keep)
        rem, rem_mask = tok.right_pad_ids(rem_ids, window,
                                          tok.pad_token_id(self.tokenizer))
        plan.window = window
        plan.w0 = w0
        plan.slot_src = slot_src
        plan.rem = rem
        plan.rem_mask = rem_mask
        return plan

    def _finish_prefix_resume(self, plan: "_PrefixPlan", cache,
                              row_map: Optional[Sequence[int]] = None
                              ) -> None:
        """Insert every full, not-yet-cached prefix page of the dispatch
        into the pool from the FINAL cache (prefix slots survive both
        suffix branches untouched), then drop the dispatch's page pins.
        ``row_map`` maps plan rows to cache rows (the grouped path's
        final cache holds member rows; any member of a group carries the
        group's prefix slots). Newly inserted pages are pinned until the
        scatter lands so a tight pool can never evict-and-reallocate a
        page between its tree insert and its data write."""
        tree = self.prefix_cache
        ps = tree.page_size
        writes = []
        fresh: List[int] = []
        for r, ids in enumerate(plan.prefix_ids):
            start, new_pages = tree.plan_insert(plan.bucket, ids)
            if not new_pages:
                continue
            tree.pool.incref(new_pages)
            fresh.extend(new_pages)
            crow = r if row_map is None else row_map[r]
            # Canonical right-padded layout: slot == token position, so
            # page k's data sits at cache slots [start + k*ps, ...).
            for j, pg in enumerate(new_pages):
                writes.append((pg, crow, start + j * ps))
        tree.pool.scatter(cache, writes)
        tree.pool.decref(fresh)
        for m in plan.matches:
            tree.release(m)

    def _abort_prefix_resume(self, plan: "_PrefixPlan") -> None:
        """Dispatch failed: drop the plan's page pins without inserting
        (there is no final cache to read pages from)."""
        for m in plan.matches:
            self.prefix_cache.release(m)

    def prefill_insert(self, bucket: int,
                       prefix_ids: List[Sequence[int]]) -> int:
        """PREFILL-ONLY dispatch (disaggregated serving — serve/migrate
        .py): compute the rows' prefix KV at the ``bucket`` extent and
        insert every full page into the pool + radix tree, decoding
        NOTHING. The prefill-role replica's unit of work: the pages it
        produces are bitwise the pages a full scoring dispatch of the
        same bucket would have inserted (generate.prefill_cache +
        the same canonical right-padded layout), so a decode replica
        that imports them resumes identically to a colocated run.

        Rows already fully page-covered are skipped (a repeat prefix
        costs nothing); callers pad the row list to a stable batch
        (serve/batcher.ContinuousBatcher.prefill) the same way score
        dispatches pad, so prefill and scoring prefills share XLA
        programs per (bucket, batch) shape. Runs on the owning
        dispatch thread (the tree's single-threaded contract). Returns
        the page-aligned tokens covered for the FIRST row (the
        migration chain's request row)."""
        tree = self.prefix_cache
        assert tree is not None, \
            "prefill_insert needs the prefix cache enabled"
        ps = tree.page_size
        rows = [list(ids)[:bucket] for ids in prefix_ids]
        aligned0 = (len(rows[0]) // ps) * ps
        todo = [ids for ids in rows
                if tree.match_len(bucket, ids) < (len(ids) // ps) * ps]
        if todo:
            pad_id = tok.pad_token_id(self.tokenizer)
            toks_arr, mask = tok.right_pad_ids(todo, bucket, pad_id)
            cache = generate.prefill_cache(
                self.params, self.cfg, jnp.asarray(toks_arr),
                jnp.asarray(mask), prefill_fn=self._prefill_fn)
            writes: List[Tuple[int, int, int]] = []
            fresh: List[int] = []
            for r, ids in enumerate(todo):
                start, new_pages = tree.plan_insert(bucket, ids)
                if not new_pages:
                    continue
                # Pin fresh pages until the scatter lands (the same
                # evict-and-reallocate guard _finish_prefix_resume
                # takes on a tight pool).
                tree.pool.incref(new_pages)
                fresh.extend(new_pages)
                for j, pg in enumerate(new_pages):
                    writes.append((pg, r, start + j * ps))
            tree.pool.scatter(cache, writes)
            tree.pool.decref(fresh)
        return min(tree.match_len(bucket, rows[0]), aligned0)

    def _prefix_plan_or_none(self, bucket: int,
                             prefix_ids: List[Sequence[int]],
                             n_real: Optional[int], total: int,
                             use_prefix_cache: Optional[bool]
                             ) -> Optional["_PrefixPlan"]:
        """Gate + build the prefix plan for one dispatch. None when the
        cache is absent or the caller opted out (``use_prefix_cache``
        False; None means 'use it iff enabled on this engine')."""
        on = (use_prefix_cache if use_prefix_cache is not None
              else self.prefix_cache is not None)
        if not on or self.prefix_cache is None:
            return None
        return self._plan_prefix_resume(
            bucket, prefix_ids, total if n_real is None else n_real)

    def degrade_to_lazy(self) -> None:
        """Degradation-ladder step one (lir_tpu/faults): drop the AOT
        registry so subsequent dispatches fall back to lazy jit — a
        fresh trace excludes a corrupt precompiled executable from the
        fault hypothesis — and reset the donation chain, whose scratch
        buffer a failed dispatch may have consumed or left in an
        undefined state. Both rebuild themselves on demand; the cost is
        one re-trace per shape, paid only after a real failure."""
        self.exec_registry = None
        self.fresh_handoff()

    @property
    def cache_manifest_key(self) -> str:
        """Cache key covering model config, runtime knobs, quant mode,
        mesh, and the bucket ladder (utils/compile_cache.manifest_key) —
        the namespace under which this engine's executables are planned,
        registered, and recorded in the on-disk manifest. Two engines
        differing in ANY of those inputs get different keys, so a
        registry or warmed cache can never serve a stale configuration."""
        if self._manifest_key is None:
            import jax as _jax

            from ..utils import compile_cache

            # Params fingerprint: shapes/dtypes/shardings (never values —
            # executables bind avals only, so same-shape engines with
            # different weights may share executables; differently
            # sharded or dtyped params may not).
            leaves = _jax.tree.leaves(self.params)
            params_fp = [(tuple(getattr(l, "shape", ())),
                          str(getattr(l, "dtype", type(l).__name__)),
                          str(getattr(l, "sharding", None)))
                         for l in leaves]
            self._manifest_key = compile_cache.manifest_key(
                self.cfg, self.rt, buckets=self.buckets,
                quant=compile_cache.quant_mode(self.params),
                mesh={"devices": _jax.device_count(),
                      "platform": _jax.default_backend(),
                      "seq_mesh": self._seq_mesh_note,
                      "params": params_fp})
        return self._manifest_key

    @property
    def digit_stop_mask(self) -> Optional[jax.Array]:
        """(V,) int32 surface-class device array for the confidence early
        stop (tokens.digit_stop_classes), or None when this tokenizer can't
        provide per-token strings (or has no EOS to signal the stop with) —
        callers then decode the full budget."""
        if self._digit_stop_mask is False:
            mask = None
            if self.eos_id is not None:
                with self._tok_lock:
                    m = tok.digit_stop_classes(self.tokenizer,
                                               self.cfg.vocab_size)
                if m is not None:
                    mask = jnp.asarray(m)
            self._digit_stop_mask = mask
        return self._digit_stop_mask

    @property
    def eos_stop_mask(self) -> Optional[jax.Array]:
        """(V,) all-transparent class table (tokens.eos_only_stop_classes)
        arming a pure all-rows-emitted-EOS stop on the sweep's binary
        branch. Gated on :attr:`digit_stop_mask` being available — the
        same real-tokenizer-with-EOS condition — so content-free
        tokenizers (FakeTokenizer) stay fully stop-free on BOTH branches
        and the bench's stop-OFF comparison keeps its meaning."""
        if self.digit_stop_mask is None:
            return None
        if self._eos_stop_mask is None:
            self._eos_stop_mask = jnp.asarray(
                tok.eos_only_stop_classes(self.cfg.vocab_size))
        return self._eos_stop_mask

    @property
    def digit_table(self) -> Tuple[np.ndarray, np.ndarray]:
        """(token ids, values) of single-token integers 0..100, resolved
        once per tokenizer (feeds the weighted-confidence readout)."""
        if self._digit_table is None:
            with self._tok_lock:
                if self._digit_table is None:
                    self._digit_table = tok.integer_token_table(self.tokenizer)
        return self._digit_table

    # -- building blocks ----------------------------------------------------

    def decode_prompts(self, prompts: Sequence[str]
                       ) -> Tuple[jax.Array, jax.Array]:
        """Tokenize once, left-pad into the smallest fitting bucket, run one
        jitted greedy decode. Returns (generated (B, T_new) int32,
        step_logits (B, T_new, V) fp32)."""
        toks, mask = self._pad_batch(prompts)
        if self.encoder_decoder:
            return generate.t5_greedy_decode(
                self.params, self.cfg, toks, mask,
                max_new_tokens=self.rt.max_new_tokens)
        return generate.greedy_decode(
            self.params, self.cfg, toks, mask,
            max_new_tokens=self.rt.max_new_tokens,
            prefill_fn=self._prefill_fn)

    def decode_fused(self, prompts: Sequence[str], yes_ids: np.ndarray,
                     no_ids: np.ndarray, with_digits: bool = False,
                     max_new_tokens: Optional[int] = None,
                     pretokenized: Optional[Sequence[Sequence[int]]] = None,
                     early_stop: bool = False, eos_stop: bool = False):
        """The production scoring path: one jitted decode with the C13/D6
        readouts fused into the scan (no (B, T, V) logit stack). Decoder-only
        models only; T5 keeps the capture path (tiny vocab stacks).

        ``max_new_tokens`` overrides the runtime default (the perturbation
        sweep passes its short per-cell budget, config.RuntimeConfig).
        ``pretokenized`` skips tokenization when the caller already holds
        the token ids (the shared-prefix fallback path). ``early_stop``
        enables the confidence digit early stop (generate._fused_tail);
        ``eos_stop`` the pure all-rows-emitted-EOS stop instead
        (:attr:`eos_stop_mask` — the sweep's binary branch). Both are
        gated on tokenizer support and only valid for calls whose
        downstream readout is position-0 (+ first-integer parse for the
        digit variant)."""
        assert not self.encoder_decoder
        assert not (early_stop and eos_stop), "pick one stop rule"
        toks, mask = self._pad_batch(prompts, pretokenized)
        if with_digits:
            digit_ids, digit_vals = self.digit_table
        else:
            digit_ids = np.zeros((0,), np.int32)
            digit_vals = np.zeros((0,), np.float32)
        stop_mask = (self.digit_stop_mask if early_stop
                     else self.eos_stop_mask if eos_stop else None)
        return generate.greedy_decode_fused(
            self.params, self.cfg, toks, mask,
            jnp.asarray(yes_ids, jnp.int32), jnp.asarray(no_ids, jnp.int32),
            jnp.asarray(digit_ids), jnp.asarray(digit_vals),
            max_new_tokens=(self.rt.max_new_tokens if max_new_tokens is None
                            else max_new_tokens),
            prefill_fn=self._prefill_fn, stop_mask=stop_mask,
            eos_id=(None if stop_mask is None
                    else jnp.int32(self.eos_id)))

    def decode_fused_shared(self, binary_prompts: Sequence[str],
                            confidence_prompts: Sequence[str],
                            yes_ids: np.ndarray, no_ids: np.ndarray,
                            new_tokens: int, conf_tokens: int,
                            early_stop: bool = False,
                            pretokenized_a: Optional[Sequence[Sequence[int]]] = None,
                            pretokenized_b: Optional[Sequence[Sequence[int]]] = None,
                            bucket: Optional[int] = None,
                            sfx_buckets_ab: Optional[Tuple[int, int]] = None,
                            reuse_cache: bool = False,
                            use_prefix_cache: Optional[bool] = None,
                            n_real: Optional[int] = None):
        """Score BOTH sweep formats with ONE shared-prefix prefill.

        Each grid cell's binary and confidence prompts share the long
        rephrased legal text and differ only in the short trailing format
        instruction. Tokenize both, split every row at the longest common
        TOKEN prefix (tokenizer-agnostic — see tokens.shared_prefix_len),
        right-pad the prefixes into the standard bucket and each format's
        suffix into a small power-of-two bucket, then run the dispatch
        program (generate.greedy_decode_dispatch) the engine routes the
        batch to (:meth:`route`): one fill of the prefix + two chunked
        suffix extensions instead of two full prefills. Returns (binary
        FusedDecodeOut, confidence FusedDecodeOut).

        The ragged scheduler passes ``pretokenized_a/b`` (cells were
        tokenized once at planning time), an explicit prefix ``bucket``
        and per-bucket ``sfx_buckets_ab`` (shape stability across a
        bucket queue), and ``reuse_cache=True`` to thread the KV cache
        buffer through the dispatch chain via donation (_CacheHandoff).
        The fallback guards below still apply and win over the overrides.

        With the cross-request prefix cache enabled (``use_prefix_cache``
        True, or None on an engine whose :attr:`prefix_cache` is built),
        a ``reuse_cache`` dispatch resumes every row's shared prefix from
        the deepest cached radix node: cached pages gather from the page
        pool into the exact slots the prefill would fill and only the
        per-row remainder window is recomputed (the program's paged
        front) — results equal to the unpaged path's (tokens exact,
        floats to the last bits; bitwise in every paged slot), prefill
        FLOPs paid only for the unshared tail. Fresh full pages insert back into the pool after
        the dispatch, so reuse spans requests, batches, and sweeps.
        ``n_real`` bounds the rows counted in PrefixCacheStats (callers
        pad dispatches by repeating the last row).
        """
        assert not self.encoder_decoder
        if pretokenized_a is not None:
            bin_ids = [list(i) for i in pretokenized_a]
            conf_ids = [list(i) for i in pretokenized_b]
        else:
            with self._tok_lock:
                bin_ids = [self.tokenizer(p).input_ids
                           for p in binary_prompts]
                conf_ids = [self.tokenizer(p).input_ids
                            for p in confidence_prompts]
        lcp = [tok.shared_prefix_len(a, b)
               for a, b in zip(bin_ids, conf_ids)]
        pad_id = tok.pad_token_id(self.tokenizer)
        sfx_buckets = (8, 16, 32, 64, 128, 256)
        sfx_a_ids = [a[n:] for a, n in zip(bin_ids, lcp)]
        sfx_b_ids = [b[n:] for b, n in zip(conf_ids, lcp)]
        max_sfx = max(len(s) for s in sfx_a_ids + sfx_b_ids)
        max_total = max(len(r) for r in bin_ids + conf_ids)
        if bucket is None or bucket < max(max(n, 1) for n in lcp):
            bucket = tok.pick_bucket([max(n, 1) for n in lcp], self.buckets)
        if sfx_buckets_ab is not None:
            ba, bb = sfx_buckets_ab
            ba = max(ba, tok.pick_bucket(
                [len(s) for s in sfx_a_ids], sfx_buckets))
            bb = max(bb, tok.pick_bucket(
                [len(s) for s in sfx_b_ids], sfx_buckets))
        else:
            ba = tok.pick_bucket([len(s) for s in sfx_a_ids], sfx_buckets)
            bb = tok.pick_bucket([len(s) for s in sfx_b_ids], sfx_buckets)
        fallback_reason = None
        if max_sfx > max(sfx_buckets):
            # A suffix longer than the largest bucket would be silently
            # right-truncated — dropping the very instruction the readout
            # depends on. Prompt pairs that diverge this early share too
            # little to be worth a shared prefill anyway.
            fallback_reason = (
                f"a prompt pair diverges {max_sfx} tokens before its end "
                f"(> {max(sfx_buckets)} suffix bucket)")
        elif max_total > max(self.buckets):
            # An over-long TOTAL prompt: the plain path left-truncates the
            # whole prompt into the largest bucket, while the shared path
            # would retain prefix-bucket + suffix-bucket tokens — more
            # context, an unpinned scoring divergence between the two paths
            # (ADVICE r3 #2). The plain path owns over-long semantics.
            fallback_reason = (
                f"a prompt ({max_total} tokens) exceeds the largest "
                f"bucket ({max(self.buckets)})")
        elif (getattr(self.cfg, "pos_embedding", None) == "learned"
              and bucket + max(ba + new_tokens, bb + conf_tokens)
              > self.cfg.max_seq_len):
            # The suffix extension appends past the prefix bucket, so decode
            # positions can reach the shared-decode cache length
            # bucket + max(ba+new, bb+conf) (generate.py T0) — beyond the
            # plain-path limit the constructor's bucket trim enforces. A
            # learned-position table would be read out of range (ADVICE r3
            # #1); the plain path's trimmed buckets stay in range.
            fallback_reason = (
                f"prefix bucket {bucket} + suffix/new-token budget "
                f"{max(ba + new_tokens, bb + conf_tokens)} would overrun "
                f"the {self.cfg.max_seq_len}-row learned-position table")
        if fallback_reason is not None:
            from ..utils.logging import get_logger

            get_logger(__name__).info(
                "shared-prefix fallback: %s — scoring this whole bucket "
                "with two full prefills", fallback_reason)
            fused = self.decode_fused(binary_prompts, yes_ids, no_ids,
                                      max_new_tokens=new_tokens,
                                      pretokenized=bin_ids,
                                      eos_stop=early_stop)
            cfused = self.decode_fused(confidence_prompts, yes_ids, no_ids,
                                       with_digits=True,
                                       max_new_tokens=conf_tokens,
                                       pretokenized=conf_ids,
                                       early_stop=early_stop)
            return fused, cfused
        # Prefix rows are RIGHT-padded — the canonical slot = position
        # layout: a token's cache slot is independent of its row's
        # length, so KV pages produced by any dispatch back any later
        # row sharing the prefix BITWISE (masked tail slots contribute
        # exact zeros either way; the plain decode_fused path keeps the
        # left-padded convention, and the shared-vs-plain comparison
        # was never bitwise). The suffix extensions read per-row
        # boundaries from the mask, so a gap of masked slots between a
        # short row's prefix end and the bucket edge is a no-op.
        prefix, prefix_mask = tok.right_pad_ids(
            [a[:n] for a, n in zip(bin_ids, lcp)], bucket, pad_id)
        sfx_a, sfx_a_mask = tok.right_pad_ids(sfx_a_ids, ba, pad_id)
        sfx_b, sfx_b_mask = tok.right_pad_ids(sfx_b_ids, bb, pad_id)
        host = dict(prefix=prefix, prefix_mask=prefix_mask, sfx_a=sfx_a,
                    sfx_a_mask=sfx_a_mask, sfx_b=sfx_b,
                    sfx_b_mask=sfx_b_mask, yes_ids=yes_ids, no_ids=no_ids)
        B = len(bin_ids)
        armed = early_stop and self.digit_stop_mask is not None
        if not reuse_cache:
            # Legacy batches: the dense program, nothing looked up, no
            # cache handed over.
            dense = self.route("shared", bucket, B, 0, ba, bb, new_tokens,
                               conf_tokens, armed).shape
            (fused, cfused), _, _ = self._run_program(dense, host,
                                                      reuse=False)
            return fused, cfused
        prefix_rows = [a[:n] for a, n in zip(bin_ids, lcp)]
        rows = B if n_real is None else n_real
        route = self.route("shared", bucket, B, 0, ba, bb, new_tokens,
                           conf_tokens, armed, prefix_rows, n_real,
                           held=self.held_trunk)
        splan = None
        plan = None
        if route.held:
            # The trunk is a value: prefilled by its own program unless
            # the engine holds these very tokens' already, then read.
            if route.trunk_run:
                self._hold_trunk(route.held_ids)
            host["trunk_cache"] = self._trunk[1]
        elif route.trunk:
            # The warm trunk lives in the TRUNK-extent radix namespace
            # (pages are reproducible only within one attention extent —
            # prefix_tree's per-bucket rule — and the cascade trunk
            # prefills at extent `trunk`, not `bucket`): a one-row plan
            # over the trunk ids, whose pages the cold dispatch inserts
            # from cache row 0's broadcast trunk slots, so the SECOND
            # dispatch sharing a trunk gathers it at zero recompute.
            plan = self._prefix_plan_or_none(
                route.trunk, [prefix_rows[0][:route.trunk]], 1, 1,
                use_prefix_cache)
        else:
            if self.cascade_supported():
                self.cascade_stats.count("dense_fallbacks")
            plan = self._prefix_plan_or_none(bucket, prefix_rows, n_real, B,
                                             use_prefix_cache)
            # Speculative decode (engine/spec.py): draft each branch's
            # continuation and verify the window in one multi-query
            # forward; consumed results are bitwise the sequential
            # program's, so shedding it is always safe.
            splan = spec_mod.build_plan(self, bin_ids, conf_ids, bucket,
                                        ba, bb, new_tokens, conf_tokens)
            if (splan is not None and self.governor is not None
                    and not self.governor.allows("spec")):
                # Governor no_spec rung: a pure HBM reclaim (the spec
                # cache runs spec_k slots per window). Re-arms when
                # pressure clears.
                splan = None
                self.spec_stats.count("fallbacks")
        spec = route.spec(plan.window if plan is not None else 0,
                          speculate=splan is not None)
        if splan is not None and not spec.spec_k:
            splan = None                  # fleet draft x paged front
            self.spec_stats.count("fallbacks")
        if splan is not None:
            host.update(splan.host_arrays())
        (fused, cfused), specs, cache = self._run_program(spec, host, plan)
        if specs is not None:
            self._spec_pending.append(specs)
            self.spec_stats.count("spec_dispatches")
            self.spec_stats.count("spec_rows", rows)
        # Rows whose trunk somebody else prefilled: all but one where
        # the trunk was run for this dispatch (inside its program or by
        # the trunk program just before it), every row where the
        # dispatch took a trunk it found held.
        found = route.held and not route.trunk_run
        shared_rows = (rows if found else max(rows - 1, 0)
                       ) if route.trunk else 0
        if route.trunk:
            self.cascade_stats.count("cascade_dispatches")
            if route.held:
                self.cascade_stats.count("trunk_held_dispatches")
            # A row counts as deduped when ALL of its trunk was shared:
            # its K/V and, for a model with a mixer, its recurrent state
            # at the trunk's end (the trunk is computed once at batch 1
            # and seeds every row with both).
            self.cascade_stats.count("trunk_rows_deduped", shared_rows)
            self.cascade_stats.count(
                "prefix_flops_saved",
                int(cascade_prefill_flops_saved(self.cfg, shared_rows + 1,
                                                route.trunk)))
        # The trunk program counted its own tokens and scans.
        self._note_prefilled(route.trunk, lcp[:rows], sfx_a_ids[:rows],
                             sfx_b_ids[:rows], cache, new_tokens,
                             conf_tokens, trunk_inside=not route.held)
        self._note_cascade_decode(route.dtrunk, rows, cache, new_tokens,
                                  conf_tokens)
        # Chunked-scan windows a layer: the prefix [+ the trunk, where
        # this program runs it], then the two suffix extends.
        self._note_recurrent(
            cache, rows, new_tokens + conf_tokens,
            windows=4 if route.trunk and not route.held else 3,
            trunk_rows=shared_rows)
        return fused, cfused

    def _hit_or_lazy(self, spec: compile_plan.ShapeSpec, lazy):
        """The callable that runs ``spec``: the compile plan's executable
        (statics baked in at lower time, so it takes the dynamic
        arguments alone), else ``lazy`` — the jitted function behind the
        same argument list, which traces on first call and is always
        correct (an unplanned shape, a failed or stalled compile, a
        registry dropped by the fault ladder)."""
        exe = None
        if self.exec_registry is not None:
            exe = self.exec_registry.get(spec)
        return lazy if exe is None else exe

    def _run_program(self, spec: compile_plan.ShapeSpec, host: dict,
                     plan: Optional["_PrefixPlan"] = None,
                     row_map: Optional[Sequence[int]] = None,
                     reuse: bool = True):
        """Run ONE dispatch program (generate.greedy_decode_dispatch) for
        ``spec`` over the dispatch's ``host`` arrays: take the donation
        chain's scratch cache, build the arguments, call the plan's
        executable or the lazy jit, hand the returned cache back to the
        chain and the ledger, and let the prefix plan insert its pages
        from it (``row_map``: plan rows -> cache rows). Any exception
        drops the plan's page pins and re-raises. ``reuse`` False runs
        the program alone: no chain, no registry, no cache returned.
        Returns the program's ``(outs, specs, cache)``."""
        key = compile_plan.handoff_key(
            spec, bool(getattr(self.cfg, "layer_kinds", ())))
        scratch = self._handoff.take(key) if reuse else None
        first = scratch is None and reuse and self.rt.donate_first
        spec = dataclasses.replace(spec,
                                   scratch=first or scratch is not None)
        program = compile_plan.dispatch_program(self, spec,
                                                return_cache=reuse)

        def lazy(params, args, scratch_cache):
            return generate.greedy_decode_dispatch(
                params, self.cfg, program, args,
                scratch_cache=scratch_cache)

        try:
            if plan is not None:
                host = {**host, **plan.host_arrays()}
            args = compile_plan.dispatch_args(self, spec, host)
            run = self._hit_or_lazy(spec, lazy) if reuse else lazy
            if first:
                scratch = compile_plan.empty_scratch(run)
            outs, specs, cache = compile_plan.registry_call(
                run, self.params, args, scratch)
        except BaseException:
            if plan is not None:
                self._abort_prefix_resume(plan)
            raise
        if reuse:
            self._handoff.put(key, cache)
            self._note_handoff(cache)
        if plan is not None:
            self._finish_prefix_resume(plan, cache, row_map=row_map)
        return outs, specs, cache

    # -- chunked prefill/decode piggybacking --------------------------------

    def piggyback_supported(self) -> bool:
        """Engine-level gate for the piggyback chain: on by config, plain
        decoder engines only (T5 and seq-parallel prefills keep their own
        paths), unpaged dispatches only (the prefix-cache resume path owns
        warm traffic), and never on a fault-wrapped engine — wrap_engine
        shadows the plain entry points at the instance level, and the
        chain must not bypass the injected dispatch sites."""
        return (self.rt.piggyback_prefill
                and not self.encoder_decoder
                and self._prefill_fn is None
                and self.prefix_cache is None
                and not getattr(self.cfg, "carries_state", False)
                and "decode_fused_shared" not in self.__dict__)

    def _piggyback_fits(self, bsz: int, total_len: int) -> bool:
        """HBM headroom gate: a piggybacked pair keeps TWO dispatch caches
        live (the parked carry + the riding dispatch's own), where the
        sequential path holds one. With a governed budget the check is
        an admission against the governor's LEDGER (params, pool, pins
        and the parked carry all already counted); otherwise it falls
        back to the raw device bytes_limit. Backends without either
        (CPU) are governed by host RAM and always pass."""
        aval = self._cache_aval()  # built at batch 1, 8 slots
        per_row_slot = sum(
            leaf.size * jnp.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(aval)) / 8
        cache_bytes = per_row_slot * bsz * total_len
        if self.governor is not None:
            if not self.governor.allows("piggyback"):
                return False
            headroom = self.governor.headroom()
            if headroom is not None:
                # The ledger already carries the parked carry under
                # "handoff"; the riding dispatch's own cache (plus
                # fragmentation slack) must fit what is left.
                return 1.2 * cache_bytes < headroom
        limit = hbm.device_bytes_limit()   # None on the CPU backend only
        if limit is None:
            return True
        return (quant.param_bytes(self.params) + 2.2 * cache_bytes
                < 0.92 * limit)

    def decode_fused_shared_piggy(
            self, pretokenized_a: Sequence[Sequence[int]],
            pretokenized_b: Sequence[Sequence[int]],
            new_tokens: int, conf_tokens: int, early_stop: bool,
            bucket: int, sfx_buckets_ab: Tuple[int, int],
            prev_yes: Optional[np.ndarray] = None,
            prev_no: Optional[np.ndarray] = None):
        """Submit one shared dispatch into the piggyback chain.

        First call of a chain runs the dispatch's prefill + suffix
        extensions and PARKS its decode scans (returns None); every later
        call fuses the parked dispatch's decode scans into its own
        prefill program (ONE device call) and returns the parked
        dispatch's (binary, confidence) outputs, scored against
        ``prev_yes``/``prev_no`` — the target ids of the PARKED batch.
        Shapes/budgets must match the parked dispatch exactly (the sweep
        only chains same-shape dispatches; asserted here). Raises
        :class:`PiggybackIneligible` when this dispatch needs the plain
        path (layout fallback, learned-position ceiling at the piggyback
        cache length, or no memory headroom for two live caches)."""
        assert not self.encoder_decoder
        bin_ids = [list(i) for i in pretokenized_a]
        conf_ids = [list(i) for i in pretokenized_b]
        lcp = [tok.shared_prefix_len(a, b)
               for a, b in zip(bin_ids, conf_ids)]
        pad_id = tok.pad_token_id(self.tokenizer)
        sfx_a_ids = [a[n:] for a, n in zip(bin_ids, lcp)]
        sfx_b_ids = [b[n:] for b, n in zip(conf_ids, lcp)]
        max_sfx = max(len(s) for s in sfx_a_ids + sfx_b_ids)
        max_total = max(len(r) for r in bin_ids + conf_ids)
        sfx_buckets = scheduler_mod.SUFFIX_BUCKETS
        ba, bb = sfx_buckets_ab
        ba = max(ba, tok.pick_bucket([len(s) for s in sfx_a_ids],
                                     sfx_buckets))
        bb = max(bb, tok.pick_bucket([len(s) for s in sfx_b_ids],
                                     sfx_buckets))
        # Learned positions count real tokens, so the table check reads
        # the slots the branches can fill; the HBM gate reads the slots
        # the program allocates (generate.cache_extent).
        total_len = bucket + ba + new_tokens + bb + conf_tokens
        if (max_sfx > max(sfx_buckets)
                or max_total > max(self.buckets)
                or bucket < max(max(n, 1) for n in lcp)):
            raise PiggybackIneligible("shared-prefix layout fallback")
        if (getattr(self.cfg, "pos_embedding", None) == "learned"
                and total_len > self.cfg.max_seq_len):
            # The piggyback cache is LONGER than the sequential one
            # (disjoint branch regions), so its learned-position ceiling
            # binds earlier than the plain path's.
            raise PiggybackIneligible("learned-position table overrun")
        if (self.governor is not None
                and not self.governor.allows("piggyback")):
            # Governor no_piggyback rung engaged: the chain's second
            # live cache is the cheapest reversible HBM to give back.
            # The sweep keeps asking per dispatch, so chaining resumes
            # the moment the rung re-arms.
            raise PiggybackIneligible(
                "memory governor: piggyback disabled under pressure")
        if not self._piggyback_fits(
                len(bin_ids), generate.cache_extent(self.cfg, total_len,
                                                    len(bin_ids))):
            raise PiggybackIneligible("no HBM headroom for two caches")

        prefix, prefix_mask = tok.right_pad_ids(
            [a[:n] for a, n in zip(bin_ids, lcp)], bucket, pad_id)
        sfx_a, sfx_a_mask = tok.right_pad_ids(sfx_a_ids, ba, pad_id)
        sfx_b, sfx_b_mask = tok.right_pad_ids(sfx_b_ids, bb, pad_id)
        stop_mask = self.digit_stop_mask if early_stop else None
        armed = stop_mask is not None
        key = (bucket, len(bin_ids), ba, bb, new_tokens, conf_tokens,
               armed)
        dispatch_args = (jnp.asarray(prefix), jnp.asarray(prefix_mask),
                         jnp.asarray(sfx_a), jnp.asarray(sfx_a_mask),
                         jnp.asarray(sfx_b), jnp.asarray(sfx_b_mask))
        budgets = dict(max_new_a=new_tokens, max_new_b=conf_tokens)
        stage = (bucket, len(bin_ids), ba, bb, new_tokens, conf_tokens)
        if self._piggy is None:
            carry = self._hit_or_lazy(
                compile_plan.piggy_prefill_spec(*stage),
                lambda p, *a: generate.shared_piggyback_prefill(
                    p, self.cfg, *a, **budgets))(self.params, *dispatch_args)
            self._piggy = dict(key=key, carry=carry,
                               slot0_a=bucket + ba,
                               slot0_b=bucket + ba + new_tokens + bb,
                               new_tokens=new_tokens,
                               conf_tokens=conf_tokens, armed=armed)
            self.kernel_stats.count("chains_opened")
            return None
        assert self._piggy["key"] == key, (
            "piggyback chain shape mismatch — drain before switching "
            f"shapes ({self._piggy['key']} vs {key})")
        carry = self._piggy["carry"]
        stop_kwargs = self._piggy_stop_kwargs()
        digit_ids, digit_vals = self.digit_table
        dyn = (self.params, carry) + dispatch_args + (
            jnp.asarray(prev_yes, jnp.int32), jnp.asarray(prev_no, jnp.int32),
            jnp.asarray(digit_ids), jnp.asarray(digit_vals))
        out_a, out_b, new_carry = self._hit_or_lazy(
            compile_plan.piggy_step_spec(*stage, stops_armed=armed),
            lambda p, *a, **kw: generate.shared_piggyback_step(
                p, self.cfg, *a, **budgets, **kw))(*dyn, **stop_kwargs)
        self._piggy["carry"] = new_carry
        self.kernel_stats.count("piggybacked_steps")
        return out_a, out_b

    def _piggy_stop_kwargs(self) -> dict:
        if not self._piggy["armed"]:
            return dict(stop_mask_a=None, stop_mask_b=None, eos_id=None)
        return dict(stop_mask_a=self.eos_stop_mask,
                    stop_mask_b=self.digit_stop_mask,
                    eos_id=jnp.int32(self.eos_id))

    def piggy_pending(self) -> bool:
        return self._piggy is not None

    def piggy_drain(self, prev_yes: np.ndarray, prev_no: np.ndarray):
        """Close the chain: run the parked dispatch's decode scans alone
        and return its (binary, confidence) outputs."""
        st = self._piggy
        assert st is not None, "no piggyback chain to drain"
        digit_ids, digit_vals = self.digit_table
        key = st["key"]
        run = self._hit_or_lazy(
            compile_plan.piggy_drain_spec(
                key[0], key[1], key[2], key[3], st["new_tokens"],
                st["conf_tokens"], stops_armed=st["armed"]),
            lambda p, *a, **kw: generate.shared_piggyback_drain(
                p, self.cfg, *a, slot0_a=st["slot0_a"],
                slot0_b=st["slot0_b"], max_new_a=st["new_tokens"],
                max_new_b=st["conf_tokens"], **kw))
        dyn = (self.params, st["carry"],
               jnp.asarray(prev_yes, jnp.int32),
               jnp.asarray(prev_no, jnp.int32),
               jnp.asarray(digit_ids), jnp.asarray(digit_vals))
        stop_kwargs = self._piggy_stop_kwargs()
        self._piggy = None
        self.kernel_stats.count("chains_drained")
        return run(*dyn, **stop_kwargs)

    def piggy_abort(self) -> None:
        """Drop the chain (a failed piggyback call): the parked dispatch's
        carry may have been consumed by donation — the caller re-runs both
        dispatches through the plain path, which recomputes from scratch."""
        if self._piggy is not None:
            self.kernel_stats.count("chain_fallbacks")
        self._piggy = None

    def decode_fused_grouped(self, groups, yes_ids: np.ndarray,
                             no_ids: np.ndarray, new_tokens: int,
                             conf_tokens: int, early_stop: bool,
                             bucket: int, sfx_bucket: int,
                             reuse_cache: bool = False,
                             use_prefix_cache: Optional[bool] = None):
        """Cross-cell prefix reuse: score every member prompt of
        ``groups`` (scheduler.PrefixGroup-shaped: ``.items`` with
        ``.bin_ids``/``.conf_ids``, shared ``.plen``) with ONE prefill per
        group. Member rows are laid out [bin, conf] per cell, cells in
        group order; ``yes_ids``/``no_ids`` are per-CELL in that order.

        Returns (FusedDecodeOut over the padded member batch, real member
        row count) — callers slice even rows for the binary readout and
        odd rows for the confidence readout. Both formats run one shared
        decode budget max(new_tokens, conf_tokens); with ``early_stop``
        the binary rows take the EOS-only stop table and the confidence
        rows the digit stop (per-row selection, generate._fused_tail), so
        the extra binary steps retire the moment the row answers.
        """
        assert not self.encoder_decoder
        pad_id = tok.pad_token_id(self.tokenizer)
        prefix_ids, sfx_ids, group_idx, cell_rows = [], [], [], 0
        for g in groups:
            gi = len(prefix_ids)
            prefix_ids.append(list(g.items[0].bin_ids[:g.plen]))
            for it in g.items:
                sfx_ids.append(list(it.bin_ids[g.plen:]))
                sfx_ids.append(list(it.conf_ids[g.plen:]))
                group_idx += [gi, gi]
                cell_rows += 1
        m = len(sfx_ids)
        g_pad = _tail_batch(len(prefix_ids), self.rt.batch_size)
        m_pad = _tail_batch(m, 2 * self.rt.batch_size)
        prefix_ids += [prefix_ids[-1]] * (g_pad - len(prefix_ids))
        sfx_ids += [sfx_ids[-1]] * (m_pad - m)
        group_idx += [group_idx[-1]] * (m_pad - m)
        if max(len(p) for p in prefix_ids) > bucket:
            raise ValueError("scheduler planned a group prefix longer than "
                             "its bucket")  # planning bug, never truncate
        if (getattr(self.cfg, "pos_embedding", None) == "learned"
                and bucket + sfx_bucket + max(new_tokens, conf_tokens)
                > self.cfg.max_seq_len):
            raise ValueError("scheduler planned a grouped dispatch past the "
                             "learned-position table")

        # RIGHT-padded group prefixes — the canonical slot = position
        # layout (see decode_fused_shared): group prefix KV pages are
        # then bitwise-valid for any later dispatch sharing the trunk.
        prefix, prefix_mask = tok.right_pad_ids(prefix_ids, bucket, pad_id)
        sfx, sfx_mask = tok.right_pad_ids(sfx_ids, sfx_bucket, pad_id)
        yes2 = np.repeat(np.asarray(yes_ids, np.int32), 2)
        no2 = np.repeat(np.asarray(no_ids, np.int32), 2)
        yes2 = np.concatenate([yes2, np.repeat(yes2[-1:], m_pad - m)])
        no2 = np.concatenate([no2, np.repeat(no2[-1:], m_pad - m)])
        host = dict(prefix=prefix, prefix_mask=prefix_mask, sfx_a=sfx,
                    sfx_a_mask=sfx_mask,
                    group_idx=np.asarray(group_idx, np.int32),
                    yes_ids=yes2, no_ids=no2)
        route = self.route(
            "grouped", bucket, m_pad, g_pad, sfx_bucket, 0, new_tokens,
            conf_tokens, early_stop and self.digit_stop_mask is not None)
        if not reuse_cache:
            (out,), _, _ = self._run_program(route.shape, host, reuse=False)
            return out, m
        # Plan rows are the PADDED prefix rows; the final cache holds
        # member rows, and any member of a group carries the group's
        # prefix slots — row_map points each prefix row at its group's
        # first member row for the page extraction.
        first_member = []
        acc = 0
        for g in groups:
            first_member.append(acc)
            acc += 2 * len(g.items)
        first_member += [first_member[-1]] * (g_pad - len(groups))
        plan = self._prefix_plan_or_none(
            bucket, prefix_ids, len(groups), g_pad, use_prefix_cache)
        (out,), _, cache = self._run_program(
            route.spec(plan.window if plan is not None else 0), host, plan,
            row_map=first_member)
        # A member row is one format branch of one cell: the row gather
        # hands each REAL member its own copy of its group's state (the
        # padded rows repeat the last member's).
        self._note_recurrent(cache, rows=0,
                             steps=max(new_tokens, conf_tokens),
                             windows=2, forks=m)
        return out, m

    def decode_completion(self, generated_ids: np.ndarray) -> str:
        """Token ids -> text, stopping at the first EOS (HF generate parity —
        the fixed-length jitted decode keeps emitting after EOS; those tokens
        must not leak into response text or the confidence-integer parse)."""
        trimmed = tok.trim_at_eos(np.asarray(generated_ids).tolist(), self.eos_id)
        with self._tok_lock:
            return self.tokenizer.decode(
                trimmed, skip_special_tokens=True).strip()

    def _pad_batch(self, prompts: Sequence[str],
                   pretokenized: Optional[Sequence[Sequence[int]]] = None
                   ) -> Tuple[jax.Array, jax.Array]:
        """Tokenize + left-pad into the smallest fitting bucket."""
        if pretokenized is not None:
            ids_list = list(pretokenized)
        else:
            with self._tok_lock:
                ids_list = [self.tokenizer(p).input_ids for p in prompts]
        bucket = tok.pick_bucket([len(i) for i in ids_list], self.buckets)
        toks_arr, mask = tok.left_pad_ids(ids_list, bucket,
                                          tok.pad_token_id(self.tokenizer))
        return jnp.asarray(toks_arr), jnp.asarray(mask)

    def _sample_from_ids(self, toks: jax.Array, mask: jax.Array,
                         key: jax.Array, temperature: float,
                         max_new_tokens: Optional[int]) -> List[str]:
        return self._sample_from_ids_raw(toks, mask, key, temperature,
                                         max_new_tokens)[0]

    def _sample_from_ids_raw(self, toks: jax.Array, mask: jax.Array,
                             key: jax.Array, temperature: float,
                             max_new_tokens: Optional[int]
                             ) -> Tuple[List[str], np.ndarray]:
        """(decoded texts, raw generated ids) — callers that must know
        whether the reply finished inside the budget (EOS emitted) need the
        ids, not just the EOS-trimmed text."""
        gen = generate.sample_decode(
            self.params, self.cfg, toks, mask, key, temperature=temperature,
            max_new_tokens=(self.rt.max_new_tokens if max_new_tokens is None
                            else max_new_tokens),
            prefill_fn=self._prefill_fn,
            # HF/API-parity EOS stop: a finished row emits EOS fill (so
            # the finished-inside-budget signal this method documents is
            # preserved) and an all-done batch skips the remaining
            # forwards; unfinished rows are bit-identical to the
            # unstopped sampler.
            eos_id=(None if self.eos_id is None
                    else jnp.int32(self.eos_id)))
        gen = np.asarray(jax.device_get(gen))
        return ([self.decode_completion(gen[j])
                 for j in range(gen.shape[0])], gen)

    def sample_completions(self, prompts: Sequence[str], key: jax.Array,
                           temperature: float = 1.0,
                           max_new_tokens: Optional[int] = None) -> List[str]:
        """One temperature-sampled completion per prompt (single jitted
        call; same bucketing as the greedy paths)."""
        toks, mask = self._pad_batch(prompts)
        return self._sample_from_ids(toks, mask, key, temperature,
                                     max_new_tokens)

    def sample_completions_with_ids(
            self, prompts: Sequence[str], key: jax.Array,
            temperature: float = 1.0,
            max_new_tokens: Optional[int] = None
    ) -> Tuple[List[str], np.ndarray]:
        toks, mask = self._pad_batch(prompts)
        return self._sample_from_ids_raw(toks, mask, key, temperature,
                                         max_new_tokens)

    # -- public API ---------------------------------------------------------

    def score_prompts_sampled(
        self, prompts: Sequence[str],
        target_texts: Sequence[Tuple[str, str]],
        n_runs: int = 10, key: Optional[jax.Array] = None,
        temperature: float = 1.0,
        max_new_tokens: Optional[int] = None,
    ) -> List[SampledScore]:
        """Reasoning-model scoring: n sampled runs per prompt, answer-count
        averaging (VERDICT r1 #7; perturb_prompts.py:412-446 locally).

        ``key`` may be per-prompt keys shaped (B, 2): each prompt then owns
        its PRNG stream, so results do not depend on batch composition (the
        sweep keys rows by grid-cell identity -> resume-deterministic).

        The reference's reasoning models expose no logprobs, so it samples
        each binary prompt REASONING_MODEL_RUNS times (API default
        temperature) and sets Token_i_Prob = (runs whose text contains
        target_i) / n_runs, if/elif order — a text containing both targets
        (e.g. "Not Covered" contains "Covered") counts toward token 1 only;
        the stored response is the most common run text. Runs loop outside
        jit on purpose: vmapping the decode over runs would multiply the KV
        cache by n_runs (a 7B batch-32 cache is ~4.5 GB — x10 cannot fit
        HBM); each run reuses the same compiled sample_decode executable.
        """
        if key is None:
            key = jax.random.PRNGKey(0)
        per_row = generate.is_per_row_keys(key)  # per-prompt streams
        all_runs: List[List[str]] = [[] for _ in prompts]
        # Tokenize/pad ONCE; only the PRNG key varies across runs.
        toks, mask = self._pad_batch(prompts)
        for run in range(n_runs):
            if per_row:
                run_key = jax.vmap(
                    lambda k: jax.random.fold_in(k, run))(key)
            else:
                run_key = jax.random.fold_in(key, run)
            texts = self._sample_from_ids(
                toks, mask, run_key, temperature, max_new_tokens)
            for j, t in enumerate(texts):
                all_runs[j].append(t.strip())

        out: List[SampledScore] = []
        for j, prompt in enumerate(prompts):
            t1, t2 = target_texts[j]
            p1, p2, most_common = score.count_averaged_responses(
                all_runs[j], t1, t2)
            out.append(SampledScore(
                prompt=prompt,
                response=most_common,
                all_responses=list(all_runs[j]),
                token_1_prob=p1,
                token_2_prob=p2,
                odds_ratio=(p1 / p2) if p2 > 0 else float("inf"),
            ))
        return out

    def score_prompts(self, prompts: Sequence[str]) -> List[PromptScore]:
        """Score every prompt; one jitted call per full batch."""
        order = np.argsort([len(p) for p in prompts], kind="stable")
        rows: List[Optional[PromptScore]] = [None] * len(prompts)
        B = self.rt.batch_size
        for start in range(0, len(order), B):
            idx = order[start:start + B]
            batch_prompts = [prompts[i] for i in idx]
            rows_out = self._score_batch(batch_prompts)
            for i, r in zip(idx, rows_out):
                rows[i] = r
        return rows  # type: ignore[return-value]

    def _score_batch(self, batch_prompts: List[str]) -> List[PromptScore]:
        n = len(batch_prompts)
        B = self.rt.batch_size
        # Tail bucket: pad to the next power of two, not the full B (at most
        # one extra compile; stops re-scoring the last prompt B-n times).
        bsz = B if n == B else _tail_batch(n, B)
        padded_prompts = batch_prompts + [batch_prompts[-1]] * (bsz - n)

        if self.encoder_decoder:
            gen, step_logits = self.decode_prompts(padded_prompts)
            res = score.readout_from_step_logits(
                step_logits, gen, jnp.int32(self.yes_id),
                jnp.int32(self.no_id), scan_positions=self.rt.scan_positions)
        else:
            yes_ids = np.full((bsz,), self.yes_id, np.int32)
            no_ids = np.full((bsz,), self.no_id, np.int32)
            fused = self.decode_fused(padded_prompts, yes_ids, no_ids)
            res = score.readout_from_fused(
                fused, jnp.asarray(yes_ids), jnp.asarray(no_ids),
                scan_positions=self.rt.scan_positions)

        res = jax.device_get(res)
        out = []
        for j in range(n):
            out.append(PromptScore(
                prompt=batch_prompts[j],
                completion=self.decode_completion(res.generated[j]),
                yes_prob=float(res.yes_prob[j]),
                no_prob=float(res.no_prob[j]),
                yes_logprob=float(res.yes_logprob[j]),
                no_logprob=float(res.no_logprob[j]),
                odds_ratio=float(res.odds_ratio[j]),
                relative_prob=float(res.relative_prob[j]),
                position_found=int(res.position_found[j]),
                yes_no_found=bool(res.yes_no_found[j]),
            ))
        return out
