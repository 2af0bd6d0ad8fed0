"""Scoring server: the supervisor loop tying queue, cache, and batcher
together into a long-running service.

Lifecycle semantics (the graceful-degradation contract):

- Every admitted request resolves with SOME status. Deadline-exceeded
  rows return partial confidence-free results rather than failing their
  batch; shed rows resolve immediately at submit.
- Device dispatches run under the serve retry policy
  (config.ServeConfig.retry: short, full-jitter, elapsed-capped —
  utils/retry.py) so one transient XLA/runtime hiccup never surfaces to
  clients.
- A dispatch that exhausts its retries enters the DEGRADATION LADDER
  (faults/ladder.py): drop the AOT registry (lazy jit re-trace excludes
  a corrupt precompiled executable), retry the batch once, then bisect
  to isolate poison rows — only the culprit rows resolve as errors, the
  rest are scored, and one pathological request can no longer take its
  neighbors (or, re-queued with new neighbors, the whole service) down.
- After ``max_consecutive_failures`` full dispatch failures in a row the
  CIRCUIT BREAKER opens (faults/breaker.py): the queue drains with error
  results and submits shed — but after ``breaker_cooldown_s`` the
  breaker goes half-open, admits traffic, and probes the device with the
  next dispatch; success closes it (healthy again, no restart needed),
  failure re-opens it for another cooldown. :attr:`healthy` reads the
  breaker, so external supervisors keep their liveness signal.
- On SIGTERM (preemption warning), :meth:`shutdown_checkpoint` stops the
  supervisor WITHOUT finishing the backlog and writes every unresolved
  request to an atomic JSON checkpoint; a restarted server re-submits
  them via :meth:`resume_from_checkpoint` — zero lost requests across a
  preemption, dedup-deduplicated against anything already served.

Dedup rides in front of admission: a submit whose content address is
already cached resolves without touching the queue or the device —
perturbation-style traffic re-asks near-identical questions constantly,
so this is the cheapest capacity the serving layer has.
"""

from __future__ import annotations

import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

from ..config import ServeConfig, TierConfig
from ..engine import compile_plan
from ..engine import hbm
from ..engine import scheduler as sched_mod
from ..engine import stream_stats
from ..engine import tokens as tok
from ..faults import (CLOSED, HALF_OPEN, CircuitBreaker, degrade_dispatch,
                      is_program_error)
from ..guard import numerics
from ..observe import registry as metrics_mod
from ..observe import tracing
from ..utils.logging import get_logger
from ..utils.manifest import atomic_write_json
from ..utils.profiling import FaultStats, ServeStats
from ..utils.retry import retry_with_exponential_backoff
from . import migrate as migrate_mod
from . import tiers as tiers_mod
from .batcher import ContinuousBatcher, FleetBatcher
from .cache import ResultCache, content_key
from .queue import (STATUS_ERROR, STATUS_EXPIRED, STATUS_OK, STATUS_SHED,
                    Pending, RequestQueue, ServeFuture, ServeRequest,
                    ServeResult)

log = get_logger(__name__)

CHECKPOINT_VERSION = 1


class ScoringServer:
    """Continuous-batching scoring service over one ScoringEngine.

    ``precompile=True`` AOT-compiles every (ladder edge x suffix edge x
    padded batch) shared executable at boot (compile_plan.sweep_specs_
    for_ladder with serve_batches — background threads, lazy-jit
    fallback on any miss), so no request ever pays a trace.
    """

    def __init__(self, engine, model_name: str,
                 config: Optional[ServeConfig] = None,
                 stats: Optional[ServeStats] = None,
                 clock: Callable[[], float] = time.monotonic,
                 precompile: bool = False,
                 tiers: Optional[TierConfig] = None):
        self.engine = engine
        self.model_name = model_name
        self.config = config or ServeConfig()
        self.stats = stats if stats is not None else ServeStats()
        self.clock = clock
        self.queue = RequestQueue(self.config.queue_depth, self.stats,
                                  clock)
        self.cache = ResultCache(self.config.cache_entries, self.stats)
        # Cross-request radix prefix cache (ServeConfig.prefix_cache, ON
        # by default): build the engine's page pool + radix index before
        # the batcher snapshots it; every dispatch then pays prefill
        # only for its rows' unshared suffixes, across requests and
        # batches, with results bitwise-identical to the unpaged path.
        if self.config.prefix_cache:
            engine.enable_prefix_cache()
        self.batcher = ContinuousBatcher(engine, self.stats,
                                         self.config.linger_s, clock,
                                         pad_full=self.config.pad_full,
                                         prefix_cache=self.config.prefix_cache)
        self.faults = FaultStats()
        # Live streaming statistics (engine/stream_stats.ServeStreamSink):
        # every OK-resolved payload folds once (keyed by content
        # address — idempotent across checkpoint/resume and dedup) into
        # a bounded ring, so the `stats` endpoint answers in-progress
        # percentile/kappa estimates mid-run without touching the
        # device. Gated on RuntimeConfig.streaming_stats.
        self.stream = None
        if (getattr(engine.rt, "streaming_stats", False)
                and self.config.stream_window > 0):
            self.stream = stream_stats.ServeStreamSink(
                window=self.config.stream_window)
        # The first program error a dispatch raised (_dispatch_refused).
        self.program_error: Optional[BaseException] = None
        self.breaker = CircuitBreaker(
            failure_threshold=self.config.max_consecutive_failures,
            cooldown_s=self.config.breaker_cooldown_s,
            clock=clock, stats=self.faults)
        # Unified telemetry spine (lir_tpu/observe): every stats object
        # this server touches registers into ONE MetricsRegistry, read
        # live by the {"op": "metrics"} JSONL endpoint and logged at
        # exit. The snapshot carries the per-device HBM gauges too, so
        # memory pressure is observable before anything OOMs.
        self.metrics = metrics_mod.MetricsRegistry()
        self.metrics.register("serve", self.stats)
        self.metrics.register("serve_faults", self.faults)
        metrics_mod.engine_registry(engine, sink=self.stream,
                                    registry=self.metrics)
        # Tiered KV residency (serve/tiers.py; config.TierConfig): the
        # governor's reclaim rungs DEMOTE radix pages down the
        # HBM -> pinned-host -> disk ladder instead of deleting them,
        # and a fresh process reseeds its radix tree from the disk tier
        # before taking traffic (restart-warm). Requires the prefix
        # cache — the tiers store PageExports of its radix paths.
        self.tiers: Optional[tiers_mod.TieredPageStore] = None
        if (tiers is not None and tiers.enabled
                and self.config.prefix_cache):
            self.tiers = tiers_mod.TieredPageStore(tiers, clock=clock)
            engine.attach_tiers(self.tiers)
            self.metrics.register("tiers", self.tiers.stats)
            if tiers.restart_warm and self.tiers.disk is not None:
                # Constructor runs before start(): the supervisor
                # thread does not exist yet, so importing into the
                # radix tree here honors its single-thread contract.
                n = self.tiers.reseed(engine)
                if n:
                    log.info("serve: restart-warm — reseeded %d KV "
                             "pages from the disk tier", n)
        rec = tracing.get_recorder()
        if rec is not None:
            self.metrics.register("trace", rec)
        self._engine_key = engine.cache_manifest_key
        # Target-token memo: written from EVERY submitter thread (submit
        # runs client-side), so its mutations take a dedicated lock —
        # racing dict writes are benign under today's GIL but the
        # guarded-by convention is enforced statically (lint/locks.py),
        # not by interpreter implementation details.
        self._memo_lock = threading.Lock()
        self._target_memo: Dict[
            Tuple[str, str], Tuple[int, int]] = {}  # guarded-by: _memo_lock
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._abort = False          # stop WITHOUT draining (checkpoint)
        self._inflight: List[Pending] = []
        # Page ops (serve/migrate.py): tree/pool work queued by the
        # disaggregation router — prefill-only dispatches, page
        # exports, page imports — drained on the supervisor thread
        # ahead of dispatch formation, so every radix-tree touch stays
        # on the one thread the tree's contract allows.
        self._page_lock = threading.Lock()
        self._page_ops: List[migrate_mod.PageOp] = []  # guarded-by: _page_lock
        engine.fresh_handoff()     # fresh donation chain per session
        if precompile and engine.rt.aot_precompile:
            # pad_full pins every dispatch to the full batch shape, so
            # only that shape needs warming; tail mode warms the whole
            # power-of-two grid.
            batches = ((engine.rt.batch_size,) if self.config.pad_full
                       else compile_plan.serve_batches(
                           engine.rt.batch_size))
            specs = compile_plan.sweep_specs_for_ladder(
                engine, sfx_buckets=(8, 16), batches=batches)
            engine.exec_registry = compile_plan.precompile_async(
                engine, specs, max_workers=engine.rt.precompile_workers)
            log.info("serve: precompiling %d executable shapes in the "
                     "background", len(specs))

    @property
    def healthy(self) -> bool:
        """True while the circuit breaker is CLOSED and no dispatch was
        refused as a program error. Half-open (probing after a cooldown)
        reads unhealthy to external supervisors but already admits
        traffic — a probe success flips this back True without a
        restart; a refused program stays refused until the code
        changes."""
        return self.breaker.state == CLOSED and self.program_error is None

    @property
    def queue_depth(self) -> int:
        """Admitted-but-undispatched rows (queue + bucketed) — the
        router's load signal (serve/router.py). Best-effort while the
        supervisor runs; placement only needs relative ordering."""
        return len(self.queue) + self.batcher.pending_rows

    def oldest_wait(self, now: Optional[float] = None) -> float:
        """Oldest bucketed-row wait in seconds (router SLO signal)."""
        return self.batcher.oldest_wait(self.clock() if now is None
                                        else now)

    @property
    def hbm_pressure(self) -> float:
        """HBM-governor ledger pressure (router placement signal —
        serve/router.py; 0.0 when ungoverned/unbounded)."""
        gov = getattr(self.engine, "governor", None)
        return 0.0 if gov is None else float(gov.pressure())

    # -- client side ---------------------------------------------------------

    def _target_ids(self, targets: Tuple[str, str]) -> Tuple[int, int]:
        with self._memo_lock:
            ids = self._target_memo.get(targets)
        if ids is None:
            with self.engine._tok_lock:
                t1, t2 = tok.target_token_ids(
                    self.engine.tokenizer, targets,
                    encoder_decoder=self.engine.encoder_decoder)
            ids = (int(t1), int(t2))
            with self._memo_lock:
                self._target_memo[targets] = ids
        return ids

    def submit(self, request: ServeRequest) -> ServeFuture:
        """Admit one request; returns a future that resolves with a
        ServeResult (possibly immediately: dedup hit, shed, breaker
        open). Tokenization runs here on the caller's thread, keeping
        the supervisor loop on the device's critical path only."""
        with tracing.span("serve/admit", request_id=request.request_id):
            return self._submit(request)

    def _submit(self, request: ServeRequest) -> ServeFuture:
        self.stats.count("submitted")
        fut = ServeFuture()
        now = self.clock()
        key = content_key(self._engine_key, request)
        if self.cache.max_entries > 0:
            hit = self.cache.get(key)
            if hit is not None:
                self.stats.count("completed")
                self.stats.record_latency(self.clock() - now)
                fut.resolve(ServeResult(
                    request_id=request.request_id, status=STATUS_OK,
                    cached=True, latency_s=self.clock() - now, **hit))
                return fut
        if not self.breaker.allow():
            self.stats.count("shed")
            fut.resolve(ServeResult(
                request_id=request.request_id, status=STATUS_SHED,
                note="server unhealthy — circuit breaker open "
                     f"(cooldown {self.config.breaker_cooldown_s:.1f}s)"))
            return fut
        gov = getattr(self.engine, "governor", None)
        if gov is not None and gov.should_shed():
            # Terminal backpressure rung of the HBM degradation ladder
            # (engine/hbm.py): memory is not coming back this tick, so
            # refuse loudly instead of queueing behind it. Re-arms
            # (stops shedding) the moment pressure clears.
            self.stats.count("shed")
            fut.resolve(ServeResult(
                request_id=request.request_id, status=STATUS_SHED,
                note=f"memory pressure — HBM governor shedding "
                     f"(pressure {gov.pressure():.2f}, engaged rungs: "
                     f"{','.join(gov.engaged_rungs())})"))
            return fut
        with self.engine._tok_lock:
            bin_ids = tuple(int(i) for i in self.engine.tokenizer(
                request.binary_prompt).input_ids)
            conf_ids = tuple(int(i) for i in self.engine.tokenizer(
                request.confidence_prompt).input_ids)
        lcp = tok.shared_prefix_len(bin_ids, conf_ids)
        t1, t2 = self._target_ids(tuple(request.targets))
        deadline = (request.deadline_s if request.deadline_s is not None
                    else self.config.deadline_for(request.klass))
        bucket = tok.assign_bucket(max(lcp, 1), self.engine.buckets)
        # Admission-time radix probe (read-only, no pins): how much of
        # this request's shared prefix is already resident — feeds the
        # batcher's prefix-aware bucket pricing; the dispatch re-looks
        # up with a pin.
        cached_hint = 0
        if self.batcher.prefix_cache:
            cached_hint = self.engine.prefix_cache.match_len(
                bucket, bin_ids[:lcp])
            # Tier promote probe: when the host/disk ladder holds a
            # DEEPER prefix than HBM, queue a promote op ahead of this
            # request's dispatch — the ordinary paged-warm import fills
            # exactly the missing tail (bitwise), and the dispatch's
            # pinned re-lookup sees the promoted pages. Advisory like
            # cached_hint: a promote that loses the race (entry
            # dropped, checksum refusal, disk stall) just means plain
            # prefill.
            if self.tiers is not None:
                prefix = bin_ids[:lcp]
                if self.tiers.match_len(bucket, prefix) > cached_hint:
                    store = self.tiers
                    self.submit_page_op(
                        lambda eng: store.promote(eng, bucket, prefix))
        pending = Pending(
            request=request, future=fut, t_submit=now,
            t_deadline=now + deadline, bin_ids=bin_ids, conf_ids=conf_ids,
            lcp=lcp, bucket=bucket,
            t1=t1, t2=t2, cache_key=key, cached_hint=cached_hint)
        self.queue.offer(pending)
        return fut

    # -- supervisor side -----------------------------------------------------

    def start(self) -> "ScoringServer":
        assert self._thread is None, "server already started"
        self._thread = threading.Thread(target=self._loop,
                                        name="serve-supervisor",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: Optional[float] = None) -> None:
        """Drain: finish everything queued (flushing partial buckets),
        then stop the supervisor."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    def _loop(self) -> None:
        while True:
            stopping = self._stop.is_set()
            if stopping and self._abort:
                return           # checkpoint path: leave the backlog be
            self._drain_page_ops()
            for p in self.queue.drain():
                self.batcher.admit(p)
            d = self.batcher.next_dispatch(self.clock(), flush=stopping)
            if d is None:
                if (stopping and len(self.queue) == 0
                        and self.batcher.pending_rows == 0):
                    return
                # Lingering rows need sub-window wakeups; an idle server
                # can sleep longer (still bounded so stop() is prompt).
                self.queue.wait_nonempty(
                    0.005 if self.batcher.pending_rows else 0.05)
                continue
            self._dispatch(*d)

    def stream_summary(self) -> Dict:
        """Live streaming-statistics estimates (the `stats` endpoint):
        percentile/kappa over the last stream_window served rows. Safe
        from any thread; empty dict when the sink is disabled."""
        if self.stream is None:
            return {}
        return self.stream.summary()

    # -- page ops (disaggregated serving — serve/migrate.py) -----------------

    def submit_page_op(self, fn) -> migrate_mod.OpFuture:
        """Queue ``fn(engine)`` for the supervisor thread (drained
        ahead of dispatch formation each loop turn) — the seam the
        disaggregation router's handoff chain runs page exports/imports
        through, so every tree/pool mutation happens on this server's
        one dispatch thread. Returns the op's completion future
        (callbacks fire on the supervisor thread)."""
        op = migrate_mod.PageOp(fn)
        with self._page_lock:
            self._page_ops.append(op)
        self.queue.kick()            # wake an idle supervisor now
        return op.future

    def submit_prefill(self, bucket: int,
                       prefix_ids) -> migrate_mod.OpFuture:
        """Queue a PREFILL-ONLY dispatch over one token prefix (the
        prefill-role replica's unit of work): compute the prefix KV at
        ``bucket`` and insert full pages into this replica's pool +
        radix tree, decoding nothing (serve/batcher.prefill). The
        future resolves with the page-aligned tokens covered."""
        ids = tuple(int(t) for t in prefix_ids)
        return self.submit_page_op(
            lambda eng: self.batcher.prefill(int(bucket), [ids]))

    def _drain_page_ops(self) -> None:
        while True:
            with self._page_lock:
                if not self._page_ops:
                    return
                op = self._page_ops.pop(0)
            op.run(self.engine)

    def _resolve_ok(self, p: Pending, payload: Dict, now: float) -> None:
        self.cache.put(p.cache_key, payload)
        if self.stream is not None:
            # Fold AFTER the row survived the numerics guard, BEFORE the
            # future resolves — keyed by content address, so a
            # checkpoint-resumed or deadline-cancelled-then-resubmitted
            # row can never fold twice.
            self.stream.fold_payload(p.cache_key,
                                     tuple(p.request.targets), payload)
        latency = now - p.t_submit
        self.stats.count("completed")
        if now > p.t_deadline:
            self.stats.count("late")
        self.stats.record_latency(latency)
        p.future.resolve(ServeResult(
            request_id=p.request.request_id, status=STATUS_OK,
            latency_s=latency, **payload))

    def _resolve_payload(self, p: Pending, payload: Dict,
                         now: float) -> None:
        """One scored row crosses the guard boundary: numerics-invalid
        payloads are QUARANTINED as error:numerics (the ladder's poison-
        row semantics — neighbors untouched, only the corrupt row is
        withheld); rows whose future already resolved (deadline passed
        mid-dispatch — see :meth:`_cancel_expired_inflight`) drop their
        payload; everything else resolves ok."""
        reason = None
        if self.engine.rt.numerics_guard:
            self.engine.guard_stats.site("checked", "serve")
            reason = numerics.check_payload(payload)
        if reason is not None:
            self.engine.guard_stats.quarantine("serve", reason)
            self.stats.count("errors")
            log.warning("numerics guard: quarantined request %s (%s)",
                        p.request.request_id, reason)
            p.future.resolve(ServeResult(
                request_id=p.request.request_id, status=STATUS_ERROR,
                note=f"{numerics.NUMERICS_ERROR} — {reason} "
                     f"(row quarantined by the numerics guard)",
                latency_s=now - p.t_submit))
            return
        if p.future.done():
            return          # expired mid-dispatch; partial already sent
        self._resolve_ok(p, payload, now)

    def _cancel_expired_inflight(self) -> None:
        """Watchdog tick callback, run on the supervisor thread while a
        WATCHED dispatch is on the device: a request whose deadline
        passes mid-dispatch resolves its partial (confidence-free)
        result IMMEDIATELY instead of waiting out the device call — the
        deadline is now enforced against wall time, not against
        whenever the dispatch happens to return."""
        now = self.clock()
        for p in self._inflight:
            if not p.future.done() and now >= p.t_deadline:
                self.stats.count("expired")
                self.engine.guard_stats.count("inflight_cancelled")
                p.future.resolve(ServeResult(
                    request_id=p.request.request_id,
                    status=STATUS_EXPIRED,
                    note=f"deadline passed mid-dispatch (waited "
                         f"{now - p.t_submit:.3f}s; dispatch watched, "
                         f"partial resolved without waiting it out)",
                    latency_s=now - p.t_submit))

    def _dispatch(self, bucket: int, rows) -> None:
        probing = self.breaker.state == HALF_OPEN
        attempts = {"n": 0}
        gov = getattr(self.engine, "governor", None)

        def call():
            attempts["n"] += 1
            try:
                return self.batcher.score(bucket, rows)
            except Exception as err:  # noqa: BLE001 — classified below
                from ..utils.profiling import is_oom_error

                if gov is not None and is_oom_error(err):
                    # Capacity, not transience: lift the OOM out of the
                    # generic retry loop (BaseException marker) so it
                    # reaches the governor's reclaim-and-retry without
                    # burning retries or feeding the breaker.
                    raise hbm.OomSignal(err) from err
                raise

        # Watched executor (guard/watchdog): the dispatch runs on a
        # watched thread priced by the SAME bucket_cost model the
        # batcher formed it with. A hang surfaces DispatchStalled into
        # the retry -> ladder -> breaker path below, and the tick
        # callback resolves deadline-expired rows partial mid-dispatch.
        wd = getattr(self.engine, "watchdog", None)
        if wd is not None and wd.enabled:
            cost = sched_mod.bucket_cost(
                len(rows), bucket, self.engine.rt.batch_size,
                self.batcher.decode_cost,
                fused_decode=self.batcher.fused_decode)
            dispatch_call = lambda: wd.watch(  # noqa: E731
                call, cost=cost, site="serve",
                on_tick=self._cancel_expired_inflight)
        else:
            dispatch_call = call

        # Per-request queue-wait spans: the slice of each row's life
        # between admission and this dispatch forming (t_submit is in
        # the recorder's time.monotonic domain — the serve clock).
        now0 = self.clock()
        for p in rows:
            tracing.add_span("serve/queue_wait", p.t_submit, now0,
                             request_id=p.request.request_id,
                             bucket=int(bucket))
        self._inflight = list(rows)
        try:
            try:
                payloads = retry_with_exponential_backoff(
                    dispatch_call, retry_on=(Exception,),
                    config=self.config.retry,
                    log=lambda m: log.warning("serve dispatch retry: %s",
                                              m),
                    clock=self.clock, give_up=is_program_error)
            except (KeyboardInterrupt, SystemExit):
                raise
            except hbm.OomSignal as sig:
                # Device OOM: governor reclaim + ONE retry; the breaker
                # never hears about it either way (capacity is not
                # device death — the same bypass guard/numerics errors
                # get). A second OOM quarantines only this dispatch.
                payloads = self._dispatch_oom(bucket, rows, sig.err,
                                              gov)
                if payloads is None:
                    return
            except Exception as err:  # noqa: BLE001 — degrade, never crash
                if is_program_error(err):
                    # Refused by the tracer/compiler: no retry, lazy-jit
                    # fallback or bisection can change that.
                    self._dispatch_refused(rows, err)
                else:
                    self._dispatch_failed(bucket, rows, err, probing)
                return
            if attempts["n"] > 1:
                # Transient fault outlived by the retry policy alone.
                self.faults.count("recovered_dispatches")
            self.breaker.record_success()
            now = self.clock()
            with tracing.span("serve/resolve", rows=len(rows)):
                for p, payload in zip(rows, payloads):
                    self._resolve_payload(p, payload, now)
        finally:
            self._inflight = []

    def _dispatch_oom(self, bucket: int, rows, err: BaseException,
                      gov) -> Optional[List[Dict]]:
        """Serve-path OOM routing (engine/hbm.py): force-engage the
        governor's reclaim rungs and retry the dispatch ONCE against
        the freed headroom. Success returns the payloads (the caller
        resolves them normally — the breaker sees a success). Failure
        quarantines ONLY this dispatch: its rows resolve as errors
        carrying the full ledger arithmetic, and the breaker's
        consecutive-failure count is NOT advanced — an undersized
        budget must not walk the server into an outage drain the way
        three unlucky big dispatches otherwise would."""
        log.warning("serve: dispatch OOMed (%r); routing through the "
                    "HBM governor", err)
        if gov.handle_oom("serve"):
            try:
                payloads = self.batcher.score(bucket, rows)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as err2:  # noqa: BLE001 — quarantined below
                err = err2
                gov.stats.count("oom_exhausted")
            else:
                self.faults.count("recovered_dispatches")
                self.breaker.record_success()
                return payloads
        note = gov.oom_message("serve", err)
        now = self.clock()
        self.stats.count("errors", len(rows))
        log.error("serve: %s", note)
        for p in rows:
            p.future.resolve(ServeResult(
                request_id=p.request.request_id, status=STATUS_ERROR,
                note=note, latency_s=now - p.t_submit))
        return None

    def _dispatch_refused(self, rows, err: BaseException) -> None:
        """A program error (faults.is_program_error): the dispatch ends
        here with that error on every row — attempted once, never
        recompiled under back-off, never bisected into "poison" rows —
        and the server reads unhealthy from now on (``program_error``),
        so the serve CLI exits non-zero instead of answering every
        later request the same way."""
        self.program_error = err
        now = self.clock()
        self.stats.count("errors", len(rows))
        log.error("serve: dispatch refused by the tracer/compiler (not "
                  "retried, not bisected): %r", err)
        for p in rows:
            p.future.resolve(ServeResult(
                request_id=p.request.request_id, status=STATUS_ERROR,
                note=f"program error (not retried): {err!r}",
                latency_s=now - p.t_submit))
        self.breaker.record_failure()

    def _dispatch_failed(self, bucket: int, rows, err: BaseException,
                         probing: bool) -> None:
        """Retries exhausted on the full batch: run the degradation
        ladder (unless this was a half-open probe — a probe exists to
        test the device cheaply, not to bisect during an outage), and
        only on TOTAL failure fall through to the breaker."""
        if self.config.degrade_ladder and not probing:
            self.faults.count("degraded_dispatches")
            self.engine.degrade_to_lazy()
            log.warning("serve: dispatch failed after retries (%r); "
                        "degrading AOT registry -> lazy jit and bisecting "
                        "%d rows", err, len(rows))
            results = degrade_dispatch(
                lambda rs: self.batcher.score(bucket, rs), rows,
                log=lambda m: log.warning("serve degrade: %s", m))
            n_ok = sum(r is not None for r in results)
            if n_ok:
                # The device works; the failure was transient or row-
                # local. Culprit rows resolve as errors, neighbors are
                # scored, the breaker sees a success.
                self.faults.count("recovered_dispatches")
                self.breaker.record_success()
                now = self.clock()
                n_poison = 0
                for p, payload in zip(rows, results):
                    if payload is None:
                        n_poison += 1
                        self.stats.count("errors")
                        p.future.resolve(ServeResult(
                            request_id=p.request.request_id,
                            status=STATUS_ERROR,
                            note=f"poison row isolated by the degradation "
                                 f"ladder: {err!r}",
                            latency_s=now - p.t_submit))
                    else:
                        self._resolve_payload(p, payload, now)
                if n_poison:
                    self.faults.count("degraded_rows", n_poison)
                    log.warning("serve: degradation ladder isolated %d "
                                "poison row(s) out of %d; dispatch "
                                "recovered", n_poison, len(rows))
                return
        # Total failure: every row errors, the breaker counts it.
        now = self.clock()
        self.stats.count("errors", len(rows))
        for p in rows:
            p.future.resolve(ServeResult(
                request_id=p.request.request_id, status=STATUS_ERROR,
                note=f"device error after retries: {err!r}",
                latency_s=now - p.t_submit))
        opened = self.breaker.record_failure()
        log.warning("serve: dispatch failed (%d consecutive, breaker %s)"
                    ": %r", self.breaker.consecutive_failures,
                    self.breaker.state, err)
        if opened:
            self._drain_open(err)

    def _drain_open(self, err: BaseException) -> None:
        """The breaker just opened: resolve every waiting request with an
        error result — fail fast and visibly instead of queueing behind
        a device that is not answering. Submits shed until the half-open
        probe succeeds."""
        note = (f"server unhealthy — circuit breaker open after "
                f"{self.breaker.consecutive_failures} consecutive "
                f"dispatch failures: {err!r}")
        n = self.queue.flush(STATUS_ERROR, note)
        n += self.batcher.flush_all(STATUS_ERROR, note)
        log.error("serve: circuit breaker OPEN; drained %d queued "
                  "requests; half-open probe in %.1fs (%s)", n,
                  self.config.breaker_cooldown_s, note)

    # -- crash-consistent shutdown/resume ------------------------------------

    def pending_requests(self) -> List[ServeRequest]:
        """Every admitted-but-unresolved request: queued, bucketed, and
        in-flight rows whose futures have not resolved. Exact once the
        supervisor thread is stopped; best-effort while it runs."""
        pendings = (self.queue.snapshot() + self.batcher.snapshot()
                    + list(self._inflight))
        return [p.request for p in pendings if not p.future.done()]

    def save_checkpoint(self, path) -> int:
        """Atomically write the unresolved-request state (manifest.
        atomic_write_json: tmp + fsync + rename — a kill mid-checkpoint
        leaves the previous checkpoint, never a torn one). Returns the
        number of requests checkpointed."""
        reqs = [r.to_record() for r in self.pending_requests()]
        # Flush the partial streaming accumulator with the checkpoint:
        # the resumed server restores the ring AND the folded-key set,
        # so rows this incarnation already counted (including rows whose
        # deadline passed mid-dispatch and will be re-submitted) are
        # never double-counted on resume.
        atomic_write_json(Path(path), {
            "version": CHECKPOINT_VERSION,
            "model": self.model_name,
            "requests": reqs,
            "stream": (self.stream.state()
                       if self.stream is not None else None),
        })
        return len(reqs)

    def shutdown_checkpoint(self, path, timeout: float = 10.0) -> int:
        """SIGTERM path (preemption warning): stop the supervisor WITHOUT
        working off the backlog — the host has seconds, not minutes —
        then checkpoint every unresolved request. In-flight dispatch
        rows are included iff their futures have not resolved, so a
        request is never both served and checkpointed."""
        self._abort = True
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        n = self.save_checkpoint(path)
        log.info("serve: shutdown checkpoint wrote %d pending requests "
                 "-> %s", n, path)
        return n

    def resume_from_checkpoint(self, path) -> List[ServeFuture]:
        """Re-submit every request from a shutdown checkpoint. Requests
        the previous incarnation already served may ride the dedup cache
        (same content address); unserved ones score fresh. Returns the
        futures in checkpoint order."""
        import json

        data = json.loads(Path(path).read_text())
        if self.stream is not None:
            self.stream.restore(data.get("stream"))
        reqs = [ServeRequest.from_record(r)
                for r in data.get("requests", ())]
        log.info("serve: resuming %d checkpointed requests from %s",
                 len(reqs), path)
        return [self.submit(r) for r in reqs]


# ---------------------------------------------------------------------------
# Fleet serving: one question across all resident models (the agreement
# axis as a request class)
# ---------------------------------------------------------------------------


def fleet_decision(token_1_prob, token_2_prob):
    """Binary decision for the agreement statistic — EXACTLY the rule
    the streaming-statistics lattice folds (engine/stream_stats.py:
    yes > no on device == float64 Relative_Prob > 0.5): 1/0, or None
    when the row is invalid (missing/non-finite/zero-total probs), so
    fleet kappa is bitwise-comparable with every other kappa this
    framework reports."""
    import math

    if token_1_prob is None or token_2_prob is None:
        return None
    t1, t2 = float(token_1_prob), float(token_2_prob)
    total = t1 + t2
    if not math.isfinite(total) or total <= 0:
        return None
    return 1 if t1 / total > 0.5 else 0


def aggregate_fleet(request_id: str, results: Dict[str, "ServeResult"],
                    latency_s: float) -> Dict:
    """Fold one fleet_score fan-out's per-model results into the
    agreement payload: per-model P(yes)/P(no)/decision, the within-
    question kappa over the valid decisions — routed through stats/
    streaming.kappa_from_counts, the SAME contingency path the
    streaming sink and the csv pipeline use, so serve-reported kappa is
    bitwise what an offline analysis of the same rows computes — and
    the pairwise disagreement fraction (1 - observed agreement over all
    model pairs)."""
    import numpy as np

    from ..stats import streaming

    per_model: Dict[str, Dict] = {}
    decisions = []
    for mid in sorted(results):
        r = results[mid]
        dec = (fleet_decision(r.token_1_prob, r.token_2_prob)
               if r.status == STATUS_OK else None)
        per_model[mid] = {
            "status": r.status,
            "token_1_prob": r.token_1_prob,
            "token_2_prob": r.token_2_prob,
            "weighted_confidence": r.weighted_confidence,
            "confidence_value": r.confidence_value,
            "decision": dec,
            "cached": r.cached,
        }
        if r.note:
            per_model[mid]["note"] = r.note
        if dec is not None:
            decisions.append(dec)
    n_ok = sum(1 for m in per_model.values()
               if m["status"] == STATUS_OK)
    if decisions:
        n_g, s_g = streaming.group_counts(
            np.zeros(len(decisions), dtype=np.int64),
            np.asarray(decisions, dtype=np.int64))
        kap = streaming.kappa_from_counts(n_g, s_g)
    else:
        kap = {"kappa": float("nan"),
               "observed_agreement": float("nan"),
               "expected_agreement": float("nan")}
    n = len(decisions)
    n_pairs = n * (n - 1) // 2
    disagreement = (1.0 - float(kap["observed_agreement"])
                    if n_pairs > 0 else float("nan"))
    status = (STATUS_OK if n_ok == len(per_model) and per_model
              else "partial" if n_ok else STATUS_ERROR)
    return {
        "request_id": request_id,
        "status": status,
        "n_models": len(per_model),
        "n_valid": n,
        "per_model": per_model,
        "kappa": {k: float(v) for k, v in kap.items()},
        "disagreement": disagreement,
        "latency_s": latency_s,
    }


class FleetScoreFuture:
    """Completion handle for one fleet fan-out: resolves when every
    per-model sub-future has (each with SOME status — the serving
    contract), then aggregates probabilities + agreement."""

    def __init__(self, request_id: str, futures: Dict[str, ServeFuture],
                 t_submit: float,
                 clock: Callable[[], float] = time.monotonic):
        self.request_id = request_id
        self._futures = futures
        self._t_submit = t_submit
        self._clock = clock

    def done(self) -> bool:
        return all(f.done() for f in self._futures.values())

    def result(self, timeout: Optional[float] = None) -> Dict:
        deadline = (None if timeout is None
                    else self._clock() + timeout)
        results: Dict[str, ServeResult] = {}
        for mid, fut in self._futures.items():
            left = (None if deadline is None
                    else max(deadline - self._clock(), 0.0))
            results[mid] = fut.result(left)
        return aggregate_fleet(self.request_id, results,
                               self._clock() - self._t_submit)


class FleetScoringServer:
    """Multiplexed scoring service over a ModelFleet: per-model dispatch
    queues (serve/batcher.FleetBatcher), resident-first selection with
    background weight prefetch, and the ``fleet_score`` request class —
    one question fanned across every fleet model, answered with
    per-model P(yes)/P(no) plus pairwise kappa/disagreement through the
    stats/streaming contingency path.

    Deliberately leaner than :class:`ScoringServer` (which remains the
    single-model production server with breaker/ladder/checkpoint):
    the fleet supervisor keeps the retry policy, deadline expiry, and
    the numerics-guard quarantine boundary — the pieces that shape
    per-row results — and trades the failure-domain machinery for
    model-multiplexing. Per-model results are BITWISE what the same
    request on a single-model ScoringServer over the same engine
    returns (pinned by tests/test_fleet.py): the dispatch path is the
    same ContinuousBatcher.score call on the same engine.
    """

    def __init__(self, fleet, config: Optional[ServeConfig] = None,
                 fleet_deadline_s: float = 60.0,
                 stats: Optional[ServeStats] = None,
                 clock: Callable[[], float] = time.monotonic,
                 tiers: Optional[TierConfig] = None):
        self.fleet = fleet
        self.config = config or ServeConfig()
        self.fleet_deadline_s = float(fleet_deadline_s)
        self.stats = stats if stats is not None else ServeStats()
        self.clock = clock
        self.queue = RequestQueue(self.config.queue_depth, self.stats,
                                  clock)
        self.batcher = FleetBatcher(fleet, self.stats,
                                    self.config.linger_s, clock,
                                    pad_full=self.config.pad_full)
        for mid in fleet.model_ids:
            fleet.engine(mid).fresh_handoff()
        # One ledger for the whole replica (engine/hbm.py): the fleet
        # adopts the first engine's governor so weight residency, page
        # pools, pins and dispatch caches all press on ONE budget — and
        # every member engine reports into it.
        if fleet.governor is None:
            for mid in fleet.model_ids:
                eng = fleet.engine(mid)
                gov = getattr(eng, "governor", None)
                if gov is not None:
                    fleet.attach_governor(gov)
                    break
        if fleet.governor is not None:
            for mid in fleet.model_ids:
                eng = fleet.engine(mid)
                if eng is not None:
                    eng.governor = fleet.governor
        # Unified telemetry spine: the serve counters, the fleet's swap
        # accounting, and every member engine's guard/compile/fault
        # stats in ONE registry ({"op": "metrics"} reads it live).
        self.metrics = metrics_mod.MetricsRegistry()
        self.metrics.register("serve", self.stats)
        self.metrics.register("fleet", fleet.stats)
        if fleet.governor is not None:
            # The shared HBM ledger's gauges ride the metrics endpoint
            # next to device_memory_stats().
            self.metrics.register("mem", fleet.governor.stats)
        for mid in fleet.model_ids:
            eng = fleet.engine(mid)
            if eng is not None:
                self.metrics.register(f"model:{mid}:guard",
                                      eng.guard_stats)
                self.metrics.register(f"model:{mid}:compile",
                                      eng.compile_stats)
        # Tiered weight residency (serve/tiers.TieredWeightStore): the
        # governor's evict_weights rung records each evicted staged
        # tree to disk first (ModelFleet.evict_idle), and a fresh
        # process re-stages every recorded model from disk before
        # taking traffic — restart-warm weights, CRC-checked per leaf.
        self.weight_tiers: Optional[tiers_mod.TieredWeightStore] = None
        if tiers is not None and tiers.enabled and tiers.disk_dir:
            self.weight_tiers = tiers_mod.TieredWeightStore(
                Path(tiers.disk_dir) / "weights")
            fleet.attach_tiers(self.weight_tiers)
            self.metrics.register("tiers", self.weight_tiers.stats)
            if tiers.restart_warm:
                n = fleet.reseed_weights(self.weight_tiers)
                if n:
                    log.info("serve: restart-warm — re-staged %d fleet "
                             "weight trees from the disk tier", n)
        rec = tracing.get_recorder()
        if rec is not None:
            self.metrics.register("trace", rec)
        # Reliability observatory (observe/sentinel.SentinelScheduler):
        # attached by the CLI/bench when a sentinel grid is configured;
        # the stats endpoint then serves its window history + alerts.
        self.observatory = None
        # Optional health gate: the elastic router (serve/router.py)
        # assigns this replica's router-side CircuitBreaker here, and
        # the sentinel scheduler pauses sweeps while it is OPEN (a
        # failover window must not alert as model drift). None = no
        # breaker fronting this server.
        self.breaker = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    @property
    def model_ids(self):
        return self.fleet.model_ids

    @property
    def queue_depth(self) -> int:
        """Router load signal — see ScoringServer.queue_depth."""
        return len(self.queue) + self.batcher.pending_rows

    def oldest_wait(self, now: Optional[float] = None) -> float:
        return self.batcher.oldest_wait(self.clock() if now is None
                                        else now)

    @property
    def hbm_pressure(self) -> float:
        """Shared-ledger pressure of this fleet replica (router
        placement signal; 0.0 when ungoverned/unbounded)."""
        gov = self.fleet.governor
        return 0.0 if gov is None else float(gov.pressure())

    def resident_models(self) -> List[str]:
        """Model ids whose weights are currently in this replica's
        WeightCache — the router's residency seed (listener events keep
        it current afterwards)."""
        return [m for m in self.fleet.model_ids if self.fleet.resident(m)]

    # -- client side ---------------------------------------------------------

    def submit(self, request: ServeRequest, model_id: str) -> ServeFuture:
        """Admit one request routed to ONE fleet model. Tokenization
        runs here with THAT model's tokenizer (per-model vocabularies —
        the reason the fleet layer is model-id-aware all the way down)."""
        with tracing.span("serve/admit", request_id=request.request_id,
                          model=model_id):
            return self._submit(request, model_id)

    def _submit(self, request: ServeRequest, model_id: str
                ) -> ServeFuture:
        self.stats.count("submitted")
        engine = self.fleet.engine(model_id)
        assert engine is not None, f"unknown fleet model {model_id}"
        fut = ServeFuture()
        now = self.clock()
        with engine._tok_lock:
            bin_ids = tuple(int(i) for i in engine.tokenizer(
                request.binary_prompt).input_ids)
            conf_ids = tuple(int(i) for i in engine.tokenizer(
                request.confidence_prompt).input_ids)
        lcp = tok.shared_prefix_len(bin_ids, conf_ids)
        with engine._tok_lock:
            t1, t2 = tok.target_token_ids(
                engine.tokenizer, tuple(request.targets),
                encoder_decoder=engine.encoder_decoder)
        deadline = (request.deadline_s if request.deadline_s is not None
                    else self.fleet_deadline_s)
        bucket = tok.assign_bucket(max(lcp, 1), engine.buckets)
        self.queue.offer(Pending(
            request=request, future=fut, t_submit=now,
            t_deadline=now + deadline, bin_ids=bin_ids,
            conf_ids=conf_ids, lcp=lcp, bucket=bucket,
            t1=int(t1), t2=int(t2), model_id=model_id))
        return fut

    def submit_fleet(self, request: ServeRequest,
                     models: Optional[List[str]] = None
                     ) -> FleetScoreFuture:
        """The fleet request class: fan ``request`` across every fleet
        model (or the ``models`` subset) and aggregate agreement."""
        mids = list(models) if models is not None else self.fleet.model_ids
        self.fleet.stats.count("fleet_requests")
        self.fleet.stats.count("fleet_rows", len(mids))
        t0 = self.clock()
        futures = {
            mid: self.submit(dataclasses_replace_id(request, mid), mid)
            for mid in mids}
        return FleetScoreFuture(request.request_id, futures, t0,
                                self.clock)

    # -- supervisor side -----------------------------------------------------

    def start(self) -> "FleetScoringServer":
        assert self._thread is None, "server already started"
        self._thread = threading.Thread(target=self._loop,
                                        name="fleet-supervisor",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: Optional[float] = None) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    def _loop(self) -> None:
        while True:
            stopping = self._stop.is_set()
            for p in self.queue.drain():
                self.batcher.admit(p)
            d = self.batcher.next_dispatch(self.clock(), flush=stopping)
            if d is None:
                if (stopping and len(self.queue) == 0
                        and self.batcher.pending_rows == 0):
                    return
                self.queue.wait_nonempty(
                    0.005 if self.batcher.pending_rows else 0.05)
                continue
            self._dispatch(*d)

    def attach_observatory(self, scheduler) -> None:
        """Install a SentinelScheduler (observe/sentinel.py): its window
        history and drift alerts ride the ``stats`` endpoint, and its
        sweep/alert counters land in this server's metrics registry."""
        self.observatory = scheduler
        if scheduler.registry is None:
            scheduler.registry = self.metrics

    def stats_summary(self) -> Dict:
        """The fleet ``stats`` endpoint payload: serve counters, fleet
        swap accounting, and — when the observatory is attached — the
        windowed drift history and alerts."""
        out = {"serve": self.stats.summary(),
               "fleet": self.fleet.stats.summary()}
        if self.observatory is not None:
            out["observatory"] = self.observatory.summary()
        return out

    def _dispatch(self, model_id: str, bucket: int, rows) -> None:
        engine = self.fleet.engine(model_id)
        now0 = self.clock()
        for p in rows:
            tracing.add_span("serve/queue_wait", p.t_submit, now0,
                             request_id=p.request.request_id,
                             model=model_id, bucket=int(bucket))
        try:
            payloads = retry_with_exponential_backoff(
                lambda: self.batcher.score(model_id, bucket, rows),
                retry_on=(Exception,), config=self.config.retry,
                log=lambda m: log.warning(
                    "fleet dispatch retry (%s): %s", model_id, m),
                clock=self.clock, give_up=is_program_error)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as err:  # noqa: BLE001 — resolve, never crash
            refused = is_program_error(err)
            if refused:
                log.error("fleet: dispatch on %s refused by the tracer/"
                          "compiler (not retried): %r", model_id, err)
            what = ("program error (not retried)" if refused
                    else "device error after retries")
            now = self.clock()
            self.stats.count("errors", len(rows))
            for p in rows:
                p.future.resolve(ServeResult(
                    request_id=p.request.request_id, status=STATUS_ERROR,
                    note=f"{what} on {model_id}: {err!r}",
                    latency_s=now - p.t_submit))
            return
        now = self.clock()
        with tracing.span("serve/resolve", model=model_id,
                          rows=len(rows)):
            self._resolve_rows(engine, model_id, rows, payloads, now)

    def _resolve_rows(self, engine, model_id: str, rows, payloads,
                      now: float) -> None:
        for p, payload in zip(rows, payloads):
            reason = None
            if engine.rt.numerics_guard:
                engine.guard_stats.site("checked", "fleet")
                reason = numerics.check_payload(payload)
            if reason is not None:
                engine.guard_stats.quarantine("fleet", reason)
                self.stats.count("errors")
                p.future.resolve(ServeResult(
                    request_id=p.request.request_id, status=STATUS_ERROR,
                    note=f"{numerics.NUMERICS_ERROR} — {reason} "
                         f"(row quarantined by the numerics guard)",
                    latency_s=now - p.t_submit))
                continue
            self.stats.count("completed")
            self.stats.record_latency(now - p.t_submit)
            p.future.resolve(ServeResult(
                request_id=p.request.request_id, status=STATUS_OK,
                latency_s=now - p.t_submit, **payload))

    def fleet_summary(self) -> Dict:
        return self.fleet.stats.summary()


def dataclasses_replace_id(request: ServeRequest,
                           model_id: str) -> ServeRequest:
    """Per-model sub-request of a fleet fan-out: same prompts/targets,
    request id suffixed with the model so every sub-result is
    attributable in logs and checkpoints."""
    import dataclasses as _dc

    return _dc.replace(
        request, request_id=f"{request.request_id}#{model_id}")
