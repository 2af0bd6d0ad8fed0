"""Continuous batcher: online dispatch formation over the bucket ladder.

The offline ragged scheduler (engine/scheduler.py) plans a KNOWN grid up
front; serving has an arrival process instead, so this module keeps the
same bucket/price machinery but runs it incrementally, Orca-style at
iteration granularity — here the "iteration" is one fused decode scan
(the engine's decode programs are fixed-budget XLA scans, so admission
happens between scans, and freed decode slots are refilled from the queue
when the next dispatch forms):

- **Bucket snapping**: every admitted request was tokenized at submit
  time and snapped to the nearest edge of the SAME precompiled ladder the
  offline sweep uses (tokens.assign_bucket over engine.buckets), so a
  request reuses the sweep's executables — with the boot precompile
  (compile_plan.sweep_specs_for_ladder + serve_batches) no request ever
  triggers a trace.
- **Slot refill**: a dispatch takes up to ``batch_size`` rows from one
  bucket queue; rows whose deadline expired while queued resolve as
  partial results and their slots refill from the same queue, so padding
  never rides where real work is waiting. An UNDERFULL ripe bucket is
  additionally promoted into the next bucket's queue whenever that
  bucket has waiting work and scheduler.bucket_cost says the promoted
  rows riding a fuller dispatch beat a padded tail of their own — the
  offline planner's slot-refill rule, run incrementally.
- **Price-model bucket selection**: among buckets that are ripe (full
  batch, or the oldest row outwaited the linger window), dispatch the one
  with the lowest cost per real row under scheduler.bucket_cost — the
  exact price model the offline planner's slot-refill rule uses, so the
  online and offline policies cannot drift apart.

Per-request results are identical to the offline sweep's for the same
cells (pinned by tests/test_serve.py): the dispatch path is the sweep's
own decode_fused_shared call with the same pretokenized ids, bucket,
suffix edges, budgets, and cache-handoff donation chain.
"""

from __future__ import annotations

import json
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..engine import scheduler as sched_mod
from ..engine import score as score_mod
from ..engine import tokens as tok
from ..engine.runner import _tail_batch
from ..engine.sweep import _decode_complete, _parse_confidence
from ..observe import tracing
from ..utils.profiling import ServeStats
from .queue import (STATUS_EXPIRED, Pending, ServeResult)


class ContinuousBatcher:
    """Per-bucket queues + dispatch formation + the engine call."""

    def __init__(self, engine, stats: ServeStats, linger_s: float,
                 clock: Callable[[], float] = time.monotonic,
                 pad_full: bool = True, prefix_cache: bool = True):
        self.engine = engine
        self.stats = stats
        self.linger_s = float(linger_s)
        self.clock = clock
        self.pad_full = pad_full
        # Cross-request radix prefix cache (ServeConfig.prefix_cache):
        # dispatches resume shared prefixes from the engine's page pool
        # and insert fresh pages after — reuse across requests AND
        # batches is the serving default. False restores the PR-3
        # behavior (exact-match dedup only).
        self.prefix_cache = bool(prefix_cache
                                 and engine.prefix_cache is not None)
        self.batch = engine.rt.batch_size
        rt = engine.rt
        # Decode budgets: exactly the sweep's derivation (engine/sweep.py)
        # so served scores equal swept scores.
        self.new_tokens = (rt.max_new_tokens if rt.sweep_full_completions
                           else min(rt.sweep_decode_tokens,
                                    rt.max_new_tokens))
        self.conf_tokens = (rt.max_new_tokens if rt.sweep_full_completions
                            else min(rt.sweep_confidence_tokens,
                                     rt.max_new_tokens))
        self.early_stop = (rt.sweep_early_stop
                           and not rt.sweep_full_completions)
        self.decode_cost = self.new_tokens + self.conf_tokens
        # Price dispatches with the engine's kernel mode: the decode
        # floor constant differs between the fused flash-decode kernels,
        # the dense fallback, and the speculative verify windows
        # (scheduler.decode_token_cost).
        self.fused_decode = bool(getattr(rt, "fused_decode", True))
        self.spec_decode = bool(
            getattr(engine, "spec_supported", lambda: False)())
        self._queues: Dict[int, Deque[Pending]] = {
            int(b): deque() for b in engine.buckets}

    # -- queue side ---------------------------------------------------------

    def admit(self, pending: Pending) -> None:
        # Admission runs on the supervisor thread AFTER the loop drains
        # page ops, so a tier promote queued at submit time has already
        # landed in the radix tree — refresh the submit-side advisory
        # hint against the live tree so bucket pricing sees promoted
        # pages as the free prefill they now are (serve/tiers.py).
        if (self.prefix_cache
                and getattr(self.engine, "_tier_store", None) is not None):
            pending.cached_hint = self.engine.prefix_cache.match_len(
                pending.bucket, pending.bin_ids[:pending.lcp])
        self._queues[pending.bucket].append(pending)

    @property
    def pending_rows(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def oldest_wait(self, now: float) -> float:
        """Seconds the OLDEST bucketed row has waited — the router's
        SLO signal (serve/router.py): a replica with a stale backlog is
        a bad home for a deadline-tight request even when its row count
        looks shallow. 0 when nothing is queued."""
        oldest = min((q[0].t_submit for q in self._queues.values() if q),
                     default=None)
        return 0.0 if oldest is None else max(now - oldest, 0.0)

    def snapshot(self) -> List[Pending]:
        """Non-destructive copy of every bucketed entry, bucket order
        (the serve state checkpoint reads this after the supervisor
        loop has stopped — the batcher itself is supervisor-private, so
        no lock is needed once that thread is joined)."""
        return [p for _, q in sorted(self._queues.items()) for p in q]

    def _expire(self, p: Pending, now: float) -> None:
        """Deadline passed while queued: a PARTIAL confidence-free result
        (status only; every measurement field None) instead of failing
        the batch or silently dropping the request."""
        self.stats.count("expired")
        p.future.resolve(ServeResult(
            request_id=p.request.request_id, status=STATUS_EXPIRED,
            note=f"deadline passed before dispatch "
                 f"(waited {now - p.t_submit:.3f}s)",
            latency_s=now - p.t_submit))

    def _batch_cap(self) -> int:
        """Dispatch-row cap: the configured batch, halved while the HBM
        governor's batch_down rung is engaged (engine/hbm.py — smaller
        dispatch caches under pressure; per-row results are unchanged,
        batch composition is masked out of every readout). Restores to
        the full batch when the rung re-arms."""
        gov = getattr(self.engine, "governor", None)
        return self.batch if gov is None else gov.batch_cap(self.batch)

    def _dispatch_rows(self, n: int) -> int:
        """Padded batch rows a dispatch of ``n`` real rows pays for:
        the full batch cap under ``pad_full`` (shape stability), else
        the offline sweep's power-of-two tail."""
        cap = self._batch_cap()
        return cap if self.pad_full else _tail_batch(n, cap)

    def _shared_trunk(self, rows: List["Pending"],
                      bucket: int) -> Tuple[int, int]:
        """(cascade-prefill trunk, decode trunk) the engine would dedupe
        for these queued rows, 0 where that phase runs dense. Advisory
        pricing input only — the dispatch routes itself from the same
        rows through the same rule (ScoringEngine.shared_trunk), so the
        price model and the routing can never disagree on the
        discount."""
        fn = getattr(self.engine, "shared_trunk", None)
        if fn is None or len(rows) < 2:
            return 0, 0
        return fn([list(p.bin_ids[:p.lcp]) for p in rows],
                  len(rows), bucket)

    def next_dispatch(self, now: float, flush: bool = False
                      ) -> Optional[Tuple[int, List[Pending]]]:
        """Form the next dispatch, or None when no bucket is ripe. A
        bucket is ripe with a full batch, once its oldest request has
        waited out the linger window, or unconditionally under ``flush``
        (shutdown drain). An underfull ripe bucket promotes into a
        NONEMPTY next bucket when the price model favors it (there must
        be work there to ride — unlike the offline planner, the online
        queue can't assume more same-bucket work is coming)."""
        import time as _time

        t_form = _time.monotonic()
        while True:
            ripe = [edge for edge, q in self._queues.items() if q
                    and (flush or len(q) >= self.batch
                         or now - q[0].t_submit >= self.linger_s)]
            if not ripe:
                return None

            def price(edge: int) -> Tuple[float, float]:
                q = self._queues[edge]
                n = min(len(q), self.batch)
                # Prefix-aware pricing: radix-cached prefix tokens of
                # the rows this dispatch would take are free prefill
                # (advisory submit-time hints; scheduler.bucket_cost).
                cached = (sum(q[i].cached_hint for i in range(n))
                          if self.prefix_cache else 0)
                picked = [q[i] for i in range(n)]
                trunk, dtrunk = self._shared_trunk(picked, edge)
                per_row = sched_mod.bucket_cost(
                    self._dispatch_rows(n), edge, self.batch,
                    self.decode_cost, cached_tokens=cached,
                    fused_decode=self.fused_decode,
                    spec_decode=self.spec_decode,
                    cascade=trunk > 0, trunk_tokens=trunk,
                    decode_trunk_frac=(dtrunk / edge if edge else 0.0)
                    ) / n
                return per_row, q[0].t_submit

            edge = min(ripe, key=price)
            q = self._queues[edge]
            n = len(q)
            if n < self.batch:
                bigger = [b for b in sorted(self._queues) if b > edge]
                nxt = bigger[0] if bigger else None
                if (nxt is not None and self._queues[nxt]
                        and n * nxt < sched_mod.bucket_cost(
                            self._dispatch_rows(n), edge, self.batch,
                            self.decode_cost,
                            fused_decode=self.fused_decode,
                            spec_decode=self.spec_decode)):
                    promoted = [q.popleft() for _ in range(n)]
                    for p in reversed(promoted):
                        self._queues[nxt].appendleft(p)
                    self.stats.count("promoted", n)
                    continue    # re-select (promotion may cascade)
            rows: List[Pending] = []
            cap = self._batch_cap()
            while q and len(rows) < cap:
                p = q.popleft()
                if now >= p.t_deadline:
                    self._expire(p, now)  # slot refills from the queue
                    continue
                rows.append(p)
            if rows:
                # Batch-formation span only when a dispatch actually
                # formed (the idle-poll None path must stay silent).
                tracing.add_span("serve/batch_form", t_form,
                                 _time.monotonic(), bucket=int(edge),
                                 rows=len(rows))
                return edge, rows
            # every candidate row expired — re-scan the other buckets

    def prefill(self, bucket: int,
                prefix_rows: List[Tuple[int, ...]]) -> int:
        """PREFILL-ONLY dispatch (disaggregated serving — serve/migrate
        .py): compute the rows' prefix KV at ``bucket`` and insert full
        pages into this engine's pool + radix tree, decoding nothing.
        Rows are padded exactly the way :meth:`score` pads its batch
        (pad_full / power-of-two tail, repeating the last row) so a
        prefill-role replica's prefill programs share the score path's
        shape discipline — and its page VALUES are bitwise the pages a
        full scoring dispatch would have inserted
        (engine.prefill_insert). Returns the page-aligned tokens
        covered for the first row."""
        n = len(prefix_rows)
        bsz = max(self._dispatch_rows(n), _tail_batch(n, self.batch))
        full = [list(r) for r in prefix_rows]
        full += [list(prefix_rows[-1])] * (bsz - n)
        with tracing.span("serve/prefill", bucket=int(bucket), rows=n):
            return self.engine.prefill_insert(bucket, full)

    def flush_all(self, status: str, note: str) -> int:
        """Resolve every bucketed request with ``status`` (health-flag
        drain); returns how many were flushed."""
        n = 0
        now = self.clock()
        for q in self._queues.values():
            while q:
                p = q.popleft()
                self.stats.count("errors")
                p.future.resolve(ServeResult(
                    request_id=p.request.request_id, status=status,
                    note=note, latency_s=now - p.t_submit))
                n += 1
        return n

    # -- engine side --------------------------------------------------------

    def score(self, bucket: int, rows: List[Pending]) -> List[Dict]:
        """One engine dispatch over ``rows`` (all snapped to ``bucket``),
        mirroring the offline sweep's shared-dispatch path exactly:
        power-of-two tail padding by repeating the last row, per-dispatch
        suffix edges from the shared suffix ladder, pretokenized ids,
        donated KV-cache handoff, position-0 readout. Returns one
        measurement payload per REAL row (padding rows are dropped)."""
        engine = self.engine
        n = len(rows)
        bsz = max(self._dispatch_rows(n), _tail_batch(n, self.batch))
        full = list(rows) + [rows[-1]] * (bsz - n)
        gov = getattr(engine, "governor", None)
        if gov is not None:
            gov.tick()      # one ladder tick per serve dispatch
        t1 = np.asarray([p.t1 for p in full], np.int32)
        t2 = np.asarray([p.t2 for p in full], np.int32)
        la = max(max(len(p.bin_ids) - p.lcp for p in full), 1)
        lb = max(max(len(p.conf_ids) - p.lcp for p in full), 1)
        ba = tok.pick_bucket([la], sched_mod.SUFFIX_BUCKETS)
        bb = tok.pick_bucket([lb], sched_mod.SUFFIX_BUCKETS)
        with tracing.span("serve/dispatch", bucket=int(bucket), rows=n):
            fused, cfused = engine.decode_fused_shared(
                [p.request.binary_prompt for p in full],
                [p.request.confidence_prompt for p in full],
                t1, t2, new_tokens=self.new_tokens,
                conf_tokens=self.conf_tokens, early_stop=self.early_stop,
                pretokenized_a=[list(p.bin_ids) for p in full],
                pretokenized_b=[list(p.conf_ids) for p in full],
                bucket=bucket, sfx_buckets_ab=(ba, bb), reuse_cache=True,
                use_prefix_cache=self.prefix_cache, n_real=n)
            res = score_mod.readout_from_fused(
                fused, jnp.asarray(t1), jnp.asarray(t2), scan_positions=1)
        with tracing.span("serve/readout", bucket=int(bucket), rows=n):
            res_h, lp_vals, lp_ids, gen_host = jax.device_get(
                (res, fused.topk_logprobs, fused.topk_ids,
                 fused.generated))
            wconf, cgen_host = jax.device_get(
                (cfused.weighted_confidence, cfused.generated))
        if self.spec_decode:
            # Prompt-lookup drafting warms itself: record the observed
            # continuations into the radix tree's token history and fold
            # the dispatch's SpecOut counters (we just synchronized on
            # the payload device_get, so the flush costs nothing extra).
            engine.spec_record(bucket, [list(p.bin_ids) for p in full],
                               gen_host, n)
            engine.spec_record(bucket, [list(p.conf_ids) for p in full],
                               cgen_host, n)
            engine.spec_flush()
        payloads: List[Dict] = []
        for j in range(n):
            conf_text = engine.decode_completion(cgen_host[j])
            conf_complete = (engine.rt.sweep_full_completions
                             or _decode_complete(cgen_host[j],
                                                 engine.eos_id))
            payloads.append(dict(
                model_response=engine.decode_completion(gen_host[j]),
                model_confidence_response=conf_text,
                token_1_prob=float(res_h.yes_prob[j]),
                token_2_prob=float(res_h.no_prob[j]),
                log_probabilities=json.dumps({
                    int(i): round(float(v), 6)
                    for i, v in zip(lp_ids[j], lp_vals[j])}),
                confidence_value=_parse_confidence(conf_text,
                                                   conf_complete),
                weighted_confidence=float(wconf[j]),
            ))
        self.stats.add_dispatch(n, bsz)
        return payloads


class FleetBatcher:
    """Per-model dispatch queues over co-resident models — the fleet
    layer's serve seam (engine/fleet.ModelFleet underneath).

    One :class:`ContinuousBatcher` per fleet model keeps the bucket/
    linger/price machinery unchanged per model; this class adds the two
    things a multi-model server needs on top:

    - **Resident-first selection**: among models with a ripe bucket, one
      whose weights are already in HBM dispatches before any model that
      would pay a swap (AlpaServe's statistical-multiplexing insight:
      co-resident models absorb each other's bursts for free). The
      resident scan order rotates per call so equally-loaded resident
      models round-robin instead of the first one starving the rest;
      a non-resident model's rows still age toward their deadlines and
      dispatch as soon as no resident work is ripe.
    - **Swap overlap**: the moment a dispatch is chosen, the next
      NON-resident model with waiting work starts streaming its weights
      in the background (fleet.prefetch), so the swap it will
      eventually pay hides behind this dispatch's device time.

    ``score`` wraps the per-model batcher's dispatch in fleet
    acquire/release, so the LRU weight cache can never evict a model
    mid-dispatch (refcount) and swap timing lands in FleetStats.
    """

    def __init__(self, fleet, stats: ServeStats, linger_s: float,
                 clock: Callable[[], float] = time.monotonic,
                 pad_full: bool = True):
        self.fleet = fleet
        self.stats = stats
        self.clock = clock
        self.batchers: Dict[str, ContinuousBatcher] = {
            mid: ContinuousBatcher(fleet.engine(mid), stats, linger_s,
                                   clock, pad_full=pad_full,
                                   prefix_cache=False)
            for mid in fleet.model_ids}
        self._rr = 0

    def admit(self, pending: Pending) -> None:
        self.batchers[pending.model_id].admit(pending)

    @property
    def pending_rows(self) -> int:
        return sum(b.pending_rows for b in self.batchers.values())

    def oldest_wait(self, now: float) -> float:
        """Oldest queued-row wait across every model's batcher (the
        router's SLO signal — see ContinuousBatcher.oldest_wait)."""
        return max((b.oldest_wait(now) for b in self.batchers.values()),
                   default=0.0)

    def snapshot(self) -> List[Pending]:
        return [p for mid in sorted(self.batchers)
                for p in self.batchers[mid].snapshot()]

    def next_dispatch(self, now: float, flush: bool = False
                      ) -> Optional[Tuple[str, int, List[Pending]]]:
        """(model_id, bucket, rows) of the next dispatch, or None when
        no model has a ripe bucket."""
        mids = list(self.batchers)
        resident = [m for m in mids if self.fleet.resident(m)]
        if resident:
            self._rr = (self._rr + 1) % len(resident)
            resident = resident[self._rr:] + resident[:self._rr]
        rest = [m for m in mids if not self.fleet.resident(m)]
        for mid in resident + rest:
            d = self.batchers[mid].next_dispatch(now, flush=flush)
            if d is None:
                continue
            bucket, rows = d
            for nxt in mids:
                if (nxt != mid and not self.fleet.resident(nxt)
                        and self.batchers[nxt].pending_rows):
                    self.fleet.prefetch(nxt)
                    break
            return mid, bucket, rows
        return None

    def flush_all(self, status: str, note: str) -> int:
        return sum(b.flush_all(status, note)
                   for b in self.batchers.values())

    def score(self, model_id: str, bucket: int,
              rows: List[Pending]) -> List[Dict]:
        """One dispatch on ``model_id``'s engine with its weights held
        resident (fleet refcount) for the duration — and, when
        RuntimeConfig.spec_draft_model names a co-resident model, that
        draft model's weights too (engine/spec.py fleet drafting:
        both refcounts held across the dispatch, so neither side can
        evict the other mid-verify)."""
        engine = self.fleet.acquire(model_id)
        draft_id = self.fleet.acquire_spec_draft(engine, model_id)
        try:
            return self.batchers[model_id].score(bucket, rows)
        finally:
            self.fleet.release_spec_draft(engine, draft_id)
            self.fleet.release(model_id)
