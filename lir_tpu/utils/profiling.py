"""Profiling and throughput accounting.

The reference's only "profiler" is dollar-cost accounting against the
MODEL_PRICING table plus RAM/GPU telemetry strings (SURVEY.md §5;
perturb_prompts.py:51-65,1021-1066, compare_base_vs_instruct.py:53-66).
The TPU-native replacements:

  - ThroughputMeter: prompts/sec/chip — the BASELINE.json headline metric —
    computed from the same counters the cost table consumed.
  - device_memory_stats(): per-device HBM usage, replacing the reference's
    psutil/cuda telemetry prints (surfaced as gauges in the observe
    metrics snapshot).

Every ``*Stats`` dataclass here registers into ONE MetricsRegistry
(lir_tpu/observe/registry.py) whose STATS_SCHEMA must list every public
field — enforced statically by the ``metrics-drift`` lint pass, so a
new counter that never reaches the metrics endpoint fails review.
Trace annotations moved to lir_tpu/observe/tracing.py (structured spans
+ Chrome export, same TraceAnnotation correlation).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Iterator, Optional

import jax

from .logging import get_logger

log = get_logger(__name__)


@dataclasses.dataclass
class ThroughputMeter:
    """Counts scored prompts and wall time; reports prompts/sec/chip.

    Pass per-batch matmul FLOPs to ``add(..., flops=...)`` (via
    ``scoring_step_flops``) to get implied TFLOPS and MFU against the
    chip's published peak in the summary — the sanity figure that would
    have caught round 1's physically impossible benchmark number at sweep
    time. FLOPs accumulate per call, so mixed-size model sweeps weight
    each model correctly. Set ``int8_dots=True`` for dynamic-int8 sweeps
    so the MFU denominator is the chip's s8 peak, not bf16's.
    """

    n_devices: int = 0
    prompts: int = 0
    tokens_in: int = 0
    tokens_out: int = 0
    elapsed: float = 0.0
    flops: float = 0.0
    int8_dots: bool = False
    _start: Optional[float] = None

    def __post_init__(self) -> None:
        if self.n_devices <= 0:
            self.n_devices = jax.device_count()

    @contextlib.contextmanager
    def measure(self) -> Iterator[None]:
        start = time.perf_counter()
        try:
            yield
        finally:
            self.elapsed += time.perf_counter() - start

    def add(self, prompts: int, tokens_in: int = 0, tokens_out: int = 0,
            flops: float = 0.0) -> None:
        self.prompts += prompts
        self.tokens_in += tokens_in
        self.tokens_out += tokens_out
        self.flops += flops

    @property
    def prompts_per_sec(self) -> float:
        return self.prompts / self.elapsed if self.elapsed > 0 else 0.0

    @property
    def prompts_per_sec_per_chip(self) -> float:
        return self.prompts_per_sec / max(self.n_devices, 1)

    def summary(self) -> Dict[str, float]:
        out = {
            "prompts": self.prompts,
            "tokens_in": self.tokens_in,
            "tokens_out": self.tokens_out,
            "elapsed_s": round(self.elapsed, 3),
            "n_devices": self.n_devices,
            "prompts_per_sec": round(self.prompts_per_sec, 4),
            "prompts_per_sec_per_chip": round(self.prompts_per_sec_per_chip, 4),
        }
        if self.flops > 0 and self.elapsed > 0:
            implied = self.flops / self.elapsed / max(self.n_devices, 1)
            out["implied_tflops_per_chip"] = round(implied / 1e12, 2)
            peak = chip_peak_flops(int8=self.int8_dots)
            if peak is not None:
                out["mfu"] = round(implied / peak, 4)
                if implied > peak:
                    log.warning(
                        "implied %.1f TFLOPS exceeds the %s peak (%.0f) — "
                        "timing is not syncing with the device",
                        implied / 1e12, jax.devices()[0].device_kind,
                        peak / 1e12)
        return out


@dataclasses.dataclass
class BucketCounters:
    """Per-bucket dispatch accounting for the ragged sweep scheduler."""

    dispatches: int = 0
    cells: int = 0            # real grid cells dispatched in this bucket
    slots: int = 0            # batch rows paid for (incl. padding rows)
    used_slots: int = 0       # batch rows carrying real work
    prompt_tokens: int = 0    # real (unpadded) prefix tokens prefilled
    slot_tokens: int = 0      # prefill rows * bucket_len — token slots paid
    refilled: int = 0         # cells promoted here from a smaller bucket's
                              # ragged tail (slot refill)


@dataclasses.dataclass
class OccupancyStats:
    """Ragged-sweep scheduler counters: per-bucket batch occupancy and
    prompt-padding waste, plus decode-step occupancy from the early-stop
    retire positions.

    Definitions (reported by ``summary()`` and printed by bench.py's
    variable-length mode):

    - batch occupancy % = real cells / batch slots paid for — slots lost
      to ragged-tail padding rows. The scheduler's slot refill (promoting
      a bucket's ragged tail into the next bucket's queue) exists to keep
      this high when the grid spreads over many buckets.
    - padding waste %  = padded prefix-token slots / total prefix-token
      slots — the FLOPs fraction the prefill burns on padding, at the
      prefix edge each dispatch RUNS at. The bucket ladder exists to keep
      this low on variable-length grids (one global bucket pads every
      short prompt to the max), and the plan-time edge
      (scheduler.PREFIX_EDGE_GRID) tightens each bucket to the rows it
      carries.
    - edge trim % = prefix-token slots the plan-time edge took off the
      ladder's own shapes / the slots those would have paid
      (``trimmed_slots`` over ``trimmed_slots`` + slot tokens).
    - decode occupancy % = decode steps that produced a live (pre-retire)
      token / decode steps paid for. Rows retired mid-scan by the early
      stop (EOS / complete-integer) idle until the batch's slowest row.
    """

    buckets: Dict[int, BucketCounters] = dataclasses.field(
        default_factory=dict)
    grouped_cells: int = 0          # cells scored via a cross-cell prefix group
    grouped_prefill_rows: int = 0   # prefix rows actually prefilled for them
    trimmed_slots: int = 0          # sum of prefill rows x (bucket - edge)
    decode_steps_live: int = 0
    decode_steps_paid: int = 0

    def bucket(self, edge: int) -> BucketCounters:
        return self.buckets.setdefault(int(edge), BucketCounters())

    def add_dispatch(self, edge: int, cells: int, slots: int,
                     prompt_tokens: int, refilled: int = 0,
                     used_slots: Optional[int] = None,
                     prefill_slots: Optional[int] = None,
                     bucket: Optional[int] = None) -> None:
        """``slots``/``used_slots`` count batch rows (occupancy);
        ``prefill_slots`` counts rows actually prefilled at ``edge``
        slots each (padding waste) — they differ in grouped dispatches,
        where member rows outnumber the shared prefix rows. ``edge`` is
        the prefix extent the dispatch runs at; ``bucket`` the ladder
        edge it was queued under (default: the same), which names the
        counters and prices what the tighter edge took off."""
        bucket = int(edge) if bucket is None else int(bucket)
        rows = slots if prefill_slots is None else prefill_slots
        b = self.bucket(bucket)
        b.dispatches += 1
        b.cells += cells
        b.slots += slots
        b.used_slots += cells if used_slots is None else used_slots
        b.prompt_tokens += prompt_tokens
        b.slot_tokens += rows * int(edge)
        b.refilled += refilled
        self.trimmed_slots += rows * (bucket - int(edge))

    def add_decode(self, steps_live: int, steps_paid: int) -> None:
        self.decode_steps_live += steps_live
        self.decode_steps_paid += steps_paid

    @property
    def occupancy_pct(self) -> float:
        slots = sum(b.slots for b in self.buckets.values())
        used = sum(b.used_slots for b in self.buckets.values())
        return 100.0 * used / slots if slots else 0.0

    @property
    def padding_waste_pct(self) -> float:
        tok = sum(b.prompt_tokens for b in self.buckets.values())
        slot_tok = sum(b.slot_tokens for b in self.buckets.values())
        return 100.0 * (slot_tok - tok) / slot_tok if slot_tok else 0.0

    @property
    def edge_trim_pct(self) -> float:
        slot_tok = sum(b.slot_tokens for b in self.buckets.values())
        ladder = slot_tok + self.trimmed_slots
        return 100.0 * self.trimmed_slots / ladder if ladder else 0.0

    @property
    def decode_occupancy_pct(self) -> float:
        if not self.decode_steps_paid:
            return 0.0
        return 100.0 * self.decode_steps_live / self.decode_steps_paid

    def summary(self) -> Dict[str, object]:
        out: Dict[str, object] = {
            "occupancy_pct": round(self.occupancy_pct, 2),
            "padding_waste_pct": round(self.padding_waste_pct, 2),
            "trimmed_slots": self.trimmed_slots,
            "edge_trim_pct": round(self.edge_trim_pct, 2),
            "per_bucket": {
                str(edge): {
                    "dispatches": b.dispatches, "cells": b.cells,
                    "slots": b.slots, "refilled": b.refilled,
                    "padding_waste_pct": round(
                        100.0 * (b.slot_tokens - b.prompt_tokens)
                        / b.slot_tokens, 2) if b.slot_tokens else 0.0,
                }
                for edge, b in sorted(self.buckets.items())
            },
        }
        if self.decode_steps_paid:
            out["decode_occupancy_pct"] = round(self.decode_occupancy_pct, 2)
        if self.grouped_cells:
            out["grouped_cells"] = self.grouped_cells
            out["grouped_prefill_rows"] = self.grouped_prefill_rows
        return out


@dataclasses.dataclass
class CompileStats:
    """Compile-plan accounting (engine/compile_plan.py): where cold-start
    time goes, and whether dispatches ran precompiled or traced lazily.

    - ``shapes``: per-shape AOT compile seconds, keyed by the spec label
      (kind/bucket/batch/suffixes/variant) — the itemized cold-start bill.
    - ``aot_hits``: dispatches served by a registry executable;
      ``aot_shapes_hit``: how many DISTINCT planned executables were
      ever dispatched (counted on a label's first hit) — against
      ``aot_shapes`` it says what share of the loaded plan ran at all;
      ``lazy_misses``: dispatches that fell back to trace-on-first-call
      (registry miss, failed compile, or precompile disabled).
    - ``load_wall_s``: wall seconds during which at least one planned
      executable was being compiled or loaded (the union of the
      ``engine/compile_load`` spans; the pool works in parallel, so the
      per-shape seconds sum to more).
    - ``persistent_requests/hits``: XLA persistent-cache counters for the
      window between ``snapshot_persistent()`` and ``finish_persistent()``
      (the jax.monitoring events are process-global; the snapshot diff
      scopes them to one sweep).
    - ``cold_start_s`` / ``warm_start_s``: end-to-end warmup wall time with
      a cold vs warm persistent cache — set by the bench, reported in its
      headline JSON.
    """

    shapes: Dict[str, float] = dataclasses.field(default_factory=dict)
    aot_hits: int = 0
    aot_shapes_hit: int = 0
    lazy_misses: int = 0
    load_wall_s: float = 0.0
    persistent_requests: int = 0
    persistent_hits: int = 0
    cold_start_s: Optional[float] = None
    warm_start_s: Optional[float] = None
    _persistent_base: Optional[Dict[str, int]] = None

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()
        self._hit_labels: set = set()
        self._loading = 0           # loads in flight
        self._loading_since = 0.0   # when the first of them began

    def record_shape(self, label: str, seconds: float) -> None:
        self.shapes[label] = round(
            self.shapes.get(label, 0.0) + seconds, 4)

    def hit(self, label: str) -> None:
        """One dispatch served by the registry executable ``label``."""
        with self._lock:
            self.aot_hits += 1
            if label not in self._hit_labels:
                self._hit_labels.add(label)
                self.aot_shapes_hit += 1

    @contextlib.contextmanager
    def loading(self) -> Iterator[None]:
        """Around one planned executable's compile or load, on any
        thread: ``load_wall_s`` grows by the time any was in flight."""
        with self._lock:
            if not self._loading:
                self._loading_since = time.monotonic()
            self._loading += 1
        try:
            yield
        finally:
            with self._lock:
                self._loading -= 1
                if not self._loading:
                    self.load_wall_s += (time.monotonic()
                                         - self._loading_since)

    @property
    def compile_s(self) -> float:
        """Total AOT compile seconds (sum over shapes; parallel compiles
        overlap on the wall clock, so this bounds — not equals — the
        cold-start contribution)."""
        return round(sum(self.shapes.values()), 4)

    def snapshot_persistent(self) -> None:
        from . import compile_cache

        self._persistent_base = compile_cache.persistent_cache_counters()

    def finish_persistent(self) -> None:
        from . import compile_cache

        now = compile_cache.persistent_cache_counters()
        base = self._persistent_base or {"requests": 0, "hits": 0}
        self.persistent_requests += now["requests"] - base["requests"]
        self.persistent_hits += now["hits"] - base["hits"]
        self._persistent_base = now

    def summary(self) -> Dict[str, object]:
        out: Dict[str, object] = {
            "aot_shapes": len(self.shapes),
            "aot_compile_s": self.compile_s,
            "aot_load_wall_s": round(self.load_wall_s, 4),
            "aot_hits": self.aot_hits,
            "aot_shapes_hit": self.aot_shapes_hit,
            "lazy_misses": self.lazy_misses,
            "persistent_cache_requests": self.persistent_requests,
            "persistent_cache_hits": self.persistent_hits,
            "persistent_cache_misses": (self.persistent_requests
                                        - self.persistent_hits),
        }
        if self.shapes:
            out["per_shape_compile_s"] = {
                k: round(v, 3) for k, v in sorted(self.shapes.items())}
        if self.cold_start_s is not None:
            out["cold_start_s"] = round(self.cold_start_s, 3)
        if self.warm_start_s is not None:
            out["warm_start_s"] = round(self.warm_start_s, 3)
        return out


@dataclasses.dataclass
class KernelStats:
    """Per-phase kernel accounting for the isolated scoring step, plus
    the piggyback-chain counters (ROADMAP item 2: make the MFU plateau
    measurable per COMPONENT, not just in aggregate).

    ``phases`` — filled by bench.py's kernel mode: for each of
    "prefill" (quadratic prompt pass), "decode" (KV-cached greedy scan),
    and "readout" (lm_head + position-0 extras), the measured seconds,
    the analytic matmul TFLOPs executed (scoring_step_flops_split), the
    implied TFLOPS, and — when the chip's peak is known — the phase MFU
    and its complement, the MXU-idle fraction. The decode row is where
    the 36% plateau lived; the fused flash-decode kernel and int8
    matmul fusion attack exactly that row.

    ``counters`` — engine-side chunked-prefill/decode piggybacking:
    chains opened, piggybacked steps (dispatches whose decode scans rode
    the next prefill call), drains, and plain-path fallbacks.
    """

    phases: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)

    def record_phase(self, name: str, seconds: float, flops: float,
                     peak: Optional[float] = None) -> None:
        entry: Dict[str, float] = {
            "seconds": round(seconds, 6),
            "tflops_executed": round(flops / 1e12, 4),
            "implied_tflops": (round(flops / seconds / 1e12, 3)
                               if seconds > 0 else 0.0),
        }
        if peak and seconds > 0:
            mfu = flops / seconds / peak
            entry["mfu"] = round(mfu, 4)
            entry["mxu_idle_frac"] = round(1.0 - mfu, 4)
        self.phases[name] = entry

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def summary(self) -> Dict[str, object]:
        out: Dict[str, object] = {k: dict(v) for k, v in
                                  sorted(self.phases.items())}
        if self.counters:
            out["piggyback"] = dict(sorted(self.counters.items()))
        return out


@dataclasses.dataclass
class ServeStats:
    """Online serving counters (lir_tpu/serve): the operator's one-look
    view of queue health, admission control, dedup effectiveness, and
    latency. Thread-safe — the supervisor loop and every submitting
    thread mutate it concurrently.

    Definitions (reported by ``summary()`` and bench.py's "serve" key):

    - submitted / admitted / shed: admission-control accounting. ``shed``
      counts both rejected newcomers and deadline-aware evictions
      (serve/queue.py) — nonzero shed under steady load means the queue
      depth or the fleet is undersized.
    - dedup hit rate = cache hits / lookups — how often a probe was
      answered from the content-addressed result cache without touching
      the device (perturbation-style traffic re-asks near-identical
      questions constantly).
    - expired: rows whose deadline passed while queued; they return
      partial confidence-free results. ``late``: rows that completed but
      past their deadline (excluded from goodput).
    - slot occupancy % = real request rows / padded batch slots across
      every dispatch — the online analogue of OccupancyStats' batch
      occupancy; low values mean the linger window is too short for the
      arrival rate. ``promoted`` counts rows the batcher's online slot
      refill moved into a bigger bucket's queue (scheduler.bucket_cost
      said riding a fuller dispatch beats a padded tail of their own).
    - latency percentiles (p50/p95/p99) over submit -> result seconds.
    """

    submitted: int = 0
    admitted: int = 0
    shed: int = 0
    completed: int = 0
    expired: int = 0
    errors: int = 0
    late: int = 0
    dedup_hits: int = 0
    dedup_misses: int = 0
    dispatches: int = 0
    slots_used: int = 0
    slots_paid: int = 0
    promoted: int = 0
    queue_depth_peak: int = 0
    _latencies: list = dataclasses.field(default_factory=list)
    _max_latencies: int = 100_000

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def note_queue_depth(self, depth: int) -> None:
        with self._lock:
            self.queue_depth_peak = max(self.queue_depth_peak, depth)

    def add_dispatch(self, used: int, paid: int) -> None:
        with self._lock:
            self.dispatches += 1
            self.slots_used += used
            self.slots_paid += paid

    def record_latency(self, seconds: float) -> None:
        with self._lock:
            if len(self._latencies) < self._max_latencies:
                self._latencies.append(float(seconds))

    @property
    def dedup_hit_rate(self) -> float:
        n = self.dedup_hits + self.dedup_misses
        return self.dedup_hits / n if n else 0.0

    @property
    def slot_occupancy_pct(self) -> float:
        return (100.0 * self.slots_used / self.slots_paid
                if self.slots_paid else 0.0)

    def latency_percentiles(self) -> Dict[str, float]:
        with self._lock:
            lat = sorted(self._latencies)
        if not lat:
            return {"p50_s": 0.0, "p95_s": 0.0, "p99_s": 0.0}

        def pct(p: float) -> float:
            i = min(len(lat) - 1, max(0, int(round(p * (len(lat) - 1)))))
            return lat[i]

        return {"p50_s": round(pct(0.50), 4), "p95_s": round(pct(0.95), 4),
                "p99_s": round(pct(0.99), 4)}

    def goodput(self, elapsed_s: float) -> float:
        """Requests completed WITHIN deadline per second of wall time —
        the serving layer's headline rate (late completions and partial
        results don't count; cache hits do: a served answer is a served
        answer)."""
        if elapsed_s <= 0:
            return 0.0
        return max(0, self.completed - self.late) / elapsed_s

    def summary(self) -> Dict[str, object]:
        out: Dict[str, object] = {
            "submitted": self.submitted,
            "admitted": self.admitted,
            "shed": self.shed,
            "completed": self.completed,
            "expired": self.expired,
            "errors": self.errors,
            "late": self.late,
            "dedup_hits": self.dedup_hits,
            "dedup_misses": self.dedup_misses,
            "dedup_hit_rate": round(self.dedup_hit_rate, 4),
            "dispatches": self.dispatches,
            "slot_occupancy_pct": round(self.slot_occupancy_pct, 2),
            "promoted": self.promoted,
            "queue_depth_peak": self.queue_depth_peak,
        }
        out.update(self.latency_percentiles())
        return out


@dataclasses.dataclass
class FaultStats:
    """Fault-injection / self-healing counters (lir_tpu/faults): what the
    failure path did, with the same one-look intent as ServeStats for the
    hot path. Thread-safe — injection sites, the supervisor loop, and the
    sweep's dispatch recovery all mutate it concurrently.

    Definitions (reported by ``summary()``, bench.py's "chaos" key, and
    ``make chaos-smoke``):

    - ``injected``: per-site injected-fault counts (FaultPlan.check) —
      the chaos schedule's ground truth, so "recovered" can be read
      against "thrown at".
    - ``recovered_dispatches``: dispatches that failed at least once
      (device error, injected fault) and still resolved rows — via the
      retry policy, the AOT->lazy fallback, or the bisection ladder.
    - ``degraded_dispatches``: dispatches that entered the degradation
      ladder (retries exhausted on the full batch).
    - ``degraded_rows``: rows the ladder resolved as error results after
      isolating them as poison — the price of not failing their batch.
    - breaker counters + ``transitions``: every circuit-breaker state
      change in order ((from, to) pairs) — the serve recovery story is
      readable from this list alone (closed->open->half_open->closed).
    """

    injected: Dict[str, int] = dataclasses.field(default_factory=dict)
    recovered_dispatches: int = 0
    degraded_dispatches: int = 0
    degraded_rows: int = 0
    preemptions: int = 0
    breaker_opens: int = 0
    breaker_probes: int = 0
    breaker_closes: int = 0
    transitions: list = dataclasses.field(default_factory=list)

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def inject(self, site: str, preemption: bool = False) -> None:
        with self._lock:
            self.injected[site] = self.injected.get(site, 0) + 1
            if preemption:
                self.preemptions += 1

    @property
    def injected_total(self) -> int:
        return sum(self.injected.values())

    def transition(self, frm: str, to: str) -> None:
        with self._lock:
            self.transitions.append((frm, to))
            if to == "open":
                self.breaker_opens += 1
            elif to == "half_open":
                self.breaker_probes += 1
            elif to == "closed":
                self.breaker_closes += 1

    def summary(self) -> Dict[str, object]:
        with self._lock:
            return {
                "injected": dict(self.injected),
                "injected_total": sum(self.injected.values()),
                "recovered_dispatches": self.recovered_dispatches,
                "degraded_dispatches": self.degraded_dispatches,
                "degraded_rows": self.degraded_rows,
                "preemptions": self.preemptions,
                "breaker_opens": self.breaker_opens,
                "breaker_probes": self.breaker_probes,
                "breaker_closes": self.breaker_closes,
                "breaker_transitions": [f"{a}->{b}"
                                        for a, b in self.transitions],
            }


@dataclasses.dataclass
class GuardStats:
    """Guard-layer counters (lir_tpu/guard): what the silent-failure
    path saw and did, per SITE ("sweep" / "serve" / "compile" /
    "barrier"). Thread-safe — the sweep writer thread, the serve
    supervisor, and compile-pool threads all mutate it concurrently.

    Definitions (reported by ``summary()``, bench.py's "chaos" key, and
    ``make chaos-smoke``):

    - ``watched``: dispatches run under an enforced watchdog deadline
      (uncalibrated observe-only runs are not counted — they cannot
      fire).
    - ``stalls``: watchdog expiries per site — each one is a dispatch
      that would have hung the run and instead cost one deadline.
      ``stall_dumps`` counts the all-thread stack dumps emitted.
    - ``checked`` / ``quarantined``: numerics-guard rows validated and
      rows withheld as ``error:numerics``; ``reasons`` histograms the
      quarantine causes (NaN probs, out-of-range confidence, ...).
    - ``inflight_cancelled``: serve rows resolved partial because their
      deadline passed while the dispatch was still on the device (the
      watched executor's tick callback).
    - ``barrier_timeouts`` / ``heartbeats``: multihost liveness —
      bounded collectives that expired (a peer presumed dead) and
      heartbeat allgathers completed.
    """

    watched: Dict[str, int] = dataclasses.field(default_factory=dict)
    stalls: Dict[str, int] = dataclasses.field(default_factory=dict)
    checked: Dict[str, int] = dataclasses.field(default_factory=dict)
    quarantined: Dict[str, int] = dataclasses.field(default_factory=dict)
    reasons: Dict[str, int] = dataclasses.field(default_factory=dict)
    stall_dumps: int = 0
    inflight_cancelled: int = 0
    barrier_timeouts: int = 0
    heartbeats: int = 0

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def site(self, field: str, site: str, n: int = 1) -> None:
        with self._lock:
            d = getattr(self, field)
            d[site] = d.get(site, 0) + n

    def quarantine(self, site: str, reason: str) -> None:
        with self._lock:
            self.quarantined[site] = self.quarantined.get(site, 0) + 1
            self.reasons[reason] = self.reasons.get(reason, 0) + 1

    @property
    def stalls_total(self) -> int:
        with self._lock:
            return sum(self.stalls.values())

    @property
    def quarantined_total(self) -> int:
        with self._lock:
            return sum(self.quarantined.values())

    def summary(self) -> Dict[str, object]:
        with self._lock:
            return {
                "watched": dict(self.watched),
                "stalls": dict(self.stalls),
                "stalls_total": sum(self.stalls.values()),
                "stall_dumps": self.stall_dumps,
                "checked": dict(self.checked),
                "quarantined": dict(self.quarantined),
                "quarantined_total": sum(self.quarantined.values()),
                "quarantine_reasons": dict(self.reasons),
                "inflight_cancelled": self.inflight_cancelled,
                "barrier_timeouts": self.barrier_timeouts,
                "heartbeats": self.heartbeats,
            }


@dataclasses.dataclass
class PrefixCacheStats:
    """Cross-request prefix cache counters (engine/prefix_tree.py over
    the models/paged.py page pool): the operator's one-look view of how
    much prefill the radix tree is saving and how hard the pool is
    churning. Thread-safe — serve admission probes and the dispatch
    thread mutate it concurrently.

    Definitions (reported by ``summary()``, logged per sweep, surfaced
    in serve stats alongside ServeStats, and in bench.py's
    "prefix_serve" key):

    - ``lookups`` / ``hits``: dispatch-time radix probes and probes that
      matched >= 1 cached page. radix hit rate = hits / lookups.
    - ``hit_tokens``: prefix tokens resumed from the pool instead of
      prefilled — THE perf number (prefill_tokens_avoided).
      ``prefill_tokens_total`` counts every prefix token a dispatch
      needed (cached + computed), so avoided_frac = hit / total.
    - ``inserted_pages`` / ``evicted_pages``: pool churn. Sustained
      eviction at low hit rates means the pool is undersized for the
      working set (DEPLOY.md §1g sizing arithmetic).
    - ``pages_in_use`` / ``pages_total``: pool occupancy gauge, updated
      at every insert/evict.
    """

    lookups: int = 0
    hits: int = 0
    hit_tokens: int = 0
    prefill_tokens_total: int = 0
    inserted_pages: int = 0
    evicted_pages: int = 0
    pages_in_use: int = 0
    pages_total: int = 0

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def gauge_pages(self, in_use: int, total: int) -> None:
        with self._lock:
            self.pages_in_use = in_use
            self.pages_total = total

    @property
    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    @property
    def avoided_frac(self) -> float:
        return (self.hit_tokens / self.prefill_tokens_total
                if self.prefill_tokens_total else 0.0)

    def summary(self) -> Dict[str, object]:
        with self._lock:
            return {
                "lookups": self.lookups,
                "hits": self.hits,
                "radix_hit_rate": round(self.hits / self.lookups, 4)
                                  if self.lookups else 0.0,
                "prefill_tokens_avoided": self.hit_tokens,
                "prefill_tokens_total": self.prefill_tokens_total,
                "avoided_frac": round(self.hit_tokens
                                      / self.prefill_tokens_total, 4)
                                if self.prefill_tokens_total else 0.0,
                "inserted_pages": self.inserted_pages,
                "evicted_pages": self.evicted_pages,
                "pages_in_use": self.pages_in_use,
                "pages_total": self.pages_total,
            }


@dataclasses.dataclass
class CascadeStats:
    """Shared-prefix cascade-prefill counters (ops/cascade_prefill +
    engine/runner routing; DEPLOY.md §1q). Thread-safe — the sweep loop
    and serve batcher threads mutate it concurrently.

    - ``cascade_dispatches`` / ``dense_fallbacks``: shared dispatches
      that took the cascade split vs ones that ran the dense path while
      cascade was ENABLED (trunk below min_trunk, too few rows, int8 KV
      cache, ...). A high fallback fraction on a shared-trunk workload
      means the eligibility knobs (CascadeConfig) are mistuned.
    - ``trunk_rows_deduped``: rows whose quadratic trunk prefill was NOT
      recomputed (rows - 1 per cascade dispatch whose trunk was run for
      it, every row of one that found its trunk held; the dense path
      pays all of them) — the dedup the cascade exists for.
    - ``trunk_programs``: runs of the trunk program (generate.
      greedy_decode_trunk): a trunk worth holding is prefilled once for
      all the consecutive dispatches that start with it.
    - ``trunk_held_dispatches``: cascade dispatches that took the
      trunk's cache as an argument (the ``"cascade_held"`` front)
      instead of prefilling it inside their own program.
    - ``prefix_flops_saved``: analytic matmul FLOPs those deduped trunk
      rows would have cost (the dense prefill's attention + projection
      terms over trunk tokens) — THE perf number; bench.py's ``cascade``
      key divides it into the dense prefill total for the implied
      prefill-MFU uplift.
    - ``cascade_decode_dispatches``: shared dispatches whose DECODE
      scans ran the trunk-aware flash-decode split dedup
      (ops/flash_decode trunk variants; DEPLOY.md §1r) — cascade-prefill
      AND dense-prefill dispatches alike, whenever the trunk extent and
      the decode-side gates line up.
    - ``trunk_bytes_deduped``: analytic HBM bytes those dispatches' trunk
      K/V tiles did NOT stream (once per decode step instead of once per
      row — profiling.cascade_decode_bytes_saved); bench.py's
      ``cascade_decode`` key divides the flat kernel's decode bytes by
      the deduped total for the headline bytes/row reduction.
    """

    cascade_dispatches: int = 0
    dense_fallbacks: int = 0
    trunk_rows_deduped: int = 0
    prefix_flops_saved: int = 0
    cascade_decode_dispatches: int = 0
    trunk_bytes_deduped: int = 0
    # Real prompt tokens the shared dispatches ran through the layers
    # (prefix + both format suffixes, a shared trunk counted once), and
    # how many of them were trunk tokens: a trunk kept across dispatches
    # would lower the second.
    tokens_prefilled: int = 0
    trunk_tokens_prefilled: int = 0
    trunk_programs: int = 0
    trunk_held_dispatches: int = 0

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def summary(self) -> Dict[str, object]:
        with self._lock:
            total = self.cascade_dispatches + self.dense_fallbacks
            return {
                "cascade_dispatches": self.cascade_dispatches,
                "dense_fallbacks": self.dense_fallbacks,
                "cascade_frac": (round(self.cascade_dispatches / total, 4)
                                 if total else 0.0),
                "trunk_rows_deduped": self.trunk_rows_deduped,
                "prefix_flops_saved": self.prefix_flops_saved,
                "cascade_decode_dispatches": self.cascade_decode_dispatches,
                "trunk_bytes_deduped": self.trunk_bytes_deduped,
            }


@dataclasses.dataclass
class FillStats:
    """The host's fill of the sweep's calls (engine/sweep._fill_windows;
    metrics source ``fill``): a call's pending grid is tokenized, planned,
    routed and handed to the compile plan in plan windows, and only the
    first of them before anything is dispatched. Lasts the engine's life
    and only grows (a reader takes the difference of two snapshots); all
    stay where they are in a call that never goes through the ragged
    plan.

    - ``fill_s``: seconds of that work, every window.
    - ``ahead_s``: those of them spent on the windows after a call's
      first, on the fill thread, while the device has the windows before
      to work on (span ``sweep/plan_ahead``). 0 in a call that is ONE
      window: no token cap, or rows short enough to share a dispatch with
      a stranger (scheduler.RaggedScheduler.closed).
    - ``windows`` / ``windows_ahead``: windows planned, and those of them
      planned behind the device.
    - ``wait_s``: seconds the dispatch loop waited for a window that was
      not ready. The device may still have had work queued, so this
      bounds from above what of ``ahead_s`` it stood idle for.
    """

    fill_s: float = 0.0
    ahead_s: float = 0.0
    windows: int = 0
    windows_ahead: int = 0
    wait_s: float = 0.0

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def add(self, seconds: float, ahead: bool) -> None:
        with self._lock:
            self.fill_s += seconds
            self.windows += 1
            if ahead:
                self.ahead_s += seconds
                self.windows_ahead += 1

    def waited(self, seconds: float) -> None:
        with self._lock:
            self.wait_s += seconds

    def summary(self) -> Dict[str, object]:
        with self._lock:
            return {
                "fill_s": round(self.fill_s, 4),
                "ahead_s": round(self.ahead_s, 4),
                "ahead_share": (round(self.ahead_s / self.fill_s, 4)
                                if self.fill_s else 0.0),
                "windows": self.windows,
                "windows_ahead": self.windows_ahead,
                "wait_s": round(self.wait_s, 4),
            }


@dataclasses.dataclass
class SparseStats:
    """Counters of block-sparse attention with a selection step
    (ops/sparse_attention; metrics source ``sparse``). All stay 0 for a
    model without such layers. Each is the host's word about the programs
    it dispatched, from row lengths and budgets alone (what a query keeps
    is decided by its position: ops/sparse_attention.kept_blocks), summed
    over the sparse layers; tests/test_sala_model.py holds them to the
    masks a dispatched program computes.

    - ``blocks_offered`` / ``blocks_kept``: per query and sparse layer,
      the blocks of main keys it may see, and those it attends.
    - ``queries`` / ``dense_queries``: queries run, and those with at most
      ``dense_len`` tokens of context (they keep every block).
    - ``pooled_key_bytes``: bytes of the pooled keys the dispatch caches
      held (computed once a dispatch, with the main keys).
    """

    blocks_kept: int = 0
    blocks_offered: int = 0
    queries: int = 0
    dense_queries: int = 0
    pooled_key_bytes: int = 0

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def summary(self) -> Dict[str, object]:
        with self._lock:
            return {
                "blocks_kept": self.blocks_kept,
                "blocks_offered": self.blocks_offered,
                "kept_share": (self.blocks_kept / self.blocks_offered
                               if self.blocks_offered else 0.0),
                "queries": self.queries,
                "dense_queries": self.dense_queries,
                "pooled_key_bytes": self.pooled_key_bytes,
            }


@dataclasses.dataclass
class RecurrentStats:
    """Counters of the second kind of per-sequence state, the recurrent
    state a state-space mixer keeps beside K/V (models/decoder._mixer;
    metrics source ``recurrent``). All stay 0 for a model without one.
    Thread-safe like its siblings.

    - ``dispatches``: shared dispatches that carried recurrent state.
    - ``state_bytes`` / ``kv_bytes``: summed over those dispatches, the
      bytes of the dispatch cache's SSM state + conv tail and of its
      K/V (shape metadata of the cache the program returned).
    - ``forks``: branches started from the state held at the shared
      prefix's end (two a row: binary and confidence). K/V rewinds by
      mask; this state is copied on write.
    - ``scan_calls`` / ``step_calls``: chunked-scan windows
      (ops/ssd_scan.ssd_scan) and single-token updates (``ssm_step``)
      the dispatched programs hold, per layer.
    - ``trunk_states_shared``: rows seeded from the ONE trunk state a
      cascade dispatch computes at batch 1 (rows - 1 per dispatch).

    ``forks``, ``scan_calls`` and ``step_calls`` are what the host says
    of the program it dispatched (runner._note_recurrent), not readings
    of the device. They are held to the program in
    tests/test_hybrid_model.py (the dispatched program traced again with
    every scan, update and rewind counted) and to the device's trace by
    benchmarks/tests/ssm_trace.py (exits 1 where the counts differ).
    """

    dispatches: int = 0
    state_bytes: int = 0
    kv_bytes: int = 0
    forks: int = 0
    scan_calls: int = 0
    step_calls: int = 0
    trunk_states_shared: int = 0

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def summary(self) -> Dict[str, object]:
        with self._lock:
            held = self.state_bytes + self.kv_bytes
            return {
                "dispatches": self.dispatches,
                "state_bytes": self.state_bytes,
                "kv_bytes": self.kv_bytes,
                "state_share": (self.state_bytes / held
                                if held else 0.0),
                "forks": self.forks,
                "scan_calls": self.scan_calls,
                "step_calls": self.step_calls,
                "trunk_states_shared": self.trunk_states_shared,
            }


@dataclasses.dataclass
class FleetStats:
    """Multi-model fleet counters (engine/fleet.py over
    models/weights.py): how much model-swap latency the async weight
    streamer hid behind compute, and how hard the LRU weight cache is
    working. Thread-safe — the prefetch worker, the fleet supervisor,
    and serve submitters all mutate it concurrently.

    Definitions (reported by ``summary()``, logged per fleet sweep,
    surfaced in serve fleet stats, and in bench.py's "fleet" key):

    - ``swap_s_hidden`` / ``swap_s_exposed``: per-load wall seconds
      overlapped with the previous model's compute vs actually waited on
      by the scoring loop. hidden > exposed is the tentpole claim — the
      prefetch pipeline genuinely hides swap cost (the sequential
      drop-and-reload baseline is 100% exposed by construction).
    - ``loads`` / ``load_s`` / ``weight_bytes_streamed``: host->device
      weight loads performed, their total wall time, and bytes shipped
      through the chunked streamer.
    - ``prefetch_hits``: acquires satisfied by a prefetched (background)
      load; ``prefetch_misses``: acquires that had to load inline
      (fully exposed); ``cache_hits``: acquires finding the model
      already resident (zero swap cost — the co-residency win).
    - ``evictions``: models dropped by the LRU weight cache under HBM
      pressure; ``resident_models`` / ``resident_bytes``: occupancy
      gauges. Sustained eviction with low cache_hits means the budget
      is undersized for the fleet (DEPLOY.md §1k arithmetic).
    - ``model_swaps``: acquires that changed the active model;
      ``fleet_requests`` / ``fleet_rows``: serve fleet_score fan-outs
      and the per-model rows they produced.
    """

    swap_s_hidden: float = 0.0
    swap_s_exposed: float = 0.0
    loads: int = 0
    load_s: float = 0.0
    weight_bytes_streamed: int = 0
    prefetch_hits: int = 0
    prefetch_misses: int = 0
    cache_hits: int = 0
    evictions: int = 0
    resident_models: int = 0
    resident_bytes: int = 0
    model_swaps: int = 0
    fleet_requests: int = 0
    fleet_rows: int = 0

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n=1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def gauge(self, field: str, value) -> None:
        with self._lock:
            setattr(self, field, value)

    @property
    def hidden_frac(self) -> float:
        total = self.swap_s_hidden + self.swap_s_exposed
        return self.swap_s_hidden / total if total > 0 else 0.0

    def summary(self) -> Dict[str, object]:
        with self._lock:
            total = self.swap_s_hidden + self.swap_s_exposed
            return {
                "swap_s_hidden": round(self.swap_s_hidden, 4),
                "swap_s_exposed": round(self.swap_s_exposed, 4),
                "swap_hidden_frac": round(self.swap_s_hidden / total, 4)
                                    if total > 0 else 0.0,
                "loads": self.loads,
                "load_s": round(self.load_s, 4),
                "weight_bytes_streamed": self.weight_bytes_streamed,
                "prefetch_hits": self.prefetch_hits,
                "prefetch_misses": self.prefetch_misses,
                "cache_hits": self.cache_hits,
                "evictions": self.evictions,
                "resident_models": self.resident_models,
                "resident_bytes": self.resident_bytes,
                "model_swaps": self.model_swaps,
                "fleet_requests": self.fleet_requests,
                "fleet_rows": self.fleet_rows,
            }


@dataclasses.dataclass
class MemStats:
    """HBM-governor counters and gauges (engine/hbm.py): the one-look
    view of who holds HBM, how close the ledger is to its budget, and
    what the pressure-driven degradation ladder did about it.
    Thread-safe — the sweep dispatch loop, the serve supervisor, and
    fleet weight-cache listeners all mutate it concurrently.

    Definitions (reported by ``summary()``, the ``{"op": "metrics"}``
    endpoint's ``mem`` source, bench.py's "memory" key, and
    ``make mem-smoke``):

    - ``ledger_bytes`` / ``budget_bytes`` / ``pressure``: the ledger
      total across registered consumers, the governed budget (0 =
      unbounded), and their ratio — the gauge the degradation ladder
      and the router's placement signal both read.
    - ``rung``: currently-engaged ladder depth (0 = fully armed).
    - ``rung_downs`` / ``rung_ups``: per-rung engage/release
      transitions — a reversible squeeze shows BOTH nonzero.
    - ``admits`` / ``denials``: admission checks passed/refused
      (projected bytes vs budget at consumer registration time).
    - ``oom_events``: real device OOMs routed through the governor,
      per site ("sweep"/"serve"); ``oom_reclaims``: OOMs where the
      ladder freed something and the dispatch retried;
      ``oom_exhausted``: OOMs nothing could be reclaimed for — the
      irreducible dispatch the caller quarantines.
    - ``squeezes``: injected ``hbm_squeeze`` budget shrinks observed
      (the chaos proof's ground truth); ``sheds``: submits refused by
      the terminal backpressure rung.
    """

    ledger_bytes: int = 0
    budget_bytes: int = 0
    pressure: float = 0.0
    rung: int = 0
    rung_downs: Dict[str, int] = dataclasses.field(default_factory=dict)
    rung_ups: Dict[str, int] = dataclasses.field(default_factory=dict)
    admits: int = 0
    denials: int = 0
    oom_events: Dict[str, int] = dataclasses.field(default_factory=dict)
    oom_reclaims: int = 0
    oom_exhausted: int = 0
    squeezes: int = 0
    sheds: int = 0

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def gauge(self, field: str, value) -> None:
        with self._lock:
            setattr(self, field, value)

    def site(self, field: str, site: str, n: int = 1) -> None:
        with self._lock:
            d = getattr(self, field)
            d[site] = d.get(site, 0) + n

    def summary(self) -> Dict[str, object]:
        with self._lock:
            return {
                "ledger_bytes": self.ledger_bytes,
                "budget_bytes": self.budget_bytes,
                "pressure": round(float(self.pressure), 4),
                "rung": self.rung,
                "rung_downs": dict(self.rung_downs),
                "rung_ups": dict(self.rung_ups),
                "admits": self.admits,
                "denials": self.denials,
                "oom_events": dict(self.oom_events),
                "oom_reclaims": self.oom_reclaims,
                "oom_exhausted": self.oom_exhausted,
                "squeezes": self.squeezes,
                "sheds": self.sheds,
            }


@dataclasses.dataclass
class RouterStats:
    """Elastic-router counters (serve/router.py): how requests spread
    over the replica set and what the failure path did. Thread-safe —
    submitter threads, replica supervisor threads (future callbacks),
    and the router tick thread all mutate it concurrently.

    Definitions (reported by ``summary()``, bench.py's "elastic" key,
    and ``make elastic-smoke``):

    - ``routed``: requests admitted through the router (dedup hits
      excluded); ``routed_resident``: requests whose placement followed
      the weight-residency signal (the model was already in the chosen
      replica's WeightCache); ``per_replica`` histograms placements.
    - ``dedup_hits``: requests answered from the router's own
      content-addressed cache without touching any replica.
    - ``failovers``: attempts re-admitted to a DIFFERENT replica after
      an error/shed result; ``re_admitted``: in-flight requests
      re-admitted because their replica was killed or its breaker
      opened mid-dispatch. Exactly-once: a re-admitted request resolves
      from whichever replica answers first (ServeFuture first-
      resolution-wins + content-address dedup).
    - ``hedged`` / ``hedge_wins`` / ``hedge_losses``: requests
      duplicated onto a second replica inside the deadline whisker, and
      which copy won the first-payload race.
    - ``zombie_payloads``: payloads that arrived from a DEAD replica
      after the request already resolved elsewhere — dropped by the
      resolve-once/dedup discipline, never double-resolved.
    - ``replica_errors`` / ``replica_sheds``: per-attempt outcomes that
      triggered the failover path; ``no_replica_sheds``: requests shed
      because no live replica would admit them.
    - ``kills`` / ``revives``: replica death/rejoin events observed.
    """

    routed: int = 0
    routed_resident: int = 0
    dedup_hits: int = 0
    completed: int = 0
    errors: int = 0
    failovers: int = 0
    re_admitted: int = 0
    hedged: int = 0
    hedge_wins: int = 0
    hedge_losses: int = 0
    zombie_payloads: int = 0
    replica_errors: int = 0
    replica_sheds: int = 0
    no_replica_sheds: int = 0
    kills: int = 0
    revives: int = 0
    per_replica: Dict[str, int] = dataclasses.field(default_factory=dict)

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def placed(self, replica_id: str) -> None:
        with self._lock:
            self.per_replica[replica_id] = (
                self.per_replica.get(replica_id, 0) + 1)

    def summary(self) -> Dict[str, object]:
        with self._lock:
            return {
                "routed": self.routed,
                "routed_resident": self.routed_resident,
                "dedup_hits": self.dedup_hits,
                "completed": self.completed,
                "errors": self.errors,
                "failovers": self.failovers,
                "re_admitted": self.re_admitted,
                "hedged": self.hedged,
                "hedge_wins": self.hedge_wins,
                "hedge_losses": self.hedge_losses,
                "zombie_payloads": self.zombie_payloads,
                "replica_errors": self.replica_errors,
                "replica_sheds": self.replica_sheds,
                "no_replica_sheds": self.no_replica_sheds,
                "kills": self.kills,
                "revives": self.revives,
                "per_replica": dict(self.per_replica),
            }


@dataclasses.dataclass
class MigrationStats:
    """Disaggregated-serving counters (serve/migrate.py + the router's
    prefill/decode role machinery, serve/router.py). Thread-safe —
    replica supervisor threads (page ops + chain callbacks), the router
    tick (timeout fallbacks), and submit threads all mutate it.

    Definitions (reported by ``summary()``, bench.py's "disagg" key,
    and ``make disagg-smoke``; DEPLOY.md §1p):

    - ``migrations``: completed page-migration chains (pages exported
      from one replica's pool and imported, checksum-verified, into
      another's); ``prefill_ops``: prefill-only dispatches run on
      prefill-role replicas.
    - ``pages_migrated`` / ``bytes_streamed`` / ``chunks_streamed``:
      transfer volume (bytes are device-leaf bytes, both directions
      counted once).
    - ``migration_s_exposed``: transfer wall seconds on the critical
      path before the decode dispatch could be admitted;
      ``migration_s_hidden``: per-chunk in-flight seconds overlapped
      away by the double-buffered window (serial sum minus wall).
    - ``refetch_fallbacks``: chains abandoned (stall past
      ``MigrationConfig.timeout_s``, corrupt chunk, source replica
      died) whose request re-prefilled LOCALLY on the decode replica —
      the never-a-wrong-answer path; ``stalls`` / ``corrupt_chunks``
      classify why.
    - ``cluster_tree_hits``: requests whose prefix the cluster index
      found already page-resident on the chosen decode replica — routed
      straight there, no migration and no prefill needed.
    """

    migrations: int = 0
    prefill_ops: int = 0
    pages_migrated: int = 0
    bytes_streamed: int = 0
    chunks_streamed: int = 0
    migration_s_exposed: float = 0.0
    migration_s_hidden: float = 0.0
    refetch_fallbacks: int = 0
    stalls: int = 0
    corrupt_chunks: int = 0
    cluster_tree_hits: int = 0

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def add_transfer(self, pages: int, nbytes: int, chunks: int,
                     exposed_s: float, hidden_s: float) -> None:
        with self._lock:
            self.migrations += 1
            self.pages_migrated += pages
            self.bytes_streamed += nbytes
            self.chunks_streamed += chunks
            self.migration_s_exposed += exposed_s
            self.migration_s_hidden += hidden_s

    def summary(self) -> Dict[str, object]:
        with self._lock:
            return {
                "migrations": self.migrations,
                "prefill_ops": self.prefill_ops,
                "pages_migrated": self.pages_migrated,
                "bytes_streamed": self.bytes_streamed,
                "chunks_streamed": self.chunks_streamed,
                "migration_s_exposed": round(self.migration_s_exposed, 4),
                "migration_s_hidden": round(self.migration_s_hidden, 4),
                "refetch_fallbacks": self.refetch_fallbacks,
                "stalls": self.stalls,
                "corrupt_chunks": self.corrupt_chunks,
                "cluster_tree_hits": self.cluster_tree_hits,
            }


@dataclasses.dataclass
class TierStats:
    """Tiered-store counters (serve/tiers.py): how cached state moved
    down and back up the HBM -> host DRAM -> disk ladder. Thread-safe —
    demotions/promotions run on each replica's supervisor thread while
    submit threads probe ``match_len`` and the metrics endpoint reads.

    Definitions (reported by ``summary()``, bench.py's "tiered" key,
    and ``make tiered-smoke``; DEPLOY.md §1s):

    - ``demotions`` / ``promotions``: per-tier movement counts (keys
      ``host``, ``disk``, ``weights``) — a demotion books the tier the
      state LANDED in, a promotion the tier it was READ from.
    - ``pages_demoted`` / ``pages_promoted``: KV page volume either
      direction; ``bytes_spilled``: bytes written to the DISK tier
      (host-pool LRU overflow + weight records); ``bytes_promoted``:
      bytes read back toward HBM.
    - ``restart_pages_reseeded`` / ``restart_weights_reseeded``: state
      recovered from the disk tier by a restart-warm boot.
    - ``checksum_refusals``: promotes refused because a host/disk chunk
      failed its checksum (chaos kind ``tier_corrupt``) — the entry is
      dropped and the request re-prefills, never a wrong answer;
      ``disk_stalls``: disk reads abandoned past
      ``TierConfig.disk_timeout_s`` (chaos kind ``disk_stall``);
      ``pin_refusals``: demotion requests refused because a dispatch
      still pinned the pages (refcount discipline — a pinned page
      never leaves HBM).
    - ``host_bytes`` / ``disk_bytes``: current tier occupancy gauges.
    """

    demotions: Dict[str, int] = dataclasses.field(default_factory=dict)
    promotions: Dict[str, int] = dataclasses.field(default_factory=dict)
    pages_demoted: int = 0
    pages_promoted: int = 0
    bytes_spilled: int = 0
    bytes_promoted: int = 0
    restart_pages_reseeded: int = 0
    restart_weights_reseeded: int = 0
    checksum_refusals: int = 0
    disk_stalls: int = 0
    pin_refusals: int = 0
    host_bytes: int = 0
    disk_bytes: int = 0

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def gauge(self, field: str, value) -> None:
        with self._lock:
            setattr(self, field, value)

    def site(self, field: str, site: str, n: int = 1) -> None:
        with self._lock:
            d = getattr(self, field)
            d[site] = d.get(site, 0) + n

    def summary(self) -> Dict[str, object]:
        with self._lock:
            return {
                "demotions": dict(self.demotions),
                "promotions": dict(self.promotions),
                "pages_demoted": self.pages_demoted,
                "pages_promoted": self.pages_promoted,
                "bytes_spilled": self.bytes_spilled,
                "bytes_promoted": self.bytes_promoted,
                "restart_pages_reseeded": self.restart_pages_reseeded,
                "restart_weights_reseeded": self.restart_weights_reseeded,
                "checksum_refusals": self.checksum_refusals,
                "disk_stalls": self.disk_stalls,
                "pin_refusals": self.pin_refusals,
                "host_bytes": self.host_bytes,
                "disk_bytes": self.disk_bytes,
            }


@dataclasses.dataclass
class LeaseStats:
    """Shard-lease counters (engine/lease.py): how leased offline-sweep
    shards moved between holders. Thread-safe for symmetry with the
    other stats objects (the lease manager itself runs on one sweep
    thread per host).

    Definitions (reported by ``summary()``, logged per leased sweep,
    and in bench.py's "elastic" key):

    - ``claims``: shards claimed fresh (unclaimed, or re-claimed by
      their own holder on resume); ``renews``: expiry extensions (one
      per manifest flush — renew-on-flush); ``releases``: leases marked
      done.
    - ``steals``: expired leases taken over from a DEAD or slow holder
      — the work-stealing event; re-scored rows fold into the streaming
      lattice as bitwise no-ops (slot idempotence), so a steal can
      never corrupt the merged accumulator.
    - ``refused``: claim attempts refused because another holder's
      lease was still live (double-claim refusal); ``lost``: renews
      refused because the lease had expired and been stolen out from
      under the holder.
    - ``expired_seen``: expired foreign leases observed (steal
      candidates); ``shards_done``: shards this holder completed;
      ``refreshes``: lease-log re-reads.
    """

    claims: int = 0
    renews: int = 0
    releases: int = 0
    steals: int = 0
    refused: int = 0
    lost: int = 0
    expired_seen: int = 0
    shards_done: int = 0
    refreshes: int = 0

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def summary(self) -> Dict[str, object]:
        with self._lock:
            return {
                "claims": self.claims,
                "renews": self.renews,
                "releases": self.releases,
                "steals": self.steals,
                "refused": self.refused,
                "lost": self.lost,
                "expired_seen": self.expired_seen,
                "shards_done": self.shards_done,
                "refreshes": self.refreshes,
            }


@dataclasses.dataclass
class StreamStats:
    """Streaming-statistics sink counters (engine/stream_stats.py): how
    much of the grid folded on device, how many host bytes the streaming
    path avoided, and what finalize/checkpoint work cost. Thread-safe —
    the sweep writer thread folds while checkpoints and the live serve
    endpoint read concurrently.

    Definitions (reported by ``summary()``, logged per sweep, and in
    bench.py's "streaming_stats" key):

    - ``rows_folded`` / ``dispatch_folds``: grid rows folded into the
      device accumulator and the fused update calls that carried them
      (one per dispatch — the tentpole invariant; rows_folded == grid
      size means no row ever needed the host).
    - ``host_bytes_avoided``: bytes of per-row dispatch payloads
      (generated ids, top-20 maps, confidence scans) that were NEVER
      device_get because the row artifact was skipped — the transfer
      the csv-reload pipeline pays per row. ``accum_bytes`` gauges the
      accumulator's own size: what DOES cross at a checkpoint/finalize.
    - ``checkpoints`` / ``merges``: accumulator snapshots written at
      flush boundaries and multihost fence merges performed.
    - ``finalize_s``: seconds spent in the grid -> CIs finalize;
      ``live_queries`` counts mid-run stats-endpoint reads.
    """

    rows_folded: int = 0
    dispatch_folds: int = 0
    host_bytes_avoided: int = 0
    accum_bytes: int = 0
    checkpoints: int = 0
    merges: int = 0
    live_queries: int = 0
    finalize_s: float = 0.0

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def gauge(self, field: str, value) -> None:
        with self._lock:
            setattr(self, field, value)

    def summary(self) -> Dict[str, object]:
        with self._lock:
            return {
                "rows_folded": self.rows_folded,
                "dispatch_folds": self.dispatch_folds,
                "host_bytes_avoided": self.host_bytes_avoided,
                "accum_bytes": self.accum_bytes,
                "checkpoints": self.checkpoints,
                "merges": self.merges,
                "live_queries": self.live_queries,
                "finalize_s": round(self.finalize_s, 4),
            }


@dataclasses.dataclass
class SpecStats:
    """Speculative-decode counters (engine/spec.py over generate.
    _spec_tail): how many tokens were drafted,
    where the drafts came from, how many survived greedy verification,
    and how many sequential decode forwards the verify windows
    replaced. Thread-safe — the sweep dispatch thread folds while the
    metrics endpoint reads.

    Definitions (reported by ``summary()``, logged per sweep, and in
    bench.py's "speculative" key):

    - ``drafted_tokens`` / ``accepted_tokens`` / ``rejected_tokens``:
      draft tokens proposed per verify window, the prefix of them the
      verifier's own argmax confirmed, and the remainder (a rejected
      draft costs only its share of the verify forward — results are
      bitwise either way). ``accept_rate`` = accepted / drafted.
    - ``draft_tree`` / ``draft_ngram`` / ``draft_fleet`` (and their
      ``accepted_*`` twins): per-source token counts — radix-tree
      continuation probes, n-gram prompt-lookup, and fleet draft
      models.
    - ``decode_forwards`` / ``seq_forwards``: verify forwards actually
      run vs the forwards the sequential scan would have run on the
      same rows; ``dispatches_saved`` is their difference — the
      headline ≥2x target is seq_forwards / decode_forwards.
    - ``spec_dispatches`` / ``spec_rows``: dispatches and rows that ran
      the speculative path; ``fallbacks`` counts spec-eligible
      dispatches that ran sequentially (layout fallback, k < 2, missing
      draft source).
    """

    drafted_tokens: int = 0
    accepted_tokens: int = 0
    rejected_tokens: int = 0
    draft_tree: int = 0
    draft_ngram: int = 0
    draft_fleet: int = 0
    accepted_tree: int = 0
    accepted_ngram: int = 0
    accepted_fleet: int = 0
    decode_forwards: int = 0
    seq_forwards: int = 0
    dispatches_saved: int = 0
    spec_dispatches: int = 0
    spec_rows: int = 0
    fallbacks: int = 0

    def __post_init__(self) -> None:
        import threading

        self._lock = threading.Lock()

    def count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def add_branch(self, drafted, accepted, chunks: int,
                   seq_steps: int) -> None:
        """Fold one branch's SpecOut readout: ``drafted``/``accepted``
        are (tree, ngram, fleet) token counts."""
        dt, dn, df = (int(x) for x in drafted)
        at, an, af = (int(x) for x in accepted)
        with self._lock:
            self.draft_tree += dt
            self.draft_ngram += dn
            self.draft_fleet += df
            self.accepted_tree += at
            self.accepted_ngram += an
            self.accepted_fleet += af
            self.drafted_tokens += dt + dn + df
            self.accepted_tokens += at + an + af
            self.rejected_tokens += (dt + dn + df) - (at + an + af)
            self.decode_forwards += int(chunks)
            self.seq_forwards += int(seq_steps)
            self.dispatches_saved += max(int(seq_steps) - int(chunks), 0)

    @property
    def accept_rate(self) -> float:
        return (self.accepted_tokens / self.drafted_tokens
                if self.drafted_tokens else 0.0)

    def summary(self) -> Dict[str, object]:
        with self._lock:
            drafted = self.drafted_tokens
            out: Dict[str, object] = {
                "drafted_tokens": drafted,
                "accepted_tokens": self.accepted_tokens,
                "rejected_tokens": self.rejected_tokens,
                "accept_rate": round(
                    self.accepted_tokens / drafted, 4) if drafted else 0.0,
                "decode_forwards": self.decode_forwards,
                "seq_forwards": self.seq_forwards,
                "dispatches_saved": self.dispatches_saved,
                "spec_dispatches": self.spec_dispatches,
                "spec_rows": self.spec_rows,
                "fallbacks": self.fallbacks,
                "draft_source": {
                    "tree": {"drafted": self.draft_tree,
                             "accepted": self.accepted_tree},
                    "ngram": {"drafted": self.draft_ngram,
                              "accepted": self.accepted_ngram},
                    "fleet": {"drafted": self.draft_fleet,
                              "accepted": self.accepted_fleet},
                },
            }
        return out


# Published peak dense-matmul throughput per chip (bf16 FLOPS). Weight-only
# int8 still computes in bf16 on the MXU, so bf16 peak is the MFU denominator
# there; dynamic int8 (s8 x s8 -> s32 dots) gets 2x this on every listed
# chip. Keys are jax Device.device_kind strings.
CHIP_PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,      # v5e
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,      # v6e / Trillium
}


# s8-dot speedup over bf16 per chip: v5e/v5p/v6e run int8 at 2x bf16 MXU
# rate; TPU v4 has NO accelerated int8 path (s8 dots run at the bf16 rate).
CHIP_INT8_MULTIPLIER = {"TPU v4": 1.0}
_DEFAULT_INT8_MULTIPLIER = 2.0


def chip_peak_flops(device=None, int8: bool = False) -> Optional[float]:
    """Peak matmul FLOPS of the given (default: first) device. None on
    the CPU backend only — callers skip the MFU gate there; an
    accelerator kind missing from ``CHIP_PEAK_BF16_FLOPS`` raises (a
    device that is not in the table is an error, not a default).
    ``int8=True`` returns the chip's s8-dot peak (2x bf16 on v5e/v5p/v6e,
    1x on v4)."""
    if device is None:
        device = jax.devices()[0]
    if getattr(device, "platform", "") == "cpu":
        return None
    kind = getattr(device, "device_kind", "")
    peak = CHIP_PEAK_BF16_FLOPS.get(kind)
    if peak is None:
        raise KeyError(
            f"no published peak for accelerator kind {kind!r}; add it to "
            f"profiling.CHIP_PEAK_BF16_FLOPS with its source")
    if int8:
        peak *= CHIP_INT8_MULTIPLIER.get(kind, _DEFAULT_INT8_MULTIPLIER)
    return peak


def decoder_matmul_params(cfg) -> int:
    """Matmul-visible parameter count of one ModelConfig decoder: the per-layer
    linear weights plus the lm_head. Embedding lookups do no matmul FLOPs."""
    D, hd = cfg.hidden_size, cfg.head_dim
    H, K, F = cfg.n_heads, cfg.n_kv_heads, cfg.intermediate_size
    per_layer = (D * H * hd          # wq
                 + 2 * D * K * hd    # wk, wv
                 + H * hd * D        # wo
                 + 2 * D * F         # w_up, w_down
                 + (D * F if cfg.gated_mlp else 0))
    return cfg.n_layers * per_layer + D * cfg.vocab_size  # + lm_head


def scoring_step_flops_split(cfg, batch: int, seq: int,
                             new_tokens: int) -> Dict[str, float]:
    """Matmul FLOPs (2 per MAC) of one fused scoring step, itemized by
    PHASE (the KernelStats breakdown): "prefill" — the quadratic prompt
    pass through the layer stack; "decode" — `new_tokens` KV-cached
    greedy steps through the layers (attention over the growing cache
    included); "readout" — the lm_head at the prefill's last position
    and once per decode step (decoder.prefill/_unembed). Sums to
    :func:`scoring_step_flops` exactly."""
    D, hd = cfg.hidden_size, cfg.head_dim
    H, L, V = cfg.n_heads, cfg.n_layers, cfg.vocab_size
    p_layers = decoder_matmul_params(cfg) - D * V
    head = 2 * D * V * batch
    prefill = 2 * p_layers * batch * seq
    prefill += 4 * batch * H * seq * seq * hd * L      # scores + weighted sum
    decode = 0.0
    for t in range(new_tokens):
        decode += 2 * p_layers * batch
        decode += 4 * batch * H * (seq + t + 1) * hd * L
    return {"prefill": float(prefill), "decode": float(decode),
            "readout": float(head * (1 + new_tokens))}


def scoring_step_flops(cfg, batch: int, seq: int, new_tokens: int) -> float:
    """Total matmul FLOPs (2 per MAC) of one fused scoring step: prefill of
    (batch, seq) + `new_tokens` KV-cached greedy decode steps. The lm_head
    runs once at the prefill's last position and once per decode step
    (decoder.prefill/_unembed). Attention score/value matmuls included.
    See :func:`scoring_step_flops_split` for the per-phase breakdown."""
    return float(sum(scoring_step_flops_split(
        cfg, batch, seq, new_tokens).values()))


def cascade_prefill_flops_saved(cfg, rows: int, trunk_len: int) -> float:
    """Analytic matmul FLOPs a cascade dispatch dedups away: the dense
    shared path prefills the ``trunk_len``-token trunk once per row —
    layer-stack linears plus the quadratic attention term, the exact
    per-row prefill arithmetic of :func:`scoring_step_flops_split` —
    while the cascade pays it ONCE, so ``rows - 1`` trunk prefills are
    saved (CascadeStats.prefix_flops_saved; the suffix-leg and merge
    work is common to both paths and cancels)."""
    if rows <= 1 or trunk_len <= 0:
        return 0.0
    D, hd = cfg.hidden_size, cfg.head_dim
    H, L, V = cfg.n_heads, cfg.n_layers, cfg.vocab_size
    p_layers = decoder_matmul_params(cfg) - D * V
    per_row = 2 * p_layers * trunk_len
    per_row += 4 * H * trunk_len * trunk_len * hd * L
    return float((rows - 1) * per_row)


def cascade_decode_bytes_saved(cfg, rows: int, trunk_len: int,
                               cache_len: int, steps: int,
                               itemsize: int = 4) -> float:
    """Analytic HBM bytes the trunk-aware flash-decode split dedup does
    NOT stream: the flat kernel's split-K grid reads every batch block's
    trunk K/V tiles from HBM each decode step, the trunk index map sends
    every block to the first one, whose tiles are fetched ONCE per step
    (ops/flash_decode.flash_decode_trunk) — so each step saves
    ``rows - batch_block`` copies of the trunk splits' K+V bytes per
    layer.

    The trunk split count mirrors the kernel's own static ladder
    exactly (``decode_split``'s divisor-of-``cache_len`` pick, then
    ``min(trunk_len, cache_len - 1) // split`` whole splits — partial
    trailing splits stay per-block), so the counter reports the bytes
    the lowered kernel really dedups, not an idealized ``trunk_len``
    bound. ``itemsize`` is the cache dtype's (float32 = 4; the engine's
    float KV caches — the int8 cache never reaches these kernels)."""
    if rows <= 1 or trunk_len <= 0 or steps <= 0 or cache_len <= 1:
        return 0.0
    from ..ops.flash_decode import batch_block, decode_split

    n_kv = getattr(cfg, "n_kv_heads", None) or cfg.n_heads
    split = decode_split(int(cache_len), int(rows), cfg.n_heads // n_kv)
    nt = max(0, min(int(trunk_len), int(cache_len) - 1)) // split
    if nt == 0:
        return 0.0
    hd = cfg.head_dim
    per_row_step = 2 * n_kv * (nt * split) * hd * itemsize * cfg.n_layers
    return float(per_row_step * (rows - batch_block(int(rows))) * steps)


def device_memory_stats() -> Dict[str, Dict[str, float]]:
    """Per-device memory stats in GiB where the backend exposes them."""
    out: Dict[str, Dict[str, float]] = {}
    for dev in jax.devices():
        try:
            stats = dev.memory_stats()
        except Exception:
            continue
        if not stats:
            continue
        out[str(dev)] = {
            k: round(v / 2**30, 3)
            for k, v in stats.items()
            if isinstance(v, (int, float)) and "bytes" in k
        }
    return out


def ensure_cpu_backend() -> bool:
    """Force the CPU backend for statistics-only work.

    The analysis/survey layers are host statistics: tiny kernels where an
    accelerator buys nothing, and a chip belongs to one process at a
    time — a statistics run that grabbed it would lock out the sweep or
    server that needs it. (tools/stats_device_bench.py measures the same
    kernels on both backends; its SCALE.md table predates the directly
    attached chip and is due a re-run.) Call before any jax computation;
    returns False when the backend was already initialized to something
    else (work proceeds there).
    """
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        return True
    except Exception:
        return jax.default_backend() == "cpu"


def is_oom_error(err: BaseException | str) -> bool:
    """True when an exception (or its text) is a device out-of-memory —
    the ONE place the TPU runtime's OOM message heuristics live
    (RESOURCE_EXHAUSTED / "out of memory", case-insensitive); bench and
    the measurement tools use it to fall down batch ladders instead of
    aborting."""
    msg = str(err)
    return ("RESOURCE_EXHAUSTED" in msg
            or "out of memory" in msg.lower())
