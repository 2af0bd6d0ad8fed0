"""MetricsRegistry: one registry, one canonical snapshot schema.

utils/profiling.py grew eleven disconnected ``*Stats`` objects across
ten PRs — each correct alone, none queryable together: no common
snapshot, no single endpoint, and a new counter was visible only if
someone remembered to log it. This module is the one place runtime
telemetry converges:

- every ``*Stats`` instance registers under a stable source name;
- :meth:`MetricsRegistry.snapshot` produces ONE canonical JSON-safe
  document: per source, the raw public fields declared in
  :data:`STATS_SCHEMA` plus the object's derived ``summary()`` dict,
  plus native registry counters/gauges and the per-device HBM gauges
  (``utils/profiling.device_memory_stats`` — WeightCache budget
  pressure is visible BEFORE ``WeightCacheOOM`` fires);
- the serve ``{"op": "metrics"}`` JSONL endpoint returns it live, the
  sweep dumps it per run, and the CLI logs it at serve exit.

:data:`STATS_SCHEMA` is the snapshot schema contract: a pure literal
mapping every registered ``*Stats`` class to the tuple of public fields
its snapshot carries. The ``metrics-drift`` lint pass
(lir_tpu/lint/metricsdrift.py) parses this literal and the profiling
dataclasses statically, so a PR that adds a counter field without
adding it here fails lint — a counter can never silently drop out of
the endpoint again.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, Optional, Tuple

SNAPSHOT_VERSION = 1

# The snapshot schema contract (parsed by lint/metricsdrift.py — keep
# this a PURE literal: string keys, tuples of string field names).
# Every public field of every *Stats dataclass in utils/profiling.py
# must appear in its class's tuple.
STATS_SCHEMA: Dict[str, Tuple[str, ...]] = {
    "OccupancyStats": (
        "buckets", "grouped_cells", "grouped_prefill_rows",
        "trimmed_slots", "decode_steps_live", "decode_steps_paid",
    ),
    "CompileStats": (
        "shapes", "aot_hits", "aot_shapes_hit", "lazy_misses",
        "load_wall_s", "persistent_requests", "persistent_hits",
        "cold_start_s", "warm_start_s",
    ),
    "KernelStats": ("phases", "counters"),
    "ServeStats": (
        "submitted", "admitted", "shed", "completed", "expired",
        "errors", "late", "dedup_hits", "dedup_misses", "dispatches",
        "slots_used", "slots_paid", "promoted", "queue_depth_peak",
    ),
    "FaultStats": (
        "injected", "recovered_dispatches", "degraded_dispatches",
        "degraded_rows", "preemptions", "breaker_opens",
        "breaker_probes", "breaker_closes", "transitions",
    ),
    "GuardStats": (
        "watched", "stalls", "checked", "quarantined", "reasons",
        "stall_dumps", "inflight_cancelled", "barrier_timeouts",
        "heartbeats",
    ),
    "PrefixCacheStats": (
        "lookups", "hits", "hit_tokens", "prefill_tokens_total",
        "inserted_pages", "evicted_pages", "pages_in_use",
        "pages_total",
    ),
    "FleetStats": (
        "swap_s_hidden", "swap_s_exposed", "loads", "load_s",
        "weight_bytes_streamed", "prefetch_hits", "prefetch_misses",
        "cache_hits", "evictions", "resident_models", "resident_bytes",
        "model_swaps", "fleet_requests", "fleet_rows",
    ),
    "StreamStats": (
        "rows_folded", "dispatch_folds", "host_bytes_avoided",
        "accum_bytes", "checkpoints", "merges", "live_queries",
        "finalize_s",
    ),
    "RouterStats": (
        "routed", "routed_resident", "dedup_hits", "completed",
        "errors", "failovers", "re_admitted", "hedged", "hedge_wins",
        "hedge_losses", "zombie_payloads", "replica_errors",
        "replica_sheds", "no_replica_sheds", "kills", "revives",
        "per_replica",
    ),
    "MigrationStats": (
        "migrations", "prefill_ops", "pages_migrated", "bytes_streamed",
        "chunks_streamed", "migration_s_exposed", "migration_s_hidden",
        "refetch_fallbacks", "stalls", "corrupt_chunks",
        "cluster_tree_hits",
    ),
    "TierStats": (
        "demotions", "promotions", "pages_demoted", "pages_promoted",
        "bytes_spilled", "bytes_promoted", "restart_pages_reseeded",
        "restart_weights_reseeded", "checksum_refusals", "disk_stalls",
        "pin_refusals", "host_bytes", "disk_bytes",
    ),
    "LeaseStats": (
        "claims", "renews", "releases", "steals", "refused", "lost",
        "expired_seen", "shards_done", "refreshes",
    ),
    "SpecStats": (
        "drafted_tokens", "accepted_tokens", "rejected_tokens",
        "draft_tree", "draft_ngram", "draft_fleet", "accepted_tree",
        "accepted_ngram", "accepted_fleet", "decode_forwards",
        "seq_forwards", "dispatches_saved", "spec_dispatches",
        "spec_rows", "fallbacks",
    ),
    "CascadeStats": (
        "cascade_dispatches", "dense_fallbacks", "trunk_rows_deduped",
        "prefix_flops_saved", "cascade_decode_dispatches",
        "trunk_bytes_deduped", "tokens_prefilled",
        "trunk_tokens_prefilled", "trunk_programs",
        "trunk_held_dispatches",
    ),
    "FillStats": (
        "fill_s", "ahead_s", "windows", "windows_ahead", "wait_s",
    ),
    "SparseStats": (
        "blocks_kept", "blocks_offered", "queries", "dense_queries",
        "pooled_key_bytes",
    ),
    "RecurrentStats": (
        "dispatches", "state_bytes", "kv_bytes", "forks", "scan_calls",
        "step_calls", "trunk_states_shared",
    ),
    "MemStats": (
        "ledger_bytes", "budget_bytes", "pressure", "rung",
        "rung_downs", "rung_ups", "admits", "denials", "oom_events",
        "oom_reclaims", "oom_exhausted", "squeezes", "sheds",
    ),
}


def _json_safe(value, depth: int = 0):
    """Best-effort JSON sanitization: numpy scalars -> python, dataclass
    -> dict, tuples -> lists, non-finite floats -> None (strict-JSON
    clients must not choke on a NaN gauge), unknown objects -> repr.
    Copies containers first so concurrent counter mutation during a
    snapshot can at worst yield a momentarily-stale value, never a
    corrupt document."""
    import math

    if depth > 8:
        return repr(value)
    if value is None or isinstance(value, (bool, int, str)):
        return value
    if isinstance(value, float):
        return value if math.isfinite(value) else None
    if dataclasses.is_dataclass(value) and not isinstance(value, type):
        return {f.name: _json_safe(getattr(value, f.name), depth + 1)
                for f in dataclasses.fields(value)
                if not f.name.startswith("_")}
    if isinstance(value, dict):
        try:
            items = list(value.items())
        except RuntimeError:        # resized mid-iteration; retry once
            items = list(dict(value).items())
        return {str(k): _json_safe(v, depth + 1) for k, v in items}
    if isinstance(value, (list, tuple, set, frozenset)):
        return [_json_safe(v, depth + 1) for v in list(value)]
    if hasattr(value, "item") and getattr(value, "ndim", None) == 0:
        return _json_safe(value.item(), depth + 1)   # numpy scalar
    if hasattr(value, "tolist"):
        return _json_safe(value.tolist(), depth + 1)  # numpy array
    return repr(value)


class MetricsRegistry:
    """Named metrics sources + native counters/gauges, one snapshot.

    Sources are the existing ``*Stats`` objects (anything with public
    fields and/or a ``summary()`` method registers as-is — no adapter
    classes); native counters/gauges cover telemetry that has no stats
    object of its own (sentinel sweeps run, alerts raised, endpoint
    polls). Thread-safe throughout: supervisors, writer threads, and
    endpoint readers all touch it concurrently.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._sources: Dict[str, object] = {}  # guarded-by: _lock
        self._counters: Dict[str, float] = {}  # guarded-by: _lock
        self._gauges: Dict[str, object] = {}   # guarded-by: _lock

    # -- registration --------------------------------------------------------

    def register(self, name: str, stats: object) -> object:
        """Register a stats source under a stable name. Re-registering
        a name replaces it (servers rebuild sinks across resume)."""
        with self._lock:
            self._sources[str(name)] = stats
        return stats

    def unregister(self, name: str) -> None:
        with self._lock:
            self._sources.pop(str(name), None)

    def sources(self) -> Dict[str, object]:
        with self._lock:
            return dict(self._sources)

    # -- native metrics ------------------------------------------------------

    def counter(self, name: str, n: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def gauge(self, name: str, value) -> None:
        with self._lock:
            self._gauges[name] = value

    # -- the canonical snapshot ----------------------------------------------

    def snapshot(self, device_memory: bool = True) -> Dict[str, object]:
        """One JSON-safe document covering every registered source:

        ``sources.<name>.fields`` — the raw public fields declared in
        :data:`STATS_SCHEMA` for the source's class (unknown classes
        fall back to their public dataclass/attribute fields);
        ``sources.<name>.summary`` — the object's own derived
        ``summary()`` when it has one; plus native ``counters`` /
        ``gauges`` and the per-device ``device_memory`` HBM gauges.
        """
        with self._lock:
            sources = dict(self._sources)
            counters = dict(self._counters)
            gauges = dict(self._gauges)
        doc: Dict[str, object] = {
            "schema_version": SNAPSHOT_VERSION,
            "time_s": time.time(),
            "counters": _json_safe(counters),
            "gauges": _json_safe(gauges),
            "sources": {},
        }
        for name, obj in sources.items():
            cls = type(obj).__name__
            fields = STATS_SCHEMA.get(cls)
            if fields is None:
                if dataclasses.is_dataclass(obj):
                    fields = tuple(f.name for f in dataclasses.fields(obj)
                                   if not f.name.startswith("_"))
                else:
                    fields = tuple(k for k in vars(obj)
                                   if not k.startswith("_"))
            entry: Dict[str, object] = {
                "type": cls,
                "fields": {f: _json_safe(getattr(obj, f, None))
                           for f in fields},
            }
            summarize = getattr(obj, "summary", None)
            if callable(summarize):
                try:
                    entry["summary"] = _json_safe(summarize())
                except Exception as err:  # noqa: BLE001 — one broken
                    # source must not take the whole endpoint down
                    entry["summary_error"] = repr(err)
            doc["sources"][name] = entry
        if device_memory:
            from ..utils.profiling import device_memory_stats

            doc["device_memory"] = _json_safe(device_memory_stats())
        return doc


def engine_registry(engine, sink=None,
                    registry: Optional[MetricsRegistry] = None
                    ) -> MetricsRegistry:
    """Register one ScoringEngine's stats objects (the per-sweep dump
    and the single-model server both use this): guard, compile, fault,
    kernel, prefix, occupancy when set, and the streaming sink's
    counters when a sink is attached; plus the process's span totals
    (observe/tracing.TOTALS: per span name, count / total / self
    seconds) as source ``spans``."""
    from . import tracing

    reg = registry if registry is not None else MetricsRegistry()
    reg.register("spans", tracing.TOTALS)
    reg.register("guard", engine.guard_stats)
    reg.register("compile", engine.compile_stats)
    reg.register("faults", engine.fault_stats)
    if getattr(engine, "kernel_stats", None) is not None:
        reg.register("kernel", engine.kernel_stats)
    if getattr(engine, "prefix_stats", None) is not None:
        reg.register("prefix_cache", engine.prefix_stats)
    if getattr(engine, "occupancy", None) is not None:
        reg.register("occupancy", engine.occupancy)
    if getattr(engine, "spec_stats", None) is not None:
        reg.register("spec", engine.spec_stats)
    if getattr(engine, "cascade_stats", None) is not None:
        reg.register("cascade", engine.cascade_stats)
    if getattr(engine, "fill_stats", None) is not None:
        reg.register("fill", engine.fill_stats)
    if getattr(engine, "recurrent_stats", None) is not None:
        reg.register("recurrent", engine.recurrent_stats)
    if getattr(engine, "sparse_stats", None) is not None:
        reg.register("sparse", engine.sparse_stats)
    if getattr(engine, "governor", None) is not None:
        # HBM-governor gauges (engine/hbm.py): ledger/pressure/rung
        # land in the snapshot next to device_memory_stats(), so budget
        # pressure is visible BEFORE anything OOMs.
        reg.register("mem", engine.governor.stats)
    if sink is not None and getattr(sink, "stats", None) is not None:
        reg.register("stream", sink.stats)
    return reg
