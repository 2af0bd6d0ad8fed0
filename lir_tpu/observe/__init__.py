"""Reliability observatory + unified telemetry spine (ROADMAP item 5).

Three pieces, each usable alone, designed to compose:

- :mod:`~lir_tpu.observe.registry` — ONE MetricsRegistry every existing
  ``*Stats`` object (utils/profiling.py) registers into, with one
  canonical JSON snapshot schema. Exposed live through the serve
  ``{"op": "metrics"}`` JSONL endpoint and dumped per sweep; the
  ``metrics-drift`` lint pass (lir_tpu/lint/metricsdrift.py) proves
  statically that no public counter field can silently drop out of it.
- :mod:`~lir_tpu.observe.tracing` — structured trace spans with id,
  parent and cause over the serving lifecycle (admit → queue →
  batch-form → dispatch → readout → resolve), the whole offline sweep
  call and the compile plan, per-name totals published as the metrics
  source ``spans``, correlated with device traces via
  ``jax.profiler.TraceAnnotation`` and ``clock_anchor`` and exportable
  as Chrome/Perfetto trace JSON (``--trace-out``).
- :mod:`~lir_tpu.observe.drift` + :mod:`~lir_tpu.observe.sentinel` —
  the reliability observatory itself: a :class:`SentinelScheduler` on
  the fleet server re-scores a sentinel grid on interval and on weight-
  cache change, folds results into TIME-WINDOWED accumulator lattices
  (engine/stream_stats.WindowedStreamSink — PR 9's lattice with a time
  axis, idempotent fold + order-free merge preserved per window), and
  computes per-window κ/CI/mean drift on device with σ-threshold
  alerts, queryable through the serve ``stats`` endpoint. "Model X's
  agreement with the fleet dropped 3σ this week" becomes a query
  instead of a postmortem.
"""

from .drift import detect_drift, window_summary
from .registry import STATS_SCHEMA, MetricsRegistry, engine_registry
from .sentinel import SentinelScheduler
from .tracing import (TraceRecorder, add_span, get_recorder, set_recorder,
                      span)

__all__ = [
    "MetricsRegistry", "STATS_SCHEMA", "engine_registry",
    "TraceRecorder", "span", "add_span", "set_recorder", "get_recorder",
    "SentinelScheduler", "window_summary", "detect_drift",
]
