"""Readers of per-layer metrics. A metric is ``metrics/<name>.json``:

    {"reader": "<one of READERS>", "args": {...}}

and the reader takes its number from the run's ``context``: the two
counter snapshots, the window's own counts, the reduced trace, the
memory reading, the configuration's sizes and the chip's peaks. A reader
that finds nothing to read returns None and the metric is left out of the
line; it never returns 0 for a share of a roofline or of a peak.

A value path is ``<root>:<dotted.path>``; the roots are ``delta`` (after
minus before, for counters that only grow), ``after``, ``window`` and
``memory``.
"""

from __future__ import annotations

import json
import re
import statistics
from pathlib import Path

from . import flops

ROOT = Path(__file__).resolve().parents[1]          # benchmarks/


def _dig(doc, path: str):
    for part in path.split("."):
        if not isinstance(doc, dict) or part not in doc:
            return None
        doc = doc[part]
    return doc


def lookup(context: dict, ref) -> float | None:
    if isinstance(ref, (int, float)):
        return float(ref)
    root, _, path = ref.partition(":")
    if root == "delta":
        a = _dig(context["counters"]["after"], path)
        b = _dig(context["counters"]["before"], path)
        return None if a is None else float(a) - float(b or 0)
    doc = (context["counters"]["after"] if root == "after"
           else context[root])
    value = _dig(doc, path)
    return None if value is None else float(value)


def counter(context, num, den=1, times=1, scale=1.0):
    """scale * num * times / den, each a number or a value path."""
    n, d, t = (lookup(context, x) for x in (num, den, times))
    if n is None or d is None or t is None or d == 0:
        return None
    return scale * n * t / d


def memory_stats(context, scale=100.0):
    m = context["memory"]
    return scale * m["peak"] / m["limit"] if m.get("limit") else None


def _matching(table: dict, pattern: str) -> tuple:
    rx = re.compile(pattern)
    hits = [v for k, v in table.items() if rx.search(k)]
    return sum(v[0] for v in hits), sum(v[1] for v in hits)


def trace_module_time(context, pattern, per="count", scale=1000.0):
    """Summed device seconds of the programs whose XLA module name matches
    ``pattern``, over their count (``per: "count"``) or whole."""
    seconds, count = _matching(context["trace"]["modules"], pattern)
    if not count:
        return None
    return scale * (seconds / count if per == "count" else seconds)


def trace_step_mfu(context, peak="bf16_flops"):
    """FLOPs the traffic needs over traced-window time x the chip's peak."""
    need = context["window"].get("needed_flops")
    t = context["trace"]
    if not need or not t["window_s"]:
        return None
    return 100.0 * need / (t["window_s"] * t["chips"]
                           * getattr(context["peaks"], peak))


def trace_kernel_roofline(context, pattern, shape):
    """The least time the chip could take for the calls of the device
    operations matching ``pattern`` over their summed device time. The
    least time of one call is the larger of FLOPs over peak and bytes
    over peak bandwidth, from ``harness/flops.py``'s function named
    ``shape`` at the mean sizes the window's traffic had
    (``window.kernel_calls``); the number of calls is the trace's."""
    seconds, count = _matching(context["trace"]["ops"], pattern)
    sizes = context["window"].get("kernel_calls", {}).get(shape)
    if not count or not sizes or not seconds:
        return None
    f, b = getattr(flops, shape)(context["spec"], **sizes)
    least, _ = flops.roofline_seconds(f, b, context["peaks"])
    return 100.0 * count * least / seconds


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile over ALL values."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1,
                       max(0, int(-(-q / 100.0 * len(ordered) // 1)) - 1))]


def window_stat(context, series, stat="p95", scale=1.0):
    """A statistic of one of the window's own series of samples."""
    values = context["samples"].get(series)
    if not values:
        return None
    if stat == "mean":
        return scale * statistics.fmean(values)
    return scale * percentile(values, {"p50": 50, "p95": 95, "p99": 99}[stat])


READERS = {f.__name__: f for f in (
    counter, memory_stats, trace_module_time, trace_step_mfu,
    trace_kernel_roofline, window_stat)}


def read_all(wanted: list, context: dict) -> dict:
    """{name: {"value", "unit"}} for the entries of ``per_layer`` that
    found something to read."""
    out = {}
    for m in wanted:
        spec = json.loads(
            (ROOT / "metrics" / f"{m['name']}.json").read_text())
        value = READERS[spec["reader"]](context, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
