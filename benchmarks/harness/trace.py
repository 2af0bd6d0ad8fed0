"""From the profiler's trace to a handful of numbers.

``jax.profiler`` writes one ``.xplane.pb`` per traced window. On a TPU it
holds one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops`` line has
one event per device operation and whose ``XLA Modules`` line one event
per executed program, and a host plane (``/host:CPU``) with a line per
thread that carries the program's own spans (``observe.tracing.span`` ->
``TraceAnnotation``), all on one clock. The reduction:

* ``busy_s``: the union of the device-operation intervals, per chip,
  averaged over the chips used; ``window_s``: the traced window;
* per program (XLA module name) and per operation: summed device seconds
  and a count. An operation's event is named by its whole HLO line; it is
  keyed here by ``<name> <result type>`` (``flash_decode_trunk
  (f32[5,8,1,23,32,128]``). Loops and conditionals are on the same line as
  the operations inside them, so they count for ``busy_s`` (a union) and
  are left out of the per-operation table;
* ``breakdown``: the ten operations that took most device time, and the
  idle gaps longer than ``GAP_FLOOR_S`` summed by what the host was doing
  at the middle of the gap: the innermost program span open there, else
  the innermost runtime event on any host thread.

Seen on the v5e (PR 23): spans opened on a Python thread other than the
main one do not reach the trace, and the sweep opens its spans on its
watchdog and writer threads; so today the gaps carry runtime names.
"""

from __future__ import annotations

import re
import time
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN = re.compile(r"^(sweep|serve|router|engine)/[\w/.-]+")
GAP_FLOOR_S = 0.0005


CONTAINER = re.compile(r"^%?(while|cond|conditional|call)[.\d]*( |$)")


def strip_id(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``jit_f(1234)`` -> ``jit_f``."""
    return re.sub(r"(\.\d+)+$|\(\d+\)$", "", name)


def op_key(name: str) -> str:
    """An HLO line -> ``<name> <result type>``, at most 96 characters."""
    head, _, rest = name.partition(" = ")
    kind = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{head.lstrip('%')} {kind}".strip()[:96]


def union_seconds(intervals: list) -> tuple:
    """(seconds covered, merged [start, end] list) of (start, end) ns."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, merged


def _events(line) -> list:
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def reduce_planes(planes: dict, window_s: float, chips: int) -> dict:
    """``planes``: {plane name: {line name: [(name, start_ns, dur_ns)]}}.
    Kept apart from the file reader so a test can feed it by hand."""
    devices = sorted((int(DEVICE_PLANE.match(n).group(1)), n)
                     for n in planes if DEVICE_PLANE.match(n))[:chips]
    if not devices:
        raise RuntimeError(f"no TPU plane in the trace (planes: "
                           f"{sorted(planes)})")
    busy, ops, modules, gaps = [], {}, {}, []
    for _, plane in devices:
        lines = planes[plane]
        ev = lines.get(OPS_LINE, [])
        seconds, merged = union_seconds([(s, s + d) for _, s, d in ev])
        busy.append(seconds)
        gaps += [(a[1], b[0]) for a, b in zip(merged, merged[1:])
                 if (b[0] - a[1]) / 1e9 >= GAP_FLOOR_S]
        for name, _, d in ev:
            if CONTAINER.match(name):
                continue
            rec = ops.setdefault(op_key(name), [0.0, 0])
            rec[0] += d / 1e9
            rec[1] += 1
        for name, _, d in lines.get(MODULES_LINE, []):
            rec = modules.setdefault(strip_id(name), [0.0, 0])
            rec[0] += d / 1e9
            rec[1] += 1
    host = [(name, s, s + d)
            for line in planes.get(HOST_PLANE, {}).values()
            for name, s, d in line]
    spans = [h for h in host if SPAN.match(h[0])]
    idle = {}
    for a, b in gaps:
        mid = (a + b) / 2
        label = "host: nothing open"
        for pool in (spans, host):
            open_ = [(e - s, name) for name, s, e in pool if s <= mid <= e]
            if open_:
                label = strip_id(min(open_)[1])[:96]
                break
        idle[label] = idle.get(label, 0.0) + (b - a) / 1e9
    n = len(devices)
    top = sorted(((k, v[0] / n) for k, v in ops.items()),
                 key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / n, "window_s": window_s, "chips": n,
        "ops": {k: (v[0] / n, v[1] / n) for k, v in ops.items()},
        "modules": {k: (v[0] / n, v[1] / n) for k, v in modules.items()},
        "breakdown": {
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v / n] for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])[:10]]},
    }


def read_planes(path: Path) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    return {p.name: {ln.name: _events(ln) for ln in p.lines}
            for p in data.planes}


class Tracer:
    """Traces one window into ``directory`` and reduces it."""

    def __init__(self, directory: Path):
        self.directory = Path(directory)
        self.window_s = 0.0

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # the program's spans suffice
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.directory), profiler_options=opts)
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()

    def file(self) -> Path:
        found = sorted(self.directory.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.directory}")
        return found[-1]

    def reduce(self, chips: int) -> dict:
        return reduce_planes(read_planes(self.file()), self.window_s, chips)
