#!/usr/bin/env python3
"""One traced run of a cell with everything the program names switched on
(on the chip):

    python3 benchmarks/tests/phase_trace.py --workload <cell> --seed <n> \
        [--seconds <s>] [--out <file.json>]

It is ``run.py --trace 1`` plus what ``run.py`` cannot do yet without an
edit (PERF.md, open questions): a ``TraceRecorder`` installed at process
start, a ``clock_anchor`` right after the profiler starts and right
before it stops, the scope tables of the dispatched programs, and the
readers of ``harness/spans.py`` over them. The last line of standard
output is the run's result line with one more key, ``named``:

* ``phase_ms_per_dispatch``: device time of the dispatch programs by
  phase scope, ``other`` and the cover (operations over program time);
* ``anchors``: the two clock offsets and their difference;
* ``spans``: per span name count, total and self seconds, set-up and
  window apart; ``device_tail_ms``; ``idle_gaps``: the reducer's own
  attribution of the idle gaps over EVERY host line (``trace.read_planes``
  drops all but one of the lines named ``python3``), and
  ``idle_named_pct``: the share of those idle seconds that lies under a
  program span other than the bare root;
* ``cost_s``: what building the tables and reading the spans took.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

PHASES = ("lir.prefill", "lir.extend", "lir.decode", "lir.readout")
HELD = {}


def install() -> object:
    """The recorder, the anchors and the table hook, around the
    harness's own code."""
    from harness import builders, trace as trace_mod

    from lir_tpu.observe import tracing

    rec = tracing.TraceRecorder(capacity=1 << 20)
    tracing.set_recorder(rec)

    class AnchoredTracer(trace_mod.Tracer):
        def start(self) -> None:
            super().start()
            HELD["window_t0"] = time.monotonic()
            tracing.clock_anchor()

        def stop(self) -> None:
            tracing.clock_anchor()
            super().stop()
            HELD["tracer"] = self
            engine = HELD["engine"]()
            counted = dict(builders.COMPILE)
            t0 = time.perf_counter()
            HELD["tables"] = engine.exec_registry.scope_tables(engine)
            HELD["tables_s"] = time.perf_counter() - t0
            # a table compiled again over a stale cache entry is this
            # tool's compile, not one inside the measured window
            builders.COMPILE.update(counted)

    trace_mod.Tracer = AnchoredTracer
    build = builders.build_engine

    def build_and_remember(*args, **kwargs):
        import weakref

        engine = build(*args, **kwargs)
        HELD["engine"] = weakref.ref(engine)
        return engine

    builders.build_engine = build_and_remember
    return rec


def summarize(events: list, own: dict, t_window: float) -> dict:
    out = {}
    for ev in events:
        part = "window" if ev["t0"] >= t_window else "setup"
        rec = out.setdefault(part, {}).setdefault(ev["name"], [0, 0.0, 0.0])
        rec[0] += 1
        rec[1] += ev["t1"] - ev["t0"]
        rec[2] += own[ev["id"]]
    return out


def named(result: dict, rec, cell: dict) -> dict:
    from harness import spans, trace as trace_mod

    t0 = time.perf_counter()
    events = rec.events()
    planes = spans.read_planes(HELD["tracer"].file())
    read_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    tables = HELD["tables"]
    split = spans.phase_seconds(planes, tables, "^jit_greedy_decode",
                                cell["chips"])
    phases = None
    if split is not None:
        runs = split["runs"]
        phases = {s: 1e3 * split["scopes"].get(s, 0.0) / runs for s in PHASES}
        phases["other"] = 1e3 * split["other_s"] / runs
        phases["program"] = 1e3 * split["module_s"] / runs
        phases["cover_pct"] = 100.0 * split["ops_s"] / split["module_s"]
        phases["runs"] = runs
        phases["unmatched_runs"] = split["unmatched_runs"]
    offsets = spans.anchor_offsets(planes, events)
    own = spans.self_seconds(events)
    tail = spans.device_tail_seconds(planes, events)
    idle = trace_mod.reduce_planes(
        planes, HELD["tracer"].window_s,
        cell["chips"])["breakdown"]["idle_gaps"]
    listed = sum(v for _, v in idle)
    bare = sum(v for k, v in idle
               if k == "sweep/call" or not trace_mod.SPAN.match(k))
    return {
        "phase_ms_per_dispatch": phases,
        "tables": [{k: t[k] for k in ("label", "module", "instructions",
                                      "recompiled")}
                   | {"scoped": len(t["scopes"])} for t in tables],
        "anchors": {"offsets_s": offsets,
                    "differ_us": (1e6 * (offsets[-1] - offsets[0])
                                  if len(offsets) > 1 else None)},
        "spans": summarize(events, own, HELD["window_t0"]),
        "dropped_spans": rec.dropped,
        "device_tail_ms": None if tail is None else 1e3 * tail,
        "idle_gaps": idle,
        "idle_listed_s": listed,
        "idle_named_pct": (100.0 * (listed - bare) / listed
                           if listed else None),
        "cost_s": {"scope_tables": HELD["tables_s"],
                   "read_planes_again": read_s,
                   "reduce_spans": time.perf_counter() - t0},
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import run as bench_run
    from harness import builders

    rec = install()
    bench, cell = bench_run.load_cell(args.workload)
    devices = builders.device_or_exit(cell["chips"])
    result = bench_run.drive(cell, bench, bench_run.load_files(cell),
                             args.seed, args.seconds, True,
                             devices[:cell["chips"]])
    result["named"] = named(result, rec, cell)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(
            {"result": result, "events": rec.events()}))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
