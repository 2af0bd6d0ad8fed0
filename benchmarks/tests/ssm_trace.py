#!/usr/bin/env python3
"""One traced run of a cell whose model has a state-space mixer, with the
two scan kernels' roofline shares read off the device trace (on the chip):

    python3 benchmarks/tests/ssm_trace.py --workload <cell> --seed <n> \
        [--seconds <s>]

It is ``run.py --trace 1`` plus what ``run.py`` cannot do without an edit
(PERF.md, open questions): ``harness/ssm.py``'s sizes among the window's
``kernel_calls`` and its reader beside the registered ones. The last line
of standard output is the run's result line with one more key, ``ssm``:
``ssd_scan_roofline`` / ``ssm_step_roofline`` (%), and per kernel the
device operations matched, their count, the count the program's own
counters give for the same window (``recurrent.scan_calls`` /
``step_calls``; the run exits 1 where the two differ), their summed
seconds, and their milliseconds per dispatch. On a cell without a mixer
``ssm`` is empty.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

HELD = {}


def install() -> None:
    """The sizes into the window's record, the reader after the others."""
    from harness import readers, ssm, sweep_window

    needed = sweep_window.needed_flops

    def needed_with_ssm(spec, mix, prompts, perts, new_bin, new_conf):
        total, offered, calls = needed(spec, mix, prompts, perts, new_bin,
                                       new_conf)
        if hasattr(spec, "ssm_heads"):
            calls = dict(calls, **ssm.window_calls(spec, mix, prompts, perts))
        return total, offered, calls

    sweep_window.needed_flops = needed_with_ssm
    read_all = readers.read_all

    def read_all_and_ssm(wanted, context):
        out = {}
        for name, m in ssm.METRICS.items():
            value = ssm.READERS[m["reader"]](context, **m["args"])
            if value is None:
                continue
            out[name] = {"value": float(value), "unit": "%"}
            rx = re.compile(m["args"]["pattern"])
            hits = {k: v for k, v in context["trace"]["ops"].items()
                    if rx.search(k)}
            seconds = sum(v[0] for v in hits.values())
            runs = sum(v[1] for k, v in context["trace"]["modules"].items()
                       if re.search("^jit_greedy_decode", k))
            # The program's own count of this kernel's calls over the
            # window (metrics source ``recurrent``) beside the trace's.
            field = {"ssd_scan": "scan_calls", "ssm_step": "step_calls"}[
                name.replace("_roofline", "")]
            out[name.replace("_roofline", "")] = {
                "ops": sorted(hits), "count": sum(v[1] for v in
                                                  hits.values()),
                "counted_by_program": readers.lookup(
                    context, f"delta:sources.recurrent.fields.{field}"),
                "seconds": seconds,
                "ms_per_dispatch": 1e3 * seconds / runs if runs else None,
                "sizes": context["window"]["kernel_calls"].get(
                    m["args"]["shape"])}
        HELD["ssm"] = out
        return read_all(wanted, context)

    readers.read_all = read_all_and_ssm


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)

    import run as bench_run
    from harness import builders

    install()
    bench, cell = bench_run.load_cell(args.workload)
    devices = builders.device_or_exit(cell["chips"])
    result = bench_run.drive(cell, bench, bench_run.load_files(cell),
                             args.seed, args.seconds, True,
                             devices[:cell["chips"]])
    result["ssm"] = HELD.get("ssm", {})
    print(json.dumps(result), flush=True)
    off = {k: (v["count"], v["counted_by_program"])
           for k, v in result["ssm"].items()
           if "count" in v and v["count"] != v["counted_by_program"]}
    if off:
        sys.exit(f"calls in the trace and by the program's count: {off}")


if __name__ == "__main__":
    main()
