#!/usr/bin/env python3
"""Readings a limit is set from, on the chip, at the cell's own size.

    python3 benchmarks/tests/readings.py --workload <cell> --seeds 1,2,3 \\
        --seconds 6 --controls int8,fp8

For each seed, in ONE process (set-up is long): build the cell as
``run.py`` does, drive a window through the timed entry (``--seconds``
sizes a serve window; a sweep window is the mix's ``window_groups``), free
the program, then read the comparison's numbers for the program and for each
control (the reference put in the program's place, one precision down).
One JSON line per seed, and a summary line last. The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--controls", default="int8,fp8")
    args = ap.parse_args()

    import importlib

    import jax

    import run as bench_run
    from harness import builders, compare

    from lir_tpu.utils import compile_cache

    _, cell = bench_run.load_cell(args.workload)
    builders.device_or_exit(cell["chips"])
    compile_cache.enable_persistent_cache()
    builders.count_compile_seconds()
    files = bench_run.load_files(cell)
    controls = tuple(c for c in args.controls.split(",") if c)
    driver = importlib.import_module(f"harness.{files['mix']['kind']}_window")
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = HERE / ".out" / f"{cell['name']}.readings"
        if out.exists():
            shutil.rmtree(out)
        out.mkdir(parents=True)
        ctx = bench_run.Context(
            spec=files["spec"], ref=files["ref"], mix=files["mix"],
            runtime=files["runtime"], seed=seed, seconds=args.seconds,
            out=out)
        record = driver.run(ctx)
        gc.collect()
        jax.clear_caches()
        got = compare.readings(files["spec"], files["ref"], seed,
                               record["answers"],
                               files["mix"]["reference_rows"], controls)
        row = {"seed": seed, "attempted": record["attempted"],
               "failed": record["failed"],
               "end_to_end": record["end_to_end"], **got}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"program_max": {k: max(r["program"][k] for r in rows)
                               for k in ("logprob_gap", "token_gap")}}
    for c in controls:
        summary[f"{c}_min"] = {k: min(r[c][k] for r in rows)
                               for k in ("logprob_gap", "token_gap")}
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
