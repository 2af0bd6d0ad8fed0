#!/usr/bin/env python3
"""Find a serve cell's knee once, on the chip: step the offered rate in
ONE process (set-up paid once) and print one line per rate.

    python3 benchmarks/tests/knee.py --config mistral-7b \\
        --traffic serve-steady --rates 4,6,8,10,12,14 --seconds 20 --seed 1

The knee is the highest rate with no request failed (shed, expired, late,
in error), and a queue that does not grow: the last request resolves
within a dispatch or two of the window's close (``drain_s``) and the
second half of the window is no slower than the first.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import run as bench_run
    from harness import builders, serve_window

    from lir_tpu.utils import compile_cache

    builders.device_or_exit(1)
    compile_cache.enable_persistent_cache()
    builders.count_compile_seconds()
    cell = {"name": f"{args.config}.{args.traffic}", "config": args.config,
            "traffic": args.traffic, "chips": 1}
    files = bench_run.load_files(cell, limits={})
    out = HERE / ".out" / f"{cell['name']}.knee"
    out.mkdir(parents=True, exist_ok=True)
    ctx = bench_run.Context(spec=files["spec"], ref=files["ref"],
                            mix=files["mix"], runtime=files["runtime"],
                            seed=args.seed, seconds=args.seconds, out=out)
    sc = serve_window.ServeCell(ctx)
    rates = [float(r) for r in args.rates.split(",")]
    print(json.dumps({"warm": sc.warm(rates[len(rates) // 2]),
                      "setup_s": bench_run.time.perf_counter()
                      - bench_run.PROCESS_START}), flush=True)
    for i, rate in enumerate(rates):
        rec = sc.measure(rate, args.seconds, stream=10 + i)
        lat = rec["samples"]["latency_s"]
        half = len(lat) // 2
        w = rec["window"]
        print(json.dumps({
            "rate": rate, "attempted": rec["attempted"],
            "failed": rec["failed"], **rec["end_to_end"],
            "completed_per_s": w["completed_per_s"], "drain_s": w["drain_s"],
            "mean_first_half_ms": 1000 * statistics.fmean(lat[:half]),
            "mean_second_half_ms": 1000 * statistics.fmean(lat[half:]),
            "gen_late_p95_ms": 1000 * serve_window.percentile(
                rec["samples"]["gen_late_s"], 95),
            "compiles_in_window": w["compiles_in_window"],
            "compile_seconds_in_window": w["compile_seconds_in_window"],
            "server": w["server"]}), flush=True)
    sc.close()


if __name__ == "__main__":
    main()
