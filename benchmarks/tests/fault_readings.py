#!/usr/bin/env python3
"""The comparison's readings with a fault planted in the expert path of the
program, on the chip at the cell's own size: what says the cell's limits
hold that path, not only attention and the shared expert.

    python3 benchmarks/tests/fault_readings.py \\
        --workload k-exaone-236b.sweep-trunk512 --seeds 1,2 \\
        --faults none,zero_routed,gate_from_s_plus_b,held_off_by_one

Faults (each planted in memory, before anything is traced):

``zero_routed``         every held expert's term dropped (the shared
                        expert alone);
``gate_from_s_plus_b``  the gate normalizes score + selection bias, not
                        the score;
``held_off_by_one``     the program takes its held weights for experts
                        [first + 1, first + count + 1).

For each fault and seed, in ONE process: build the cell as ``run.py``
does, drive a window of ``--groups`` groups through the timed entry, and
hold its answers against the float32 reference under the cell's limits.
One JSON line each; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))


@contextlib.contextmanager
def planted(fault: str):
    """The program with ``fault`` in its expert path while inside."""
    import jax
    import jax.numpy as jnp

    from harness import builders

    from lir_tpu.engine import compile_plan
    from lir_tpu.models import moe

    saved = {"route": moe.route, "_held_terms": moe._held_terms,
             "program_config": builders.program_config}

    def route_from_s_plus_b(x32, router, cfg):
        logits = jnp.dot(x32, router["w"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        u = jax.nn.sigmoid(logits) + router["bias"].astype(jnp.float32)
        _, idx = jax.lax.top_k(u, cfg.expert_top_k)
        w = jnp.take_along_axis(u, idx, axis=-1)
        return idx, cfg.router_scale * w / jnp.sum(w, -1, keepdims=True)

    def held_dropped(*args, **kw):
        out, counts = saved["_held_terms"](*args, **kw)
        return jnp.zeros_like(out), counts

    def held_shifted(spec, ref, check=True):
        cfg = saved["program_config"](spec, ref, check)
        first, count = cfg.experts_held
        return dataclasses.replace(cfg, experts_held=(first + 1, count))

    if fault == "zero_routed":
        moe._held_terms = held_dropped
    elif fault == "gate_from_s_plus_b":
        moe.route = route_from_s_plus_b
    elif fault == "held_off_by_one":
        builders.program_config = held_shifted
    elif fault != "none":
        raise SystemExit(f"fault_readings.py: no fault {fault!r}")
    # Nothing traced before may serve: the process's executables are
    # keyed by configuration and shape, not by what the code computes.
    jax.clear_caches()
    compile_plan.exec_cache_clear()
    try:
        yield
    finally:
        moe.route, moe._held_terms = saved["route"], saved["_held_terms"]
        builders.program_config = saved["program_config"]
        jax.clear_caches()
        compile_plan.exec_cache_clear()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="none,zero_routed,"
                    "gate_from_s_plus_b,held_off_by_one")
    ap.add_argument("--groups", type=int, default=3)
    args = ap.parse_args()

    import importlib

    import run as bench_run
    from harness import builders, compare

    from lir_tpu.utils import compile_cache

    _, cell = bench_run.load_cell(args.workload)
    builders.device_or_exit(cell["chips"])
    compile_cache.enable_persistent_cache()
    builders.count_compile_seconds()
    files = bench_run.load_files(cell)
    mix = dict(files["mix"], window_groups=args.groups)
    driver = importlib.import_module(f"harness.{mix['kind']}_window")
    for fault in args.faults.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            out = HERE / ".out" / f"{cell['name']}.faults"
            if out.exists():
                shutil.rmtree(out)
            out.mkdir(parents=True)
            ctx = bench_run.Context(
                spec=files["spec"], ref=files["ref"], mix=mix,
                runtime=files["runtime"], seed=seed, seconds=0.0, out=out)
            with planted(fault):
                record = driver.run(ctx)
            gc.collect()
            correct, numbers = compare.check(
                files["spec"], files["ref"], seed, record["answers"],
                mix["reference_rows"], files["limits"], record["attempted"],
                record["failed"])
            print(json.dumps({"fault": fault, "seed": seed,
                              "correct": correct, "compared": numbers}),
                  flush=True)


if __name__ == "__main__":
    main()
