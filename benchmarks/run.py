#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``configs/<config>.json``) under a traffic mix
(``traffic/<traffic>.json``). The last line of standard output is the
result. A run that finds no TPU, or fewer chips than the cell asks for,
exits non-zero with one line and prints no result. No phase is wrapped in
a catch: an exception ends the run non-zero with its traceback.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(REPO))


@dataclasses.dataclass
class Context:
    spec: object
    ref: object
    mix: dict
    runtime: dict
    seed: int
    seconds: float
    out: Path
    trace: object = None
    check_config: bool = True
    setup_s: float = 0.0

    def elapsed(self) -> float:
        """Seconds since the process started, on ``setup_s``' clock."""
        return time.perf_counter() - PROCESS_START

    def setup_done(self) -> None:
        self.setup_s = self.elapsed()


def load_cell(name: str) -> tuple:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        sys.exit(f"benchmarks/run.py: no workload {name!r} in BENCHMARK.json "
                 f"(known: {sorted(cells)})")
    return bench, cells[name]


def load_files(cell: dict, limits: dict | None = None) -> dict:
    """The cell's data files, each found by the name BENCHMARK.json gives.
    ``limits`` stands in for ``limits/<cell>.json`` (tests and tools whose
    cell has none)."""
    from harness import compare, traffic

    raw = json.loads((HERE / "configs" / f"{cell['config']}.json").read_text())
    ref = importlib.import_module(f"references.{raw['lir_tpu']['reference']}")
    return {"ref": ref, "spec": ref.spec_from_config(cell["config"], raw),
            "runtime": dict(raw["lir_tpu"]["runtime"]),
            "mix": traffic.load_mix(cell["traffic"]),
            "limits": (compare.load_limits(cell["name"])
                       if limits is None else limits)}


def drive(cell: dict, bench: dict, files: dict, seed: int, seconds: float,
          trace: bool, devices, check_config: bool = True) -> dict:
    """Everything after the look for a chip; returns the result line."""
    import jax

    from harness import builders, compare, readers, trace as trace_mod
    from harness.peaks import peaks_for

    from lir_tpu.utils import compile_cache

    compile_cache.enable_persistent_cache()
    builders.count_compile_seconds()
    ref, spec, mix, limits = (files[k] for k in
                              ("ref", "spec", "mix", "limits"))
    out = HERE / ".out" / cell["name"]
    if out.exists():
        shutil.rmtree(out)               # a repeated path resumes, scores nothing
    out.mkdir(parents=True)
    ctx = Context(spec=spec, ref=ref, mix=mix, runtime=files["runtime"],
                  seed=seed, seconds=seconds, out=out,
                  check_config=check_config,
                  trace=trace_mod.Tracer(out / "trace") if trace else None)
    driver = importlib.import_module(f"harness.{mix['kind']}_window")
    record = driver.run(ctx)

    peak, limit = builders.peak_bytes()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    gc.collect()
    jax.clear_caches()
    window = record["window"]
    print(json.dumps({"window": window, "setup_s": ctx.setup_s,
                      "memory": {"peak": peak, "limit": limit,
                                 "in_use_after_free": builders.in_use()}}),
          flush=True)
    if window["compiles_in_window"]:
        raise RuntimeError(f"{window['compiles_in_window']} programs were "
                           "compiled or loaded inside the measured window")

    result = {"attempted": record["attempted"], "failed": record["failed"]}
    if trace:
        reduced = ctx.trace.reduce(chips=cell["chips"])
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        context = {"trace": reduced, "window": window, "spec": spec,
                   "counters": record["counters"], "peaks":
                   peaks_for(dev.device_kind), "memory": {"peak": peak,
                                                         "limit": limit},
                   "samples": record.get("samples", {}),
                   "traffic": record.get("traffic", {})}
        wanted = [m for m in bench["per_layer"]
                  if cell["name"] in m.get("workloads", [cell["name"]])]
        result["metrics"] = readers.read_all(wanted, context)
        result["breakdown"] = reduced["breakdown"]
    else:
        e2e = dict(record["end_to_end"], setup_s=ctx.setup_s)
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in e2e.items()}
    result["device"] = device

    t0 = time.perf_counter()
    correct, numbers = compare.check(
        spec, ref, seed, record["answers"], mix["reference_rows"], limits,
        record["attempted"], record["failed"])
    result = {"correct": correct, **result,
              "reference_s": time.perf_counter() - t0, "compared": numbers}
    for name, n in numbers.items():
        print(f"compared {name} {float(n['value'])!r} limit "
              f"{float(n['limit'])!r}",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell = load_cell(args.workload)

    from harness import builders

    devices = builders.device_or_exit(cell["chips"])
    result = drive(cell, bench, load_files(cell), args.seed, args.seconds,
                   bool(args.trace), devices[:cell["chips"]])
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
