"""Measure the vectorized statistics kernels on the REAL TPU vs host CPU.

VERDICT r1 weak #3: the CLI pins statistics to CPU (`ensure_cpu_backend`)
on the argument that dispatch latency swamps tiny kernels — but
BASELINE.json config 2 ("10k resamples -> vmap on single TPU core")
had never actually been measured. This tool runs the production stats
kernels — the same ones the survey/analysis layers call, at the
reference's own problem sizes (SURVEY.md §6 bootstrap budgets) — on both
backends and appends the numbers to SCALE.md, so the backend-pinning
policy is a measurement, not an assertion.

Every kernel result is a host-side float (BootstrapResult / dict), so the
timings are host-materialization-synced by construction — the same
verified-timing discipline as bench.py.

Run (parent orchestrates both backends as subprocesses, one after the
other; the parent itself never imports jax, so the chip is free for the
"tpu" child — one process per chip):
    python tools/stats_device_bench.py
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

SCALE_MD = REPO / "SCALE.md"

# (name, reference sizing note)
KERNELS = [
    ("pearson_boot_1k", "C34: bootstrap Pearson CI, n=50, 1000 resamples"),
    ("corr_matrix_boot_1k",
     "C30: 10-model correlation matrix, 50 prompts, 1000 resamples"),
    ("aggregate_kappa_1k", "C30: pooled kappa, 10x50 binary, 1000-fold CI"),
    ("truncnorm_mc_100k",
     "C22: truncated-normal MC fit, n=2000, 100k samples/iter"),
]


def _build_and_time(name: str):
    import jax
    import numpy as np

    rng = np.random.default_rng(42)
    key = jax.random.PRNGKey(0)

    if name == "pearson_boot_1k":
        from lir_tpu.stats.bootstrap import bootstrap_correlation
        x = rng.uniform(size=50)
        y = 0.6 * x + 0.4 * rng.uniform(size=50)
        fn = lambda: bootstrap_correlation(x, y, key, n_boot=1000).estimate
    elif name == "corr_matrix_boot_1k":
        from lir_tpu.stats.correlations import bootstrap_correlation_matrix
        piv = rng.uniform(size=(50, 10))
        fn = lambda: bootstrap_correlation_matrix(
            piv, key, n_bootstrap=1000)["mean_correlation"]
    elif name == "aggregate_kappa_1k":
        from lir_tpu.stats.kappa import aggregate_kappa
        binary = (rng.uniform(size=(10, 50)) > 0.5).astype(np.int32)
        fn = lambda: aggregate_kappa(binary, key, n_boot=1000)["aggregate_kappa"]
    elif name == "truncnorm_mc_100k":
        from lir_tpu.stats.fits import truncated_normal_mc_fit
        data = np.clip(rng.normal(0.6, 0.25, size=2000), 0.0, 1.0)
        fn = lambda: truncated_normal_mc_fit(
            data, key, n_simulations=100_000)[0]["KS Statistic"]
    else:
        raise KeyError(name)

    t0 = time.perf_counter()
    first = float(np.asarray(fn()))
    cold = time.perf_counter() - t0
    warm = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        v = float(np.asarray(fn()))
        warm = min(warm, time.perf_counter() - t0)
    assert np.isfinite(v), (name, v)
    return {"cold_s": round(cold, 4), "warm_s": round(warm, 4),
            "value": round(first, 6)}


def child(backend: str) -> None:
    import jax

    dev = jax.devices()[0]
    out = {"backend": backend, "platform": dev.platform,
           "device_kind": getattr(dev, "device_kind", "?")}
    for name, _ in KERNELS:
        out[name] = _build_and_time(name)
        print(f"# {backend}: {name} {out[name]}", file=sys.stderr)
    print(json.dumps(out))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--child", choices=["cpu", "tpu"])
    args = parser.parse_args()
    if args.child:
        child(args.child)
        return

    results = {}
    for backend in ("cpu", "tpu"):
        env = dict(os.environ)
        if backend == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, __file__, "--child", backend],
            capture_output=True, text=True, cwd=REPO, timeout=1800,
            env=env)
        sys.stderr.write(proc.stderr[-2000:])
        if proc.returncode != 0:
            print(f"{backend} child failed rc={proc.returncode}")
            sys.exit(1)
        results[backend] = json.loads(proc.stdout.strip().splitlines()[-1])

    # Refuse to record a "TPU vs CPU" table measured on two CPU backends
    # (e.g. no reachable chip and jax silently fell back) — the whole point
    # of this tool is honest data.
    if results["tpu"]["platform"] == "cpu":
        print("ABORT: the 'tpu' child ran on the CPU backend "
              f"({results['tpu']['device_kind']}); no table written.")
        sys.exit(1)
    if results["cpu"]["platform"] != "cpu":
        print("ABORT: the 'cpu' child did not run on CPU "
              f"({results['cpu']['platform']}); no table written.")
        sys.exit(1)

    date = datetime.date.today().isoformat()
    kind = results["tpu"]["device_kind"]
    lines = [
        f"\n## stats kernels: TPU vs host CPU — {kind}, {date}\n",
        "\nBASELINE config 2 measured (VERDICT r1 weak #3). Warm best-of-3,",
        "\nhost-materialization-synced; reference problem sizes.\n",
        "\n| kernel (reference sizing) | cpu warm s | tpu warm s |"
        " tpu/cpu | tpu cold s |\n",
        "|---|---|---|---|---|\n",
    ]
    for name, note in KERNELS:
        c, t = results["cpu"][name], results["tpu"][name]
        ratio = t["warm_s"] / max(c["warm_s"], 1e-9)
        lines.append(f"| {note} | {c['warm_s']:.3f} | {t['warm_s']:.3f} | "
                     f"{ratio:.1f}x | {t['cold_s']:.1f} |\n")
        dv = abs(results["cpu"][name]["value"] - results["tpu"][name]["value"])
        if dv > 1e-2:
            lines.append(f"|   (value drift {dv:.3g} — inspect!) | | | | |\n")
    text = "".join(lines)
    SCALE_MD.write_text(SCALE_MD.read_text() + text)
    print(text)


if __name__ == "__main__":
    main()
