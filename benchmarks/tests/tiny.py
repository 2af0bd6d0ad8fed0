"""A cell cut to a size a CPU test can hold: the same files, drivers and
comparison as a chip run, the sizes of the reference module's own
``tiny``, batch 8, Pallas kernels interpreted. Only tests use it;
``run.py`` cannot reach it."""

from __future__ import annotations

import json

import run as bench_run

LIMITS = {"logprob_gap": 0.5, "token_gap": 0.5, "min_served_tokens": 10}


def files_for(config: str, mix: str) -> tuple:
    """(cell, bench, files) with the sizes shrunk."""
    bench = json.loads((bench_run.REPO / "BENCHMARK.json").read_text())
    cell = {"name": f"{config}.{mix}", "config": config, "traffic": mix,
            "chips": 1}
    files = bench_run.load_files(cell, limits=dict(LIMITS))
    files["spec"] = files["ref"].tiny(files["spec"])
    files["runtime"] = dict(files["runtime"], batch_size=8)
    files["mix"] = dict(files["mix"], group_rows=8, reference_rows=6,
                        window_groups=4, trace_groups=3)
    return cell, bench, files


def drive(config: str, mix: str, seed: int = 5, seconds: float = 2.0):
    """One whole run after the look for a chip, on the CPU."""
    import jax

    from lir_tpu.models import decoder

    decoder.FUSED_DECODE_INTERPRET_ON_CPU = True
    decoder.CASCADE_INTERPRET_ON_CPU = True
    decoder.SSM_INTERPRET_ON_CPU = True
    cell, bench, files = files_for(config, mix)
    return bench_run.drive(cell, bench, files, seed, seconds, False,
                           jax.devices()[:1], check_config=False)
