#!/usr/bin/env python3
"""Record the small trace the reducer's test reads (on the chip):

    python3 benchmarks/tests/record_trace.py <out.xplane.pb>

The sweep cell at two layers of 512 wide (heads of 128, so the Pallas
kernels compile), batch 8, one short traced window; prints the reduction
so the test's expectations can be written down beside the file.
"""

import dataclasses
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE / "tests"))


def main() -> None:
    import jax

    import run as bench_run
    import tiny
    from harness import builders, trace

    devices = builders.device_or_exit(1)
    cell, bench, files = tiny.files_for("mistral-7b", "sweep-trunk512")
    files["spec"] = dataclasses.replace(
        files["spec"], vocab=2048, d=512, layers=2, heads=4, kv_heads=2,
        head_dim=128, ffn=1024)
    files["mix"] = dict(files["mix"], trace_groups=3)
    out = bench_run.drive(cell, bench, files, 5, 1.0, True, devices[:1],
                          check_config=False)
    src = trace.Tracer(HERE / ".out" / cell["name"] / "trace").file()
    shutil.copy(src, sys.argv[1])
    reduced = trace.reduce_planes(trace.read_planes(src), 1.0, 1)
    print(json.dumps({"result": out, "busy_s": reduced["busy_s"],
                      "modules": reduced["modules"],
                      "top_ops": reduced["breakdown"]["device_ops"]}))


if __name__ == "__main__":
    main()
