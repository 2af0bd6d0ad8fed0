#!/usr/bin/env python
"""Tier-1 guard: run the tier-1 suite the way the driver runs it and hold
the pass count to the driver's own floor.

The driver's command is six xdist workers, one test file per worker
(`--dist loadfile`), its pass count the junit report's (tests minus
errors, failures and skips). The floor is NOT recorded here: it is the
newest `tests.floor` less `tests.allowance` of `PERF_LEDGER.jsonl`, the
driver's record of what this PR is held to. pytest's exit code is not the
gate (the suite holds known-failing timing tests, ROADMAP D0); the pass
count is. A single-process run of the whole suite can segfault in one
test and cut the count short; the worker-per-file run reaches the end.

`make verify` and the pre-push hook (`make install-hooks`) run this.

Usage:
    python tools/check_tier1.py [--timeout SECS] [--workers N]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import xml.etree.ElementTree as ET
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def pytest_args(workers: int, junit: str) -> list:
    return ["-m", "pytest", "tests/", "-q", "-m", "not slow",
            "--continue-on-collection-errors", "-p", "no:cacheprovider",
            "-p", "xdist", "-n", str(workers), "--dist", "loadfile",
            f"--junitxml={junit}", "-p", "no:randomly"]


def ledger_floor() -> tuple:
    """(floor, allowance) of the ledger's newest line that has one."""
    ledger = REPO / "PERF_LEDGER.jsonl"
    if not ledger.exists():
        return None, 0
    for line in reversed(ledger.read_text().splitlines()):
        tests = json.loads(line).get("tests") if line.strip() else None
        if tests and tests.get("floor") is not None:
            return int(tests["floor"]), int(tests.get("allowance") or 0)
    return None, 0


def count_passed(junit: str) -> int:
    suite = ET.parse(junit).getroot()
    if suite.tag != "testsuite":
        suite = suite.find("testsuite")
    n = lambda key: int(suite.get(key, 0))  # noqa: E731
    return max(n("tests") - n("errors") - n("failures") - n("skipped"), 0)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--timeout", type=int, default=1470,
                    help="suite timeout in seconds (the driver's)")
    ap.add_argument("--workers", type=int, default=6)
    args = ap.parse_args()

    floor, allowance = ledger_floor()
    print(f"tier-1 guard: running the suite on {args.workers} workers "
          f"(ledger floor {floor}, allowance {allowance}) ...", flush=True)
    with tempfile.TemporaryDirectory() as tmp:
        junit = os.path.join(tmp, "t1.xml")
        try:
            proc = subprocess.run(
                [sys.executable, *pytest_args(args.workers, junit)],
                cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                capture_output=True, text=True, timeout=args.timeout)
        except subprocess.TimeoutExpired:
            print(f"TIER-1 FAIL: suite exceeded {args.timeout}s", flush=True)
            return 1
        print("\n".join((proc.stdout + proc.stderr).strip()
                        .splitlines()[-3:]))
        if not os.path.exists(junit):
            print("TIER-1 FAIL: the run wrote no junit report")
            return 1
        passed = count_passed(junit)
    print(f"PASSED={passed} (ledger floor {floor}, allowance {allowance})")
    if floor is not None and passed < floor - allowance:
        print(f"TIER-1 FAIL: {passed} < {floor} - {allowance} — tests that "
              "passed at the driver's baseline no longer do.")
        return 1
    print("tier-1 guard: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
