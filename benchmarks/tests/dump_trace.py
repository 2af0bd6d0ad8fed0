#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, the names that took most
time on each device line, and the program's spans on the host.

    python3 benchmarks/tests/dump_trace.py <file.xplane.pb> [<out.json>]
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import trace  # noqa: E402


def main() -> None:
    planes = trace.read_planes(Path(sys.argv[1]))
    doc = {}
    for pname, lines in planes.items():
        doc[pname] = {}
        for lname, ev in lines.items():
            by = {}
            for name, start, dur in ev:
                rec = by.setdefault(trace.strip_id(name), [0.0, 0])
                rec[0] += dur / 1e9
                rec[1] += 1
            keep = (trace.DEVICE_PLANE.match(pname)
                    or any(trace.SPAN.match(n) for n in by))
            top = sorted(by.items(), key=lambda kv: -kv[1][0])[:40]
            doc[pname][lname] = {
                "events": len(ev),
                "first_ns": min((s for _, s, _ in ev), default=None),
                "last_ns": max((s + d for _, s, d in ev), default=None),
                "top": [[k, v[0], v[1]] for k, v in top] if keep else []}
    text = json.dumps(doc, indent=1)
    if len(sys.argv) > 2:
        Path(sys.argv[2]).write_text(text)
    print(text[:20000])


if __name__ == "__main__":
    main()
