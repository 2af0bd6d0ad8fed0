"""Tests of the benchmark's yardstick; run by hand, outside tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
for p in (HERE, HERE.parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
