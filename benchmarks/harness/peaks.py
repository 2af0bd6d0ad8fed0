"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports
it. A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


V5E = Peaks(197e12, 393e12, 819e9, 16e9,
            'Google Cloud documentation, "TPU v5e"')
TABLE = {"TPU v5 lite": V5E, "TPU v5e": V5E}     # both names JAX has used


def peaks_for(device_kind: str) -> Peaks:
    if device_kind not in TABLE:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add a row to harness/peaks.py with its source "
                       f"(known: {sorted(TABLE)})")
    return TABLE[device_kind]
