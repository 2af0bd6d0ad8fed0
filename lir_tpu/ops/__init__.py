"""Custom kernels: the single entry point for every attention kernel in
the framework.

- ``flash_attention`` (Pallas): prefill/full-sequence attention without
  the (S, S) score matrix — O(S * hd) memory, online softmax.
- ``flash_decode`` (Pallas): fused single-query decode attention over the
  KV cache — K-split online softmax + log-sum-exp combine, the decode-
  phase complement of ``flash_attention`` (ROADMAP item 2's MFU floor).
- ``cascade_attention`` (Pallas): shared-trunk prefill decomposition —
  the trunk's attention once per dispatch as dense MXU matmuls (optional
  in-kernel s8×s8 QK^T) plus per-row suffix attention, merged by
  ``merge_partials`` (ROADMAP item 1's prefill plateau).
- ``ssd_scan`` / ``ssm_step`` (Pallas): the chunked selective scan of a
  Mamba-2 mixer, initial state in and final state out, and its
  single-token update for the decode step (``ops/ssd_scan.py``; import
  them from the module: a name re-exported here would shadow it).
- ``merge_partials`` (``ops/lse.py``): the one log-sum-exp partial-merge
  both the decode split-K reduction and the cascade trunk/suffix merge
  reduce through.
- ``ring_attention`` / ``ulysses_attention`` (explicit collectives): the
  multi-chip sequence-parallel kernels, re-exported from
  parallel/ring_attention.py so kernel consumers import ONE surface;
  ``reference_attention`` is the dense single-device ground truth every
  kernel is pinned against in tests.

SURVEY.md §2.5: none were *required* for reference parity; flash
attention extends the long-context ceiling and flash decode attacks the
decode-phase MFU plateau.
"""

from .flash_attention import (  # noqa: F401
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    flash_attention,
)
from .cascade_prefill import (  # noqa: F401
    cascade_attention,
    pick_block_n,
)
from .flash_decode import (flash_decode, flash_decode_mq,  # noqa: F401
                           pick_split)
from .lse import merge_partials  # noqa: F401
from ..parallel.ring_attention import (  # noqa: F401
    reference_attention,
    ring_attention,
    ulysses_attention,
)
