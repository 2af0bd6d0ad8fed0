"""Operations and bytes the TRAFFIC needs, from shapes alone.

Nothing here looks at what the engine dispatched: padding, recomputed
trunks, speculative windows and refilled slots are the engine's choices
and count as waste, not as work.

A forward pass over one new token at position ``p`` (0-based, so it
attends ``p + 1`` keys) costs, per layer, ``2 * layer_matmul_params``
for the projections and the feed-forward and ``4 * heads * head_dim *
(p + 1)`` for the two attention products. Logits cost ``2 * d * vocab``
at each position they are needed.
"""

from __future__ import annotations


def tokens_flops(spec, start: int, stop: int) -> float:
    """Forward FLOPs of the new tokens at positions ``start..stop-1`` of
    one sequence (whatever lies before ``start`` is already cached)."""
    n = max(stop - start, 0)
    keys = n * (start + stop + 1) / 2.0          # sum of (p + 1)
    per_layer = (2.0 * spec.layer_matmul_params * n
                 + 4.0 * spec.heads * spec.head_dim * keys)
    return spec.layers * per_layer


def logits_flops(spec, positions: int) -> float:
    return 2.0 * spec.d * spec.vocab * positions


def scoring_cell_flops(spec, shared: int, n_bin: int, n_conf: int,
                       new_bin: int, new_conf: int, trunk: int = 0) -> float:
    """One grid cell: a binary and a confidence prompt that share their
    first ``shared`` tokens, ``new_*`` greedy tokens read from each. The
    first ``trunk`` tokens are someone else's to count (the cell's group
    pays them once). The last generated token of each branch is read, not
    fed back."""
    f = tokens_flops(spec, trunk, shared)
    f += tokens_flops(spec, shared, n_bin + max(new_bin - 1, 0))
    f += tokens_flops(spec, shared, n_conf + max(new_conf - 1, 0))
    return f + logits_flops(spec, new_bin + new_conf)


def decode_attention_call(spec, batch: int, extent: float,
                          trunk: int = 0) -> tuple:
    """(FLOPs, bytes) one decode-attention call needs for ``batch`` rows
    whose live cache extent is ``extent`` keys, the first ``trunk`` of
    them shared by all rows: both products for one query per head, every
    live key and value read once in bfloat16 (the shared ones once for
    the batch), queries read and outputs written."""
    flops = 4.0 * batch * spec.heads * spec.head_dim * extent
    kv = 2.0 * spec.kv_heads * spec.head_dim * 2 * (
        trunk + batch * (extent - trunk))
    qo = 2.0 * batch * spec.heads * spec.head_dim * 2
    return flops, kv + qo


def cascade_prefill_call(spec, batch: int, length: float,
                         trunk: int = 0) -> tuple:
    """(FLOPs, bytes) one cascade-prefill attention call needs: ``batch``
    rows of ``length`` queries, causal within each row, the first
    ``trunk`` positions shared by all rows (computed and read once)."""
    tri = lambda n: n * (n + 1) / 2.0  # noqa: E731
    keys = tri(trunk) + batch * (tri(length) - tri(trunk))
    flops = 4.0 * spec.heads * spec.head_dim * keys
    tokens = trunk + batch * (length - trunk)
    kv = 2.0 * spec.kv_heads * spec.head_dim * 2 * tokens
    qo = 2.0 * spec.heads * spec.head_dim * 2 * tokens
    return flops, kv + qo


def roofline_seconds(flops: float, nbytes: float, peaks) -> tuple:
    """(least seconds the chip could take, which bound applies)."""
    t_f, t_b = flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
