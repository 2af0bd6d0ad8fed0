"""Pallas flash attention: exact attention without the (S, S) score matrix.

The single-chip complement to parallel/ring_attention.py: within one chip,
XLA's default attention materializes the (B, H, S, S) score tensor in HBM
(O(S^2) memory); this kernel streams K/V blocks through VMEM with an online
softmax, so peak memory is O(S * hd) and the score tile lives entirely
on-chip. Use when a long sequence fits one chip's weights but not its
attention scores; shard over the mesh's ``seq`` axis (ring attention) when
it doesn't.

Layout contract matches models/decoder.py and parallel/ring_attention.py:
(B, S, H, hd), causal or full, with an optional per-row key validity mask
(any pattern — masking semantics equal the dense path's additive bias for
every real-token position; masked-query rows come back 0 and are ignored
downstream, exactly like the dense path's uniform-garbage pad rows).

Kernel design (pallas_guide.md patterns):
  grid = (B, H, S / BLOCK_Q); each program owns one query tile in VMEM and
  fori_loops over K/V tiles with ``pl.ds`` dynamic slices, carrying the
  (m, l, acc) online-softmax state as loop values. Causal programs stop at
  the diagonal block, and the loop starts at the row's first valid key
  block (both traced fori_loop bounds), so left-pad and upper-triangle work
  is skipped. Matmuls request fp32 accumulation (preferred_element_type).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _flash_kernel(start_ref, slope_ref, mask_ref, kpos_ref, q_ref, k_ref,
                  v_ref, o_ref, *, causal: bool, block_q: int, block_k: int,
                  sm_scale: float, alibi: bool):
    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale          # (bq, hd)
    seq_len = k_ref.shape[2]
    n_kblocks = seq_len // block_k
    first_valid = start_ref[b, 0]  # index of the row's first valid key

    q_pos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)[:, 0]

    m0 = jnp.full((block_q,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
        kmask = mask_ref[0, 0, pl.ds(j * block_k, block_k)] > 0  # (bk,)
        if alibi:
            # ALiBi: + slope_h * mask-aware key position (bloom). Matches
            # decoder._causal_bias exactly — positions come in precomputed.
            kp = kpos_ref[0, 0, pl.ds(j * block_k, block_k)]      # (bk,)
            s = s + slope_ref[h, 0] * kp.astype(jnp.float32)[None, :]
        valid = kmask[None, :]
        if causal:
            k_pos = j * block_k + lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            valid = valid & (q_pos[:, None] >= k_pos)
        s = jnp.where(valid, s, -jnp.inf)

        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    # Blocks before the row's first valid key contribute nothing; causal
    # programs additionally stop at their diagonal block.
    lower = first_valid // block_k
    if causal:
        upper = lax.min(
            jnp.int32(n_kblocks),
            (qi * block_q + block_q + block_k - 1) // block_k,
        )
    else:
        upper = n_kblocks
    m, l, acc = lax.fori_loop(lower, upper, body, (m0, l0, acc0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    causal: bool = True,
    key_mask: jnp.ndarray | None = None,
    alibi_slopes: jnp.ndarray | None = None,
    key_positions: jnp.ndarray | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    """Exact attention, (B, S, H, hd) layout, O(S*hd) memory.

    ``key_mask``: optional (B, S) {0,1} validity mask over key positions —
    any pattern (left pad, right pad, holes). Equivalent to the dense
    path's additive key-mask bias for every valid query position; rows of
    fully-masked queries return 0.
    ``alibi_slopes``: optional (H,) per-head ALiBi slopes (bloom). Adds
    ``slope_h * key_position`` to the scores; ``key_positions`` (B, S)
    mask-aware positions must be given with it (decoder.mask_positions).
    S must be divisible by the block sizes (blocks shrink automatically for
    short sequences). ``interpret=True`` runs the kernel in the Pallas
    interpreter (CPU tests).
    """
    B, S, H, hd = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(
            f"seq len {S} must be divisible by blocks ({block_q}, {block_k})"
        )
    alibi = alibi_slopes is not None
    if alibi and key_positions is None:
        raise ValueError("alibi_slopes requires key_positions")
    sm_scale = 1.0 / np.sqrt(hd)
    if key_mask is None:
        key_mask = jnp.ones((B, S), jnp.int32)
    key_mask = jnp.asarray(key_mask, jnp.int32)
    if key_positions is None:
        key_positions = jnp.zeros((B, S), jnp.int32)
    key_positions = jnp.asarray(key_positions, jnp.int32)
    if alibi_slopes is None:
        slopes = jnp.zeros((H, 1), jnp.float32)
    else:
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(H, 1)
    # First valid key index per row (loop lower bound; 0 when all-masked —
    # such rows are garbage on every path).
    first_valid = jnp.argmax(key_mask, axis=-1).astype(jnp.int32)

    # Kernel-friendly layout: (B, H, S, hd).
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    kernel = functools.partial(
        _flash_kernel, causal=causal, block_q=block_q, block_k=block_k,
        sm_scale=sm_scale, alibi=alibi)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, S // block_q),
        in_specs=[
            # Per-row first-valid index: whole (B, 1) array in SMEM (TPU
            # lowering wants full-array blocks for tiny scalars); programs
            # index it by their batch id.
            pl.BlockSpec(index_map=lambda b, h, i: (0, 0),
                         memory_space=pltpu.SMEM),
            # Per-head ALiBi slopes, whole (H, 1) array in SMEM.
            pl.BlockSpec(index_map=lambda b, h, i: (0, 0),
                         memory_space=pltpu.SMEM),
            # Key mask as (B, 1, S): one (1, 1, S) block per program.
            pl.BlockSpec((1, 1, S), lambda b, h, i: (b, 0, 0)),
            # Mask-aware key positions, same layout as the mask.
            pl.BlockSpec((1, 1, S), lambda b, h, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(first_valid[:, None], slopes, key_mask[:, None, :],
      key_positions[:, None, :], qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)
