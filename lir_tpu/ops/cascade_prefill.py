"""Shared-prefix cascade attention for the prefill phase (ROADMAP item 1).

The 36% MFU plateau of the isolated scoring step (BENCH_r05) is a
PREFILL problem as much as a decode one: the paper's axis-1 workload asks
thousands of rephrasings of ~5 long legal-prompt trunks, so every
shared-trunk dispatch recomputes trunk attention once PER ROW even though
each row's queries see byte-identical trunk KV. This module is the
Hydragen-style decomposition (Juravsky et al.): attention over a
dispatch's cache splits into

- a PREFIX leg — every (row, position, head) query attends the ONE
  shared trunk KV block. Because the trunk KV carries no batch axis, the
  whole dispatch's queries flatten into a single (N, hd) x (hd, Tt)
  dense matmul per kv head (inter-query batching): one MXU-saturating
  GEMM instead of B batched thin ones, and a warm trunk gathered from
  the radix page pool costs zero recompute;
- a per-row SUFFIX leg — each rephrasing's tail attends its own
  remainder KV with ordinary causal masking;

merged by the same log-sum-exp combination the Flash-Decoding split-K
kernel uses (ops/lse.merge_partials — lifted out of flash_decode's
inline combines so all three fused paths share one reduction). The split
is exact: trunk keys all precede every suffix query, so the prefix leg
needs neither mask nor causality, and the merge reproduces softmax over
the full key axis bitwise-stably (parity vs the dense path is pinned at
every ladder extent by tests/test_cascade.py).

The prefix leg optionally fuses int8 QK^T INSIDE the kernel
(models/quant.py's dynamic rule — the same per-vector machinery
``shared_quant``/``QuantActivation`` apply around matmuls, here applied
to q and trunk-k blocks in VMEM): scores run s8 x s8 -> s32 on the MXU
at half the VMEM read traffic, scales fold on the s32 scores, softmax
and the PV contraction stay fp32. ``interpret=True`` runs the kernel in
the Pallas interpreter so tier-1 exercises it on CPU; production CPU
keeps the dense path (models/decoder.CASCADE_INTERPRET_ON_CPU is the
test hook, mirroring FUSED_DECODE_INTERPRET_ON_CPU).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..models.quant import dynamic_quant
from .lse import merge_partials

# Flattened-query block edge: one MXU-shaped tile of inter-query-batched
# rows per grid program (the lane width; same edge family as
# flash_attention's DEFAULT_BLOCK_Q/K).
DEFAULT_BLOCK_N = 128


def pick_block_n(n: int, want: int = DEFAULT_BLOCK_N) -> int:
    """Query-block edge for N flattened rows: ``want`` when N reaches it
    (the padded tail block is masked by construction — pad rows are
    sliced off after the kernel), else N rounded up to a sublane
    multiple of 8 so tiny dispatches lower without relayout."""
    if n >= want:
        return int(want)
    return max(8 * ((int(n) + 7) // 8), 8)


def _prefix_kernel(slope_ref, kscale_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                   l_ref, *, sm_scale: float, alibi: bool, int8_qk: bool):
    """One (kv head, query block) program of the prefix leg.

    q block: (bn, hd) flattened (row, position, group) queries; k/v: the
    WHOLE (Tt, hd) trunk for this kv head in VMEM — the trunk is one
    block on purpose (a bucket-ladder trunk at hd <= 128 is <= 512 KiB
    per side, and one block keeps the online-softmax state scalar per
    query row). Every trunk key precedes every query and every trunk
    slot is real, so there is no mask and no causal term; the partial
    (o, m, l) triple is always finite. Per-query-row operands (slopes,
    m, l) ride as (bn, 1) columns and per-key ones as (1, Tt) rows: the
    TPU's block rule wants a 2-D minor pair, and a column/row already
    sits on the sublanes/lanes it broadcasts along.
    """
    if int8_qk:
        # models/quant.dynamic_quant on the query block INSIDE the
        # kernel; the trunk keys arrive already quantized by the same
        # rule (once per dispatch, not once per query block) with their
        # scales as a (1, Tt) row. s8 x s8 -> s32 on the MXU, scales
        # (and the softmax 1/sqrt(hd)) folded on the s32 scores.
        qq, qs = dynamic_quant(q_ref[0])
        s32 = jnp.dot(qq, k_ref[0].T, preferred_element_type=jnp.int32)
        s = s32.astype(jnp.float32) * (qs.astype(jnp.float32)
                                       * sm_scale)[:, None] * kscale_ref[0]
    else:
        q = q_ref[0].astype(jnp.float32) * sm_scale       # (bn, hd)
        s = jnp.dot(q, k_ref[0].astype(jnp.float32).T,
                    preferred_element_type=jnp.float32)   # (bn, Tt)
    if alibi:
        # ALiBi bias depends on the KEY position only (decoder.
        # _causal_bias) and trunk slot t IS position t, so the bias is
        # slope_row * iota — no position array needs to ride along.
        kp = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = s + slope_ref[0] * kp.astype(jnp.float32)

    m = s.max(axis=-1, keepdims=True)                     # (bn, 1)
    p = jnp.exp(s - m)
    o_ref[0] = jnp.dot(p, v_ref[0].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    m_ref[0] = m
    l_ref[0] = p.sum(axis=-1, keepdims=True)


def _prefix_partials(q, trunk_k, trunk_v, slopes, int8_qk: bool,
                     block_n: int, interpret: bool):
    """Prefix-leg partials: (o, m, l) shaped (B, K, R, G, hd) / (B, K, R, G).

    Inter-query batching: q (B, R, H, hd) flattens to (K, N, hd) with
    N = B*R*G — the whole dispatch is one dense GEMM per kv head against
    the single-row trunk — padded to a block multiple host-side (pad
    rows compute garbage partials that are sliced off before the merge).
    """
    B, R, H, hd = q.shape
    K, Tt = trunk_k.shape[0], trunk_k.shape[1]
    G = H // K
    N = B * R * G
    sm_scale = 1.0 / math.sqrt(hd)
    bn = pick_block_n(N, block_n)
    n_pad = -N % bn
    qf = (q.reshape(B, R, K, G, hd).transpose(2, 0, 1, 3, 4)
          .reshape(K, N, hd))
    qf = jnp.pad(qf, ((0, 0), (0, n_pad), (0, 0)))
    alibi = slopes is not None
    if alibi:
        # Per-flattened-row slope: row n = (b*R + r)*G + g belongs to
        # query head h = kh*G + g.
        sl = jnp.broadcast_to(
            jnp.asarray(slopes, jnp.float32).reshape(K, 1, G),
            (K, B * R, G)).reshape(K, N)
    else:
        sl = jnp.zeros((K, N), jnp.float32)
    sl = jnp.pad(sl, ((0, 0), (0, n_pad)))[..., None]     # (K, npad, 1)
    npad = N + n_pad
    if int8_qk:
        trunk_k, kscale = dynamic_quant(trunk_k)          # s8, (K, Tt)
    else:
        kscale = jnp.ones((K, Tt), jnp.float32)
    kscale = kscale[:, None, :]                           # (K, 1, Tt)

    kernel = functools.partial(_prefix_kernel, sm_scale=sm_scale,
                               alibi=alibi, int8_qk=int8_qk)
    f32 = jnp.float32
    o_p, m_p, l_p = pl.pallas_call(
        kernel,
        grid=(K, npad // bn),
        in_specs=[
            pl.BlockSpec((1, bn, 1), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, 1, Tt), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, bn, hd), lambda h, i: (h, i, 0)),
            # The whole trunk per program (see _prefix_kernel).
            pl.BlockSpec((1, Tt, hd), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, Tt, hd), lambda h, i: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn, hd), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, bn, 1), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, bn, 1), lambda h, i: (h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, npad, hd), f32),
            jax.ShapeDtypeStruct((K, npad, 1), f32),
            jax.ShapeDtypeStruct((K, npad, 1), f32),
        ],
        interpret=interpret,
        name="cascade_attention_prefix",
    )(sl, kscale, qf, trunk_k, trunk_v)

    def unflat(x):
        x = x[:, :N]
        x = x.reshape((K, B, R, G) + x.shape[2:])
        return jnp.moveaxis(x, 0, 1)                      # (B, K, R, G, ...)

    return unflat(o_p), unflat(m_p[..., 0]), unflat(l_p[..., 0])


def _suffix_partials(q, sfx_k, sfx_v, suffix_mask, q_positions, slopes):
    """Suffix-leg partials over each row's OWN remainder KV: causal
    within the window (key position <= query position, mask-aware — the
    exact ``decoder._causal_bias`` rule, so ragged right-padded and
    left-padded windows both behave like unpadded rows), ALiBi on key
    positions, grouped GQA contraction against un-repeated k/v. Plain
    XLA on purpose: the per-row window is short (R x R) and batched thin
    — there is no (S, T) tile to save, exactly why decode steps stay
    dense too. A fully-masked (pad) query row yields m = -inf / l = 0
    and defers entirely to the prefix leg in the merge."""
    B, R, H, hd = q.shape
    K = sfx_k.shape[2]
    G = H // K
    sm_scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, R, K, G, hd).astype(jnp.float32) * sm_scale
    s = jnp.einsum("brkgd,btkd->bkrgt", qg, sfx_k.astype(jnp.float32))
    kp = q_positions.astype(jnp.float32)                  # keys = queries
    if slopes is not None:
        sl = jnp.asarray(slopes, jnp.float32).reshape(K, G)
        s = s + sl[None, :, None, :, None] * kp[:, None, None, None, :]
    valid = ((suffix_mask[:, None, :] > 0)
             & (q_positions[:, None, :] <= q_positions[:, :, None]))
    s = jnp.where(valid[:, None, :, None, :], s, -jnp.inf)
    m = s.max(axis=-1)                                    # (B, K, R, G)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    o = jnp.einsum("bkrgt,btkd->bkrgd", p, sfx_v.astype(jnp.float32))
    return o, m, p.sum(axis=-1)


# Flattened (position, group) query rows one fused-cascade program takes:
# bounds the (rows, trunk) fp32 score tile inside the scoped VMEM limit
# at the widest group the zoo has (falcon MQA, G = 71).
FUSED_MAX_ROWS = 512


def pick_window_rows(window: int, n_groups: int,
                     max_rows: int = FUSED_MAX_ROWS) -> int:
    """Window positions per fused-cascade program: the largest divisor
    ``r`` of the window whose ``r * G`` flattened rows fit ``max_rows``
    and fill whole sublane groups (or the whole window); when no divisor
    fits, the smallest sublane-aligned one."""
    ok = [r for r in range(1, window + 1)
          if window % r == 0 and ((r * n_groups) % 8 == 0 or r == window)]
    fit = [r for r in ok if r * n_groups <= max_rows]
    return max(fit) if fit else min(ok)


def _fused_cascade_kernel(slope_ref, qpos_ref, kpos_ref, smask_ref, q_ref,
                          sk_ref, sv_ref, tk_ref, tv_ref, o_ref, *,
                          sm_scale: float, alibi: bool):
    """One (kv head, batch row, query block) program of the FULLY-FUSED
    cascade: prefix leg + suffix leg + log-sum-exp merge in a single
    kernel, so the partial (o, m, l) triples never round-trip through
    HBM. Every per-element op mirrors the two-leg path exactly — the
    prefix block is :func:`_prefix_kernel`'s arithmetic, the suffix
    block is :func:`_suffix_partials`' (per (row, kv head) slice), and
    the merge is :func:`~lir_tpu.ops.lse.merge_partials`' stacked-sum
    order — so the fused output is BITWISE the two-leg path's (pinned
    across the cascade matrix by tests/test_cascade.py)."""
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale        # (rG, hd)
    # Per-flattened-row slopes and query positions arrive HOST-built as
    # (rG, 1) columns (like _prefix_partials' flattened slope array):
    # building them in-kernel from a (G,) block lets XLA contract the
    # bias mul+add into an FMA, a 1-ulp drift off the two-leg lowering.
    slope = slope_ref[0]                                  # (rG, 1)

    # Prefix leg (== _prefix_kernel, non-int8): no mask, no causality.
    s = jnp.dot(q, tk_ref[0].astype(jnp.float32).T,
                preferred_element_type=jnp.float32)       # (rG, Tt)
    if alibi:
        kp_t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = s + slope * kp_t.astype(jnp.float32)
    m_t = s.max(axis=-1, keepdims=True)                   # (rG, 1)
    p = jnp.exp(s - m_t)
    o_t = jnp.dot(p, tv_ref[0].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    l_t = p.sum(axis=-1, keepdims=True)

    # Suffix leg (== _suffix_partials for this (b, kh) slice): causal
    # within the window, mask-aware, ALiBi on absolute key positions.
    sk = sk_ref[0, 0].astype(jnp.float32)                 # (R, hd)
    s2 = jnp.dot(q, sk.T, preferred_element_type=jnp.float32)  # (rG, R)
    kp = kpos_ref[0]                                      # (1, R)
    if alibi:
        s2 = s2 + slope * kp.astype(jnp.float32)
    valid = (smask_ref[0] > 0) & (kp <= qpos_ref[0])      # (rG, R)
    s2 = jnp.where(valid, s2, -jnp.inf)
    m_s = s2.max(axis=-1, keepdims=True)
    p2 = jnp.exp(s2 - m_s)
    p2 = jnp.where(valid, p2, 0.0)                        # all-masked row
    o_s = jnp.dot(p2, sv_ref[0, 0].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    l_s = p2.sum(axis=-1, keepdims=True)

    # In-VMEM merge: merge_partials' exact stacked-reduction order over
    # the two partials, trunk first.
    m_p = jnp.stack([m_t, m_s])                           # (2, rG, 1)
    m = m_p.max(axis=0)
    w = jnp.where(jnp.isfinite(m_p), jnp.exp(m_p - m[None]), 0.0)
    l = (w * jnp.stack([l_t, l_s])).sum(axis=0)
    o = (w * jnp.stack([o_t, o_s])).sum(axis=0)
    o_ref[0, 0] = o / jnp.maximum(l, 1e-30)


def _cascade_fused(q, sfx_k, sfx_v, trunk_k, trunk_v, suffix_mask,
                   q_positions, slopes, interpret: bool):
    """Single-launch cascade attention: grid (K, B, query blocks), each
    program owns a block of one row's R*G flattened queries against the
    whole trunk plus the row's own suffix window, merged in VMEM — one
    kernel, zero HBM round-trips for the partials."""
    B, R, H, hd = q.shape
    K, Tt = trunk_k.shape[0], trunk_k.shape[1]
    G = H // K
    RG = R * G
    rG = pick_window_rows(R, G) * G
    sm_scale = 1.0 / math.sqrt(hd)
    alibi = slopes is not None
    if alibi:
        sl = jnp.broadcast_to(
            jnp.asarray(slopes, jnp.float32).reshape(K, 1, G),
            (K, R, G)).reshape(K, RG, 1)
    else:
        sl = jnp.zeros((K, RG, 1), jnp.float32)
    qf = (q.reshape(B, R, K, G, hd).transpose(0, 2, 1, 3, 4)
          .reshape(B, K, RG, hd))
    skt = sfx_k.transpose(0, 2, 1, 3)                     # (B, K, R, hd)
    svt = sfx_v.transpose(0, 2, 1, 3)
    kpos = jnp.asarray(q_positions, jnp.int32)[:, None, :]  # keys = queries
    qpos = jnp.repeat(jnp.asarray(q_positions, jnp.int32), G,
                      axis=1)[..., None]                  # (B, RG, 1)
    smask = jnp.asarray(suffix_mask, jnp.int32)[:, None, :]
    kernel = functools.partial(_fused_cascade_kernel, sm_scale=sm_scale,
                               alibi=alibi)
    out = pl.pallas_call(
        kernel,
        grid=(K, B, RG // rG),
        in_specs=[
            pl.BlockSpec((1, rG, 1), lambda h, b, i: (h, i, 0)),
            pl.BlockSpec((1, rG, 1), lambda h, b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, R), lambda h, b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda h, b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, rG, hd), lambda h, b, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, R, hd), lambda h, b, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, R, hd), lambda h, b, i: (b, h, 0, 0)),
            pl.BlockSpec((1, Tt, hd), lambda h, b, i: (h, 0, 0)),
            pl.BlockSpec((1, Tt, hd), lambda h, b, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rG, hd),
                               lambda h, b, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, RG, hd), jnp.float32),
        interpret=interpret,
        name="cascade_attention",
    )(sl, qpos, kpos, smask, qf, skt, svt, trunk_k, trunk_v)
    out = out.reshape(B, K, R, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, R, H, hd).astype(q.dtype)


@functools.partial(jax.jit,
                   static_argnames=("int8_qk", "block_n", "interpret",
                                    "fused_suffix"))
def cascade_attention(q, sfx_k, sfx_v, trunk_k, trunk_v, suffix_mask,
                      q_positions, alibi_slopes=None, int8_qk: bool = False,
                      block_n: int = DEFAULT_BLOCK_N,
                      interpret: bool = False,
                      fused_suffix: bool = True) -> jnp.ndarray:
    """Shared-trunk cascade attention for one layer's remainder window.

    ``q``: (B, R, H, hd) post-RoPE queries at the dispatch's remainder
    positions. ``sfx_k``/``sfx_v``: (B, R, K, hd) the window's own
    post-RoPE k/v (un-repeated GQA). ``trunk_k``/``trunk_v``:
    (K, Tt, hd) the SHARED trunk KV — one row, no batch axis; slot t is
    position t and every slot is real. ``suffix_mask``: (B, R) validity
    of the remainder positions; ``q_positions``: (B, R) mask-aware
    ABSOLUTE positions (trunk_len + window-local). Returns (B, R, H, hd)
    in q's dtype — softmax over trunk + window keys, exact.

    ``fused_suffix`` (default ON, RuntimeConfig.cascade_fused_suffix)
    runs prefix + suffix + merge as ONE Pallas launch with the partials
    merged in VMEM — bitwise the two-leg path below. The int8-QK^T
    variant keeps the two-leg split (its prefix leg quantizes in-kernel
    over flattened query blocks; --no-cascade-fused-suffix restores the
    two-leg path for float too).
    """
    if fused_suffix and not int8_qk:
        return _cascade_fused(q, sfx_k, sfx_v, trunk_k, trunk_v,
                              suffix_mask, q_positions, alibi_slopes,
                              interpret)
    B, R, H, hd = q.shape
    o_t, m_t, l_t = _prefix_partials(q, trunk_k, trunk_v, alibi_slopes,
                                     int8_qk, block_n, interpret)
    o_s, m_s, l_s = _suffix_partials(q, sfx_k, sfx_v, suffix_mask,
                                     q_positions, alibi_slopes)
    out = merge_partials(jnp.stack([o_t, o_s], axis=2),
                         jnp.stack([m_t, m_s], axis=2),
                         jnp.stack([l_t, l_s], axis=2),
                         axis=2)                          # (B, K, R, G, hd)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, R, H, hd).astype(q.dtype)
