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

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# Query rows ((position, group) flattened) of one fused-cascade program,
# the float32 scores it may hold in VMEM (rows x trunk and window keys),
# and keys of one of its suffix chunks. A row block skips the key chunks
# none of its queries may attend, so finer blocks and chunks skip more,
# but each program pays a fixed cost (its block DMAs and grid step) that
# outweighs the finer skip at the sweep's shapes: on a v5e the largest
# blocks tried (1,200 rows over 64 trunk + 384 window keys: falcon's
# group of 71 at 16 positions) and lane-wide chunks ran fastest at every
# head layout (PERF.md, PR 40). Longer prefixes take fewer rows.
FUSED_ROWS = 1200
FUSED_SCORES = FUSED_ROWS * (64 + 384)
FUSED_CHUNK = 128


def fused_edges(window: int, n_groups: int, trunk_len: int) -> tuple:
    """(positions a row block takes, keys a suffix chunk takes) of the
    fused cascade over a remainder window of ``window`` positions behind
    a ``trunk_len`` trunk, ``n_groups`` query heads a kv head: the
    largest divisor ``r`` of the window whose ``r * G`` rows fill whole
    sublane groups (or the whole window), fit :data:`FUSED_ROWS`, and
    whose scores over trunk and window fit :data:`FUSED_SCORES`, else
    the smallest sublane-aligned one; the largest divisor of the
    window up to :data:`FUSED_CHUNK` keys on the bfloat16 sublane grid
    (16), else the whole window as one chunk."""
    ok = [r for r in range(1, window + 1)
          if window % r == 0 and ((r * n_groups) % 8 == 0 or r == window)]
    fit = [r for r in ok if r * n_groups <= FUSED_ROWS
           and r * n_groups * (trunk_len + window) <= FUSED_SCORES]
    chunks = [c for c in range(16, min(FUSED_CHUNK, window) + 1, 16)
              if window % c == 0]
    return (max(fit) if fit else min(ok)), (max(chunks) if chunks
                                            else window)


def key_chunks(first_pos, last_slot, trunk_len: int, chunk: int,
               window=None) -> tuple:
    """The fused cascade kernel's loop bounds for row blocks: ``(lo, hi,
    trunk)``, the first and last suffix chunk a block visits and whether
    it visits the trunk (0 / 1), each shaped like ``first_pos``.

    ``first_pos``: the position of each block's first query (positions
    never fall along a row, so it is the block's least); ``last_slot``:
    each block's last window slot. A key at slot j of the window sits at
    position trunk_len + j at most (the canonical layout; pads repeat an
    earlier position), and a query attends no key past its own slot, so
    chunks past the one holding ``last_slot`` are never attended, and
    under a ``window`` neither are those before the one holding slot
    first_pos - trunk_len - window + 1, nor the trunk once that slot is
    past it. Takes numpy and jax arrays alike (the kernel's table is
    built on the device, the counter's on the host)."""
    hi = last_slot // chunk + 0 * first_pos
    if window is None:
        return 0 * first_pos, hi, 1 + 0 * first_pos
    lo = (first_pos - trunk_len - window + 1).clip(0) // chunk
    trunk = (first_pos - window + 1 < trunk_len).astype(np.int32)
    return lo, hi, trunk


def key_pairs(q_positions, suffix_mask, trunk_len: int, n_groups: int,
              windows) -> tuple:
    """(computed, attended) (query, key) pairs of the fused cascade
    kernel over one dispatch's layers, a query counted once for its
    ``n_groups`` heads: the keys of the chunks :func:`key_chunks` hands
    the kernel (with :func:`fused_edges`' blocks), and those the dense
    mask admits (trunk keys within the window, own keys valid, not past
    the query and within the window). Host numpy, no device work.
    ``q_positions`` / ``suffix_mask``: (B, R) as the kernel gets them;
    ``windows``: each layer's window, None where it attends every key."""
    qp = np.asarray(q_positions, np.int64)
    valid = np.asarray(suffix_mask) > 0
    B, R = qp.shape
    r, chunk = fused_edges(R, n_groups, trunk_len)
    last = np.arange(r - 1, R, r)
    computed = attended = 0
    for window, layers in collections.Counter(windows).items():
        lo, hi, trunk = key_chunks(qp[:, ::r], last, trunk_len, chunk,
                                   window)
        keys = (np.minimum((hi + 1) * chunk, R) - lo * chunk
                + trunk * trunk_len)
        computed += layers * r * int(keys.sum())
        w = np.iinfo(np.int64).max // 2 if window is None else window
        seen = np.clip(trunk_len - (qp - w + 1).clip(0), 0, trunk_len)
        for b in range(B):
            kp = qp[b][valid[b]]
            seen[b] += (np.searchsorted(kp, qp[b], "right")
                        - np.searchsorted(kp, qp[b] - w, "right"))
        attended += layers * int(seen.sum())
    return computed, attended


def _fused_cascade_kernel(bounds_ref, *refs, sm_scale: float, alibi: bool,
                          windowed: bool, chunk: int, n_blocks: int):
    """One (kv head, batch row, row block) program of the FULLY-FUSED
    cascade: the block's queries attend the shared trunk and the row's
    own window keys under ONE softmax, visiting only the key chunks
    :func:`key_chunks` says some query of the block may attend
    (``bounds_ref``, prefetched: lo, hi, trunk for each (row, block)).
    Two passes over the visited chunks: the scores and their maximum
    (kept per lane, reduced across lanes once), then the exponentials,
    their sum (per lane too) and P.V; the result is written once, in the
    output's dtype. Every score is the two-leg path's float32 arithmetic
    (the prefix block :func:`_prefix_kernel`'s, the suffix chunks
    :func:`_suffix_partials`' masks); only the order of the softmax sums
    differs.

    ``windowed``: a layer that attends a window, whose width rides the
    first operand: a key counts, trunk or own, only where its position
    lies within it of the query's. ``alibi``: the next operand is the
    rows' slopes."""
    if windowed:
        win_ref, refs = refs[0], refs[1:]
    if alibi:
        slope_ref, refs = refs[0], refs[1:]
    (qpos_ref, kpos_ref, smask_ref, q_ref, sk_ref, sv_ref, tk_ref, tv_ref,
     o_ref, s_sc, t_sc, mx_sc, lx_sc, l_sc, acc_sc) = refs
    f32 = jnp.float32
    at = 3 * (pl.program_id(1) * n_blocks + pl.program_id(2))
    lo, hi, trunk = bounds_ref[at], bounds_ref[at + 1], bounds_ref[at + 2]
    q = q_ref[0, 0].astype(f32) * sm_scale                # (rG, hd)
    # Per-flattened-row slopes and query positions arrive HOST-built as
    # (rG, 1) columns (like _prefix_partials' flattened slope array).
    qpos = qpos_ref[0]                                    # (rG, 1)
    if alibi:
        slope = slope_ref[0]                              # (rG, 1)
    if windowed:
        lo_pos = qpos - win_ref[...]                      # (rG, 1)
    R = sk_ref.shape[2]
    chunks = [(c, c * chunk, (c + 1) * chunk) for c in range(R // chunk)]

    # Pass 1: the scores of the visited keys; masked and skipped ones
    # read -inf.
    t_sc[...] = jnp.full(t_sc.shape, -jnp.inf, f32)
    mx_sc[...] = jnp.full(mx_sc.shape, -jnp.inf, f32)

    @pl.when(trunk > 0)
    def _trunk_scores():
        # The prefix leg's keys: no mask, no causality, slot t IS
        # position t.
        s = jnp.dot(q, tk_ref[0].astype(f32).T,
                    preferred_element_type=f32)           # (rG, Tt)
        if alibi or windowed:
            kp_t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if alibi:
            s = s + slope * kp_t.astype(f32)
        if windowed:
            s = jnp.where(kp_t > lo_pos, s, -jnp.inf)
        t_sc[...] = s

    for c, a, e in chunks:
        @pl.when((lo <= c) & (c <= hi))
        def _chunk_scores(c=c, a=a, e=e):
            # The suffix leg's keys a:e: causal within the window,
            # mask-aware, ALiBi on absolute key positions.
            sk = sk_ref[0, 0, a:e, :].astype(f32)         # (C, hd)
            s = jnp.dot(q, sk.T, preferred_element_type=f32)  # (rG, C)
            kp = kpos_ref[0, :, a:e]                      # (1, C)
            if alibi:
                s = s + slope * kp.astype(f32)
            valid = (smask_ref[0, :, a:e] > 0) & (kp <= qpos)
            if windowed:
                valid = valid & (kp > lo_pos)
            s = jnp.where(valid, s, -jnp.inf)
            s_sc[c] = s
            mx_sc[...] = jnp.maximum(mx_sc[...], s)

    m = jnp.maximum(t_sc[...].max(axis=-1, keepdims=True),
                    mx_sc[...].max(axis=-1, keepdims=True))
    # A row that attends no key at all exponentiates against 0: every
    # weight is then 0 and so is its result.
    m = jnp.where(m == -jnp.inf, 0.0, m)

    # Pass 2: exponentials, their sums, and P.V, trunk first.
    l_sc[...] = jnp.zeros(l_sc.shape, f32)
    lx_sc[...] = jnp.zeros(lx_sc.shape, f32)
    acc_sc[...] = jnp.zeros(acc_sc.shape, f32)

    @pl.when(trunk > 0)
    def _trunk_values():
        p = jnp.exp(t_sc[...] - m)
        l_sc[...] = p.sum(axis=-1, keepdims=True)
        acc_sc[...] = jnp.dot(p, tv_ref[0].astype(f32),
                              preferred_element_type=f32)

    for c, a, e in chunks:
        @pl.when((lo <= c) & (c <= hi))
        def _chunk_values(c=c, a=a, e=e):
            p = jnp.exp(s_sc[c] - m)
            lx_sc[...] += p
            acc_sc[...] += jnp.dot(p, sv_ref[0, 0, a:e, :].astype(f32),
                                   preferred_element_type=f32)

    l = l_sc[...] + lx_sc[...].sum(axis=-1, keepdims=True)
    o_ref[0, 0] = (acc_sc[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _cascade_fused(q, sfx_k, sfx_v, trunk_k, trunk_v, suffix_mask,
                   q_positions, slopes, interpret: bool, window=None):
    """Single-launch cascade attention: grid (K, B, row blocks), each
    program owns a block of one row's R*G flattened queries against the
    trunk and the chunks of the row's own window that block may attend
    (:func:`key_chunks`), under one softmax in VMEM — one kernel, zero
    HBM round-trips, the result written in q's dtype."""
    B, R, H, hd = q.shape
    K, Tt = trunk_k.shape[0], trunk_k.shape[1]
    G = H // K
    RG = R * G
    r, chunk = fused_edges(R, G, Tt)
    rG, n_blocks = r * G, R // r
    sm_scale = 1.0 / math.sqrt(hd)
    qf = (q.reshape(B, R, K, G, hd).transpose(0, 2, 1, 3, 4)
          .reshape(B, K, RG, hd))
    skt = sfx_k.transpose(0, 2, 1, 3)                     # (B, K, R, hd)
    svt = sfx_v.transpose(0, 2, 1, 3)
    positions = jnp.asarray(q_positions, jnp.int32)
    kpos = positions[:, None, :]                          # keys = queries
    qpos = jnp.repeat(positions, G, axis=1)[..., None]    # (B, RG, 1)
    smask = jnp.asarray(suffix_mask, jnp.int32)[:, None, :]
    win = None if window is None else jnp.asarray(window, jnp.int32)
    bounds = jnp.stack(key_chunks(positions[:, ::r],
                                  jnp.arange(r - 1, R, r, dtype=jnp.int32),
                                  Tt, chunk, win), axis=-1)
    bounds = bounds.astype(jnp.int32).reshape(-1)         # (B*n_blocks*3,)
    lead, lead_specs = [], []
    if win is not None:
        lead.append(win.reshape(1, 1))
        lead_specs.append(pl.BlockSpec((1, 1), lambda h, b, i, t: (0, 0)))
    if slopes is not None:
        lead.append(jnp.broadcast_to(
            jnp.asarray(slopes, jnp.float32).reshape(K, 1, G),
            (K, R, G)).reshape(K, RG, 1))
        lead_specs.append(pl.BlockSpec((1, rG, 1),
                                       lambda h, b, i, t: (h, i, 0)))
    kernel = functools.partial(_fused_cascade_kernel, sm_scale=sm_scale,
                               alibi=slopes is not None,
                               windowed=win is not None, chunk=chunk,
                               n_blocks=n_blocks)
    f32 = jnp.float32
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(K, B, n_blocks),
            in_specs=lead_specs + [
                pl.BlockSpec((1, rG, 1), lambda h, b, i, t: (b, i, 0)),
                pl.BlockSpec((1, 1, R), lambda h, b, i, t: (b, 0, 0)),
                pl.BlockSpec((1, 1, R), lambda h, b, i, t: (b, 0, 0)),
                pl.BlockSpec((1, 1, rG, hd), lambda h, b, i, t: (b, h, i, 0)),
                pl.BlockSpec((1, 1, R, hd), lambda h, b, i, t: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, R, hd), lambda h, b, i, t: (b, h, 0, 0)),
                pl.BlockSpec((1, Tt, hd), lambda h, b, i, t: (h, 0, 0)),
                pl.BlockSpec((1, Tt, hd), lambda h, b, i, t: (h, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, rG, hd),
                                   lambda h, b, i, t: (b, h, i, 0)),
            scratch_shapes=[pltpu.VMEM((R // chunk, rG, chunk), f32),
                            pltpu.VMEM((rG, Tt), f32),
                            pltpu.VMEM((rG, chunk), f32),
                            pltpu.VMEM((rG, chunk), f32),
                            pltpu.VMEM((rG, 1), f32),
                            pltpu.VMEM((rG, hd), f32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, RG, hd), q.dtype),
        interpret=interpret,
        name="cascade_attention",
    )(bounds, *lead, qpos, kpos, smask, qf, skt, svt, trunk_k, trunk_v)
    out = out.reshape(B, K, R, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, R, H, hd)


@functools.partial(jax.jit,
                   static_argnames=("int8_qk", "block_n", "interpret",
                                    "fused_suffix"))
def cascade_attention(q, sfx_k, sfx_v, trunk_k, trunk_v, suffix_mask,
                      q_positions, alibi_slopes=None, int8_qk: bool = False,
                      block_n: int = DEFAULT_BLOCK_N,
                      interpret: bool = False,
                      fused_suffix: bool = True,
                      window=None) -> jnp.ndarray:
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
    runs prefix + suffix as ONE Pallas launch under one softmax
    in VMEM over only the key chunks a row block may attend — the two-leg
    path's scores, summed in another order. The int8-QK^T
    variant keeps the two-leg split (its prefix leg quantizes in-kernel
    over flattened query blocks; --no-cascade-fused-suffix restores the
    two-leg path for float too).

    ``window`` (a traced scalar, or None): a query attends only keys whose
    positions lie within ``window`` of its own, in both legs; the fused
    launch alone takes one.
    """
    if fused_suffix and not int8_qk:
        return _cascade_fused(q, sfx_k, sfx_v, trunk_k, trunk_v,
                              suffix_mask, q_positions, alibi_slopes,
                              interpret, window)
    if window is not None:
        raise NotImplementedError(
            "a window runs in the fused cascade launch only (float QK^T, "
            "cascade_fused_suffix)")
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
