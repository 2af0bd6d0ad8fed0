"""Pallas fused flash-decode: single-query attention over the KV cache.

The decode phase of the scoring step is where the 36% MFU plateau lives
(BENCH_r05): each greedy step attends ONE query per row over the whole
cache, and XLA's dense lowering materializes the (B, H, 1, T) score row,
the fp32 softmax, and the probability row as separate HBM round-trips
between three kernels. This kernel is the Flash-Decoding treatment (Dao
et al.): because the query axis is a single position, parallelism must
come from the KEY axis — the cache's sequence dimension is split into
blocks, each grid program reduces its block with an online softmax into a
partial (o, m, l) triple, and the partials combine with one log-sum-exp
reduction. Scores, exponentials, and probability-weighted sums never
leave VMEM; HBM traffic drops to the cache read plus O(B*H*hd) partials.

Layout contract matches the decode path exactly (models/decoder.
_attention_cached): q is (B, H, hd) — one post-RoPE query per row — and
k/v arrive in the CACHE layout (K, T, B, hd) (head-major/batch-minor, the
order the decode while-loop carries), un-repeated for GQA/MQA: grouped
query heads contract against their kv head inside the kernel, so the
cache is never copied K -> H. Masking semantics equal the dense path's
additive bias: a key is valid iff its mask bit is set AND its mask-aware
position does not exceed the query's; ALiBi families add
``slope_h * key_position`` exactly as ``decoder._causal_bias`` does.

The decode step's layer loop carries the cache STACKED, (L, K, T, B, hd)
a side, and updates it where it lies (models/decoder._scan_blocks); the
kernel is handed that stacked operand and a ``layer`` index, prefetched
as a scalar, and its K/V index map starts with the layer: it reads
``(layer, head, split, rows)`` blocks in place, so no layer is ever
sliced out of the cache for a call. One layer's (K, T, B, hd) sides are
the L = 1 case of the same call.

Block sizes align to the flash_attention edges (DEFAULT_BLOCK_K): the
split width is the largest divisor of T no wider than the requested
block (preferring sublane-aligned multiples of 8), falling back to a
single full-width split — any cache extent therefore lowers without
padding or out-of-bounds tail blocks, and the extents the dispatch
programs allocate are chosen so that the divisor is a wide one
(:func:`decode_extent`: prefix edge + suffix + decode budget, grown by
a few masked slots onto an extent that splits well). The batch axis
rides in blocks of 8 rows (the
TPU's sublane tile — see ``_decode_kernel``); one kernel and one
pallas_call sit behind all four entry points, and all of them compile
for a described v5e in tests/test_tpu_compile.py. ``interpret=True`` runs the kernel in the
Pallas interpreter so tier-1 exercises it on CPU (tests/test_kernels.py);
production CPU runs keep the dense path (models/decoder.FUSED_DECODE_
INTERPRET_ON_CPU is the test hook, mirroring FLASH_INTERPRET_ON_CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import DEFAULT_BLOCK_K
from .lse import merge_partials


def pick_split(total: int, want: int = DEFAULT_BLOCK_K) -> int:
    """Split width for a cache of ``total`` slots: the largest divisor of
    ``total`` that is <= ``want``, preferring sublane-aligned multiples of
    8; ``total`` itself (one split) when nothing smaller divides. Exact
    division — never a padded or out-of-bounds tail block."""
    want = min(int(want), int(total))
    for b in range(want, 7, -1):
        if total % b == 0 and b % 8 == 0:
            return b
    for b in range(want, 0, -1):
        if total % b == 0:
            return b
    return int(total)


# Sublane edge of the TPU's (8, 128) register tile: the cache's batch axis
# is the second-minor dimension of a K/V block, so blocks carry whole
# sublane groups of rows (Mosaic refuses a block whose second-minor
# extent is neither a multiple of 8 nor the array's own).
SUBLANE = 8
# fp32 score-tile budget per grid program (elements): the (rows, keys)
# tile is BATCH_BLOCK times wider than the useful scores (see
# _decode_kernel), so wide query groups (falcon MQA, G = 71) take a
# narrower key split to stay inside the scoped VMEM limit.
_SCORE_TILE_ELEMS = 256 * 1024
# Masked cache slots ride the key-position array as this sentinel (above
# any real position), so one int32 operand carries validity AND position.
_MASKED_POS = 1 << 30


def batch_block(batch: int) -> int:
    """Rows of the cache's batch axis one K/V block carries: one sublane
    group when the batch divides into them, else a single row — a block
    only the interpreter takes (the TPU gate requires ``batch % 8 == 0``,
    see models/decoder._decode_kernels_lower)."""
    return SUBLANE if batch % SUBLANE == 0 else 1


def _split_cap(batch: int, n_groups: int, block_k: int) -> int:
    """Widest key split a (batch, query-group) shape may take: the
    requested block, or what keeps the score tile inside its budget."""
    bb = batch_block(batch)
    cap = max(SUBLANE, _SCORE_TILE_ELEMS // (bb * bb * n_groups))
    return min(int(block_k), cap)


def decode_split(total: int, batch: int, n_groups: int,
                 block_k: int = DEFAULT_BLOCK_K) -> int:
    """Key-split width every decode entry point uses for a (cache extent,
    batch, query-group) shape — chosen from those alone (never from the
    verify-window length), so the single- and multi-query kernels and
    their trunk variants share one split ladder and their partials line
    up split for split."""
    return pick_split(total, _split_cap(batch, n_groups, block_k))


# Most slots :func:`decode_extent` adds to what a dispatch needs.
EXTENT_GROWTH = 32


def decode_extent(need: int, batch: int, n_groups: int,
                  block_k: int = DEFAULT_BLOCK_K) -> int:
    """Cache extent for a dispatch that needs ``need`` slots (prefix edge
    + suffix edge + decode budget): the smallest multiple of 8 >= ``need``,
    at most EXTENT_GROWTH slots above it, that :func:`decode_split` cuts
    into sublane-aligned splits at least half as wide as the widest it may
    take. The split must divide the extent exactly, so an extent with few
    divisors (552 = 8 * 3 * 23) falls to narrow splits (23 of 24) and the
    decode step pays per grid program, not per byte; a few slots more
    (560 = 5 * 112) keep the grid short. The added slots are masked like
    any slot a row does not fill. Every dispatch program sizes its cache
    through this one rule (engine/generate.cache_extent), so the AOT plan,
    the donated handoff buffer and the dispatch agree by construction.
    Where no candidate splits well the extent is ``need`` on the 8 grid.
    Monotone in ``need``."""
    first = -(-int(need) // SUBLANE) * SUBLANE
    cap = _split_cap(batch, n_groups, block_k)
    for total in range(first, int(need) + EXTENT_GROWTH + 1, SUBLANE):
        split = pick_split(total, cap)
        if split % SUBLANE == 0 and 2 * split >= min(cap, total):
            return total
    return first


def _decode_kernel(*refs, sm_scale: float, alibi: bool, n_blocks: int = 0):
    """One (kv head, key split, batch block, window query) program.

    The first operand is the prefetched layer index: the index maps spend
    it (they pick the layer's K/V blocks out of the stacked cache), the
    body does not read it. ``n_blocks`` > 0 (the batch blocks of the
    call): a layer that attends a window, whose width, live-split table
    and fetch table ride the prefetch after the layer
    (:func:`_window_tables`): a key counts only where its position also
    lies within the window of the query's, and a program whose split no
    query of its batch block reaches computes nothing and hands the
    merge an empty partial (its K/V index map names the block the
    program before it fetched, so no DMA is issued for it either).

    The cache block is (split, bb, hd): ``bb`` batch rows ride the
    sublanes under every key slot, which is the cache's own (B, hd)
    minor pair — no relayout in HBM or VMEM. Collapsing the two leading
    axes is free (bb is a whole sublane group), giving a (split*bb, hd)
    key matrix whose row ``t*bb + b`` is row b's key t. The block's
    queries, rows ordered (b, g), contract against ALL of it in one MXU
    matmul; a score is kept only where the key's batch row is the
    query's own (the block diagonal), everything else masks to -inf
    exactly like an invalid slot. The bb-fold padding costs MXU work the
    decode step has to spare, and buys plain 2-D matmuls and lane
    reductions — nothing the TPU compiler has to shuffle across
    sublanes."""
    win_ref = live_ref = None
    if n_blocks:
        _, win_ref, live_ref, _ = refs[:4]
        refs = refs[4:]
    else:
        refs = refs[1:]
    (rowb_ref, laneb_ref, qpos_ref, kpos_ref, slope_ref, q_ref, k_ref,
     v_ref, o_ref, m_ref, l_ref) = refs

    def attend():
        # MXU operands stay in the cache dtype (bf16 on the chip: one MXU
        # pass, as the dense path's einsums run); accumulation is fp32.
        kb = k_ref[0, 0]                                  # (bs, bb, hd)
        bs, bb, hd = kb.shape
        k = kb.reshape(bs * bb, hd)
        v = v_ref[0, 0].reshape(bs * bb, hd)
        q = q_ref[0, 0, 0].astype(k.dtype)                # (R, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        kp = kpos_ref[0, 0]                               # (1, bs*bb)
        qp = qpos_ref[0, 0]                               # (R, 1)
        if alibi:
            # Per-row slope of this kv head's query group (h = kh*G + g).
            s = s + slope_ref[0] * kp.astype(jnp.float32)
        valid = (laneb_ref[...] == rowb_ref[...]) & (kp <= qp)  # (R, bs*bb)
        if win_ref is not None:
            valid = valid & (kp > qp - win_ref[0])
        s = jnp.where(valid, s, -jnp.inf)

        m = s.max(axis=-1, keepdims=True)                 # (R, 1)
        p = jnp.exp(s - m)
        p = jnp.where(valid, p, 0.0)                      # all-masked split
        o_ref[0, 0, 0, 0] = jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        m_ref[0, 0, 0, 0] = m
        l_ref[0, 0, 0, 0] = p.sum(axis=-1, keepdims=True)

    if live_ref is None:
        attend()
        return
    live = live_ref[pl.program_id(1) * n_blocks + pl.program_id(2)] > 0
    pl.when(live)(attend)

    @pl.when(jnp.logical_not(live))
    def _empty():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)


def _window_tables(kpos, qpos, window, n_blocks: int, n_splits: int):
    """(live, fetch) of a windowed call, each (n_splits * n_blocks,) int32
    in grid order (split-major, batch block minor): whether any query of
    the batch block reaches a key of the split (its position in the
    query's window and not past the query), and the (split * n_blocks +
    batch block) whose K/V block the program's index map names: its own
    where live, else the last live one before it in grid order (the
    block the pipeline holds already: no DMA), else the first live one.
    ``kpos`` (nB, n_splits, 1, L) with masked slots at _MASKED_POS,
    ``qpos`` (nB, S, R, 1)."""
    q_hi = qpos.max(axis=(1, 2, 3))                       # (nB,)
    q_lo = qpos.min(axis=(1, 2, 3))
    kp = kpos[:, :, 0, :]                                 # (nB, n_splits, L)
    hit = ((kp <= q_hi[:, None, None])
           & (kp > (q_lo - window)[:, None, None])).any(axis=-1)
    live = hit.T.reshape(-1)                              # (n_splits * nB,)
    order = jnp.arange(live.shape[0], dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(live, order, -1))
    first = jnp.argmax(live).astype(jnp.int32)
    fetch = jnp.where(last < 0, first, last).astype(jnp.int32)
    return live.astype(jnp.int32), fetch


def _window_keys(fetch, kpos, q_positions, window, n_blocks: int, nt: int,
                 block_slots: int):
    """(3,) int32 of a windowed call, for one kv head: the (key slot,
    batch row) pairs its K blocks bring in from HBM, the pairs its queries
    attend (key not past the query and within its window), and 1 (the
    call). A block is brought in where the index map names another block
    than the program before it in grid order (the pipeline keeps the one
    it holds): a skipped program's costs nothing, a trunk split, named
    once for every batch block, one block. ``fetch`` as
    :func:`_window_tables` gives it; ``kpos`` (B, T) with masked slots at
    _MASKED_POS; ``q_positions`` (B, S)."""
    jf, i_f = fetch // n_blocks, fetch % n_blocks
    named = jf * n_blocks + jnp.where(jf < nt, 0, i_f)
    fetched = 1 + jnp.sum(named[1:] != named[:-1])
    qp = q_positions.astype(jnp.int32)[:, :, None]
    kp = kpos[:, None, :]
    needed = jnp.sum((kp <= qp) & (kp > qp - window))
    return jnp.stack([fetched * block_slots, needed, 1]).astype(jnp.int32)


def _decode_call(name: str, q, k, v, q_positions, key_mask, key_positions,
                 alibi_slopes, trunk_len: int, block_k: int,
                 interpret: bool, layer=None, window=None):
    """The one pallas_call behind all four entry points, under the entry
    point's ``name``: the name a profiler trace shows for the kernel
    (``flash_decode_trunk``, ``flash_decode_mq`` ...), pinned here so
    that renaming a jitted wrapper cannot silence the benchmark's
    ``decode_kernel_roofline``. ``q``:
    (B, S, H, hd), ``q_positions``: (B, S). ``k`` / ``v``: the STACKED
    cache sides (L, K, T, B, hd) with ``layer`` the (traced) index of the
    layer to attend over, prefetched as a scalar so that the K/V index
    map names that layer's blocks where they lie: no (K, T, B, hd) slice
    is ever made for the call. One layer's (K, T, B, hd) sides with
    ``layer`` None are the L = 1 case (the leading axis is a free
    reshape). Grid (K, T/split, B/bb, S):
    the window-query axis is innermost and the batch-block axis next, so
    consecutive programs that name the same K/V block skip its DMA —
    every query of a verify window reuses the block its row already
    loaded, and for the leading ``trunk_len`` slots (identical in every
    row by the cascade contract) every batch block names block 0: the
    trunk's K/V leaves HBM once per (kv head, split), not once per
    row.

    ``window`` (a traced scalar, or None): a key counts only where its
    position also lies within ``window`` of the query's, and the program
    of a (split, batch block) that no query of the block reaches is
    skipped, its K/V DMA with it (:func:`_decode_kernel`); the kernel
    is then named ``flash_decode_windowed``, and the call returns
    ``(out, keys)`` (:func:`_window_keys`)."""
    B, S, H, hd = q.shape
    if layer is None:
        k, v, layer = k[None], v[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    K, T = k.shape[1], k.shape[2]
    G = H // K
    bb = batch_block(B)
    nB = B // bb
    R = bb * G
    sm_scale = 1.0 / np.sqrt(hd)
    alibi = alibi_slopes is not None
    if key_positions is None:
        key_positions = jnp.maximum(jnp.cumsum(key_mask, axis=-1) - 1, 0)
    kpos = jnp.where(jnp.asarray(key_mask, jnp.int32) > 0,
                     jnp.asarray(key_positions, jnp.int32), _MASKED_POS)
    split = decode_split(T, B, G, block_k)
    n_splits = T // split
    nt = max(0, min(int(trunk_len), T - 1)) // split
    L = split * bb

    kpos_bt = kpos
    # Lane order of a split's keys is (slot, batch row); one full-extent
    # (1, L) row per (batch block, split), so any split width lowers.
    kpos = (kpos.reshape(nB, bb, n_splits, split).transpose(0, 2, 3, 1)
            .reshape(nB, n_splits, 1, L))
    # Row order of a block's queries is (batch row, group).
    qg = (q.reshape(nB, bb, S, K, G, hd).transpose(0, 3, 2, 1, 4, 5)
          .reshape(nB, K, S, R, hd))
    qpos = jnp.broadcast_to(
        q_positions.astype(jnp.int32).reshape(nB, bb, S).transpose(0, 2, 1)
        [..., None], (nB, S, bb, G)).reshape(nB, S, R, 1)
    rowb = jnp.repeat(jnp.arange(bb, dtype=jnp.int32), G).reshape(R, 1)
    laneb = jnp.tile(jnp.arange(bb, dtype=jnp.int32), split).reshape(1, L)
    if alibi:
        slopes = jnp.broadcast_to(
            jnp.asarray(alibi_slopes, jnp.float32).reshape(K, 1, G),
            (K, bb, G)).reshape(K, R, 1)
    else:
        slopes = jnp.zeros((K, R, 1), jnp.float32)

    # Every index map takes the prefetched layer index last; only K/V's
    # spends it.
    if nt:
        def kv_index(h, j, i, s, l):
            return (l[0], h, j, jnp.where(j < nt, 0, i), 0)
    else:
        def kv_index(h, j, i, s, l):
            return (l[0], h, j, i, 0)

    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               alibi=alibi)
    prefetch = (layer,)
    if window is not None:
        # The window's tables ride as three more prefetched scalar
        # arrays; every index map takes them after the layer.
        win = jnp.asarray(window, jnp.int32).reshape(1)
        live, fetch = _window_tables(kpos, qpos, win[0], nB, n_splits)
        keys = _window_keys(fetch, kpos_bt, q_positions, win[0], nB, nt, L)
        prefetch = (layer, win, live, fetch)
        kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                                   alibi=alibi, n_blocks=nB)
        name = "flash_decode_windowed"

        def kv_index(h, j, i, s, l, w, lv, f):
            at = f[j * nB + i]
            jf, i_f = at // nB, at % nB
            return (l[0], h, jf, jnp.where(jf < nt, 0, i_f), 0)

    def grid_map(fn):
        """An index map of the grid alone, whatever rides the scalar
        prefetch after it."""
        return lambda h, j, i, s, *_: fn(h, j, i, s)

    f32 = jnp.float32
    o_p, m_p, l_p = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(K, n_splits, nB, S),
            in_specs=[
                pl.BlockSpec((R, 1), grid_map(lambda h, j, i, s: (0, 0))),
                pl.BlockSpec((1, L), grid_map(lambda h, j, i, s: (0, 0))),
                pl.BlockSpec((1, 1, R, 1),
                             grid_map(lambda h, j, i, s: (i, s, 0, 0))),
                pl.BlockSpec((1, 1, 1, L),
                             grid_map(lambda h, j, i, s: (i, j, 0, 0))),
                pl.BlockSpec((1, R, 1),
                             grid_map(lambda h, j, i, s: (h, 0, 0))),
                pl.BlockSpec((1, 1, 1, R, hd),
                             grid_map(lambda h, j, i, s: (i, h, s, 0, 0))),
                pl.BlockSpec((1, 1, split, bb, hd), kv_index),
                pl.BlockSpec((1, 1, split, bb, hd), kv_index),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, 1, R, hd),
                             grid_map(lambda h, j, i, s: (i, h, s, j, 0, 0))),
                pl.BlockSpec((1, 1, 1, 1, R, 1),
                             grid_map(lambda h, j, i, s: (i, h, s, j, 0, 0))),
                pl.BlockSpec((1, 1, 1, 1, R, 1),
                             grid_map(lambda h, j, i, s: (i, h, s, j, 0, 0))),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((nB, K, S, n_splits, R, hd), f32),
            jax.ShapeDtypeStruct((nB, K, S, n_splits, R, 1), f32),
            jax.ShapeDtypeStruct((nB, K, S, n_splits, R, 1), f32),
        ],
        interpret=interpret,
        name=name,
    )(*prefetch, rowb, laneb, qpos, kpos, slopes, qg, k, v)

    # Log-sum-exp combine across splits (ops/lse.merge_partials, shared
    # with the cascade-prefill merge): renormalize each partial by the
    # global row max, then sum the weighted accumulators and weights. A
    # fully-masked split carries m = -inf and weight exactly 0.
    out = merge_partials(o_p, m_p[..., 0], l_p[..., 0], axis=3)
    out = out.reshape(nB, K, S, bb, G, hd).transpose(0, 3, 2, 1, 4, 5)
    out = out.reshape(B, S, H, hd).astype(q.dtype)
    return out if window is None else (out, keys)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    q_positions: jnp.ndarray,
    key_mask: jnp.ndarray,
    key_positions: jnp.ndarray | None = None,
    alibi_slopes: jnp.ndarray | None = None,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    layer: jnp.ndarray | None = None,
    window: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """One decode step of attention, fused. Returns (B, H, hd) in q's dtype.

    ``q``: (B, H, hd) single query per row (post-RoPE). ``k``/``v``:
    (K, T, B, hd) cache layout, K the kv-head count (un-repeated GQA/MQA);
    or, with ``layer`` (a scalar, traced or not), the stacked cache sides
    (L, K, T, B, hd) of which the kernel reads that layer alone, in place
    (the decode step's layer loop, models/decoder._block). All four entry
    points take the pair the same way. ``window`` (this entry point and
    :func:`flash_decode_trunk`): the layer's window, a traced scalar
    (:func:`_decode_call`); None, no window. With a window the entry
    point returns ``(out, keys)``, ``keys`` the call's key slots
    (:func:`_window_keys`).
    ``q_positions``: (B,) mask-aware query positions. ``key_mask``: (B, T)
    {0,1} validity over cache slots (any pattern). ``key_positions``:
    (B, T) mask-aware slot positions (decoder.mask_positions of the cache
    mask); defaults to the mask's own cumsum. ``alibi_slopes``: optional
    (H,) per-head slopes (bloom) added as ``slope * key_position``.

    Each grid program owns one key split of one batch block in VMEM and
    emits a partial (o, m, l); the final output is the log-sum-exp
    combination of the splits — exact attention, any split count.
    """
    out = _decode_call("flash_decode", q[:, None], k, v,
                       q_positions[:, None], key_mask, key_positions,
                       alibi_slopes, 0, block_k, interpret, layer, window)
    return out[:, 0] if window is None else (out[0][:, 0], out[1])


@functools.partial(jax.jit,
                   static_argnames=("trunk_len", "block_k", "interpret"))
def flash_decode_trunk(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    q_positions: jnp.ndarray,
    key_mask: jnp.ndarray,
    key_positions: jnp.ndarray | None = None,
    alibi_slopes: jnp.ndarray | None = None,
    trunk_len: int = 0,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    layer: jnp.ndarray | None = None,
    window: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Trunk-aware decode step for shared-prefix (cascade) dispatches.

    Arguments as :func:`flash_decode` plus static ``trunk_len``: the
    leading cache extent whose KV is bitwise-identical across the batch
    (the shared trunk a cascade/shared dispatch broadcast or prefilled
    into every row). Same kernel, same split ladder, same per-split
    arithmetic and merge as the flat entry point; the only difference is
    the K/V index map, which sends every batch block to block 0 for the
    splits that lie fully inside the trunk, so those tiles are fetched
    once per (kv head, split) instead of once per batch block. Per step
    and layer it saves ``2 * K * nt*split * hd * itemsize * (B - bb)``
    trunk bytes, nt the trunk split count and bb the batch block.
    """
    out = _decode_call("flash_decode_trunk", q[:, None], k, v,
                       q_positions[:, None], key_mask, key_positions,
                       alibi_slopes, trunk_len, block_k, interpret,
                       layer, window)
    return out[:, 0] if window is None else (out[0][:, 0], out[1])


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode_mq(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    q_positions: jnp.ndarray,
    key_mask: jnp.ndarray,
    key_positions: jnp.ndarray | None = None,
    alibi_slopes: jnp.ndarray | None = None,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    layer: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Multi-query fused decode attention: S queries per row over the KV
    cache in ONE kernel launch — the speculative-decode verify path
    (ROADMAP item 3: the k drafted positions verify in one dispatch).

    ``q``: (B, S, H, hd) post-RoPE queries — the teacher-forced draft
    window, already written into the cache at their slots. ``q_positions``:
    (B, S) per-query mask-aware positions: causality (``kp <= qp`` per
    query) is what keeps a query from seeing later drafts, exactly as
    ``decoder._causal_bias`` orders the dense path. Other arguments as
    :func:`flash_decode`. The window rides an extra innermost grid axis
    of the single-query kernel, so per-query results are the single-query
    kernel's for the same cache state (pinned by tests/test_spec_decode).
    """
    return _decode_call("flash_decode_mq", q, k, v, q_positions, key_mask,
                        key_positions, alibi_slopes, 0, block_k, interpret,
                        layer)


@functools.partial(jax.jit,
                   static_argnames=("trunk_len", "block_k", "interpret"))
def flash_decode_mq_trunk(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    q_positions: jnp.ndarray,
    key_mask: jnp.ndarray,
    key_positions: jnp.ndarray | None = None,
    alibi_slopes: jnp.ndarray | None = None,
    trunk_len: int = 0,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    layer: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Trunk-aware multi-query decode: :func:`flash_decode_mq` with the
    :func:`flash_decode_trunk` index map, so speculative verify windows
    in a shared-trunk dispatch fetch the trunk KV once per (kv head,
    split) per verify pass."""
    return _decode_call("flash_decode_mq_trunk", q, k, v, q_positions,
                        key_mask, key_positions, alibi_slopes, trunk_len,
                        block_k, interpret, layer)
