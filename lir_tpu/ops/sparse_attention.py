"""Block-sparse attention with a selection step (InfLLM-v2, as MiniCPM4 and
MiniCPM-SALA run it in their softmax layers).

A query at position ``p`` with at most ``dense_len`` tokens of context
(``p + 1 <= dense_len``) attends every key before it. Past that it keeps,
of the 64-token blocks of its context, the first ``init_blocks``, every
block that reaches into its last ``window`` positions, and the ``topk``
best of the others (all of them where fewer exist); one softmax runs over
the tokens of the blocks kept. The blocks are scored from POOLED keys: the
mean of k over each ``kernel`` tokens at ``stride``, per kv head; a query
scores the kernels that lie wholly before it with ``softmax(q . pooled /
sqrt(hd))``, the scores of the query heads of one kv group are summed (the
group shares one choice), and a block takes the best score of the kernels
that overlap it.

What the callers hand over (models/mixed.py) is a context in two parts:

- the MAIN keys, whose slot IS their position and whose real tokens are
  the first ``main_len`` slots: a dispatch's shared trunk (one row, read
  by every row's queries) or a row's own prefix. The selection runs over
  these; their pooled keys are computed once, when they are;
- the TAIL keys, a row's own few slots behind the main ones (the window
  behind a trunk, a format suffix, decoded tokens), masked and positioned
  by the cache's own mask. They all lie inside the local window of every
  query that can see them (the callers hold the tail under ``window``
  tokens), so they are always kept.

The main leg is :func:`attend_main`: a flash loop over the main keys in
tiles, the block choice applied as a mask. It computes the scores of a
tile's masked blocks too (a tile is skipped only where it lies wholly
ahead of the queries); gathering the kept blocks alone is the next step,
and ``sparse_*_roofline`` (benchmarks/harness/sala.py), which counts the
kept keys only, says how far it is. On the TPU it is a ``pallas_call``
pinned by ``name=`` (``sparse_prefill`` for windows of queries,
``sparse_decode`` for single ones); elsewhere, and as what the kernel is
held to in tests, the same mathematics in XLA. The tail leg is plain XLA
over the few tail slots; ops/lse.merge_partials joins the two.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lse import merge_partials

MAX_TILE_ROWS = 1024          # (query token, group head) rows a program takes
MAX_KEY_TILE = 1024           # main keys a program step takes
SELECT_CHUNK = 1024           # queries the selection scores at a time

_NT = (((1,), (1,)), ((), ()))        # a @ b.T


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------

def n_kernels(length: int, kernel: int, stride: int) -> int:
    """Pooling kernels that fit ``length`` tokens."""
    return max((length - kernel) // stride + 1, 0)


def pool_keys(k: jax.Array, kernel: int, stride: int) -> jax.Array:
    """Mean of ``k`` (..., T, hd) over each ``kernel`` tokens at ``stride``:
    (..., n_kernels, hd) float32. ``kernel`` is a multiple of ``stride``."""
    assert kernel % stride == 0, (kernel, stride)
    T, hd = k.shape[-2:]
    nk, r = n_kernels(T, kernel, stride), kernel // stride
    if nk == 0:
        return jnp.zeros(k.shape[:-2] + (0, hd), jnp.float32)
    strides = (k[..., :(nk + r - 1) * stride, :].astype(jnp.float32)
               .reshape(k.shape[:-2] + (nk + r - 1, stride, hd)).sum(-2))
    return sum(strides[..., i:i + nk, :] for i in range(r)) / kernel


def _block_scores(probs: jax.Array, n_blocks: int, block: int, kernel: int,
                  stride: int) -> jax.Array:
    """Kernel scores (..., NK) -> block scores (..., NB): a block takes the
    best of the kernels that overlap it (kernel ``i`` covers positions
    [stride i, stride i + kernel))."""
    rho, lead = block // stride, kernel // stride - 1
    width = rho + lead
    nk = probs.shape[-1]
    right = max((n_blocks - 1) * rho + width - lead - nk, 0)
    padded = jnp.pad(probs, [(0, 0)] * (probs.ndim - 1) + [(lead, right)])
    padded = padded[..., :(n_blocks - 1) * rho + width]
    return lax.reduce_window(
        padded, -jnp.inf, lax.max, (1,) * (probs.ndim - 1) + (width,),
        (1,) * (probs.ndim - 1) + (rho,), "VALID")


def block_roles(qpos, main_len, n_blocks: int, *, block: int,
                init_blocks: int, window: int, dense_len: int):
    """What positions alone decide, for queries at ``qpos`` (..., N) over
    main keys of which the first ``main_len`` (..., 1) are real: per block
    (..., N, NB) whether it holds a key the query may see (``valid``),
    whether the query keeps it whatever its score (``fixed``: every valid
    block of a query with at most ``dense_len`` tokens of context, else
    the first blocks and those reaching into the last ``window``
    positions), and per query the last main key it may see (``bound``).
    numpy or jax arrays alike."""
    xp = jnp if isinstance(qpos, jax.Array) else np
    b = xp.arange(n_blocks, dtype=xp.int32)
    bound = xp.minimum(qpos, main_len - 1)                    # (..., N)
    valid = b * block <= bound[..., None]
    dense = (qpos + 1 <= dense_len)[..., None]
    local = (b + 1) * block >= (qpos - window + 2)[..., None]
    fixed = valid & (dense | (b < init_blocks) | local)
    return valid, fixed, bound


def select_blocks(q, pooled, qpos, main_len, *, n_blocks: int, block: int,
                  kernel: int, stride: int, topk: int, init_blocks: int,
                  window: int, dense_len: int, all_dense: bool = False,
                  recent=None):
    """The blocks each query keeps. q: (Bm, K, G, N, hd), the queries of a
    kv group side by side; pooled: (Bm, K, NK, hd); qpos: (Bm, N) absolute
    positions; main_len: (Bm,). ``all_dense``: the caller knows from
    shapes alone that no query lies past ``dense_len`` (nothing is scored
    then). ``recent`` (Bm, K, G, N, NKr): each query's logits over the
    kernels that hold tail keys (:func:`recent_kernel_logits`; -inf where
    it may not see one); they share the softmax and score no block.
    Returns (keep (Bm, K, N, NB) bool, bound (Bm, N) int32: the
    last main key a query may see, -1 for none)."""
    Bm, K, G, N, hd = q.shape
    nk = pooled.shape[2]
    valid, fixed, bound = block_roles(
        qpos, main_len[:, None], n_blocks, block=block,
        init_blocks=init_blocks, window=window, dense_len=dense_len)
    if nk == 0 or all_dense:
        return jnp.broadcast_to(fixed[:, None], (Bm, K, N, n_blocks)), bound
    ends = jnp.arange(nk, dtype=jnp.int32) * stride + kernel - 1
    # Wholly before the query, and wholly real.
    last = jnp.minimum(qpos - 1, main_len[:, None] - 1)       # (Bm, N)

    def chunk(args):
        qc, lastc, validc, fixedc, extra = args
        # (Bm,K,G,n,hd) (Bm,n) (Bm,n,NB) x2 (Bm,K,G,n,NKr)
        s = jnp.einsum("bkgnd,bkjd->bkgnj", qc.astype(jnp.float32),
                       pooled) / math.sqrt(hd)
        seen = (ends <= lastc[..., None])[:, None, None]      # (Bm,1,1,n,NK)
        s = jnp.where(seen, s, -jnp.inf)
        m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True),
                        jnp.max(extra, axis=-1, keepdims=True, initial=-jnp.inf))
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.where(seen, jnp.exp(s - m), 0.0)
        z = (p.sum(-1, keepdims=True)
             + jnp.exp(extra - m).sum(-1, keepdims=True))
        p = p / jnp.maximum(z, 1e-30)
        score = _block_scores(p.sum(axis=2), n_blocks, block, kernel, stride)
        others = (validc & ~fixedc)[:, None]                  # (Bm,1,n,NB)
        score = jnp.where(others, score, -1.0)
        # Exactly ``topk`` of the others: neighbouring blocks share the
        # kernel that straddles them, so equal scores are common, and
        # lax.top_k breaks a tie toward the lower index.
        best, where = lax.top_k(score, min(topk, n_blocks))
        where = jnp.where(best >= 0.0, where, n_blocks)
        top = (where[..., None] == jnp.arange(n_blocks, dtype=where.dtype)
               ).any(axis=-2)
        return fixedc[:, None] | top

    if recent is None:
        recent = jnp.zeros((Bm, K, G, N, 0), jnp.float32)
    step = min(SELECT_CHUNK, N)
    pad = -N % step
    if pad:
        q, recent = (jnp.pad(a, ((0, 0),) * 3 + ((0, pad), (0, 0)))
                     for a in (q, recent))
        last, valid, fixed = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                      (a.ndim - 2))
                              for a in (last, valid, fixed))
    n_chunks = (N + pad) // step

    def split(a, axis):
        a = a.reshape(a.shape[:axis] + (n_chunks, step) + a.shape[axis + 1:])
        return jnp.moveaxis(a, axis, 0)

    keep = lax.map(chunk, (split(q, 3), split(last, 1), split(valid, 1),
                           split(fixed, 1), split(recent, 3)))
    # (chunks, Bm, K, step, NB)
    keep = jnp.moveaxis(keep, 0, 2).reshape(Bm, K, N + pad, n_blocks)
    return keep[:, :, :N], bound


def recent_kernel_logits(q, main_k, tail_k, main_len, tail_mask, qpos, *,
                         kernel: int, stride: int):
    """Logits of each query over the pooling kernels that hold tail keys:
    the softmax over "the kernels wholly before the query" runs over these
    too, though they score no main block. q: (B, K, G, S, hd); main_k:
    (Bm, K, Tm, hd) with Bm 1 (shared) or B; tail_k: (B, K, Tt, hd);
    main_len: (Bm,) real main keys; tail_mask: (B, Tt); qpos: (B, S). A
    row's real tail keys sit, in slot order, at the positions behind its
    main ones. Returns (B, K, G, S, NKr) float32, -inf where the query may
    not see the kernel."""
    B, K, _, _, hd = q.shape
    Tm, Tt = main_k.shape[2], tail_k.shape[2]
    i32 = jnp.int32
    ml = jnp.broadcast_to(main_len.astype(i32), (B,))
    start = jnp.maximum((ml - kernel) // stride + 1, 0) * stride     # (B,)
    span = kernel + -(-Tt // stride) * stride
    pos = start[:, None] + jnp.arange(span, dtype=i32)               # (B, R)
    # Only the first ``kernel`` of these positions can be main keys.
    head = jnp.clip(pos[:, :kernel], 0, max(Tm - 1, 0))
    if main_k.shape[0] == 1:
        from_main = jnp.take(main_k[0], head, axis=1).transpose(1, 0, 2, 3)
    else:
        from_main = jnp.take_along_axis(main_k, head[:, None, :, None],
                                        axis=2)
    order = jnp.argsort(1 - (tail_mask > 0).astype(i32), axis=-1,
                        stable=True)
    slot = jnp.take_along_axis(
        order, jnp.clip(pos - ml[:, None], 0, Tt - 1), axis=1)
    from_tail = jnp.take_along_axis(tail_k, slot[:, None, :, None], axis=2)
    in_main = (pos[:, :kernel] < ml[:, None])[:, None, :, None]
    keys = jnp.concatenate(
        [jnp.where(in_main, from_main, from_tail[:, :, :kernel]),
         from_tail[:, :, kernel:]], axis=2)
    pooled = pool_keys(keys, kernel, stride)                  # (B,K,NKr,hd)
    ends = (start[:, None] + jnp.arange(pooled.shape[2], dtype=i32) * stride
            + kernel - 1)                                     # (B, NKr)
    total = ml + jnp.sum(tail_mask > 0, axis=-1).astype(i32)
    seen = ((ends[:, None, :] < qpos[:, :, None])
            & (ends < total[:, None])[:, None, :])            # (B, S, NKr)
    logits = jnp.einsum("bkgsd,bkjd->bkgsj", q.astype(jnp.float32),
                        pooled) / math.sqrt(hd)
    return jnp.where(seen[:, None, None], logits, -jnp.inf)


def kept_blocks(positions, main_len: int, *, block: int, topk: int,
                init_blocks: int, window: int, dense_len: int) -> tuple:
    """(blocks kept, blocks offered, queries at or under ``dense_len``)
    summed over queries at ``positions`` (numpy ints) over ``main_len``
    main keys: what positions alone decide, since a query keeps exactly
    ``topk`` of the others where that many exist. Host counters
    (engine/runner) and the benchmark's kept-key sizes read it."""
    pos = np.asarray(positions, np.int64).reshape(-1)
    if pos.size == 0 or main_len <= 0:
        return 0, 0, int(pos.size)
    nb = -(-int(main_len) // block)
    valid, fixed, _ = block_roles(pos, np.int64(main_len), nb, block=block,
                                  init_blocks=init_blocks, window=window,
                                  dense_len=dense_len)
    others = (valid & ~fixed).sum(-1)
    kept = fixed.sum(-1) + np.minimum(others, topk)
    return int(kept.sum()), int(valid.sum()), int((pos + 1 <= dense_len).sum())


# ---------------------------------------------------------------------------
# Main leg
# ---------------------------------------------------------------------------

def key_tile(length: int, block: int) -> tuple:
    """(keys a program step takes, padded length): the length rounded up
    to whole lanes, in the largest tile of whole lanes and whole blocks
    that divides it."""
    unit = 128 * block // math.gcd(128, block)
    padded = -(-length // unit) * unit
    tile = max(t for t in range(unit, MAX_KEY_TILE + unit, unit)
               if padded % t == 0 and (t <= MAX_KEY_TILE or t == unit))
    return tile, padded


def query_tile(n: int, groups: int) -> tuple:
    """(query tokens a program takes, padded count): whole sublane groups
    of a bfloat16 tile, at most MAX_TILE_ROWS (token, head) rows."""
    cap = max(MAX_TILE_ROWS // groups // 16 * 16, 16)
    bn = min(cap, -(-n // 16) * 16, 256)
    return bn, -(-n // bn) * bn


def attend_main_xla(q, k, v, keep, bound, *, block: int):
    """The main leg in XLA. q: (Bm, K, G, N, hd); k, v: (Bm, K, T, hd);
    keep: (Bm, K, N, NB); bound: (Bm, N). Returns partials (o (Bm, K, G,
    N, hd) unnormalised float32, m, l (Bm, K, G, N))."""
    T, hd = k.shape[-2:]
    s = jnp.einsum("bkgnd,bktd->bkgnt", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    ok = (jnp.repeat(keep, block, axis=-1)[..., :T]
          & (jnp.arange(T, dtype=jnp.int32) <= bound[:, None, :, None]))
    s = jnp.where(ok[:, :, None], s, -jnp.inf)
    m = s.max(axis=-1)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)[..., None])
    o = jnp.einsum("bkgnt,bktd->bkgnd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, p.sum(axis=-1)


def _main_kernel(layer_ref, tmax_ref, q_ref, k_ref, v_ref, keep_ref, qb_ref,
                 o_ref, m_ref, l_ref, acc, m_s, l_s, *, G: int, bn: int,
                 tile: int, block: int, sm_scale: float):
    """One (main row, kv head, query tile, key tile) program; the key axis
    is the innermost, sequential one. The query tile is ``bn`` tokens by
    the ``G`` heads of the kv group, head-major, so one K/V tile serves
    G * bn rows. ``layer_ref`` (prefetched) is spent by the K/V index
    maps, ``tmax_ref`` holds each query tile's last visible key."""
    del layer_ref
    f32 = jnp.float32
    b, n, t = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    hd = q_ref.shape[-1]

    @pl.when(t == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when(t * tile <= tmax_ref[b, n])
    def _():
        q = q_ref[0, 0].reshape(G * bn, hd)
        k, v = k_ref[0, 0, 0], v_ref[0, 0, 0]                 # (tile, hd)
        s = lax.dot_general(q, k, _NT, preferred_element_type=f32) * sm_scale
        # The tile's block choice (bn, blocks) spread over its keys by a
        # 0/1 matmul: Mosaic has no repeat along lanes.
        per = tile // block
        spread = (lax.broadcasted_iota(jnp.int32, (per, tile), 1) // block
                  == lax.broadcasted_iota(jnp.int32, (per, tile), 0))
        kept = jnp.dot(keep_ref[0, 0, 0], spread.astype(f32),
                       preferred_element_type=f32)            # (bn, tile)
        kpos = t * tile + lax.broadcasted_iota(jnp.int32, (bn, tile), 1)
        ok = (kept > 0.5) & (kpos <= qb_ref[0])
        s = jnp.where(ok[None], s.reshape(G, bn, tile), -jnp.inf
                      ).reshape(G * bn, tile)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(m_prev - m_safe)
        l_s[...] = alpha * l_s[...] + p.sum(axis=-1, keepdims=True)
        acc[...] = alpha * acc[...] + jnp.dot(p.astype(v.dtype), v,
                                              preferred_element_type=f32)
        m_s[...] = m_new

    @pl.when(t == pl.num_programs(3) - 1)
    def _():
        o_ref[0, 0] = acc[...].reshape(G, bn, hd)
        m_ref[0, 0] = m_s[...].reshape(G, bn, 1)
        l_ref[0, 0] = l_s[...].reshape(G, bn, 1)


def attend_main(q, k, v, keep, bound, *, block: int, layer=None,
                interpret: bool = False, name: str = "sparse_prefill"):
    """The main leg as a kernel; arguments and results as
    :func:`attend_main_xla`, but ``k`` / ``v`` may be the cache's stacked
    (L, Bm, K, T, hd) leaves with ``layer`` (a traced scalar, prefetched)
    the layer to read: the kernel picks that layer's tiles out of the
    stacked operand."""
    if layer is None:
        k, v, layer = k[None], v[None], 0
    Bm, K, G, N, hd = q.shape
    T = k.shape[3]
    tile, Tp = key_tile(T, block)
    if Tp != T:
        # Lengths off the lane grid (tests): the layer's keys, padded.
        pad = ((0, 0),) * 3 + ((0, Tp - T), (0, 0))
        k, v = (jnp.pad(lax.dynamic_index_in_dim(a, layer, keepdims=True),
                        pad) for a in (k, v))
        layer = 0
    per, n_tiles = tile // block, Tp // tile
    bn, Np = query_tile(N, G)
    f32 = jnp.float32
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, Np - N), (0, 0)))
    bound = jnp.pad(bound.astype(jnp.int32), ((0, 0), (0, Np - N)),
                    constant_values=-1)
    keep = jnp.pad(keep.astype(f32), ((0, 0), (0, 0), (0, Np - N),
                                      (0, n_tiles * per - keep.shape[-1])))
    keep = keep.reshape(Bm, K, Np, n_tiles, per).transpose(0, 1, 3, 2, 4)
    tmax = bound.reshape(Bm, Np // bn, bn).max(axis=-1)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def kv_map(b, h, n, t, l, tmax):
        # A tile wholly ahead of the queries is not fetched again.
        return (l[0], b, h, jnp.minimum(t, jnp.maximum(tmax[b, n], 0)
                                        // tile), 0)

    kernel = functools.partial(_main_kernel, G=G, bn=bn, tile=tile,
                               block=block, sm_scale=1.0 / math.sqrt(hd))
    q_spec = pl.BlockSpec((1, 1, G, bn, hd), lambda b, h, n, t, l, m:
                          (b, h, 0, n, 0))
    col = pl.BlockSpec((1, 1, G, bn, 1), lambda b, h, n, t, l, m:
                       (b, h, 0, n, 0))
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Bm, K, Np // bn, n_tiles),
            in_specs=[
                q_spec,
                pl.BlockSpec((1, 1, 1, tile, hd), kv_map),
                pl.BlockSpec((1, 1, 1, tile, hd), kv_map),
                pl.BlockSpec((1, 1, 1, bn, per), lambda b, h, n, t, l, m:
                             (b, h, t, n, 0)),
                pl.BlockSpec((1, bn, 1), lambda b, h, n, t, l, m: (b, n, 0)),
            ],
            out_specs=[q_spec, col, col],
            scratch_shapes=[pltpu.VMEM((G * bn, hd), f32),
                            pltpu.VMEM((G * bn, 1), f32),
                            pltpu.VMEM((G * bn, 1), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((Bm, K, G, Np, hd), f32),
                   jax.ShapeDtypeStruct((Bm, K, G, Np, 1), f32),
                   jax.ShapeDtypeStruct((Bm, K, G, Np, 1), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name=name,
    )(layer, tmax, q, k, v, keep, bound[..., None])
    return o[:, :, :, :N], m[:, :, :, :N, 0], l[:, :, :, :N, 0]


# ---------------------------------------------------------------------------
# Tail leg and the whole
# ---------------------------------------------------------------------------

TAIL_ROWS = 8                 # rows whose tail scores are live at a time


def attend_tail(q, k, v, mask, kpos, qpos):
    """A row's queries over its own tail slots, in XLA. q: (B, K, G, S,
    hd); k, v: (B, K, Tt, hd); mask, kpos: (B, Tt) the slots' validity and
    positions; qpos: (B, S). Returns partials shaped like the main leg's
    (o (B, K, G, S, hd), m, l (B, K, G, S))."""
    hd = q.shape[-1]

    def rows(args):
        q, k, v, mask, kpos, qpos = args
        s = jnp.einsum("bkgsd,bktd->bkgst", q, k,
                       preferred_element_type=jnp.float32) / math.sqrt(hd)
        ok = (mask[:, None, :] > 0) & (kpos[:, None, :] <= qpos[:, :, None])
        s = jnp.where(ok[:, None, None], s, -jnp.inf)
        m = s.max(axis=-1)
        p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)[..., None])
        o = jnp.einsum("bkgst,bktd->bkgsd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o, m, p.sum(axis=-1)

    B, S = qpos.shape
    args = (q, k, v, mask, kpos, qpos)
    if S == 1 or B <= TAIL_ROWS or B % TAIL_ROWS:
        return rows(args)
    split = lambda a: a.reshape((B // TAIL_ROWS, TAIL_ROWS) + a.shape[1:])  # noqa: E731
    out = lax.map(rows, tuple(split(a) for a in args))
    return tuple(a.reshape((B,) + a.shape[2:]) for a in out)


def merge(main, tail):
    """Softmax over main and tail keys from the two legs' partials."""
    (o1, m1, l1), (o2, m2, l2) = main, tail
    return merge_partials(jnp.stack([o1, o2]), jnp.stack([m1, m2]),
                          jnp.stack([l1, l2]), axis=0)
