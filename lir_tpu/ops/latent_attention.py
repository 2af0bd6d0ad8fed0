"""Latent attention kernels (MLA: DeepSeek-V3, Kimi-K2; models/mla.py).

A latent-attention layer keeps, a token, one latent ``c`` (width
``kv_lora_rank``) and one rotated key part ``k_pe`` that every head
shares; head ``h``'s key is ``[c W_k,h | k_pe]`` and its value ``c
W_v,h``. Two forms attend over it:

- EXPANDED (:func:`prefill`, the kernel ``latent_prefill``): the pass
  turns the latent of the keys it reads into per-head keys (``qk_nope +
  qk_rope`` wide) and values (``v_head_dim``) and attends with every
  head's own query, one running softmax over two sets of keys: the MAIN
  part (the rows' prefixes, or ONE shared trunk every row reads) and the
  rows' OWN keys (their window, format suffix), each a query reading only
  its own row's. The main keys lie at slot == position (right-padded, the
  shared paths' canonical layout); own keys carry their positions and
  rows. Queries of all rows ride together over a shared main part, so a
  key tile serves a whole tile of queries whatever their rows.
- ABSORBED (:func:`decode`, the kernel ``latent_decode``): the query
  folds ``W_k,h`` in (``q_lat = q_nope W_k,h^T``), so every head scores
  the latent itself: MQA with ``heads`` queries over one key ``[c |
  k_pe]`` whose first ``kv_lora_rank`` columns are the value. The kernel
  reads the main part of the stacked latent cache where it lies (the
  layer prefetched), a shared trunk once for each block of
  :data:`DECODE_ROWS` rows, and hands back partials; the rows' own slots
  are a small XLA leg (:func:`decode_own`) merged after
  (ops/lse.merge_partials).

Each has an XLA twin of the same arithmetic (:func:`prefill_xla`,
:func:`decode_main_xla`): the CPU's path and the tests' yardstick.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lse import merge_partials

QUERY_TILE = 512         # query rows a prefill program takes
KEY_TILE = 512           # keys a prefill program step takes
MAX_MAIN_TILE = 1024     # latent slots a decode program step takes, at most
DECODE_ROWS = 8          # batch rows a decode program takes over a shared trunk
NO_KEY = 1 << 30         # the position of an own slot no query attends
_NT = (((1,), (1,)), ((), ()))        # a @ b.T
_VMEM = 64 * 1024 * 1024


def _up(n: int, unit: int) -> int:
    return -(-int(n) // unit) * unit


def query_tile(n: int) -> int:
    """Query rows a prefill program takes: QUERY_TILE, or ``n`` on the
    sublane grid of a bfloat16 tile where fewer."""
    return min(QUERY_TILE, _up(max(n, 1), 16))


def main_tile(length: int) -> tuple:
    """(slots a decode step takes, padded length) over a main part of
    ``length`` slots: the largest multiple of 16 up to MAX_MAIN_TILE that
    divides it, so the stacked cache is read where it lies; a length with
    no such divisor of at least 128 is padded to the 128 grid."""
    best = max((t for t in range(16, MAX_MAIN_TILE + 1, 16)
                if length % t == 0), default=0)
    if best >= 128:
        return best, length
    padded = _up(length, 128)
    return max(t for t in range(128, MAX_MAIN_TILE + 1, 128)
               if padded % t == 0), padded


# ---------------------------------------------------------------------------
# Expanded form
# ---------------------------------------------------------------------------

def prefill_xla(q, main_k, main_v, main_len, q_pos, own=None, *,
                sm_scale: float):
    """The expanded form in XLA. q: (Bm, H, N, dq) queries at positions
    q_pos (Bm, N) (-1: a padding query, attends nothing); main_k/v:
    (Bm, H, Tm, dq/dv), slot == position, the first ``main_len`` (Bm,)
    slots real; ``own``: None or (own_k (Bm, H, No, dq), own_v, own_pos
    (Bm, No), own_row (Bm, No), q_row (Bm, N)). A query attends the main
    slots at or before its position and the own keys of its row at or
    before it. Returns (Bm, H, N, dv) in q's dtype."""
    f32 = jnp.float32
    Tm = main_k.shape[2]
    slot = jnp.arange(Tm, dtype=jnp.int32)
    ok = ((slot[None, None, :] < main_len[:, None, None])
          & (slot[None, None, :] <= q_pos[:, :, None]))
    s = jnp.einsum("bhnd,bhtd->bhnt", q, main_k,
                   preferred_element_type=f32) * sm_scale
    s = jnp.where(ok[:, None], s, -jnp.inf)
    v = main_v
    if own is not None:
        own_k, own_v, own_pos, own_row, q_row = own
        ok2 = ((own_pos[:, None, :] <= q_pos[:, :, None])
               & (own_row[:, None, :] == q_row[:, :, None]))
        s2 = jnp.einsum("bhnd,bhtd->bhnt", q, own_k,
                        preferred_element_type=f32) * sm_scale
        s = jnp.concatenate([s, jnp.where(ok2[:, None], s2, -jnp.inf)], -1)
        v = jnp.concatenate([main_v, own_v], axis=2)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    o = jnp.einsum("bhnt,bhtd->bhnd", p.astype(v.dtype), v,
                   preferred_element_type=f32)
    return (o / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)).astype(q.dtype)


def _online(q, k, v, ok, acc, m_s, l_s, sm_scale):
    """One key tile into the running softmax of a query tile."""
    f32 = jnp.float32
    s = lax.dot_general(q, k, _NT, preferred_element_type=f32) * sm_scale
    s = jnp.where(ok, s, -jnp.inf)
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    alpha = jnp.exp(m_prev - m_safe)
    l_s[...] = alpha * l_s[...] + p.sum(axis=-1, keepdims=True)
    acc[...] = alpha * acc[...] + jnp.dot(p.astype(v.dtype), v,
                                          preferred_element_type=f32)
    m_s[...] = m_new


def _prefill_kernel(mlen_ref, mcount_ref, ofirst_ref, ocount_ref, *refs,
                    nm: int, has_own: bool, tile: int, sm_scale: float):
    """One (main row, head, query tile, key tile) program; the key axis is
    the innermost, sequential one: the first ``nm`` steps the main part's
    tiles (those past the tile's last query skipped, not fetched again),
    then the own keys' tiles the tile's rows reach."""
    if has_own:
        (q_ref, mk_ref, mv_ref, qpos_ref, ok_ref, ov_ref, opos_ref,
         orow_ref, qrow_ref, o_ref, acc, m_s, l_s) = refs
    else:
        q_ref, mk_ref, mv_ref, qpos_ref, o_ref, acc, m_s, l_s = refs
    b, n, t = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    bq = q_ref.shape[2]

    @pl.when(t == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when((t < nm) & (t < mcount_ref[b, n]))
    def _():
        slot = t * tile + lax.broadcasted_iota(jnp.int32, (bq, tile), 1)
        ok = (slot < mlen_ref[b]) & (slot <= qpos_ref[0])
        _online(q_ref[0, 0], mk_ref[0, 0], mv_ref[0, 0], ok, acc, m_s, l_s,
                sm_scale)

    if has_own:
        @pl.when((t >= nm) & (t - nm < ocount_ref[b, n]))
        def _():
            ok = ((opos_ref[0] <= qpos_ref[0])
                  & (orow_ref[0] == qrow_ref[0]))
            _online(q_ref[0, 0], ok_ref[0, 0], ov_ref[0, 0], ok, acc, m_s,
                    l_s, sm_scale)

    @pl.when(t == pl.num_programs(3) - 1)
    def _():
        o_ref[0, 0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)
                       ).astype(o_ref.dtype)


def _tiles_reached(pos, row, q_pos, q_row, bq: int, tile: int):
    """(first tile, tiles) of the own keys each query tile reaches: keys
    of the tile's rows at or before its last query. pos/row (Bm, No);
    q_pos/q_row (Bm, Np)."""
    Bm, Np = q_pos.shape
    qp = q_pos.reshape(Bm, Np // bq, bq)
    qr = q_row.reshape(Bm, Np // bq, bq)
    real = qp >= 0
    rmin = jnp.min(jnp.where(real, qr, NO_KEY), -1)
    rmax = jnp.max(jnp.where(real, qr, -1), -1)
    qmax = jnp.max(qp, -1)
    hit = ((row[:, None, :] >= rmin[..., None])
           & (row[:, None, :] <= rmax[..., None])
           & (pos[:, None, :] <= qmax[..., None]))        # (Bm, nq, No)
    idx = jnp.arange(pos.shape[1], dtype=jnp.int32)
    first = jnp.min(jnp.where(hit, idx, NO_KEY), -1)
    last = jnp.max(jnp.where(hit, idx, -1), -1)
    count = jnp.where(last >= 0, last // tile - first // tile + 1, 0)
    return jnp.where(last >= 0, first // tile, 0).astype(jnp.int32), \
        count.astype(jnp.int32)


def prefill(q, main_k, main_v, main_len, q_pos, own=None, *,
            sm_scale: float, own_span: int = 0, interpret: bool = False,
            name: str = "latent_prefill"):
    """The expanded form as a kernel; arguments and result as
    :func:`prefill_xla`. ``own_span`` (static): the most own keys one
    query tile's rows can hold between them (0: all of them), which
    bounds the grid's own steps."""
    f32 = jnp.float32
    Bm, H, N, dq = q.shape
    dv = main_v.shape[-1]
    Tm = main_k.shape[2]
    bq, tile = query_tile(N), KEY_TILE
    Np, Tp = _up(N, bq), _up(Tm, tile)
    nq, nm = Np // bq, Tp // tile
    q = jnp.pad(q, ((0, 0), (0, 0), (0, Np - N), (0, 0)))
    q_pos = jnp.pad(q_pos.astype(jnp.int32), ((0, 0), (0, Np - N)),
                    constant_values=-1)
    main_k, main_v = (jnp.pad(a, ((0, 0), (0, 0), (0, Tp - Tm), (0, 0)))
                      for a in (main_k, main_v))
    main_len = jnp.minimum(main_len.astype(jnp.int32), Tm)
    qmax = q_pos.reshape(Bm, nq, bq).max(-1)
    last = jnp.minimum(qmax, main_len[:, None] - 1)
    mcount = jnp.where(last >= 0, last // tile + 1, 0).astype(jnp.int32)
    zeros = jnp.zeros((Bm, nq), jnp.int32)
    operands, no = [], 0
    if own is not None:
        own_k, own_v, own_pos, own_row, q_row = own
        No = own_k.shape[2]
        Op = _up(No, tile)
        pad = ((0, 0), (0, 0), (0, Op - No), (0, 0))
        own_k, own_v = jnp.pad(own_k, pad), jnp.pad(own_v, pad)
        own_pos = jnp.pad(own_pos.astype(jnp.int32), ((0, 0), (0, Op - No)),
                          constant_values=NO_KEY)
        own_row = jnp.pad(own_row.astype(jnp.int32), ((0, 0), (0, Op - No)),
                          constant_values=-1)
        q_row = jnp.pad(q_row.astype(jnp.int32), ((0, 0), (0, Np - N)),
                        constant_values=-1)
        ofirst, ocount = _tiles_reached(own_pos, own_row, q_pos, q_row, bq,
                                        tile)
        no = Op // tile
        if own_span:
            no = min(no, -(-own_span // tile) + 1)
        operands = [own_k, own_v, own_pos[:, None, :], own_row[:, None, :],
                    q_row[..., None]]
    else:
        ofirst = ocount = zeros

    def main_map(b, h, n, t, ml, mc, of, oc):
        return (b, h, jnp.minimum(t, jnp.maximum(mc[b, n] - 1, 0)), 0)

    def own_tile(b, n, t, of, oc):
        return of[b, n] + jnp.clip(t - nm, 0, jnp.maximum(oc[b, n] - 1, 0))

    def own_map(b, h, n, t, ml, mc, of, oc):
        return (b, h, own_tile(b, n, t, of, oc), 0)

    def own_row_map(b, h, n, t, ml, mc, of, oc):
        return (b, 0, own_tile(b, n, t, of, oc))

    def q_map(b, h, n, t, *_):
        return (b, h, n, 0)

    def col_map(b, h, n, t, *_):
        return (b, n, 0)

    in_specs = [pl.BlockSpec((1, 1, bq, dq), q_map),
                pl.BlockSpec((1, 1, tile, dq), main_map),
                pl.BlockSpec((1, 1, tile, dv), main_map),
                pl.BlockSpec((1, bq, 1), col_map)]
    if own is not None:
        in_specs += [pl.BlockSpec((1, 1, tile, dq), own_map),
                     pl.BlockSpec((1, 1, tile, dv), own_map),
                     pl.BlockSpec((1, 1, tile), own_row_map),
                     pl.BlockSpec((1, 1, tile), own_row_map),
                     pl.BlockSpec((1, bq, 1), col_map)]
    kernel = functools.partial(_prefill_kernel, nm=nm,
                               has_own=own is not None, tile=tile,
                               sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(Bm, H, nq, nm + no),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, bq, dv), q_map),
            scratch_shapes=[pltpu.VMEM((bq, dv), f32),
                            pltpu.VMEM((bq, 1), f32),
                            pltpu.VMEM((bq, 1), f32)]),
        out_shape=jax.ShapeDtypeStruct((Bm, H, Np, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name=name,
    )(main_len, mcount, ofirst, ocount, q, main_k, main_v, q_pos[..., None],
      *operands)
    return out[:, :, :N]


# ---------------------------------------------------------------------------
# Absorbed form
# ---------------------------------------------------------------------------

def decode_main_xla(q, latent, main_len, *, value_width: int,
                    sm_scale: float):
    """The absorbed form's main leg in XLA. q: (B, H, dl) absorbed
    queries (``[q_lat | q_pe]``); latent: (Bm, Tm, dl) with Bm 1 (a
    trunk every row reads) or B, the first ``main_len`` (Bm,) slots real,
    all of them before every query. Returns partials (o (B, H, dv)
    float32 unnormalised, m, l (B, H)), dv = ``value_width``, the
    latent's first columns."""
    f32 = jnp.float32
    B = q.shape[0]
    latent = jnp.broadcast_to(latent, (B,) + latent.shape[1:])
    main_len = jnp.broadcast_to(main_len, (B,))
    s = jnp.einsum("bhd,btd->bht", q, latent,
                   preferred_element_type=f32) * sm_scale
    ok = jnp.arange(latent.shape[1])[None, :] < main_len[:, None]
    s = jnp.where(ok[:, None], s, -jnp.inf)
    m = s.max(-1)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)[..., None])
    o = jnp.einsum("bht,btd->bhd", p.astype(latent.dtype),
                   latent[..., :value_width], preferred_element_type=f32)
    return o, m, p.sum(-1)


def decode_own(q, latent, mask, kpos, qpos, *, value_width: int,
               sm_scale: float):
    """The absorbed form over each row's own slots, in XLA. latent: (B,
    To, dl); mask, kpos: (B, To) the slots' validity and positions; qpos:
    (B,). Partials as :func:`decode_main_xla`'s."""
    f32 = jnp.float32
    s = jnp.einsum("bhd,btd->bht", q, latent,
                   preferred_element_type=f32) * sm_scale
    ok = (mask > 0) & (kpos <= qpos[:, None])
    s = jnp.where(ok[:, None], s, -jnp.inf)
    m = s.max(-1)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)[..., None])
    o = jnp.einsum("bht,btd->bhd", p.astype(latent.dtype),
                   latent[..., :value_width], preferred_element_type=f32)
    return o, m, p.sum(-1)


def decode_self(q, latent, *, value_width: int, sm_scale: float):
    """The absorbed form's partials over each row's own new key, its
    latent (B, dl) not yet in the cache."""
    f32 = jnp.float32
    s = jnp.einsum("bhd,bd->bh", q, latent, preferred_element_type=f32)
    o = jnp.broadcast_to(latent[:, None, :value_width].astype(f32),
                         q.shape[:2] + (value_width,))
    return o, s * sm_scale, jnp.ones_like(s)


def merge(*parts):
    """Softmax over every set of keys from their partials (o, m, l)."""
    o, m, l = (jnp.stack(p) for p in zip(*parts))
    return merge_partials(o, m, l, axis=0)


def _decode_kernel(layer_ref, mlen_ref, mcount_ref, q_ref, k_ref, o_ref,
                   m_ref, l_ref, acc, m_s, l_s, *, tile: int, dv: int,
                   shared: bool, sm_scale: float):
    """One (block of rows, latent tile) program: the block's rows x heads
    queries against one tile of the main latent, the value its first
    ``dv`` columns."""
    del layer_ref
    i, t = pl.program_id(0), pl.program_id(1)
    b = 0 if shared else i

    @pl.when(t == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when(t < mcount_ref[b])
    def _():
        k = k_ref[0, 0]                                     # (tile, dl)
        rows = q_ref.shape[1]
        slot = t * tile + lax.broadcasted_iota(jnp.int32, (rows, tile), 1)
        _online(q_ref[0], k, k[:, :dv], slot < mlen_ref[b], acc, m_s, l_s,
                sm_scale)

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = acc[...]
        m_ref[0] = m_s[...]
        l_ref[0] = l_s[...]


def decode_main(q, latent, main_len, *, value_width: int, sm_scale: float,
                layer=None, interpret: bool = False,
                name: str = "latent_decode"):
    """The absorbed form's main leg as a kernel; arguments and result as
    :func:`decode_main_xla`, but ``latent`` may be the cache's stacked
    (L, Bm, Tm, dl) leaf with ``layer`` (traced, prefetched) the layer to
    read: the kernel reads that layer's tiles where they lie. Over a
    shared trunk (Bm 1) a program takes DECODE_ROWS rows (all where
    fewer, or where the batch is no multiple of it), so each tile is read
    once for the block; over per-row main parts, one row."""
    f32 = jnp.float32
    if layer is None:
        latent, layer = latent[None], 0
    B, H, dl = q.shape
    _, Bm, Tm, _ = latent.shape
    dv = value_width
    tile, Tp = main_tile(Tm)
    if Tp != Tm:
        latent = jnp.pad(lax.dynamic_index_in_dim(latent, layer, keepdims=True),
                         ((0, 0), (0, 0), (0, Tp - Tm), (0, 0)))
        layer = 0
    shared = Bm == 1
    rows = (DECODE_ROWS if B % DECODE_ROWS == 0 else B) if shared else 1
    nb, R = B // rows, rows * H
    main_len = jnp.minimum(jnp.broadcast_to(main_len.astype(jnp.int32),
                                            (Bm,)), Tm)
    mcount = (main_len + tile - 1) // tile
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def k_map(i, t, l, ml, mc):
        b = 0 if shared else i
        return (l[0], b, jnp.minimum(t, jnp.maximum(mc[b] - 1, 0)), 0)

    def row_map(i, t, *_):
        return (i, 0, 0)

    kernel = functools.partial(_decode_kernel, tile=tile, dv=dv,
                               shared=shared, sm_scale=sm_scale)
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nb, Tp // tile),
            in_specs=[pl.BlockSpec((1, R, dl), row_map),
                      pl.BlockSpec((1, 1, tile, dl), k_map)],
            out_specs=[pl.BlockSpec((1, R, dv), row_map),
                       pl.BlockSpec((1, R, 1), row_map),
                       pl.BlockSpec((1, R, 1), row_map)],
            scratch_shapes=[pltpu.VMEM((R, dv), f32),
                            pltpu.VMEM((R, 1), f32),
                            pltpu.VMEM((R, 1), f32)]),
        out_shape=[jax.ShapeDtypeStruct((nb, R, dv), f32),
                   jax.ShapeDtypeStruct((nb, R, 1), f32),
                   jax.ShapeDtypeStruct((nb, R, 1), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name=name,
    )(layer, main_len, mcount, q.reshape(nb, R, dl), latent)
    return (o.reshape(B, H, dv), m.reshape(B, H), l.reshape(B, H))
