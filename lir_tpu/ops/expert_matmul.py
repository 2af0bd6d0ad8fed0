"""Grouped matmul over the experts one chip holds (models/moe.py).

The rows of a call are laid out by expert: each held expert's rows sit in
one run of whole ``tm``-row tiles (its last tile padded with zero rows),
the runs back to back from row 0, the buffer's tail unused. ``tiles`` (the
tile count of each run) says where each run lies; the kernel's grid visits
only the runs' tiles (a DYNAMIC grid of ``sum(tiles)`` row tiles, from the
scalar-prefetched tile -> expert table), so an expert that got no row in a
call is never read, and the unused tail costs nothing. The weights are
int8 with a per-output-column float32 scale (models/quant.QuantTensor,
stacked over the held experts): each (tk, tn) block is converted to the
activation dtype in VMEM, the float32 accumulator multiplied by the
column scales once, at the last k step.

Two entry points, both under ``name="expert_matmul…"`` so a profiler
trace names them (benchmarks' ``expert_matmul_roofline``):
:func:`gate_up` (``silu(x Wg) * (x Wu)``, the two products of a gated
expert in one pass over ``x``) and :func:`down`. :func:`gate_up_xla` /
:func:`down_xla` are the same products through ``lax.ragged_dot``: the
path where Pallas does not lower, and what the kernels are held to in
tests/test_exaone_moe.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Row tile bounds: a bfloat16 block fills whole packed sublane groups at
# 16 rows; 256 rows of a prefill chunk keep the expert weights' re-reads
# (one per row tile) under the MXU time of the tile.
TM_MIN, TM_MAX = 16, 256


def pick_tm(tokens: int, top_k: int, num_experts: int) -> int:
    """Row tile for a call over ``tokens`` tokens: the power of two at
    least the rows an expert expects (``tokens * top_k / num_experts``
    under uniform routing), within [TM_MIN, TM_MAX]. A decode step's
    handful of rows an expert then pays a 16-row tile, not a 256-row
    one."""
    want = -(-tokens * top_k // num_experts)
    tm = TM_MIN
    while tm < min(want, TM_MAX):
        tm *= 2
    return tm


def _block(dim: int, want: int) -> int:
    """The widest of ``want``, ``want / 2``, ... (down to 128) dividing
    ``dim``, else ``dim`` whole."""
    b = want
    while b >= 128:
        if dim % b == 0:
            return b
        b //= 2
    return dim


def _kernel(*refs, n_pre: int, n_w: int, nk: int, act: bool):
    """One (row tile, column block, k block) program. ``refs``: the
    ``n_pre`` prefetched tables, the row block, ``n_w`` int8 weight
    blocks, their ``n_w`` scale rows, the output block, then ``n_w``
    float32 accumulators."""
    x_ref = refs[n_pre]
    refs = refs[n_pre + 1:]
    w_refs, s_refs = refs[:n_w], refs[n_w:2 * n_w]
    o_ref, accs = refs[2 * n_w], refs[2 * n_w + 1:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]
    for w, acc in zip(w_refs, accs):
        blk = w[...]
        blk = blk.reshape(blk.shape[-2:])
        acc[...] += jnp.dot(x, blk.astype(x.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        ys = [acc[...] * s[...].reshape(1, -1) for acc, s in zip(accs, s_refs)]
        y = jax.nn.silu(ys[0]) * ys[1] if act else ys[0]
        o_ref[...] = y.astype(o_ref.dtype)


def _grouped(name: str, x, weights, tile_expert, n_active, tm: int,
             act: bool, interpret: bool, layer=None):
    """``x`` (M, K) rows laid out by expert, ``weights`` QuantTensors
    (E, K, N) — or, with ``layer`` (a traced index), the stack (L, E, K,
    N) of every expert layer, whose blocks the index maps pick in place
    (no layer of it is sliced out for the call) — ``tile_expert`` (M //
    tm,) int32, ``n_active`` the number of row tiles in use (traced).
    Returns (M, N) in x's dtype; rows past ``n_active * tm`` are never
    written."""
    M, K = x.shape
    N = weights[0].q.shape[-1]
    tk, tn = _block(K, 1024), _block(N, 512)
    nk = K // tk
    n_w = len(weights)
    qs = [w.q for w in weights]
    scales = [w.scale.astype(jnp.float32)[..., None, :] for w in weights]
    if layer is None:
        prefetch = (tile_expert,)
        w_spec = pl.BlockSpec((1, tk, tn),
                              lambda i, n, k, te: (te[i], k, n))
        s_spec = pl.BlockSpec((1, 1, tn), lambda i, n, k, te: (te[i], 0, n))
    else:
        prefetch = (jnp.asarray(layer, jnp.int32).reshape(1), tile_expert)
        w_spec = pl.BlockSpec((1, 1, tk, tn),
                              lambda i, n, k, l, te: (l[0], te[i], k, n))
        s_spec = pl.BlockSpec((1, 1, 1, tn),
                              lambda i, n, k, l, te: (l[0], te[i], 0, n))
    kernel = functools.partial(_kernel, n_pre=len(prefetch), n_w=n_w, nk=nk,
                               act=act)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(n_active, N // tn, nk),
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda i, n, k, *_: (i, k))]
            + [w_spec] * n_w + [s_spec] * n_w,
            out_specs=pl.BlockSpec((tm, tn), lambda i, n, k, *_: (i, n)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * n_w),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*prefetch, x, *qs, *scales)


def gate_up(x, w_gate, w_up, tile_expert, n_active, tm: int,
            interpret: bool = False, layer=None):
    """``silu(x Wg) * (x Wu)`` of each row under its expert's weights."""
    return _grouped("expert_matmul_gate_up", x, (w_gate, w_up),
                    tile_expert, n_active, tm, True, interpret, layer)


def down(h, w_down, tile_expert, n_active, tm: int, interpret: bool = False,
         layer=None):
    """``h Wd`` of each row under its expert's weights."""
    return _grouped("expert_matmul_down", h, (w_down,), tile_expert,
                    n_active, tm, False, interpret, layer)


def _ragged(x, w, tiles, tm: int):
    """Each run's rows times its expert's matrix: an int8 QuantTensor
    (scaled on the output) or a dense one."""
    sizes = tiles * tm
    quantized = hasattr(w, "q")
    y = lax.ragged_dot(x, (w.q if quantized else w).astype(x.dtype), sizes,
                       preferred_element_type=jnp.float32)
    if not quantized:
        return y
    row_expert = jnp.repeat(jnp.arange(tiles.shape[0], dtype=jnp.int32),
                            sizes, total_repeat_length=x.shape[0])
    return y * jnp.take(w.scale.astype(jnp.float32), row_expert, axis=0)


def gate_up_xla(x, w_gate, w_up, tiles, tm: int):
    """:func:`gate_up` through ``lax.ragged_dot`` (``tiles``: row tiles of
    each expert's run); rows past the runs come out 0."""
    y = jax.nn.silu(_ragged(x, w_gate, tiles, tm)) * _ragged(x, w_up, tiles,
                                                              tm)
    return y.astype(x.dtype)


def down_xla(h, w_down, tiles, tm: int):
    """:func:`down` through ``lax.ragged_dot``."""
    return _ragged(h, w_down, tiles, tm).astype(h.dtype)
