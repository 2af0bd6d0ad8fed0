"""Pallas selective scan for a Mamba-2 mixer (state-space duality form).

The recurrence, per sequence and head (head width P, state width N, the
``B``/``C`` vectors shared by the heads of one group)::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (x) B_t        S: (P, N)
    y_t = S_t C_t

Written token by token it reads and writes the whole state once per token:
at 32 heads of 128 x 256 float32 and 40 rows that is 336 MB a token a
layer, ~0.4 ms at the chip's bandwidth, 157 ms for a 384-token window
against ~67 ms for the layer's matmuls. :func:`ssd_scan` is the chunked
form (Dao & Gu, "Transformers are SSMs"): within a chunk of ``chunk``
tokens the outputs are three matmuls (``C B^T`` masked by the decay
between the two tokens, times ``x``; ``C S^T`` for what the chunk's start
state adds), and the state moves once per chunk (``x^T B`` weighted by
each token's decay to the chunk's end). It takes an initial state and
returns the final one, so a window continues where the last ended
(shared trunk -> remainder -> format suffix).

A token whose ``dt`` is 0 leaves the state as it was (decay 1, nothing
added): the callers zero ``dt`` at masked slots, which makes padding a
no-op on the state whatever side it lies on. The ``D * x`` skip term and
everything else that is elementwise stay with the caller, in XLA.

:func:`ssm_step` is the single-token update of the decode step, also a
kernel: in XLA the update and the ``S C`` read are two fusions and the
state streams three times (read, write, read); here each program reads a
row's block of heads once and writes it once, in place
(``input_output_aliases``), and the device operation has a name the
trace can be read by.

Both kernels take the state either as one layer's ``(B, H, P, N)`` or as
the cache's stacked ``(L, B, H, P, N)`` leaf with a ``layer`` index (a
traced scalar, prefetched): the state's block index then starts with that
layer, input aliased to output, so the call reads and writes that layer's
blocks where they lie and leaves the rest of the buffer untouched. That
is how the layer loop of ``models/decoder._scan_blocks`` uses them: the
stacked state rides the loop's carry and no layer of it is ever sliced
out or written back whole. One layer's state alone is the ``L`` = 1 case.

Both kernels take the state in float32 and keep it there. The products
that touch it run at ``Precision.HIGHEST``; the two that do not (``C
B^T`` and the masked ``(Q, Q)`` weights times ``x``) take their operands
as they come (bfloat16 on the chip) and accumulate in float32. The device
operations are named ``ssd_scan`` / ``ssm_step`` by ``name=`` on the
``pallas_call``; both compile for a described v5e in
tests/test_tpu_compile.py. ``interpret=True`` runs them in the Pallas
interpreter (tests/test_hybrid_model.py); production CPU runs take
:func:`ssd_scan_tokens`, the recurrence as written above, for windows and
single tokens alike.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128           # the published ``mamba_chunk_size``
HEAD_BLOCK = 8                # heads a program carries (one sublane tile)

_HIGHEST = lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))        # a @ b.T
_TN = (((0,), (0,)), ((), ()))        # a.T @ b


def head_block(n_heads: int, n_groups: int) -> int:
    """Heads per program: at most :data:`HEAD_BLOCK`, a divisor of the
    heads of one group (a program reads one group's ``B`` and ``C``).
    Where every head is a group of its own (lightning attention: ``B`` is
    the head's key, ``C`` its query) a program carries a block of heads
    with each head's own ``B`` and ``C``."""
    per_group = n_heads // n_groups
    span = n_heads if per_group == 1 else per_group
    hb = min(HEAD_BLOCK, span)
    while span % hb:
        hb -= 1
    return hb


def scan_chunk(length: int, chunk: int = DEFAULT_CHUNK) -> tuple:
    """(chunk width, padded length): windows no longer than ``chunk`` are
    one chunk of their own length rounded up to the sublane tile; longer
    ones are padded to whole chunks (the padding carries ``dt`` = 0)."""
    if length <= chunk:
        q = -(-length // 8) * 8
        return q, q
    return chunk, -(-length // chunk) * chunk


# ---------------------------------------------------------------------------
# The recurrence as written (CPU path, and what the kernels are pinned to)
# ---------------------------------------------------------------------------

def ssd_scan_tokens(x, dt, a, b, c, state):
    """Token-by-token recurrence. x: (B, T, H, P); dt: (B, T, H) float32
    (0 at masked slots); a: (H,) float32, negative; b, c: (B, T, G, N);
    state: (B, H, P, N) float32. Returns (y (B, T, H, P) in x's dtype,
    final state)."""
    H, G = x.shape[2], b.shape[2]
    f32 = jnp.float32

    def step(s, xs):
        xt, dtt, bt, ct = xs
        bt = jnp.repeat(bt.astype(f32), H // G, axis=1)        # (B, H, N)
        ct = jnp.repeat(ct.astype(f32), H // G, axis=1)
        decay = jnp.exp(dtt * a)[:, :, None, None]
        s = decay * s + (dtt[:, :, None] * xt.astype(f32)
                         )[..., None] * bt[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, ct, precision=_HIGHEST)

    swap = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731
    state, y = lax.scan(step, state, (swap(x), swap(dt), swap(b), swap(c)))
    return swap(y).astype(x.dtype), state


# ---------------------------------------------------------------------------
# Chunked scan kernel
# ---------------------------------------------------------------------------

def _scan_kernel(layer_ref, x_ref, b_ref, c_ref, cs_tl_ref, dt_tl_ref,
                 cs_hl_ref, dt_hl_ref, s0_ref, y_ref, s_ref, *, hb: int,
                 P: int, own_group: bool = False):
    """One (row, head block, chunk) program; the chunk axis is the
    innermost, sequential one and the output state block (its index does
    not depend on the chunk) carries the state between chunks.
    ``layer_ref`` (prefetched) is spent by the state's index maps.
    ``own_group``: the ``B`` / ``C`` blocks hold one (Q, N) slice a head
    of the block instead of one for all of them."""
    del layer_ref
    f32 = jnp.float32
    s0_ref, s_ref = s0_ref.at[0], s_ref.at[0]     # the layer's blocks
    N = s_ref.shape[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    def group(j):
        bm, cm = b_ref[0, :, j * N:(j + 1) * N], c_ref[0, :, j * N:(j + 1) * N]
        return (lax.dot_general(cm, bm, _NT, preferred_element_type=f32),
                cm.astype(f32), bm.astype(f32))

    Q = b_ref.shape[1]
    if not own_group:
        cb, cm32, bm32 = group(0)
    rows = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lower = rows >= cols
    for j in range(hb):
        if own_group:
            cb, cm32, bm32 = group(j)
        x = x_ref[0, :, j * P:(j + 1) * P]                    # (Q, P)
        cs_c = cs_tl_ref[0, 0, :, j:j + 1]                    # (Q, 1)
        dt_c = dt_tl_ref[0, 0, :, j:j + 1]
        cs_r = cs_hl_ref[0, j:j + 1, :]                       # (1, Q)
        dt_r = dt_hl_ref[0, j:j + 1, :]
        # The chunk's last running sum, as a row and as a column (Mosaic
        # broadcasts along one axis at a time).
        end_r = jnp.broadcast_to(cs_r[:, Q - 1:Q], (1, s_ref.shape[-1]))
        end_c = jnp.broadcast_to(end_r[:, :1], (Q, 1))
        # Decay from token s to token t >= s of the chunk; the other
        # half would overflow exp, so it is cut before.
        decay = jnp.where(lower, jnp.exp(jnp.minimum(cs_c - cs_r, 0.0)),
                          0.0)
        w = (cb * decay * dt_r).astype(x.dtype)
        y = jnp.dot(w, x, preferred_element_type=f32)
        s = s_ref[0, j]                                       # (P, N)
        y = y + jnp.exp(cs_c) * lax.dot_general(
            cm32, s, _NT, precision=_HIGHEST, preferred_element_type=f32)
        y_ref[0, :, j * P:(j + 1) * P] = y.astype(y_ref.dtype)
        to_end = dt_c * jnp.exp(end_c - cs_c)                 # (Q, 1)
        s_ref[0, j] = jnp.exp(end_r) * s + lax.dot_general(
            x.astype(f32) * to_end, bm32, _TN, precision=_HIGHEST,
            preferred_element_type=f32)


def _stacked(state, layer):
    """(stacked float32 state (L, B, H, P, N), layer index (1,) int32):
    one layer's state with ``layer`` None is the L = 1 case."""
    if layer is None:
        state, layer = state[None], 0
    return (state.astype(jnp.float32),
            jnp.asarray(layer, jnp.int32).reshape(1))


def ssd_scan(x, dt, a, b, c, state, *, chunk: int = DEFAULT_CHUNK,
             interpret: bool = False, layer=None, name: str = "ssd_scan"):
    """Chunked selective scan; arguments and results as
    :func:`ssd_scan_tokens`. With ``layer``, ``state`` is the stacked
    (L, B, H, P, N) leaf: layer ``layer`` of it is the initial state and
    is overwritten, in place, by the final one; the whole leaf comes
    back."""
    one_layer = layer is None
    state, layer = _stacked(state, layer)
    B, T, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    hb = head_block(H, G)
    nh = H // hb
    Q, Tp = scan_chunk(T, chunk)
    nc = Tp // Q
    f32 = jnp.float32

    def pad_t(v):
        return jnp.pad(v, ((0, 0), (0, Tp - T)) + ((0, 0),) * (v.ndim - 2))

    dt = pad_t(dt.astype(f32))
    # Running sum of dt * A inside each chunk, in float32, once here and
    # handed over in both orientations (a kernel cannot turn a column
    # into a row cheaply): (B, nh, T, hb) for columns, (B, H, T) for rows.
    cs = jnp.cumsum((dt * a).reshape(B, nc, Q, H), axis=2).reshape(B, Tp, H)
    tl = lambda v: v.reshape(B, Tp, nh, hb).transpose(0, 2, 1, 3)  # noqa: E731
    hl = lambda v: v.transpose(0, 2, 1)  # noqa: E731

    per_group = H // G
    own = per_group == 1 and hb > 1
    kernel = functools.partial(_scan_kernel, hb=hb, P=P, own_group=own)
    # One group's (Q, N) block, or with a group a head the block's heads'.
    bc_spec = (pl.BlockSpec((1, Q, hb * N), lambda i, h, k, l: (i, k, h))
               if own else
               pl.BlockSpec((1, Q, N), lambda i, h, k, l:
                            (i, k, (h * hb) // per_group)))
    state_spec = pl.BlockSpec((1, 1, hb, P, N),
                              lambda i, h, k, l: (l[0], i, h, 0, 0))
    y, state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nh, nc),
            in_specs=[
                pl.BlockSpec((1, Q, hb * P), lambda i, h, k, l: (i, k, h)),
                bc_spec, bc_spec,
                pl.BlockSpec((1, 1, Q, hb), lambda i, h, k, l: (i, h, k, 0)),
                pl.BlockSpec((1, 1, Q, hb), lambda i, h, k, l: (i, h, k, 0)),
                pl.BlockSpec((1, hb, Q), lambda i, h, k, l: (i, h, k)),
                pl.BlockSpec((1, hb, Q), lambda i, h, k, l: (i, h, k)),
                state_spec,
            ],
            out_specs=[
                pl.BlockSpec((1, Q, hb * P), lambda i, h, k, l: (i, k, h)),
                state_spec,
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Tp, H * P), x.dtype),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # Operand 8 (the layer index is operand 0) is the state.
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(layer, pad_t(x).reshape(B, Tp, H * P), pad_t(b).reshape(B, Tp, G * N),
      pad_t(c).reshape(B, Tp, G * N), tl(cs), tl(dt), hl(cs), hl(dt), state)
    return y[:, :T].reshape(B, T, H, P), state[0] if one_layer else state


# ---------------------------------------------------------------------------
# Single-token update kernel (decode)
# ---------------------------------------------------------------------------

def _step_kernel(layer_ref, dt_ref, decay_ref, x_ref, b_ref, c_ref, s_ref,
                 y_ref, so_ref, *, hb: int, P: int, own_group: bool = False):
    """One (row, head block) program. The outer product and the read ride
    the MXU on tiles whose first row alone is live: ``x^T B`` contracts
    the tile's 8 rows (7 of them zero) and gives ``x (x) B`` without a
    column vector; ``C S^T`` gives ``y`` as a row. ``layer_ref``
    (prefetched) is spent by the state's index maps."""
    del layer_ref
    f32 = jnp.float32
    i, h = pl.program_id(0), pl.program_id(1)
    s_ref, so_ref = s_ref.at[0], so_ref.at[0]     # the layer's blocks

    def tile(row):
        """(1, n) -> float32 (8, n) whose first row alone is live."""
        n = row.shape[-1]
        live = lax.broadcasted_iota(jnp.int32, (8, n), 0) == 0
        return jnp.where(live, jnp.broadcast_to(row.astype(f32), (8, n)),
                         0.0)

    N = s_ref.shape[-1]
    if not own_group:
        b8, c8 = tile(b_ref[0]), tile(c_ref[0])               # (8, N)
    for j in range(hb):
        if own_group:           # each head of the block its own B and C
            b8 = tile(b_ref[0, :, j * N:(j + 1) * N])
            c8 = tile(c_ref[0, :, j * N:(j + 1) * N])
        dt, decay = dt_ref[i, h * hb + j], decay_ref[i, h * hb + j]
        x8 = tile(x_ref[0, :, j * P:(j + 1) * P])             # (8, P)
        outer = lax.dot_general(x8, b8, _TN, precision=_HIGHEST,
                                preferred_element_type=f32)   # (P, N)
        s = decay * s_ref[0, j] + dt * outer
        so_ref[0, j] = s
        y = lax.dot_general(c8, s, _NT, precision=_HIGHEST,
                            preferred_element_type=f32)       # (8, P)
        y_ref[0, :, j * P:(j + 1) * P] = y[0:1].astype(y_ref.dtype)


def ssm_step(x, dt, a, b, c, state, *, interpret: bool = False,
             layer=None, name: str = "ssm_step"):
    """One token of the recurrence. x: (B, H, P); dt: (B, H) float32;
    b, c: (B, G, N); state: (B, H, P, N) float32, updated in place, or
    with ``layer`` the stacked (L, B, H, P, N) leaf, of which that layer
    alone is read and written. Returns (y (B, H, P) in x's dtype,
    state)."""
    one_layer = layer is None
    state, layer = _stacked(state, layer)
    B, H, P = x.shape
    G, N = b.shape[1], b.shape[2]
    hb = head_block(H, G)
    per_group = H // G
    f32 = jnp.float32
    dt = dt.astype(f32)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    own = per_group == 1 and hb > 1
    kernel = functools.partial(_step_kernel, hb=hb, P=P, own_group=own)
    bc_spec = (pl.BlockSpec((1, 1, hb * N), lambda i, h, l: (i, 0, h))
               if own else
               pl.BlockSpec((1, 1, N), lambda i, h, l:
                            (i, 0, (h * hb) // per_group)))
    state_spec = pl.BlockSpec((1, 1, hb, P, N),
                              lambda i, h, l: (l[0], i, h, 0, 0))
    y, state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // hb),
            in_specs=[
                smem, smem,
                pl.BlockSpec((1, 1, hb * P), lambda i, h, l: (i, 0, h)),
                bc_spec, bc_spec,
                state_spec,
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hb * P), lambda i, h, l: (i, 0, h)),
                state_spec,
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, 1, H * P), x.dtype),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # Operand 6 (the layer index is operand 0) is the state.
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=name,
    )(layer, dt, jnp.exp(dt * a), x.reshape(B, 1, H * P),
      b.reshape(B, 1, G * N), c.reshape(B, 1, G * N), state)
    return y.reshape(B, H, P), state[0] if one_layer else state
