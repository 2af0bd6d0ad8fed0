"""The expert layer: a sigmoid router over every expert, the terms of the
experts this chip holds, and the shared expert (K-EXAONE; ModelConfig's
``num_experts`` and after).

For each token ``s = sigmoid(x Wr)`` over all ``num_experts`` experts in
float32, ``chosen = top_k(s + b)`` (``b`` the per-expert selection bias),
the gate ``g_e = router_scale * s_e / sum(s over chosen)`` over ALL the
chosen experts, held here or not, and

    m = shared(x) + sum over chosen e that this chip holds of g_e expert_e(x)

Every expert is a gated SiLU MLP (int8 weights, per-column scales). The
held experts' rows are laid out by expert in whole row tiles and run
through the grouped matmul of ops/expert_matmul.py; nothing stands in for
the experts other chips hold. No token is dropped: the row buffer of a
chunk of ``T`` tokens is sized for the worst case, ``T * min(top_k,
held)`` rows plus a tile's padding an expert, and a prefill is cut into
chunks of at most :data:`CHUNK_TOKENS` tokens so that buffer stays small.

``expert_layer`` returns the layer's output and its routing counts, an
int32 vector of ``2 * held + 1``: the picks of each held expert, the rows
the grouped matmul computed for each (padding included), and the tokens
routed. Masked (padding) tokens route nowhere.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import expert_matmul as gmm
from .quant import (QuantTensor, matmul as _mm,
                    shared_quant as _shared_quant)

# Most tokens one pass of the grouped matmul takes: its worst-case row
# buffer is top_k (8) times this many rows of the model's width.
CHUNK_TOKENS = 4096


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RouteCounts:
    """The cache's last leaf in a model with experts, summed over the
    passes of one dispatch (decoder.rewind keeps it across its branches):
    ``counts``, the routing counts of every expert layer, ``(expert
    layers, 2 * held + 1)`` int32; ``keys``, (3,) int32, what the window
    layers' decode kernel calls fetched (ops/flash_decode._window_keys:
    key slots brought in, key slots attended, calls); ``latent``, (4,)
    int32 in a model with latent attention, None otherwise: the latent
    counters of models/mla.py."""

    counts: jax.Array
    keys: jax.Array
    latent: Any = None


def counts_width(cfg) -> int:
    return 2 * cfg.experts_held[1] + 1


def empty_counts(cfg) -> RouteCounts:
    latent = (jnp.zeros((4,), jnp.int32)
              if getattr(cfg, "kv_lora_rank", 0) else None)
    return RouteCounts(jnp.zeros((cfg.expert_layers, counts_width(cfg)),
                                 jnp.int32), jnp.zeros((3,), jnp.int32),
                       latent)


def _kernels_lower(cfg) -> bool:
    from . import decoder

    if jax.default_backend() == "tpu":
        return cfg.fused_decode
    return decoder.MOE_INTERPRET_ON_CPU


def route(x32, router, cfg):
    """(chosen experts (T, top_k) int32, gates (T, top_k) float32) of
    float32 tokens ``x32`` (T, D)."""
    logits = jnp.dot(x32, router["w"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(s + router["bias"].astype(jnp.float32),
                       cfg.expert_top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    gates = cfg.router_scale * w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, gates


def layout(idx, mask, first: int, count: int, tm: int, rows: int):
    """Where each (token, choice) pair of a chunk lies in the row buffer:
    each held expert's picks in one run of whole ``tm``-row tiles, the
    runs back to back from row 0.

    Returns (the pair each buffer row holds (rows,): ``idx.size`` for a
    padding row, row tiles of each held expert (count,), picks of each
    held expert (count,))."""
    T, K = idx.shape
    P = T * K
    local = idx - first
    held = (local >= 0) & (local < count)
    if mask is not None:
        held = held & (mask[:, None] > 0)
    e = jnp.where(held, local, count).reshape(P)
    picks = jnp.zeros((count + 1,), jnp.int32).at[e].add(1)[:count]
    tiles = (picks + tm - 1) // tm
    start = jnp.cumsum(tiles * tm) - tiles * tm        # first row of a run
    order = jnp.argsort(e, stable=True)
    se = jnp.minimum(e[order], count - 1)
    rank = jnp.arange(P, dtype=jnp.int32) - (jnp.cumsum(picks) - picks)[se]
    dest = jnp.where(e[order] < count, start[se] + rank, rows)
    src = jnp.full((rows,), P, jnp.int32).at[dest].set(
        order.astype(jnp.int32), mode="drop")
    return src, tiles, picks


def _gather(x, tok, n_active, tm: int):
    """The row buffer: row r holds token ``tok[r]``'s activations (0 past
    the tokens), written a row tile at a time over the ``n_active`` tiles
    in use only; the rows past them stay 0 and no kernel reads them."""
    def tile(i, buf):
        t = lax.dynamic_slice_in_dim(tok, i * tm, tm)
        rows = jnp.take(x, t, axis=0, mode="fill", fill_value=0)
        return lax.dynamic_update_slice_in_dim(buf, rows, i * tm, 0)

    return lax.fori_loop(0, n_active, tile,
                         jnp.zeros((tok.shape[0], x.shape[1]), x.dtype))


def _combine(y, tok, gate, n_active, tm: int, tokens: int):
    """Sum of each token's rows of ``y`` times their gates, a row tile at
    a time over the tiles in use (a token's rows lie in different
    experts' runs, so no tile holds one token twice)."""
    def tile(i, out):
        t = lax.dynamic_slice_in_dim(tok, i * tm, tm)
        g = lax.dynamic_slice_in_dim(gate, i * tm, tm)
        rows = lax.dynamic_slice_in_dim(y, i * tm, tm).astype(jnp.float32)
        return out.at[t].add(g[:, None] * rows, mode="drop")

    return lax.fori_loop(
        0, n_active, tile, jnp.zeros((tokens, y.shape[1]), jnp.float32))


def _experts_of(lp):
    """The held experts' weights an expert layer multiplies by, and the
    index of its layer in them: a layer loop hands the stack of every
    expert layer (``expert_layer`` set), which the kernels read in place;
    None when ``lp["experts"]`` is the layer's own."""
    return lp["experts"], lp.get("expert_layer")


def _held_terms(x, idx, gates, mask, lp, cfg):
    """Sum over each token's held chosen experts of g_e expert_e(x), and
    the chunk's counts. x (T, D)."""
    T, D = x.shape
    first, count = cfg.experts_held
    K = cfg.expert_top_k
    tm = gmm.pick_tm(T, K, cfg.num_experts)
    rows = -(-(T * min(K, count) + count * (tm - 1)) // tm) * tm
    with jax.named_scope("lir.moe.route"):
        src, tiles, picks = layout(idx, mask, first, count, tm, rows)
        n_active = jnp.sum(tiles)
        tok = src // K                                   # T past the tokens
        gate = jnp.take(gates.reshape(-1), src, mode="fill", fill_value=0)
    with jax.named_scope("lir.moe.experts"):
        xb = _gather(x, tok, n_active, tm)
        ex, layer = _experts_of(lp)
        if _kernels_lower(cfg) and isinstance(ex["w_up"], QuantTensor):
            interpret = jax.default_backend() != "tpu"
            te = jnp.repeat(jnp.arange(count, dtype=jnp.int32), tiles,
                            total_repeat_length=rows // tm)
            h = gmm.gate_up(xb, ex["w_gate"], ex["w_up"], te, n_active, tm,
                            interpret=interpret, layer=layer)
            y = gmm.down(h, ex["w_down"], te, n_active, tm,
                         interpret=interpret, layer=layer)
        else:
            if layer is not None:
                ex = jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(a, layer,
                                                       keepdims=False), ex)
            h = gmm.gate_up_xla(xb, ex["w_gate"], ex["w_up"], tiles, tm)
            y = gmm.down_xla(h, ex["w_down"], tiles, tm)
        out = _combine(y, tok, gate, n_active, tm, T)
    routed = T if mask is None else jnp.sum(mask > 0).astype(jnp.int32)
    counts = jnp.concatenate([picks, tiles * tm,
                              jnp.reshape(routed, (1,)).astype(jnp.int32)])
    return out, counts


def shared(h, lp):
    """The shared expert(s) over ``h`` (B, S, D)."""
    sh = lp["shared"]
    hq = _shared_quant(h, sh["w_up"], sh["w_gate"])
    hidden = jax.nn.silu(_mm(hq, sh["w_gate"])) * _mm(hq, sh["w_up"])
    return _mm(hidden, sh["w_down"])


def expert_layer(h, lp, cfg, mask=None):
    """The expert layer over ``h`` (B, S, D), ``mask`` (B, S) its real
    slots (None: all). Returns (output (B, S, D), counts (2 * held + 1,)
    int32)."""
    B, S, D = h.shape
    T = B * S
    x = h.reshape(T, D)
    flat_mask = None if mask is None else mask.reshape(T)
    with jax.named_scope("lir.moe.route"):
        idx, gates = route(x.astype(jnp.float32), lp["router"], cfg)
    # The fewest chunks of at most CHUNK_TOKENS, equal ones: the pass is
    # padded to n * Tc tokens, masked, which route nowhere.
    n = -(-T // CHUNK_TOKENS)
    Tc = -(-T // n)
    if n == 1:
        routed, counts = _held_terms(x, idx, gates, flat_mask, lp, cfg)
    else:
        pad = n * Tc - T
        if pad:
            real = (jnp.ones((T,), jnp.int32) if flat_mask is None
                    else flat_mask)
            flat_mask = jnp.pad(real, (0, pad))
            x, idx, gates = (jnp.pad(a, ((0, pad), (0, 0)))
                             for a in (x, idx, gates))
        parts = (x.reshape(n, Tc, D), idx.reshape(n, Tc, -1),
                 gates.reshape(n, Tc, -1),
                 None if flat_mask is None else flat_mask.reshape(n, Tc))

        def chunk(total, part):
            xc, ic, gc, mc = part
            out, c = _held_terms(xc, ic, gc, mc, lp, cfg)
            return total + c, out

        counts, routed = lax.scan(
            chunk, jnp.zeros((counts_width(cfg),), jnp.int32), parts)
        routed = routed.reshape(n * Tc, D)[:T]
    with jax.named_scope("lir.moe.shared"):
        out = shared(h, lp).astype(jnp.float32) + routed.reshape(B, S, D)
    return out.astype(h.dtype), counts
