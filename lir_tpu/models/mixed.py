"""A decoder whose layers differ in kind (``ModelConfig.layer_kinds``):
MiniCPM-SALA's softmax layers with block selection (``"sparse"``) among
lightning linear-attention layers (``"lightning"``), one mixer a layer,
then the gated MLP, every branch scaled by ``cfg.residual_scale``.

``params["layers"]`` is ``{"sparse": ..., "lightning": ...}``, each group
stacked over its own layers; the layer loop runs ``cfg.layer_runs`` (the
published order as runs of one kind) as a ``lax.scan`` over rounds of one
run a kind, each run a loop over layer indices, the cache riding the
carry and every layer updating its own layer of the stacked leaves where
they lie (as decoder._scan_blocks).

The cache is a tuple of six stacked leaves::

    (tail_k, tail_v,     (Ls, B, K, Tt, hd)   a row's own slots behind the main ones
     state,              (Ll, B, H, P, P) f32 lightning state, a row each
     pooled,             (Ls, Bm, K, NK, hd)  f32 pooled keys of the main keys
     main_k, main_v)     (Ls, Bm, K, Tm, hd)  slot == position

K/V for the ``Ls`` sparse layers only, state for the ``Ll`` lightning
layers only. ``Bm`` is B after :func:`prefill` (each row's prefix is its
main part) and 1 after :func:`cascade_extend` (the dispatch's shared trunk,
held ONCE with its pooled keys, read by every row's queries; the rows'
remainder windows, format suffixes and decoded tokens live in the tail).
Cache slot ``s`` of the engine's masks is main slot ``s`` below ``Tm`` and
tail slot ``s - Tm`` from there. ``decoder.rewind`` takes ``[2:]`` from the
snapshot: the state as it stood, and the same main leaves.

The selection reads positions as main SLOTS, so a row's main part is
right-padded (the shared paths' canonical layout); a left-padded prompt
is right while no query lies past ``sparse_dense_len``. The tail is held
under ``sparse_window`` slots: it lies inside every query's local window
and is always kept (ops/sparse_attention.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import sparse_attention as sparse
from ..ops import ssd_scan as scan_ops
from . import decoder
from .decoder import _act, _apply_rope, _mm, _norm, _rope_sincos, _shared_quant
from .registry import ModelConfig

def lightning_slopes(n_heads: int) -> jax.Array:
    """Lightning Attention's per-head decay exponents: head ``h`` decays by
    ``exp(-2^(-8 (h + 1) / H))`` a token, the same in every layer."""
    return jnp.exp2(-8.0 * (jnp.arange(n_heads, dtype=jnp.float32) + 1.0)
                    / n_heads)


# ---------------------------------------------------------------------------
# Parameters and cache
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32):
    """Random weights in the served layout (tests; the benchmark's come
    from benchmarks/references/sala.py)."""
    ks = iter(jax.random.split(key, 64))
    D, F = cfg.hidden_size, cfg.intermediate_size

    def w(*shape, scale=0.02):
        return (scale * jax.random.normal(next(ks), shape)).astype(dtype)

    def ones(*shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape)).astype(dtype)

    def group(L, q_width, kv_width, head_dim, out_norm):
        g = {"ln1": {"scale": ones(L, D)}, "ln2": {"scale": ones(L, D)},
             "wq": w(L, D, q_width), "wk": w(L, D, kv_width),
             "wv": w(L, D, kv_width), "wo": w(L, q_width, D),
             "wg": w(L, D, q_width), "q_norm": ones(L, head_dim),
             "k_norm": ones(L, head_dim), "w_up": w(L, D, F),
             "w_gate": w(L, D, F), "w_down": w(L, F, D)}
        if out_norm:
            g["o_norm"] = ones(L, q_width)
        return g

    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hl, P = cfg.lightning_heads, cfg.lightning_head_dim
    layers = {"sparse": group(cfg.kind_layers("sparse"), H * hd, K * hd, hd,
                              False),
              "lightning": group(cfg.kind_layers("lightning"), Hl * P,
                                 Hl * P, P, True)}
    params = {"tok_embed": w(cfg.vocab_size, D), "layers": layers,
              "final_ln": {"scale": ones(D)}}
    if not cfg.tie_embeddings:
        params["lm_head"] = w(D, cfg.vocab_size)
    return params


def init_cache(cfg: ModelConfig, batch: int, main_len: int, tail_len: int,
               dtype=jnp.float32):
    """The six leaves, zero: ``batch`` rows of ``main_len`` main slots and
    of ``tail_len`` tail slots."""
    Ls, Ll = cfg.kind_layers("sparse"), cfg.kind_layers("lightning")
    K, hd = cfg.n_kv_heads, cfg.head_dim
    Hl, P = cfg.lightning_heads, cfg.lightning_head_dim
    nk = sparse.n_kernels(main_len, cfg.sparse_kernel, cfg.sparse_stride)
    tail = jnp.zeros((Ls, batch, K, tail_len, hd), dtype)
    main = jnp.zeros((Ls, batch, K, main_len, hd), dtype)
    return (tail, tail, jnp.zeros((Ll, batch, Hl, P, P), jnp.float32),
            jnp.zeros((Ls, batch, K, nk, hd), jnp.float32), main, main)


def cache_kinds(cache) -> tuple:
    """(the K/V leaves, the recurrent-state leaves) of a mixed cache: what
    the host counts as ``recurrent.kv_bytes`` / ``state_bytes``."""
    return (cache[0], cache[1], cache[4], cache[5]), (cache[2],)


# ---------------------------------------------------------------------------
# The two mixers
# ---------------------------------------------------------------------------

def _head_norm(x, scale, eps):
    """RMSNorm over each head's own width. x: (B, S, heads, hd)."""
    xf = x.astype(jnp.float32)
    xf = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


def _sparse_kernels_lower(cfg: ModelConfig) -> bool:
    if jax.default_backend() == "tpu":
        return cfg.fused_decode
    return decoder.SPARSE_INTERPRET_ON_CPU


def _lightning(h, lp, cfg: ModelConfig, win, state, layer):
    """Lightning attention over the window: ``S_t = lambda_h S_{t-1} +
    v_t (x) k_t``, ``o_t = S_t q_t / sqrt(P)``, the scan ops/ssd_scan
    computes with ``dt`` = 1 at real slots (0 at masked ones: the state
    stands still), ``A`` = -slope, ``B`` = k, ``C`` = q, ``x`` = v.
    ``state`` is the stacked (Ll, B, H, P, P) leaf, layer ``layer`` of it
    read and written where it lies."""
    B, S, _ = h.shape
    H, P = cfg.lightning_heads, cfg.lightning_head_dim
    f32 = jnp.float32
    hq = _shared_quant(h, lp["wq"], lp["wk"], lp["wv"])
    q = _head_norm(_mm(hq, lp["wq"]).reshape(B, S, H, P), lp["q_norm"],
                   cfg.norm_eps)
    k = _head_norm(_mm(hq, lp["wk"]).reshape(B, S, H, P), lp["k_norm"],
                   cfg.norm_eps)
    v = _mm(hq, lp["wv"]).reshape(B, S, H, P)
    if cfg.lightning_rope:
        q = _apply_rope(q, win["sin"], win["cos"], P)
        k = _apply_rope(k, win["sin"], win["cos"], P)
    q = q * jnp.asarray(1.0 / math.sqrt(P), q.dtype)
    dt = jnp.broadcast_to(win["mask"].astype(f32)[:, :, None], (B, S, H))
    a = -lightning_slopes(H)
    kernels = decoder._ssm_kernels_lower(cfg)
    interpret = jax.default_backend() != "tpu"
    with jax.named_scope("lir.lightning"):
        if S == 1 and kernels:
            y, state = scan_ops.ssm_step(
                v[:, 0], dt[:, 0], a, k[:, 0], q[:, 0], state,
                interpret=interpret, layer=layer, name="lightning_step")
            y = y[:, None]
        elif kernels:
            y, state = scan_ops.ssd_scan(
                v, dt, a, k, q, state, chunk=cfg.lightning_chunk,
                interpret=interpret, layer=layer, name="lightning_scan")
        else:
            y, new = scan_ops.ssd_scan_tokens(
                v, dt, a, k, q,
                lax.dynamic_index_in_dim(state, layer, keepdims=False))
            state = lax.dynamic_update_index_in_dim(state, new, layer, 0)
    y = y.reshape(B, S, H * P).astype(f32)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
    y = (y * lp["o_norm"].astype(f32)).astype(h.dtype)
    if cfg.output_gate:
        y = y * jax.nn.sigmoid(_mm(h, lp["wg"]).astype(f32)).astype(h.dtype)
    return _mm(y, lp["wo"]), state


def _sparse(h, lp, cfg: ModelConfig, win, cache, layer):
    """Softmax attention with block selection over the window's queries.
    ``win["fill"]``: the window IS the rows' main part (:func:`prefill`),
    its k/v and pooled keys are written to the main leaves; otherwise its
    k/v go to tail slots ``[win["at"], win["at"] + S)`` and the queries
    read the main leaves and the tail."""
    B, S, _ = h.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // K
    tail_k, tail_v, state, pooled, main_k, main_v = cache
    hq = _shared_quant(h, lp["wq"], lp["wk"], lp["wv"])
    q = _mm(hq, lp["wq"]).reshape(B, S, H, hd)
    k = _mm(hq, lp["wk"]).reshape(B, S, K, hd)
    v = _mm(hq, lp["wv"]).reshape(B, S, K, hd)
    if cfg.qk_norm:
        q = _head_norm(q, lp["q_norm"], cfg.norm_eps)
        k = _head_norm(k, lp["k_norm"], cfg.norm_eps)
    if cfg.attn_rope:
        q = _apply_rope(q, win["sin"], win["cos"], hd)
        k = _apply_rope(k, win["sin"], win["cos"], hd)
    qg = q.reshape(B, S, K, G, hd).transpose(0, 2, 3, 1, 4)   # (B,K,G,S,hd)
    k_t, v_t = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)  # (B,K,S,hd)
    sizes = dict(block=cfg.sparse_block, init_blocks=cfg.sparse_init_blocks,
                 window=cfg.sparse_window, dense_len=cfg.sparse_dense_len)
    interpret = jax.default_backend() != "tpu"
    kernels = _sparse_kernels_lower(cfg)
    name = "sparse_decode" if S == 1 else "sparse_prefill"

    def main_leg(qm, km, vm, pk, qpos, main_len, stacked, recent=None):
        n_blocks = -(-km.shape[-2] // cfg.sparse_block)
        with jax.named_scope("lir.sparse_select"):
            keep, bound = sparse.select_blocks(
                qm, pk, qpos, main_len, n_blocks=n_blocks,
                kernel=cfg.sparse_kernel, stride=cfg.sparse_stride,
                topk=cfg.sparse_topk, all_dense=win["all_dense"],
                recent=recent, **sizes)
        with jax.named_scope("lir.sparse_attend"):
            if kernels:
                return sparse.attend_main(
                    qm, km, vm, keep, bound, block=cfg.sparse_block,
                    layer=layer if stacked else None, interpret=interpret,
                    name=name)
            if stacked:
                km, vm = (lax.dynamic_index_in_dim(a, layer, keepdims=False)
                          for a in (km, vm))
            return sparse.attend_main_xla(qm, km, vm, keep, bound,
                                          block=cfg.sparse_block)

    if win["fill"]:
        pk = sparse.pool_keys(k_t, cfg.sparse_kernel, cfg.sparse_stride)
        o, _, l = main_leg(qg, k_t, v_t, pk, win["qpos"], win["main_len"],
                           False)
        o = o / jnp.maximum(l, 1e-30)[..., None]
        at = (layer, 0, 0, 0, 0)
        main_k = lax.dynamic_update_slice(main_k, k_t[None].astype(
            main_k.dtype), at)
        main_v = lax.dynamic_update_slice(main_v, v_t[None].astype(
            main_v.dtype), at)
        pooled = lax.dynamic_update_slice(pooled, pk[None], at)
    else:
        at = (layer, 0, 0, win["at"], 0)
        tail_k = lax.dynamic_update_slice(tail_k, k_t[None].astype(
            tail_k.dtype), at)
        tail_v = lax.dynamic_update_slice(tail_v, v_t[None].astype(
            tail_v.dtype), at)
        pk = lax.dynamic_index_in_dim(pooled, layer, keepdims=False)
        own_k, own_v = (lax.dynamic_index_in_dim(a, layer, keepdims=False)
                        for a in (tail_k, tail_v))
        recent = None
        if not win["all_dense"]:
            with jax.named_scope("lir.sparse_select"):
                recent = sparse.recent_kernel_logits(
                    qg, lax.dynamic_index_in_dim(main_k, layer,
                                                 keepdims=False),
                    own_k, win["main_len"], win["tail_mask"], win["qpos"],
                    kernel=cfg.sparse_kernel, stride=cfg.sparse_stride)
        if main_k.shape[1] == 1 and B > 1:
            # The shared trunk: every row's queries side by side.
            flat = lambda a: a.transpose(1, 2, 0, 3, 4).reshape(  # noqa: E731
                1, K, G, B * S, a.shape[-1])
            o, m, l = main_leg(flat(qg), main_k, main_v, pk,
                               win["qpos"].reshape(1, B * S),
                               win["main_len"][:1], True,
                               None if recent is None else flat(recent))
            back = lambda a: jnp.moveaxis(  # noqa: E731
                a[0].reshape((K, G, B, S) + a.shape[4:]), 2, 0)
            main = (back(o), back(m), back(l))
        else:
            main = main_leg(qg, main_k, main_v, pk, win["qpos"],
                            win["main_len"], True, recent)
        with jax.named_scope("lir.sparse_attend"):
            own = sparse.attend_tail(qg, own_k, own_v, win["tail_mask"],
                                     win["tail_pos"], win["qpos"])
            o = sparse.merge(main, own)
    out = o.transpose(0, 3, 1, 2, 4).reshape(B, S, H * hd).astype(h.dtype)
    if cfg.output_gate:
        out = out * jax.nn.sigmoid(_mm(h, lp["wg"]).astype(jnp.float32)
                                   ).astype(h.dtype)
    return (_mm(out, lp["wo"]),
            (tail_k, tail_v, state, pooled, main_k, main_v))


def _mlp(x, lp, cfg: ModelConfig):
    mlp_q = _shared_quant(x, lp["w_up"], lp["w_gate"])
    hidden = _act(_mm(mlp_q, lp["w_gate"]), cfg.activation) * _mm(
        mlp_q, lp["w_up"])
    return _mm(hidden, lp["w_down"])


KIND_ORDER = ("sparse", "lightning")


def layer_rounds(cfg: ModelConfig) -> tuple:
    """``cfg.layer_runs`` folded into rounds of one run a kind, in
    :data:`KIND_ORDER`, a kind's absent run of length 0: ``(first, count)``,
    each ``(rounds, kinds)``. MiniCPM-SALA's ``S L8 S L6 S2 L4 S L6 S3`` is
    five rounds."""
    rounds, last = [], len(KIND_ORDER)
    for kind, first, count in cfg.layer_runs:
        j = KIND_ORDER.index(kind)
        if j <= last:
            rounds.append([(0, 0)] * len(KIND_ORDER))
        rounds[-1][j] = (first, count)
        last = j
    table = np.asarray(rounds, np.int32).reshape(-1, len(KIND_ORDER), 2)
    return table[..., 0], table[..., 1]


def _run_layers(params, cfg: ModelConfig, x, win, cache):
    """The layer loop: a ``lax.scan`` over :func:`layer_rounds`, inside it
    one loop a kind over the run's indices into the kind's own stack (its
    trip count the scanned run's length), (activations, cache) the carry.
    So a pass holds each kind's layer ONCE however many runs the published
    order has: a program is traced, lowered and compiled for two layer
    bodies a pass, not for one a run. A layer's weights are read out of
    the stacked group by a dynamic index, as a scan over the group would
    read them."""
    rs = jnp.asarray(cfg.residual_scale, x.dtype)

    def layer_of(kind):
        group = params["layers"][kind]

        def body(layer, carry):
            h, cache = carry
            lp = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, layer, keepdims=False),
                group)
            u = _norm(h, lp["ln1"], cfg)
            if kind == "sparse":
                mix, cache = _sparse(u, lp, cfg, win, cache, layer)
            else:
                mix, state = _lightning(u, lp, cfg, win, cache[2], layer)
                cache = cache[:2] + (state,) + cache[3:]
            h = h + rs * mix
            h = h + rs * _mlp(_norm(h, lp["ln2"], cfg), lp, cfg)
            return h, cache

        return body

    def one_round(carry, run):
        first, count = run
        for j, kind in enumerate(KIND_ORDER):
            carry = lax.fori_loop(first[j], first[j] + count[j],
                                  layer_of(kind), carry)
        return carry, None

    (x, cache), _ = lax.scan(one_round, (x, cache), layer_rounds(cfg))
    return x, cache


def _window(cfg: ModelConfig, qpos, mask, **more) -> dict:
    """What every layer of a pass reads about its window of queries."""
    sin, cos = _rope_sincos(qpos, cfg.lightning_head_dim, cfg.rope_theta)
    return dict(qpos=qpos, mask=mask, sin=sin, cos=cos, **more)


def tail_fits(cfg: ModelConfig, main_len: int, tail_len: int) -> bool:
    """Whether a cache of ``main_len`` main and ``tail_len`` tail slots can
    be attended: the tail inside the local window, or no query past
    ``dense_len`` at all (the engine's router asks before it splits a
    dispatch at a trunk)."""
    return (main_len + tail_len <= cfg.sparse_dense_len
            or tail_len <= cfg.sparse_window)


def _check_tail(cfg: ModelConfig, main_len: int, tail_len: int) -> bool:
    """Whether no query of this cache can lie past ``dense_len`` (static);
    a tail past the local window is refused where one can."""
    all_dense = main_len + tail_len <= cfg.sparse_dense_len
    if not tail_fits(cfg, main_len, tail_len):
        raise NotImplementedError(
            f"{cfg.name}: {tail_len} cache slots behind the main keys lie "
            f"past the local window ({cfg.sparse_window}); the selection "
            "runs over main keys only")
    return all_dense


# ---------------------------------------------------------------------------
# Entry points (models/decoder.py hands over to these)
# ---------------------------------------------------------------------------

def _fill(params, cfg: ModelConfig, tokens, attn_mask, tail_len: int):
    B, S = tokens.shape
    positions = decoder.mask_positions(attn_mask)
    x = decoder._embed(params, cfg, tokens, positions)
    cache = init_cache(cfg, B, S, tail_len, x.dtype)
    win = _window(cfg, positions, attn_mask, fill=True,
                  main_len=jnp.sum(attn_mask, axis=-1).astype(jnp.int32),
                  all_dense=S <= cfg.sparse_dense_len)
    x, cache = _run_layers(params, cfg, x, win, cache)
    return x, cache, positions


def forward(params, cfg: ModelConfig, tokens, attn_mask):
    x, _, _ = _fill(params, cfg, tokens, attn_mask, 0)
    return decoder._unembed(params, cfg, x)


def prefill(params, cfg: ModelConfig, tokens, attn_mask, max_len: int):
    _check_tail(cfg, tokens.shape[1], max_len - tokens.shape[1])
    x, cache, positions = _fill(params, cfg, tokens, attn_mask,
                                max_len - tokens.shape[1])
    logits = decoder._unembed(params, cfg, x[:, -1:, :])[:, 0, :]
    return logits, cache, positions[:, -1] + 1


def _over_cache(params, cfg: ModelConfig, cache, tokens, qpos, mask,
                cache_mask, key_positions, start_index):
    """A window of queries at cache slots ``[start_index, start_index +
    S)`` (tail slots) over the main keys and the tail."""
    Tm = cache[4].shape[3]
    Bm = cache[4].shape[1]
    all_dense = _check_tail(cfg, Tm, cache[0].shape[3])
    main_len = (jnp.full((1,), Tm, jnp.int32) if Bm == 1 else
                jnp.sum(cache_mask[:, :Tm], axis=-1).astype(jnp.int32))
    x = decoder._embed(params, cfg, tokens, qpos)
    win = _window(cfg, qpos, mask, fill=False, at=start_index - Tm,
                  main_len=main_len, all_dense=all_dense,
                  tail_mask=cache_mask[:, Tm:],
                  tail_pos=key_positions[:, Tm:])
    return _run_layers(params, cfg, x, win, cache)


def extend(params, cfg: ModelConfig, cache, suffix_tokens, suffix_mask,
           cache_mask, start_index):
    S2 = suffix_tokens.shape[1]
    key_positions = decoder.mask_positions(cache_mask)
    qpos = lax.dynamic_slice_in_dim(key_positions, start_index, S2, axis=1)
    x, cache = _over_cache(params, cfg, cache, suffix_tokens, qpos,
                           suffix_mask, cache_mask, key_positions,
                           start_index)
    last = jnp.maximum(jnp.sum(suffix_mask, axis=-1) - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
    logits = decoder._unembed(params, cfg, x_last)[:, 0, :]
    nxt = jnp.take_along_axis(qpos, last[:, None], axis=1)[:, 0] + 1
    return logits, cache, nxt


def cascade_extend(params, cfg: ModelConfig, trunk_cache, rem_tokens,
                   rem_mask, trunk_len: int, total_len: int):
    """The rows' remainder windows over ONE trunk: ``trunk_cache`` is
    :func:`prefill`'s of the trunk at one row, every slot real. The trunk's
    K/V and pooled keys stay as they are, one row, read by every row; its
    lightning state is every row's on entry."""
    B, R = rem_tokens.shape
    _, _, state, pooled, main_k, main_v = trunk_cache
    assert main_k.shape[1] == 1 and main_k.shape[3] == trunk_len
    tail = init_cache(cfg, B, 0, total_len - trunk_len, main_k.dtype)[0]
    cache = (tail, tail, jnp.broadcast_to(state, (state.shape[0], B)
                                          + state.shape[2:]),
             pooled, main_k, main_v)
    qpos = trunk_len + decoder.mask_positions(rem_mask)
    zeros = jnp.zeros((B, total_len - trunk_len - R), rem_mask.dtype)
    cache_mask = jnp.concatenate(
        [jnp.ones((B, trunk_len), rem_mask.dtype), rem_mask, zeros], axis=1)
    _, cache = _over_cache(params, cfg, cache, rem_tokens, qpos, rem_mask,
                           cache_mask, decoder.mask_positions(cache_mask),
                           trunk_len)
    return cache


def decode_step(params, cfg: ModelConfig, cache, token, position,
                step_index, prompt_mask):
    x, cache = _over_cache(
        params, cfg, cache, token[:, None], position[:, None],
        jnp.ones((token.shape[0], 1), jnp.int32), prompt_mask,
        decoder.mask_positions(prompt_mask), step_index)
    return decoder._unembed(params, cfg, x)[:, 0, :], cache
