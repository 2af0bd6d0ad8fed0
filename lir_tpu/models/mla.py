"""Latent attention (MLA: DeepSeek-V3, Kimi-K2; ``ModelConfig.kv_lora_rank``)
in front of the dense MLP or the expert layer (models/moe.py).

Each layer, on the normed input ``x``::

    c_q = RMSNorm(x W_qa)                         (q_lora_rank)
    [q_nope | q_pe]_h = c_q W_qb                  (qk_nope + qk_rope a head)
    [c | k_pe] = x W_kva;  c = RMSNorm(c)         (kv_lora_rank + qk_rope)
    [k_nope | v]_h = c W_kvb                      (qk_nope + v_head a head)
    score_h(i, j) = (q_nope . k_nope + q_pe . k_pe) * s,  s = softmax_scale
    out = concat_h(sum_j p v) W_o

``q_pe`` and ``k_pe`` are rotated (rotate-half, YaRN frequencies where
``rope_scaling`` says; decoder._rope_sincos), one ``k_pe`` for every head.

The cache holds ``[c | k_pe]`` alone, ``kv_lora_rank + qk_rope`` values a
token a layer, in three leaves::

    (tail,     (L, B, Tt, dl)   a row's own slots behind the main ones
     main,     (L, Bm, Tm, dl)  slot == position
     counts)   models/moe.RouteCounts: routing counts and the latent counters

``Bm`` is B after :func:`prefill` (each row's prefix is its main part) and 1
after :func:`cascade_extend` (the dispatch's shared trunk, held ONCE; the
rows' remainder windows, format suffixes and decoded tokens live in the
tail). Cache slot ``s`` of the engine's masks is main slot ``s`` below
``Tm`` and tail slot ``s - Tm`` from there, as models/mixed.py lays it out;
a row's main part is right-padded (its real slots lead).

A pass over new tokens (prefill, a window behind a trunk, a format
suffix) attends in the EXPANDED form: it expands the latent of every key
it reads into per-head keys and values inside the pass (scope
``lir.mla.expand``) and runs ops/latent_attention.prefill (``lir.mla.
prefill``). A decode step attends in the ABSORBED form against the latent
itself (``lir.mla.decode``): no per-head K/V is ever stored.

The counts leaf's ``latent`` (4,) int32 counts, summed over layers and
passes, in this order: key positions expanded, key positions the
dispatch's passes need (each once: the main part where a pass reads it
first, every row's own real keys), queries attended absorbed, queries
attended expanded (profiling.LatentStats).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import latent_attention as la
from . import decoder, moe
from .quant import QuantTensor, matmul as _mm, shared_quant as _shared_quant
from .registry import ModelConfig

def softmax_scale(cfg: ModelConfig) -> float:
    """``(qk_nope + qk_rope) ** -0.5``, times YaRN's ``mscale(factor,
    mscale_all_dim) ** 2`` where the rotation is stretched."""
    s = float(cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.rope_scaling:
        factor, *_, mscale_all_dim = cfg.rope_scaling
        s *= decoder.yarn_mscale(factor, mscale_all_dim) ** 2
    return s


def _kernels_lower(cfg: ModelConfig) -> bool:
    if jax.default_backend() == "tpu":
        return cfg.fused_decode
    return decoder.LATENT_INTERPRET_ON_CPU


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, main_len: int, tail_len: int,
               dtype=jnp.float32):
    """The three leaves, zero: ``batch`` rows of ``main_len`` main slots
    and of ``tail_len`` tail slots."""
    L, dl = cfg.n_layers, cfg.kv_lora_rank + cfg.qk_rope_dim
    return (jnp.zeros((L, batch, tail_len, dl), dtype),
            jnp.zeros((L, batch, main_len, dl), dtype),
            moe.empty_counts(cfg))


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    xf = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


def _project(h, lp, cfg: ModelConfig, sin, cos):
    """(q (B, S, H, qk_nope + qk_rope), latent (B, S, dl)) of the normed
    input h (B, S, D): the queries and the latent, both rotated parts
    rotated."""
    B, S, _ = h.shape
    H, dn, dr, r = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                    cfg.kv_lora_rank)
    hq = _shared_quant(h, lp["wq_a"], lp["wkv_a"])
    cq = _rms(_mm(hq, lp["wq_a"]), lp["q_a_norm"], cfg.norm_eps)
    q = _mm(cq, lp["wq_b"]).reshape(B, S, H, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], decoder._apply_rope(q[..., dn:], sin, cos, dr)], -1)
    kv = _mm(hq, lp["wkv_a"])
    c = _rms(kv[..., :r], lp["kv_a_norm"], cfg.norm_eps)
    k_pe = decoder._apply_rope(kv[..., None, r:], sin, cos, dr)[:, :, 0]
    return q, jnp.concatenate([c, k_pe.astype(c.dtype)], -1)


def _heads(w, cfg: ModelConfig):
    """W_kvb by head: (payload (r, H, qk_nope + v), scale (H, qk_nope + v)
    or None) of a QuantTensor or a plain matrix."""
    H = cfg.n_heads
    width = cfg.qk_nope_dim + cfg.v_head_dim
    if isinstance(w, QuantTensor):
        return (w.q.reshape(-1, H, width),
                w.scale.reshape(H, width).astype(jnp.float32))
    return w.reshape(-1, H, width), None


def _expand(latent, lp, cfg: ModelConfig):
    """Per-head keys (Bx, H, T, qk_nope + qk_rope) and values (Bx, H, T,
    v) of a latent (Bx, T, dl)."""
    Bx, T, _ = latent.shape
    H, dn, dv, r = (cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim,
                    cfg.kv_lora_rank)
    with jax.named_scope("lir.mla.expand"):
        kv = _mm(latent[..., :r], lp["wkv_b"]).reshape(Bx, T, H, dn + dv)
        k_pe = jnp.broadcast_to(latent[:, :, None, r:],
                                (Bx, T, H, latent.shape[-1] - r))
        k = jnp.concatenate([kv[..., :dn], k_pe.astype(kv.dtype)], -1)
        return k.transpose(0, 2, 1, 3), kv[..., dn:].transpose(0, 2, 1, 3)


def _absorb(q, lp, cfg: ModelConfig):
    """Absorbed queries (B, H, dl) of one query a row q (B, H, dq): the
    unrotated part through W_k,h^T into the latent's width, the rotated
    part as it is."""
    dn = cfg.qk_nope_dim
    w, scale = _heads(lp["wkv_b"], cfg)
    qn = q[..., :dn].astype(jnp.float32)
    if scale is not None:
        qn = qn * scale[:, :dn]
    q_lat = jnp.einsum("bhj,chj->bhc", qn.astype(q.dtype),
                       w[..., :dn].astype(q.dtype),
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat.astype(q.dtype), q[..., dn:]], -1)


def _unabsorb(o_lat, lp, cfg: ModelConfig, dtype):
    """The heads' outputs (B, H * v) from their latent-weighted sums
    o_lat (B, H, kv_lora_rank) float32: through W_v,h."""
    dn = cfg.qk_nope_dim
    w, scale = _heads(lp["wkv_b"], cfg)
    o = jnp.einsum("bhc,chj->bhj", o_lat.astype(dtype),
                   w[..., dn:].astype(dtype),
                   preferred_element_type=jnp.float32)
    if scale is not None:
        o = o * scale[:, dn:]
    return o.reshape(o.shape[0], -1).astype(dtype)


# ---------------------------------------------------------------------------
# The three ways a layer attends
# ---------------------------------------------------------------------------

def _attend_expanded(q, main_k, main_v, main_len, q_pos, own, cfg,
                     own_span: int):
    with jax.named_scope("lir.mla.prefill"):
        if _kernels_lower(cfg):
            return la.prefill(q, main_k, main_v, main_len, q_pos, own,
                              sm_scale=softmax_scale(cfg),
                              own_span=own_span,
                              interpret=jax.default_backend() != "tpu")
        return la.prefill_xla(q, main_k, main_v, main_len, q_pos, own,
                              sm_scale=softmax_scale(cfg))


def _fill_attend(win):
    """The prefill pass: the window IS the rows' main part; its latent is
    written to the main leaf and attended expanded."""
    def attend(h, lp, cfg, layer, cache):
        B, S, _ = h.shape
        q, latent = _project(h, lp, cfg, win["sin"], win["cos"])
        main = lax.dynamic_update_slice(
            cache[1], latent[None].astype(cache[1].dtype), (layer, 0, 0, 0))
        k, v = _expand(latent, lp, cfg)
        o = _attend_expanded(q.transpose(0, 2, 1, 3), k, v, win["main_len"],
                             win["qpos"], None, cfg, 0)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, -1)
        return _mm(o, lp["wo"]), (cache[0], main) + cache[2:], None

    return attend


def _over_attend(win, at: int):
    """A window of queries at tail slots ``[at, at + S)`` (``at`` static)
    over the main part and the tail slots before them, expanded. Over a
    shared trunk every row's queries ride together."""
    def attend(h, lp, cfg, layer, cache):
        B, S, _ = h.shape
        H = cfg.n_heads
        tail, main = cache[:2]
        Bm, To = main.shape[1], at + S
        q, latent = _project(h, lp, cfg, win["sin"], win["cos"])
        tail = lax.dynamic_update_slice(
            tail, latent[None].astype(tail.dtype), (layer, 0, at, 0))
        mk, mv = _expand(lax.dynamic_index_in_dim(main, layer,
                                                  keepdims=False), lp, cfg)
        own_latent = lax.dynamic_slice_in_dim(
            lax.dynamic_index_in_dim(tail, layer, keepdims=False), 0, To,
            axis=1)                                          # (B, To, dl)
        ok, ov = _expand(own_latent, lp, cfg)                # (B, H, To, .)
        qh = q.transpose(0, 2, 1, 3)                         # (B, H, S, dq)
        own_pos = jnp.where(win["tail_mask"][:, :To] > 0,
                            win["tail_pos"][:, :To], la.NO_KEY)
        rows = jnp.arange(B, dtype=jnp.int32)
        if Bm == 1 and B > 1:
            def flat(a):
                return a.transpose(1, 0, 2, 3).reshape(1, H, -1, a.shape[-1])

            qh, ok, ov = flat(qh), flat(ok), flat(ov)
            q_pos = win["qpos"].reshape(1, B * S)
            q_row = jnp.repeat(rows, S)[None]
            own = (ok, ov, own_pos.reshape(1, B * To),
                   jnp.repeat(rows, To)[None], q_row)
            bq = la.query_tile(B * S)
            span = min(B, (bq - 1) // S + 2) * To
        else:
            zeros = jnp.zeros((B, S), jnp.int32)
            q_pos = win["qpos"]
            own = (ok, ov, own_pos, jnp.zeros((B, To), jnp.int32), zeros)
            span = 0
        o = _attend_expanded(qh, mk, mv, win["main_len"], q_pos, own, cfg,
                             span)
        if Bm == 1 and B > 1:
            o = o[0].reshape(H, B, S, -1).transpose(1, 2, 0, 3)
        else:
            o = o.transpose(0, 2, 1, 3)
        o = o.reshape(B, S, -1)
        return _mm(o, lp["wo"]), (tail, main) + cache[2:], None

    return attend


def _decode_attend(win):
    """A decode step: the token's absorbed query attends the main part
    (the kernel, or XLA), the tail slots before it (XLA) and its own new
    key, merged; its latent is handed out, and :func:`decode_step` writes
    every layer's to the step's tail slot after the layer loop. Written
    inside the loop, the stacked tail went through two whole copies a
    layer (XLA laid the loop's carry out slot-major, the per-row product
    wanted it row-major)."""
    def attend(h, lp, cfg, layer, cache):
        tail, main = cache[:2]
        q, latent = _project(h, lp, cfg, win["sin"], win["cos"])
        latent = latent[:, 0].astype(tail.dtype)
        r, sm = cfg.kv_lora_rank, softmax_scale(cfg)
        with jax.named_scope("lir.mla.decode"):
            qa = _absorb(q[:, 0], lp, cfg)                   # (B, H, dl)
            if _kernels_lower(cfg):
                main_part = la.decode_main(
                    qa, main, win["main_len"], value_width=r, sm_scale=sm,
                    layer=layer, interpret=jax.default_backend() != "tpu")
            else:
                main_part = la.decode_main_xla(
                    qa, lax.dynamic_index_in_dim(main, layer,
                                                 keepdims=False),
                    win["main_len"], value_width=r, sm_scale=sm)
            own = la.decode_own(
                qa, lax.dynamic_index_in_dim(tail, layer, keepdims=False),
                win["tail_mask"], win["tail_pos"], win["qpos"][:, 0],
                value_width=r, sm_scale=sm)
            new = la.decode_self(qa, latent, value_width=r, sm_scale=sm)
            o = _unabsorb(la.merge(main_part, own, new), lp, cfg, h.dtype)
        return _mm(o[:, None], lp["wo"]), cache, latent

    return attend


# ---------------------------------------------------------------------------
# The layer loop
# ---------------------------------------------------------------------------

def _run_layers(params, cfg: ModelConfig, x, attend, cache, mask):
    """Every layer over x (B, S, D): one ``lax.scan`` a run of
    decoder._layer_runs (the dense layers, then the expert layers), the
    cache riding the carry, each layer updating its own layer of the
    stacked leaves where they lie. ``mask`` (B, S): the real tokens, the
    only ones an expert layer routes. ``attend(h, lp, cfg, layer, cache)
    -> (out, cache, y)``; returns (x, cache, the layers' ``y`` stacked,
    or None)."""
    def body_of(routed):
        def body(carry, xs):
            h, cache = carry
            lp = decoder._of_layer(params, cfg, xs, routed, None, None,
                                   None)[0]
            layer = xs[1]
            a, cache, y = attend(decoder._norm(h, lp["ln1"], cfg), lp, cfg,
                                 layer, cache)
            h = h + a
            u = decoder._norm(h, lp["ln2"], cfg)
            if routed:
                m, c = moe.expert_layer(u, lp, cfg, mask=mask)
                held = cache[2]
                cache = cache[:2] + (dataclasses.replace(
                    held, counts=held.counts.at[
                        layer - cfg.first_dense_layers].add(c)),)
            else:
                m = decoder._mlp(u, lp, cfg)
            return (h + m, cache), y

        return body

    ys = []
    for lp, first, routed in decoder._layer_runs(params, cfg):
        (x, cache), y = lax.scan(body_of(routed), (x, cache),
                                 decoder._run_xs(cfg, lp, first, True))
        ys.append(y)
    return x, cache, (None if ys[0] is None else jnp.concatenate(ys))


def _counted(cache, cfg: ModelConfig, expanded, needed, absorbed, queries):
    """The cache with one pass's latent counters added, every layer's."""
    held = cache[2]
    add = jnp.stack([jnp.asarray(n, jnp.int32) for n in
                     (expanded, needed, absorbed, queries)]) * cfg.n_layers
    return cache[:2] + (dataclasses.replace(held, latent=held.latent + add),)


def _window(cfg: ModelConfig, qpos, **more) -> dict:
    sin, cos = decoder._rope_sincos(qpos, cfg.qk_rope_dim, cfg.rope_theta,
                                    cfg.rope_scaling)
    return dict(qpos=qpos, sin=sin, cos=cos, **more)


# ---------------------------------------------------------------------------
# Entry points (models/decoder.py hands over to these)
# ---------------------------------------------------------------------------

def _fill(params, cfg: ModelConfig, tokens, attn_mask, tail_len: int):
    B, S = tokens.shape
    positions = decoder.mask_positions(attn_mask)
    x = decoder._embed(params, cfg, tokens, positions)
    cache = init_cache(cfg, B, S, tail_len, x.dtype)
    real = jnp.sum(attn_mask, axis=-1).astype(jnp.int32)
    win = _window(cfg, positions, main_len=real)
    x, cache, _ = _run_layers(params, cfg, x, _fill_attend(win), cache,
                              attn_mask)
    n = jnp.sum(real)
    return x, _counted(cache, cfg, B * S, n, 0, n), positions


def forward(params, cfg: ModelConfig, tokens, attn_mask):
    x, _, _ = _fill(params, cfg, tokens, attn_mask, 0)
    return decoder._unembed(params, cfg, x)


def prefill(params, cfg: ModelConfig, tokens, attn_mask, max_len: int):
    x, cache, positions = _fill(params, cfg, tokens, attn_mask,
                                max_len - tokens.shape[1])
    logits = decoder._unembed(params, cfg, x[:, -1:, :])[:, 0, :]
    return logits, cache, positions[:, -1] + 1


def _over_cache(params, cfg: ModelConfig, cache, tokens, qpos, mask,
                cache_mask, key_positions, attend_of):
    Tm = cache[1].shape[2]
    Bm = cache[1].shape[1]
    x = decoder._embed(params, cfg, tokens, qpos)
    win = _window(cfg, qpos,
                  main_len=jnp.sum(cache_mask[:Bm, :Tm], -1).astype(
                      jnp.int32),
                  tail_mask=cache_mask[:, Tm:],
                  tail_pos=key_positions[:, Tm:])
    return _run_layers(params, cfg, x, attend_of(win, Tm), cache, mask)


def _extend_pass(params, cfg: ModelConfig, cache, tokens, mask, cache_mask,
                 start_index: int, first: bool):
    """A window of ``tokens`` (B, S) RIGHT-padded at cache slots
    ``[start_index, start_index + S)`` (static, past the main part).
    ``first``: the pass is the first of its dispatch to read the main
    part (its keys count as needed once)."""
    B, S = tokens.shape
    Bm, Tm = cache[1].shape[1], cache[1].shape[2]
    key_positions = decoder.mask_positions(cache_mask)
    qpos = lax.dynamic_slice_in_dim(key_positions, start_index, S, axis=1)
    at = int(start_index) - Tm
    x, cache, _ = _over_cache(params, cfg, cache, tokens, qpos, mask,
                              cache_mask, key_positions,
                              lambda win, Tm: _over_attend(win, at))
    n = jnp.sum(mask)
    main_real = jnp.sum(cache_mask[:Bm, :Tm])
    cache = _counted(cache, cfg, Bm * Tm + B * (at + S),
                     n + (main_real if first else 0), 0, n)
    return x, cache, qpos


def extend(params, cfg: ModelConfig, cache, suffix_tokens, suffix_mask,
           cache_mask, start_index):
    x, cache, qpos = _extend_pass(params, cfg, cache, suffix_tokens,
                                  suffix_mask, cache_mask, start_index,
                                  False)
    last = jnp.maximum(jnp.sum(suffix_mask, axis=-1) - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
    logits = decoder._unembed(params, cfg, x_last)[:, 0, :]
    nxt = jnp.take_along_axis(qpos, last[:, None], axis=1)[:, 0] + 1
    return logits, cache, nxt


def cascade_extend(params, cfg: ModelConfig, trunk_cache, rem_tokens,
                   rem_mask, trunk_len: int, total_len: int):
    """The rows' remainder windows over ONE trunk: ``trunk_cache`` is
    :func:`prefill`'s of the trunk at one row, every slot real; its latent
    stays as it is, one row, read by every row."""
    B, R = rem_tokens.shape
    tail0, main, counts = trunk_cache
    assert main.shape[1] == 1 and main.shape[2] == trunk_len
    tail = jnp.zeros((main.shape[0], B, total_len - trunk_len,
                      main.shape[3]), main.dtype)
    zeros = jnp.zeros((B, total_len - trunk_len - R), rem_mask.dtype)
    cache_mask = jnp.concatenate(
        [jnp.ones((B, trunk_len), rem_mask.dtype), rem_mask, zeros], axis=1)
    _, cache, _ = _extend_pass(params, cfg, (tail, main, counts), rem_tokens,
                               rem_mask, cache_mask, trunk_len, True)
    return cache


def decode_step(params, cfg: ModelConfig, cache, token, position,
                step_index, prompt_mask):
    B = token.shape[0]
    Tm = cache[1].shape[2]
    # The new token's slot is attended as its own key (mla._decode_attend),
    # not through the tail, which it reaches after the layer loop.
    x, cache, latents = _over_cache(
        params, cfg, cache, token[:, None], position[:, None],
        jnp.ones((B, 1), jnp.int32), prompt_mask.at[:, step_index].set(0),
        decoder.mask_positions(prompt_mask),
        lambda win, Tm: _decode_attend(win))
    tail = lax.dynamic_update_slice(cache[0], latents[:, :, None],
                                    (0, 0, step_index - Tm, 0))
    cache = _counted((tail,) + cache[1:], cfg, 0, 0, B, 0)
    return decoder._unembed(params, cfg, x)[:, 0, :], cache
