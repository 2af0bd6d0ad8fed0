"""Unified functional decoder-only transformer.

One forward covers the whole reference model zoo (SURVEY.md §2.6): GPT-2, the
GPT-NeoX family (pythia / dolly-v2 / stablelm-alpha / RedPajama / h2ogpt),
Llama-2 / Mistral / Qwen / Baichuan2, Falcon (MQA + shared-LN parallel block),
Bloom (ALiBi + embedding LayerNorm) and OPT — selected purely by
``registry.ModelConfig`` knobs. The reference reaches these architectures via
``transformers`` torch classes (analysis/compare_base_vs_instruct.py:423-455);
here they are a single JAX program so XLA can fuse and shard them.

Design (TPU-first):
- Layers are STACKED along a leading axis and iterated with ``lax.scan`` —
  one compiled block body regardless of depth, fast compiles, remat-friendly.
- Params/activations run in the param dtype (bf16 on TPU); softmax and the
  final logits are computed in fp32 (SURVEY.md §7 hard part 3).
- KV-cache prefill/decode split so scoring can capture per-step logits
  (the C13 measurement primitive, compare_base_vs_instruct.py:185-305).
- No data-dependent Python control flow below ``jit``; masks make padding a
  no-op so the whole scoring grid runs at fixed shapes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .registry import ModelConfig
from .quant import (QuantTensor, dynamic_quant as _quant_kv, matmul as _mm,
                    shared_quant as _shared_quant)

Params = Dict[str, Any]

# Test hook: when True, the flash-attention route also engages on CPU with
# the Pallas interpreter, so the DECODER-LEVEL routing (mask plumbing, ALiBi
# slopes/positions wiring) is testable without a chip. Production leaves
# this False: CPU runs dense.
FLASH_INTERPRET_ON_CPU = False

# Same hook for the fused flash-decode kernel (ops/flash_decode): tier-1
# exercises the decode-step routing under the Pallas interpreter on CPU;
# production CPU runs dense, production TPU runs the kernel compiled
# (cfg.fused_decode, default on; RuntimeConfig.fused_decode opts out).
FUSED_DECODE_INTERPRET_ON_CPU = False

# Same hook for the shared-prefix cascade-prefill kernel
# (ops/cascade_prefill): tier-1 and the cascade smoke run the prefix-leg
# Pallas kernel under the interpreter on CPU; production CPU dispatches
# stay on the dense shared path (the engine's cascade routing checks this
# hook, runner.ScoringEngine.cascade_supported).
CASCADE_INTERPRET_ON_CPU = False

# Same hook for the selective-scan kernels (ops/ssd_scan): tier-1 runs the
# mixer's chunked scan and single-token update under the interpreter on
# CPU; production CPU runs the recurrence token by token in XLA.
SSM_INTERPRET_ON_CPU = False

# Same hook for the block-sparse attention kernel (ops/sparse_attention)
# of a model whose softmax layers select blocks (models/mixed.py).
SPARSE_INTERPRET_ON_CPU = False


# ---------------------------------------------------------------------------
# Param init (random weights for tests; real weights come from models/loader.py)
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    """Random-normal init with the exact tree layout the loader fills."""
    if cfg.layer_kinds:
        from . import mixed

        return mixed.init_params(cfg, key, dtype)
    k = iter(jax.random.split(key, 64))
    D, H, K, hd, F, L = (cfg.hidden_size, cfg.n_heads, cfg.n_kv_heads,
                         cfg.head_dim, cfg.intermediate_size, cfg.n_layers)

    def w(*shape, scale=0.02):
        return (scale * jax.random.normal(next(k), shape)).astype(dtype)

    def norm_p(*lead) -> Params:
        p = {"scale": jnp.ones((*lead, D), dtype)}
        if cfg.norm == "layernorm":
            p["bias"] = jnp.zeros((*lead, D), dtype)
        return p

    layers: Params = {
        "ln1": norm_p(L),
        "wq": w(L, D, H * hd), "wk": w(L, D, K * hd), "wv": w(L, D, K * hd),
        "wo": w(L, H * hd, D),
        "w_up": w(L, D, F), "w_down": w(L, F, D),
    }
    if not cfg.shared_block_ln:
        layers["ln2"] = norm_p(L)
    if cfg.gated_mlp:
        layers["w_gate"] = w(L, D, F)
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((L, H * hd), dtype)
        layers["bk"] = jnp.zeros((L, K * hd), dtype)
        layers["bv"] = jnp.zeros((L, K * hd), dtype)
    if cfg.attn_out_bias:
        layers["bo"] = jnp.zeros((L, D), dtype)
    if cfg.mlp_bias:
        layers["b_up"] = jnp.zeros((L, F), dtype)
        layers["b_down"] = jnp.zeros((L, D), dtype)
    if cfg.has_mixer:
        Hs, C = cfg.ssm_heads, cfg.ssm_conv_dim
        layers["w_in"] = w(L, D, cfg.ssm_in_width)
        layers["w_out"] = w(L, cfg.ssm_inner, D)
        layers["conv_w"] = w(L, cfg.ssm_conv, C, scale=0.5)
        layers["conv_b"] = w(L, C, scale=0.1)
        layers["a_log"] = jnp.log(jax.random.uniform(
            next(k), (L, Hs), minval=1.0, maxval=16.0)).astype(dtype)
        layers["ssm_d"] = jnp.ones((L, Hs), dtype)
        layers["dt_bias"] = w(L, Hs, scale=0.5)
        layers["ssm_norm"] = jnp.ones((L, cfg.ssm_inner), dtype)

    params: Params = {"tok_embed": w(cfg.vocab_size, D, scale=0.02), "layers": layers}
    if cfg.pos_embedding == "learned":
        params["pos_embed"] = w(cfg.max_seq_len + cfg.learned_pos_offset, D)
    if cfg.embedding_norm:
        params["embed_ln"] = {"scale": jnp.ones((D,), dtype),
                              "bias": jnp.zeros((D,), dtype)}
    if cfg.final_norm:
        params["final_ln"] = norm_p()
    if not cfg.tie_embeddings:
        params["lm_head"] = w(D, cfg.vocab_size)
    return params


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def _norm(x: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.norm_eps)
        return (xf * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    xf = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
    out = xf * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def _act(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        # The exact GELU as torch's nn.GELU() writes it, through erf in
        # float32: jax.nn.gelu(approximate=False) is 0.5*x*erfc(-x/sqrt 2),
        # and XLA expands erfc into both its branches (~70 vector ops an
        # element in the up-projection's epilogue); erf stays one op.
        xf = x.astype(jnp.float32)
        return (0.5 * xf * (1 + lax.erf(xf * math.sqrt(0.5)))).astype(x.dtype)
    if kind == "gelu_new":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.relu(x)


def _rope_sincos(positions: jax.Array, rotary_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """sin/cos tables for rotate-half RoPE. positions: (..., S) int."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., S, rd/2)
    return jnp.sin(angles), jnp.cos(angles)


def _apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array, rotary_dim: int) -> jax.Array:
    """x: (B, S, nH, hd); rotate-half convention (HF llama/neox/falcon)."""
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    x1, x2 = rot[..., : rotary_dim // 2], rot[..., rotary_dim // 2:]
    sin = sin[:, :, None, :].astype(x.dtype)   # (B, S, 1, rd/2)
    cos = cos[:, :, None, :].astype(x.dtype)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out, rest], axis=-1) if rest.shape[-1] else out


def alibi_slopes(n_heads: int) -> jax.Array:
    """ALiBi per-head slopes (bloom). Matches HF build_alibi_tensor."""
    closest = 2 ** math.floor(math.log2(n_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(closest) - 3)))
    slopes = [base ** (i + 1) for i in range(closest)]
    if closest != n_heads:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * closest) - 3)))
        slopes += [extra_base ** (2 * i + 1) for i in range(n_heads - closest)]
    return jnp.asarray(slopes, dtype=jnp.float32)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array, bias: jax.Array,
               cfg: ModelConfig,
               key_mask: Optional[jax.Array] = None) -> jax.Array:
    """q: (B,S,H,hd); k,v: (B,T,K,hd); bias: (B,H|1,S,T) additive fp32.

    With ``cfg.use_flash_attention``, full-sequence self-attention (the
    prefill) routes through the Pallas flash kernel, masking keys with the
    batch's actual attention mask (any padding pattern); ALiBi families
    (bloom) pass their per-head slopes + mask-aware key positions into the
    kernel. Decode steps keep the dense path ON PURPOSE: a decode query is
    one position, so its score row is (B, H, 1, T) — already O(T) memory
    with no (S, T) tile to avoid; a flash kernel would only add launch
    overhead per step. Non-block-divisible lengths also fall back dense."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K

    from ..ops.flash_attention import (
        DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_attention,
    )

    block = max(DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
    flash_ok = (
        cfg.use_flash_attention
        and key_mask is not None
        and k.shape[1] == S
        # Blocks shrink to S when S <= block, so every power-of-two bucket
        # (64..1024) qualifies; only ragged lengths fall back dense.
        and (S % block == 0 or S <= block)
        # Pallas lowers on TPU only; CPU (tests, virtual meshes) runs dense
        # unless the interpreter test hook is on.
        and (jax.default_backend() == "tpu" or FLASH_INTERPRET_ON_CPU)
    )
    if flash_ok:
        if K != H:  # the Pallas kernel wants per-query-head k/v
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
        interpret = (FLASH_INTERPRET_ON_CPU
                     and jax.default_backend() != "tpu")
        if cfg.pos_embedding == "alibi":
            out = flash_attention(
                q, k, v, causal=True, key_mask=key_mask,
                alibi_slopes=alibi_slopes(cfg.n_heads),
                key_positions=mask_positions(key_mask),
                interpret=interpret)
        else:
            out = flash_attention(q, k, v, causal=True, key_mask=key_mask,
                                  interpret=interpret)
        return out.reshape(B, S, H * hd)

    # GQA/MQA contracts GROUPED query heads against the UN-REPEATED k/v
    # (same h = k*G + g convention as _attention_cached): repeating k/v to
    # H heads would materialize an H/K-times copy inside every layer of
    # the prefill scan — ~600 MB transient per layer for falcon's 71:1
    # MQA at batch 32 / seq 1024.
    T = k.shape[1]
    qg = q.reshape(B, S, K, G, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    scores = scores.reshape(B, H, S, T) / math.sqrt(hd) + bias
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    pg = probs.reshape(B, K, G, S, T)
    out = jnp.einsum("bkgst,btkd->bskgd", pg, v)
    return out.reshape(B, S, H * hd)


def _attention_cached_int8(q: jax.Array, kq, ks, vq, vs,
                           bias: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Decode-step attention over the int8 cache (payload (K, T, B, hd) +
    scales (K, T, B)). All dots run s8 x s8 -> s32 on the MXU: the query
    and the value-scale-folded probabilities are quantized dynamically
    per vector, so neither a bf16 copy of the cache nor one of the weights
    ever materializes. Softmax stays fp32.

    GQA/MQA contracts GROUPED query heads against the un-repeated cache
    (q reshaped to (B, S, K, G, hd)) — repeating the cache K -> H would
    materialize an H/K-times copy of the whole cache inside the decode
    loop, giving back the HBM the int8 cache exists to save.
    """
    B, S, H, hd = q.shape
    K = kq.shape[0]
    G = H // K
    qq, qs = _quant_kv(q)                                   # (B,S,H,hd),(B,S,H)
    qq = qq.reshape(B, S, K, G, hd)
    s32 = jnp.einsum("bskgd,ktbd->bkgst", qq, kq,
                     preferred_element_type=jnp.int32)
    scores = (s32.astype(jnp.float32).reshape(B, H, S, -1)
              * qs.transpose(0, 2, 1)[:, :, :, None]        # (B,H,S,1)
              * jnp.repeat(ks.transpose(2, 0, 1), G, axis=1)[:, :, None, :])
    scores = scores / math.sqrt(hd) + bias
    probs = jax.nn.softmax(scores, axis=-1)                 # fp32 (B,H,S,T)
    # Fold v scales in, then dynamically quantize the weighted probs.
    pw = probs * jnp.repeat(vs.transpose(2, 0, 1), G, axis=1)[:, :, None, :]
    pq, ps = _quant_kv(pw)                                  # (B,H,S,T),(B,H,S)
    pq = pq.reshape(B, K, G, S, -1)
    o32 = jnp.einsum("bkgst,ktbd->bskgd", pq, vq,
                     preferred_element_type=jnp.int32)
    out = (o32.astype(jnp.float32).reshape(B, S, H, hd)
           * ps.transpose(0, 2, 1)[..., None])
    return out.astype(q.dtype).reshape(B, S, H * hd)


def _decode_kernels_lower(batch: int) -> bool:
    """Where the fused decode kernels lower. On the TPU backend the
    static rule is ``batch % 8 == 0``: ops/flash_decode's K/V blocks
    carry whole sublane groups of cache rows (the cache's (B, hd) minor
    pair is the TPU's register tile), so the power-of-two tail batches
    below one group (1, 2, 4) decode dense. CPU runs them only under the
    interpreter test hook, at any batch."""
    if jax.default_backend() == "tpu":
        return batch % 8 == 0
    return FUSED_DECODE_INTERPRET_ON_CPU


def _fused_decode_ok(cfg: ModelConfig, batch: int, S: int,
                     fused_ctx) -> bool:
    """Static routing decision for the fused flash-decode kernel: a single-
    query decode step, a non-int8 cache, the flag on, and a backend and
    batch the kernel lowers for (:func:`_decode_kernels_lower`)."""
    return (cfg.fused_decode
            and not cfg.kv_cache_int8
            and fused_ctx is not None
            and S == 1
            and _decode_kernels_lower(batch))


def _attention_cached_flash(q: jax.Array, k: jax.Array, v: jax.Array,
                            cfg: ModelConfig, fused_ctx,
                            trunk_len: int = 0, layer=None) -> jax.Array:
    """Decode-step attention through the fused Pallas flash-decode kernel
    (ops/flash_decode): the (B, H, 1, T) score row, fp32 softmax, and
    probability row stay in VMEM instead of round-tripping HBM between
    three XLA kernels. Same cache layout (K, T, B, hd), same GQA/MQA
    grouped contraction against the un-repeated cache, same masking
    semantics as :func:`_attention_cached` (pinned by tests/
    test_kernels.py); ALiBi rides per-head slopes + mask-aware key
    positions exactly like the prefill flash kernel. ``k`` / ``v`` are
    the STACKED cache sides (L, K, T, B, hd) and ``layer`` the layer to
    attend over: the kernel picks that layer's blocks out of the stacked
    operand, so the layer loop never slices it out.

    ``trunk_len`` > 0 (a shared-trunk dispatch with cascade decode on)
    routes through the trunk-aware variant: the cache's leading
    ``trunk_len`` slots are identical across rows, so the trunk splits
    read K/V from the first batch block ONCE per kv head for all rows'
    queries — the flat kernel's arithmetic exactly (the split ladder,
    per-split arithmetic and merge are unchanged; only the trunk tiles'
    HBM reads dedup)."""
    from ..ops.flash_decode import flash_decode, flash_decode_trunk

    B, S, H, hd = q.shape
    q_pos, key_mask, key_positions = fused_ctx
    interpret = (FUSED_DECODE_INTERPRET_ON_CPU
                 and jax.default_backend() != "tpu")
    slopes = (alibi_slopes(cfg.n_heads) if cfg.pos_embedding == "alibi"
              else None)
    if trunk_len > 0:
        out = flash_decode_trunk(q[:, 0], k, v, q_pos, key_mask,
                                 key_positions=key_positions,
                                 alibi_slopes=slopes, trunk_len=trunk_len,
                                 interpret=interpret, layer=layer)
    else:
        out = flash_decode(q[:, 0], k, v, q_pos, key_mask,
                           key_positions=key_positions, alibi_slopes=slopes,
                           interpret=interpret, layer=layer)
    return out.reshape(B, S, H * hd)


def _fused_decode_mq_ok(cfg: ModelConfig, batch: int, S: int,
                        fused_ctx) -> bool:
    """Static routing decision for the MULTI-QUERY fused decode kernel
    (the speculative verify window): same gates as :func:`_fused_decode_ok`
    but for a window of S > 1 teacher-forced queries carrying per-query
    positions (fused_ctx positions shaped (B, S))."""
    return (cfg.fused_decode
            and not cfg.kv_cache_int8
            and fused_ctx is not None
            and S > 1
            and getattr(fused_ctx[0], "ndim", 1) == 2
            and _decode_kernels_lower(batch))


def _attention_cached_flash_mq(q: jax.Array, k: jax.Array, v: jax.Array,
                               cfg: ModelConfig, fused_ctx,
                               trunk_len: int = 0, layer=None) -> jax.Array:
    """Verify-window attention through the multi-query fused kernel
    (ops/flash_decode.flash_decode_mq): S teacher-forced queries per row
    attend over the cache (the window's own k/v already written) in one
    launch, each query's reduction bitwise the single-query kernel's —
    the speculative verify path's decode-step parity contract.
    ``trunk_len`` > 0 routes the trunk-aware sibling so PR-13
    speculative verify windows ride the trunk-split dedup too (see
    :func:`_attention_cached_flash`, also for the stacked ``k`` / ``v``
    and ``layer``)."""
    from ..ops.flash_decode import flash_decode_mq, flash_decode_mq_trunk

    B, S, H, hd = q.shape
    q_pos, key_mask, key_positions = fused_ctx
    interpret = (FUSED_DECODE_INTERPRET_ON_CPU
                 and jax.default_backend() != "tpu")
    slopes = (alibi_slopes(cfg.n_heads) if cfg.pos_embedding == "alibi"
              else None)
    if trunk_len > 0:
        out = flash_decode_mq_trunk(q, k, v, q_pos, key_mask,
                                    key_positions=key_positions,
                                    alibi_slopes=slopes,
                                    trunk_len=trunk_len,
                                    interpret=interpret, layer=layer)
    else:
        out = flash_decode_mq(q, k, v, q_pos, key_mask,
                              key_positions=key_positions,
                              alibi_slopes=slopes, interpret=interpret,
                              layer=layer)
    return out.reshape(B, S, H * hd)


def _attention_cascade(q: jax.Array, k: jax.Array, v: jax.Array,
                       trunk_kv: Tuple[jax.Array, jax.Array],
                       suffix_mask: jax.Array, q_positions: jax.Array,
                       cfg: ModelConfig, int8_qk: bool) -> jax.Array:
    """Cascade-aware sibling of :func:`_attention_cached` for the
    shared-trunk PREFILL window (ops/cascade_prefill): the dispatch's
    remainder queries split into a prefix leg over the single-row shared
    trunk KV (one inter-query-batched dense matmul per kv head, int8
    QK^T optional) and a per-row causal suffix leg over the window's own
    k/v, merged by the flash split-K log-sum-exp rule (ops/lse). Same
    grouped GQA contraction against un-repeated k/v, same ALiBi
    key-position convention as every other attention route here. q:
    (B, R, H, hd); k/v: (B, R, K, hd) post-RoPE window k/v; trunk_kv:
    (K, Tt, hd) pair."""
    from ..ops.cascade_prefill import cascade_attention

    B, R, H, hd = q.shape
    interpret = jax.default_backend() != "tpu"
    slopes = (alibi_slopes(cfg.n_heads) if cfg.pos_embedding == "alibi"
              else None)
    tk, tv = trunk_kv
    out = cascade_attention(q, k, v, tk, tv, suffix_mask, q_positions,
                            alibi_slopes=slopes, int8_qk=int8_qk,
                            interpret=interpret,
                            fused_suffix=cfg.cascade_fused_suffix)
    return out.reshape(B, R, H * hd)


def _attention_cached(q: jax.Array, k: jax.Array, v: jax.Array,
                      bias: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Decode-step attention over the CACHE layout (K, T, B, hd).

    The cache is stored head-major/batch-minor on purpose: it is the
    layout XLA's decode while-loop prefers for these dots, so the loop
    carry aliases the prefill output instead of inserting two full-cache
    layout copies (measured 2x 2.08 GiB at 7B batch 32 — the difference
    between fitting a chip and OOM; see SCALE.md). q: (B, S=1, H, hd);
    k, v: one layer of the stacked cache, read by the caller with
    ``lax.dynamic_index_in_dim`` (a read XLA may fuse into these dots).
    GQA/MQA contracts grouped query heads against the un-repeated cache
    (see _attention_cached_int8).
    """
    B, S, H, hd = q.shape
    K = k.shape[0]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    scores = jnp.einsum("bskgd,ktbd->bkgst", qg, k).astype(jnp.float32)
    scores = scores.reshape(B, H, S, -1) / math.sqrt(hd) + bias
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    pg = probs.reshape(B, K, G, S, -1)
    out = jnp.einsum("bkgst,ktbd->bskgd", pg, v)
    return out.reshape(B, S, H * hd)


def _ssm_kernels_lower(cfg: ModelConfig) -> bool:
    """Where the selective-scan kernels run: the TPU backend of an engine
    whose Pallas routes are on (``cfg.fused_decode`` is what a sharded
    engine turns off: GSPMD cannot partition a Mosaic call), or CPU
    under the interpreter test hook."""
    if jax.default_backend() == "tpu":
        return cfg.fused_decode
    return SSM_INTERPRET_ON_CPU


def _ssm_columns(cfg: ModelConfig) -> Optional[jax.Array]:
    """The per-column muP vector over the input projection's output
    (z | x | B | C | dt), or None when every multiplier is 1."""
    if all(m == 1.0 for m in cfg.ssm_multipliers):
        return None
    gn = cfg.ssm_groups * cfg.ssm_state
    widths = (cfg.ssm_inner, cfg.ssm_inner, gn, gn, cfg.ssm_heads)
    return jnp.concatenate([jnp.full((n,), m, jnp.float32) for n, m in
                            zip(widths, cfg.ssm_multipliers)])


def _mixer(h: jax.Array, lp: Params, cfg: ModelConfig, state: jax.Array,
           tail: jax.Array, mask: Optional[jax.Array], layer=None):
    """Mamba-2 mixer over h (B, S, D), the block's normed input.

    ``state`` (B, Hs, P, N) float32 and ``tail`` (B, conv - 1, C), the
    conv's last inputs, are the row's recurrent state on entry; returned
    as they stand after each row's LAST REAL token. ``mask`` (B, S) marks
    real slots (None: all). A masked slot is a no-op on both: its conv
    input is zeroed (left padding then reads like the empty history) and
    its step size is zeroed (decay 1, nothing added), so the state a
    right-padded row hands on is the state at its own end, and the tail
    is gathered there. Returns (out (B, S, D), state, tail).

    With ``layer`` (the layer loop over a cache), ``state`` and ``tail``
    are the cache's STACKED leaves (L, ...) and come back whole, layer
    ``layer`` of each updated where it lies: the scan kernels read and
    write that layer's blocks of the stacked state in place
    (ops/ssd_scan), the tail (0.3 MB a layer) is read and written with a
    dynamic slice. Only the token-by-token fallback (CPU, a sharded
    engine) takes the layer's state out and puts it back."""
    from ..ops import ssd_scan as scan_ops

    B, S, _ = h.shape
    Hs, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    inner, gn, taps = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state, cfg.ssm_conv
    f32 = jnp.float32

    if cfg.ssm_in_multiplier != 1.0:
        h = h * jnp.asarray(cfg.ssm_in_multiplier, h.dtype)
    proj = _mm(h, lp["w_in"])
    mu = _ssm_columns(cfg)
    if mu is not None:
        proj = (proj * mu).astype(h.dtype)
    z, xbc, dt = (proj[..., :inner], proj[..., inner:inner + cfg.ssm_conv_dim],
                  proj[..., inner + cfg.ssm_conv_dim:])
    if mask is not None:
        xbc = xbc * mask[:, :, None].astype(xbc.dtype)

    # Depthwise causal conv over [tail | window], then SiLU.
    stacked = layer is not None
    own = (lax.dynamic_index_in_dim(tail, layer, keepdims=False) if stacked
           else tail)
    seq = jnp.concatenate([own.astype(xbc.dtype), xbc], axis=1)
    conv = lp["conv_b"].astype(f32)
    for k in range(taps):
        conv = conv + seq[:, k:k + S].astype(f32) * lp["conv_w"][k].astype(f32)
    xbc = jax.nn.silu(conv).astype(h.dtype)
    if mask is None:
        own = seq[:, S:]
    else:
        # The taps - 1 inputs ending at each row's last real slot (slot
        # -1, the carried tail itself, for a row with none).
        slots = jnp.arange(S, dtype=jnp.int32)
        last = jnp.max(jnp.where(mask > 0, slots, -1), axis=1)       # (B,)
        idx = last[:, None] + 1 + jnp.arange(taps - 1, dtype=jnp.int32)
        own = jnp.take_along_axis(seq, idx[:, :, None], axis=1)
    tail = (lax.dynamic_update_index_in_dim(tail, own.astype(tail.dtype),
                                            layer, 0) if stacked else own)

    x = xbc[..., :inner].reshape(B, S, Hs, P)
    bm = xbc[..., inner:inner + gn].reshape(B, S, G, N)
    cm = xbc[..., inner + gn:].reshape(B, S, G, N)
    dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
    if mask is not None:
        dt = dt * mask[:, :, None].astype(f32)
    a = -jnp.exp(lp["a_log"].astype(f32))

    kernels = _ssm_kernels_lower(cfg)
    interpret = jax.default_backend() != "tpu"
    if S == 1 and kernels:
        y, state = scan_ops.ssm_step(x[:, 0], dt[:, 0], a, bm[:, 0],
                                     cm[:, 0], state, interpret=interpret,
                                     layer=layer)
        y = y[:, None]
    elif kernels:
        y, state = scan_ops.ssd_scan(x, dt, a, bm, cm, state,
                                     chunk=cfg.ssm_chunk,
                                     interpret=interpret, layer=layer)
    elif stacked:
        y, new = scan_ops.ssd_scan_tokens(
            x, dt, a, bm, cm,
            lax.dynamic_index_in_dim(state, layer, keepdims=False))
        state = lax.dynamic_update_index_in_dim(state, new, layer, 0)
    else:
        y, state = scan_ops.ssd_scan_tokens(x, dt, a, bm, cm, state)
    y = y.astype(f32) + lp["ssm_d"].astype(f32)[:, None] * x.astype(f32)

    # Gate, then RMSNorm over each group of heads on its own (the
    # published ``mamba_norm_before_gate`` false, ``mamba_rms_norm`` true).
    y = y.reshape(B, S, inner) * jax.nn.silu(z.astype(f32))
    yg = y.reshape(B, S, G, inner // G)
    yg = yg * lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                        + cfg.norm_eps)
    y = (yg.reshape(B, S, inner) * lp["ssm_norm"].astype(f32)).astype(h.dtype)
    return _mm(y, lp["w_out"]), state, tail


def _block(x: jax.Array, lp: Params, cfg: ModelConfig, sin, cos,
           bias: jax.Array, cache=None, layer=None,
           cache_index: Optional[jax.Array] = None,
           key_mask: Optional[jax.Array] = None,
           attn_impl=None, fused_ctx=None, trunk_len: int = 0,
           rec=None, rec_mask: Optional[jax.Array] = None):
    """One transformer block, in one of two forms.

    Over a cache (``extend``, ``verify_extend``, ``decode_step``):
    ``cache`` is the whole STACKED cache as :func:`init_cache` lays it
    out and ``layer`` this block's (traced) index into it. The block
    writes the window's k/v into its layer's slots ``[cache_index,
    cache_index + S)`` with one ``dynamic_update_slice`` on the stacked
    buffer, attends over that layer (the fused kernels index it inside
    the stacked operand; the dense routes read it with a dynamic index),
    moves a mixer's state of that layer in place (:func:`_mixer`), and
    returns (new_x, cache): the same buffers, nothing of them copied or
    re-stacked.

    Without one (``forward``, ``prefill``, ``cascade_extend``): returns
    (new_x, (k, v)), the window's own post-rope k/v (B, S, K, hd) for the
    caller to lay into a cache; a model with a state-space mixer
    (``cfg.has_mixer``) starts from ``rec``, this layer's (SSM state,
    conv tail) on entry (None: the empty history), and returns (new_x,
    (k, v, state, tail)). ``rec_mask`` (B, S) marks the window's real
    slots for the mixer in both forms (None: all).

    ``attn_impl(q, k, v, key_mask) -> (B, S, H*hd)`` replaces dense
    attention when given (the sequence-parallel path, parallel/seq_forward);
    it owns causality/ALiBi itself, so ``bias`` may be None then.
    ``fused_ctx`` — a (query positions (B,), cache mask (B, T), cache
    key positions (B, T)) triple — arms the fused flash-decode route for
    single-query cache steps (:func:`_fused_decode_ok`); the dense path
    and its ``bias`` remain the fallback on every other shape/backend.
    ``trunk_len`` (static) marks the cache's leading shared-trunk slots
    for the trunk-aware fused decode kernels (cascade decode) — 0 on
    every non-shared dispatch and whenever the fused route is off.
    """
    B, S, _ = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    h_attn_in = _norm(x, lp["ln1"], cfg)
    h_qkv = h_attn_in
    if cfg.attention_in_multiplier != 1.0:
        h_qkv = h_qkv * jnp.asarray(cfg.attention_in_multiplier, x.dtype)
    # Dynamic-int8 trees quantize the attention input ONCE for the whole
    # q/k/v triple (quant.shared_quant) — bit-identical to per-matrix
    # quantization, two fewer VPU amax/round passes per block.
    h_qkv = _shared_quant(h_qkv, lp["wq"], lp["wk"], lp["wv"])
    q = _mm(h_qkv, lp["wq"])
    k = _mm(h_qkv, lp["wk"])
    v = _mm(h_qkv, lp["wv"])
    if cfg.key_multiplier != 1.0:
        k = k * jnp.asarray(cfg.key_multiplier, k.dtype)
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    if cfg.pos_embedding == "rotary":
        rd = cfg.rotary_dim
        q = _apply_rope(q, sin, cos, rd)
        k = _apply_rope(k, sin, cos, rd)

    if cache is not None:
        # Insert the window's k/v at this layer's slots of the stacked
        # cache, attend over the layer. A side is (L, K, T, B, hd) — see
        # _attention_cached for the layer's own layout.
        ck, cv = cache[:2]
        # (B, S, K, hd) -> (1, K, S, B, hd)
        k_t = k.transpose(2, 1, 0, 3)[None]
        v_t = v.transpose(2, 1, 0, 3)[None]
        at = (layer, 0, cache_index, 0, 0)

        def of_layer(a):
            return lax.dynamic_index_in_dim(a, layer, keepdims=False)

        if cfg.kv_cache_int8:
            (ckq, cks), (cvq, cvs) = ck, cv
            k_q, k_s = _quant_kv(k_t)
            v_q, v_s = _quant_kv(v_t)
            ckq = lax.dynamic_update_slice(ckq, k_q, at)
            cks = lax.dynamic_update_slice(cks, k_s, at[:-1])
            cvq = lax.dynamic_update_slice(cvq, v_q, at)
            cvs = lax.dynamic_update_slice(cvs, v_s, at[:-1])
            ck, cv = (ckq, cks), (cvq, cvs)
            attn = _attention_cached_int8(q, of_layer(ckq), of_layer(cks),
                                          of_layer(cvq), of_layer(cvs),
                                          bias, cfg)
        else:
            ck = lax.dynamic_update_slice(ck, k_t.astype(ck.dtype), at)
            cv = lax.dynamic_update_slice(cv, v_t.astype(cv.dtype), at)
            if _fused_decode_ok(cfg, q.shape[0], S, fused_ctx):
                attn = _attention_cached_flash(q, ck, cv, cfg, fused_ctx,
                                               trunk_len=trunk_len,
                                               layer=layer)
            elif _fused_decode_mq_ok(cfg, q.shape[0], S, fused_ctx):
                attn = _attention_cached_flash_mq(q, ck, cv, cfg, fused_ctx,
                                                  trunk_len=trunk_len,
                                                  layer=layer)
            else:
                attn = _attention_cached(q, of_layer(ck), of_layer(cv),
                                         bias, cfg)
    elif attn_impl is not None:
        # Prefill/forward: hand back this layer's (post-rope) k/v so prefill
        # can fill the cache without re-projecting them.
        ck, cv = k, v
        attn = attn_impl(q, k, v, key_mask)
    else:
        ck, cv = k, v
        attn = _attention(q, k, v, bias, cfg, key_mask=key_mask)
    attn = _mm(attn, lp["wo"])
    if cfg.attn_out_bias:
        attn = attn + lp["bo"]
    if cfg.attention_out_multiplier != 1.0:
        attn = attn * jnp.asarray(cfg.attention_out_multiplier, attn.dtype)
    if cfg.has_mixer:
        # The mixer reads the same normed input as attention; the two
        # branches are summed into one residual update.
        if cache is not None:
            rec = cache[2:]
        elif rec is None:
            rec = _empty_rec(cfg, B, x.dtype)
        mix, state, tail = _mixer(h_attn_in, lp, cfg, rec[0], rec[1],
                                  rec_mask, layer=layer)
        if cfg.ssm_out_multiplier != 1.0:
            mix = mix * jnp.asarray(cfg.ssm_out_multiplier, mix.dtype)
        attn = attn + mix

    if cfg.parallel_block:
        mlp_in = h_attn_in if cfg.shared_block_ln else _norm(x, lp["ln2"], cfg)
    else:
        x = x + attn
        mlp_in = _norm(x, lp["ln2"], cfg)

    # Gated MLPs share one quantized copy of mlp_in across w_up/w_gate.
    mlp_q = (_shared_quant(mlp_in, lp["w_up"], lp["w_gate"])
             if cfg.gated_mlp else mlp_in)
    up = _mm(mlp_q, lp["w_up"])
    if cfg.mlp_bias:
        up = up + lp["b_up"]
    if cfg.gated_mlp:
        gate = _mm(mlp_q, lp["w_gate"])
        if cfg.mlp_multipliers[0] != 1.0:
            gate = gate * jnp.asarray(cfg.mlp_multipliers[0], gate.dtype)
        hidden = _act(gate, cfg.activation) * up
    else:
        hidden = _act(up, cfg.activation)
    mlp = _mm(hidden, lp["w_down"])
    if cfg.mlp_bias:
        mlp = mlp + lp["b_down"]
    if cfg.mlp_multipliers[1] != 1.0:
        mlp = mlp * jnp.asarray(cfg.mlp_multipliers[1], mlp.dtype)

    out = x + attn + mlp if cfg.parallel_block else x + mlp
    if cfg.has_mixer:
        return out, (ck, cv, state, tail)
    return out, (ck, cv)


def _empty_rec(cfg: ModelConfig, batch: int, dtype) -> Tuple:
    """One layer's recurrent state before any token: (SSM state, conv
    tail), both zero."""
    return (jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim,
                       cfg.ssm_state), jnp.float32),
            jnp.zeros((batch, cfg.ssm_conv - 1, cfg.ssm_conv_dim), dtype))


def refuse_recurrent(cfg, what: str) -> None:
    """The one plain error of everything that cannot hold recurrent state
    yet (ROADMAP M4): paged / radix prefix reuse (pages hold K/V, not the
    state at a page's edge), speculative verify (no roll-back to the last
    accepted token), the piggyback chain (one parked cache, two branches),
    tiering and migration (pages again). They refuse; none answers
    wrongly."""
    if getattr(cfg, "carries_state", False):
        raise NotImplementedError(
            f"{cfg.name}: {what} cannot carry a recurrent state (a "
            "state-space mixer's SSM state + conv tail, a linear-attention "
            "layer's state) yet; it holds K/V only (ROADMAP M4)")


def rewind(cache, snapshot):
    """The cache a second branch starts from after a first branch ran on
    ``snapshot``'s successor ``cache``: K/V is rewound by the branch's own
    mask (the first branch's slots are masked away and overwritten), so
    the buffers carry over; recurrent state cannot be rewound, so it is
    taken from ``snapshot``, the state as it stood at the shared prefix's
    end. A cache without recurrent state passes through."""
    return cache if len(cache) == 2 else tuple(cache[:2]) + tuple(snapshot[2:])


def _embed(params: Params, cfg: ModelConfig, tokens: jax.Array,
           positions: jax.Array) -> jax.Array:
    x = jnp.take(params["tok_embed"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    if cfg.pos_embedding == "learned":
        # mode="clip": an out-of-table position reuses the last row instead
        # of jnp.take's default NaN fill silently poisoning every logit.
        # The engine additionally refuses buckets that could overflow the
        # table (runner.ScoringEngine), so this is defense in depth.
        x = x + jnp.take(params["pos_embed"],
                         positions + cfg.learned_pos_offset, axis=0,
                         mode="clip")
    if cfg.embedding_norm:
        ln = {"scale": params["embed_ln"]["scale"], "bias": params["embed_ln"]["bias"]}
        x = _norm(x, ln, dataclasses.replace(cfg, norm="layernorm"))
    return x


def _unembed(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.final_norm:
        x = _norm(x, params["final_ln"], cfg)
    head = params["tok_embed"].T if cfg.tie_embeddings else params["lm_head"]
    if isinstance(head, QuantTensor):
        logits = _mm(x.astype(jnp.float32), head)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                            head.astype(jnp.float32))
    if cfg.lm_head_multiplier != 1.0:
        logits = logits * cfg.lm_head_multiplier
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


def _causal_bias(attn_mask: jax.Array, positions: jax.Array, cfg: ModelConfig,
                 key_positions: Optional[jax.Array] = None,
                 key_mask: Optional[jax.Array] = None) -> jax.Array:
    """Additive fp32 attention bias (B, H|1, S, T).

    ``positions`` are mask-aware indices (pads get 0). Causality compares
    positions, so left-padded batches behave exactly like unpadded prompts.
    """
    if key_positions is None:
        key_positions, key_mask = positions, attn_mask
    neg = jnp.float32(-1e9)
    qp = positions[:, :, None]           # (B, S, 1)
    kp = key_positions[:, None, :]       # (B, 1, T)
    allowed = (kp <= qp) & (key_mask[:, None, :] > 0)
    bias = jnp.where(allowed, 0.0, neg)[:, None, :, :]  # (B, 1, S, T)
    if cfg.pos_embedding == "alibi":
        slopes = alibi_slopes(cfg.n_heads)  # (H,)
        alibi = slopes[None, :, None, None] * kp.astype(jnp.float32)[:, None, :, :]
        bias = bias + alibi
    return bias


def mask_positions(attn_mask: jax.Array) -> jax.Array:
    """Mask-aware position ids: pads -> 0, tokens -> 0..n-1 (left-pad safe)."""
    return jnp.maximum(jnp.cumsum(attn_mask, axis=-1) - 1, 0)


# ---------------------------------------------------------------------------
# Public forwards
# ---------------------------------------------------------------------------

def _scan_blocks(params: Params, cfg: ModelConfig, x, sin, cos, bias,
                 cache=None, cache_index=None, key_mask=None, attn_impl=None,
                 fused_ctx=None, trunk_len: int = 0, rec_mask=None):
    """lax.scan over the stacked layer params. ``cache`` is the stacked
    (ck, cv) pair, with (SSM state, conv tail) after it for a model with
    a mixer; ``rec_mask`` marks the window's real slots for that state.

    The cache rides the loop's CARRY beside the activations, and the loop
    runs over (layer params, layer index): each block updates its layer
    of the stacked buffers where they lie (:func:`_block`) and the loop
    hands back the buffers it was given. As the scan's ``xs`` / ``ys``
    instead, every layer would be sliced out and stacked into a second
    buffer, and a caller whose own loop carries the cache (the decode
    loop, engine/generate._stepped) would copy the whole of it once more
    per step. Returns (x, cache), the cache None where none came."""
    if cache is None:
        def plain(h, lp):
            h, _ = _block(h, lp, cfg, sin, cos, bias, key_mask=key_mask,
                          attn_impl=attn_impl, rec_mask=key_mask)
            return h, None

        x, _ = lax.scan(plain, x, params["layers"])
        return x, None

    def body(carry, xs):
        h, stacked = carry
        lp, layer = xs
        return _block(h, lp, cfg, sin, cos, bias, cache=stacked, layer=layer,
                      cache_index=cache_index, fused_ctx=fused_ctx,
                      trunk_len=trunk_len, rec_mask=rec_mask), None

    n_layers = jax.tree.leaves(cache)[0].shape[0]
    (x, cache), _ = lax.scan(
        body, (x, tuple(cache)),
        (params["layers"], jnp.arange(n_layers, dtype=jnp.int32)))
    return x, cache


def _mixed(cfg: ModelConfig, attn_impl=None):
    """models/mixed.py, which runs a model whose layers differ in kind
    behind every entry point below (``cfg.layer_kinds``)."""
    from . import mixed

    if attn_impl is not None:
        raise NotImplementedError(
            f"{cfg.name}: sequence-parallel attention over layers that "
            "differ in kind")
    return mixed


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            attn_mask: Optional[jax.Array] = None,
            attn_impl=None) -> jax.Array:
    """Full-sequence causal forward. tokens: (B, S) int32 -> fp32 logits (B,S,V).

    ``attn_impl`` (see _block) swaps in a sequence-parallel attention; the
    O(S*T) bias tensor is then never materialized — required for
    long-context prefill, where (S, T) would not fit.
    """
    if attn_mask is None:
        attn_mask = jnp.ones_like(tokens)
    if cfg.layer_kinds:
        return _mixed(cfg, attn_impl).forward(params, cfg, tokens, attn_mask)
    positions = mask_positions(attn_mask)
    x = _embed(params, cfg, tokens, positions)
    sin = cos = None
    if cfg.pos_embedding == "rotary":
        sin, cos = _rope_sincos(positions, cfg.rotary_dim, cfg.rope_theta)
    bias = None if attn_impl is not None else _causal_bias(attn_mask, positions, cfg)
    x, _ = _scan_blocks(params, cfg, x, sin, cos, bias, key_mask=attn_mask,
                        attn_impl=attn_impl)
    return _unembed(params, cfg, x)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.float32):
    """Per-layer KV cache stacked on the layer axis: (L, K, T, B, hd) pair.

    Head-major/batch-minor on purpose: this is the physical order XLA's
    decode while-loop assigns to the cache anyway, and the (B, hd) minor
    pair is the block the decode kernel reads (ops/flash_decode). The
    layer axis leads because the layer loop carries these buffers whole
    and updates them where they lie (:func:`_scan_blocks`): a step writes
    one token slot per layer at ``(layer, 0, slot, 0, 0)`` and the kernels
    read ``(layer, head, split, rows)`` blocks of the same operand, so
    prefill's output, every extension and every decode step share ONE
    buffer per side.

    With ``cfg.kv_cache_int8`` each side becomes a (payload int8
    (L, K, T, B, hd), scale f32 (L, K, T, B)) pair — half the HBM.

    A model whose layers differ in kind has its own six leaves
    (models/mixed.init_cache): here, ``max_len`` main slots a row.
    """
    if cfg.layer_kinds:
        return _mixed(cfg).init_cache(cfg, batch, max_len, 0, dtype)
    shape = (cfg.n_layers, cfg.n_kv_heads, max_len, batch, cfg.head_dim)
    if cfg.kv_cache_int8:
        def side():
            return (jnp.zeros(shape, jnp.int8),
                    jnp.zeros(shape[:-1], jnp.float32))
        return (side(), side())
    kv = (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
    if cfg.has_mixer:
        # Beside K/V, per layer and row: the SSM state (float32) and the
        # conv's last inputs. Batch is axis 1 here (models/cache.
        # gather_rows knows), slots there are none: the state is the
        # whole history folded, whatever the cache's extent.
        state, tail = _empty_rec(cfg, batch, dtype)
        L = cfg.n_layers
        return kv + (jnp.zeros((L,) + state.shape, state.dtype),
                     jnp.zeros((L,) + tail.shape, tail.dtype))
    return kv


# Phase scopes (observe/tracing's naming: ``lir.<phase>``): every dispatch
# program is built from these entry points, so each device operation's
# op_name says which phase it belongs to (engine/compile_plan.scope_table
# reads it back). The outermost scope decides: a caller that wraps an
# ``extend`` in its own scope (the paged prefix window is prefill, the
# speculative verify window is decode) re-labels it.
@jax.named_scope("lir.prefill")
def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array,
            attn_mask: jax.Array, max_len: int, attn_impl=None):
    """Run the prompt, fill the KV cache, return last-position logits.

    tokens/attn_mask: (B, S) with LEFT padding (so position S-1 is the prompt
    end for every row — mirrors the reference's unpadded single-prompt calls).
    Returns (logits_last (B, V) fp32, cache, next_positions (B,)).

    Masked padding is a positional no-op, so RIGHT-padded callers (the
    shared-prefix paths' canonical slot == position layout,
    engine/generate.py) are equally valid — they must simply ignore the
    returned logits/next_positions, which read slot S-1 (a pad there).

    ``attn_impl`` routes the prompt pass through sequence-parallel attention
    (parallel/seq_forward): the quadratic phase runs seq-sharded, and the
    returned cache holds the same per-layer k/v for ordinary decode.
    """
    if cfg.layer_kinds:
        return _mixed(cfg, attn_impl).prefill(params, cfg, tokens, attn_mask,
                                              max_len)
    B, S = tokens.shape
    positions = mask_positions(attn_mask)
    x = _embed(params, cfg, tokens, positions)
    sin = cos = None
    if cfg.pos_embedding == "rotary":
        sin, cos = _rope_sincos(positions, cfg.rotary_dim, cfg.rope_theta)
    bias = None if attn_impl is not None else _causal_bias(attn_mask, positions, cfg)

    # Scan layers, capturing each block's (post-rope) k/v — returned by
    # _block itself, no re-projection — into a (L, ...) stack. Each layer's
    # k/v is transposed to the cache layout (K, S, B, hd) and padded to
    # max_len INSIDE the body: the scan's output stacking then allocates
    # the cache at its final (L, K, T, B, hd) size directly, in the layout
    # the decode loop consumes. Stacking first and padding/transposing the
    # (L, ...) tensor afterwards would materialize the whole cache twice —
    # exactly what used to OOM a 7B at batch 32 / seq 1024 on one chip.
    pad = max_len - S
    pad_spec = ((0, 0), (0, pad), (0, 0), (0, 0))

    def body(h, lp):
        h_out, new = _block(h, lp, cfg, sin, cos, bias,
                            key_mask=attn_mask, attn_impl=attn_impl,
                            rec_mask=attn_mask)
        k = new[0].transpose(2, 1, 0, 3)  # (B, S, K, hd) -> (K, S, B, hd)
        v = new[1].transpose(2, 1, 0, 3)
        if cfg.kv_cache_int8:
            def side(x):
                xq, xs = _quant_kv(x)
                return (jnp.pad(xq, pad_spec), jnp.pad(xs, pad_spec[:-1]))
            return h_out, (side(k), side(v))
        # A mixer's state and conv tail (new[2:]) stack beside K/V as
        # they stand after each row's last real token.
        return h_out, (jnp.pad(k, pad_spec), jnp.pad(v, pad_spec)) + new[2:]

    x, cache = lax.scan(body, x, params["layers"])
    logits = _unembed(params, cfg, x[:, -1:, :])[:, 0, :]
    next_positions = positions[:, -1] + 1
    return logits, cache, next_positions


@jax.named_scope("lir.extend")
def extend(params: Params, cfg: ModelConfig, cache, suffix_tokens: jax.Array,
           suffix_mask: jax.Array, cache_mask: jax.Array, start_index: int):
    """Teacher-forced multi-token cache extension (chunked prefill).

    Runs ``suffix_tokens`` (B, S2), RIGHT-padded, through the layers in ONE
    forward pass, attending over the already-filled cache plus the suffix
    itself, and inserts the suffix k/v at cache slots
    [start_index, start_index + S2). This is how the perturbation sweep
    shares one prefill between the binary and confidence formats: the long
    rephrased text is prefilled once, then each short format suffix is
    extended here at ~S2/S of the prefill cost (the reference pays two full
    forward passes per cell, perturb_prompts.py:551-726).

    cache_mask: (B, T) validity over the FULL cache, already including the
    suffix slots (pads 0). Pad-slot k/v values are garbage but carry mask 0,
    so attention never sees them. Returns (last-valid-position logits
    (B, V) fp32, new_cache, next_positions (B,)).
    """
    if cfg.layer_kinds:
        return _mixed(cfg).extend(params, cfg, cache, suffix_tokens,
                                  suffix_mask, cache_mask, start_index)
    B, S2 = suffix_tokens.shape
    key_positions = mask_positions(cache_mask)
    qpos = lax.dynamic_slice_in_dim(key_positions, start_index, S2, axis=1)
    x = _embed(params, cfg, suffix_tokens, qpos)
    sin = cos = None
    if cfg.pos_embedding == "rotary":
        sin, cos = _rope_sincos(qpos, cfg.rotary_dim, cfg.rope_theta)
    bias = _causal_bias(suffix_mask, qpos, cfg,
                        key_positions=key_positions, key_mask=cache_mask)
    x, new_cache = _scan_blocks(params, cfg, x, sin, cos, bias,
                                cache=cache, cache_index=start_index,
                                rec_mask=suffix_mask)
    # Per-row last REAL suffix position (right padding varies by row).
    last = jnp.maximum(jnp.sum(suffix_mask, axis=-1) - 1, 0)      # (B,)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)  # (B, 1, D)
    logits = _unembed(params, cfg, x_last)[:, 0, :]
    next_positions = jnp.take_along_axis(qpos, last[:, None], axis=1)[:, 0] + 1
    return logits, new_cache, next_positions


@jax.named_scope("lir.prefill")
def cascade_extend(params: Params, cfg: ModelConfig, trunk_cache,
                   rem_tokens: jax.Array, rem_mask: jax.Array,
                   trunk_len: int, total_len: int, int8_qk: bool = False):
    """Shared-trunk cascade prefill: build a B-row cache from ONE trunk.

    The dense shared path (:func:`prefill` in generate.greedy_decode_
    fused_shared) recomputes the trunk's quadratic attention once per
    row even when every row shares it. Here the trunk KV is computed (or
    page-pool-gathered) ONCE at batch 1 — ``trunk_cache`` is a
    (L, K, Tt, 1, hd) pair, every slot real, slot == position — and only
    each row's remainder ``rem_tokens``/``rem_mask`` (B, R),
    RIGHT-padded (slot trunk_len + r == position, the canonical layout),
    runs through the layers, attending via the cascade split
    (:func:`_attention_cascade`): prefix leg against this layer's trunk
    KV + causal suffix leg over the window, merged exactly. The returned
    cache broadcasts the trunk KV across rows at slots [0, trunk_len),
    writes the remainder k/v at [trunk_len, trunk_len + R), and
    zero-pads to ``total_len`` — the drop-in analogue of ``prefill``'s
    cache output for a shared-trunk dispatch (no logits: the shared
    paths discard the prefill logits anyway and read branch logits from
    the suffix extensions). Requires a non-int8 KV cache (the engine
    gates routing, runner.cascade_supported).
    """
    assert not cfg.kv_cache_int8, "cascade prefill needs a float KV cache"
    if cfg.layer_kinds:
        return _mixed(cfg).cascade_extend(params, cfg, trunk_cache,
                                          rem_tokens, rem_mask, trunk_len,
                                          total_len)
    B, R = rem_tokens.shape
    L, K, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    qpos = trunk_len + mask_positions(rem_mask)                  # (B, R)
    x = _embed(params, cfg, rem_tokens, qpos)
    sin = cos = None
    if cfg.pos_embedding == "rotary":
        sin, cos = _rope_sincos(qpos, cfg.rotary_dim, cfg.rope_theta)
    tck, tcv = trunk_cache[:2]                            # (L, K, Tt, 1, hd)

    def body(h, xs):
        lp, layer = xs
        tk, tv = layer[:2]

        def impl(q, k, v, key_mask):
            return _attention_cascade(q, k, v,
                                      (tk[:, :, 0, :], tv[:, :, 0, :]),
                                      rem_mask, qpos, cfg, int8_qk)

        # The trunk's recurrent state, computed once at batch 1, is every
        # row's state on entry (the remainders continue from it).
        rec = tuple(jnp.broadcast_to(a, (B,) + a.shape[1:])
                    for a in layer[2:]) or None
        h, new = _block(h, lp, cfg, sin, cos, None,
                        key_mask=rem_mask, attn_impl=impl, rec=rec,
                        rec_mask=rem_mask)
        return h, new

    _, new = lax.scan(body, x, (params["layers"], tuple(trunk_cache)))
    rk, rv = new[:2]

    # Assemble the B-row cache in the (L, K, T, B, hd) layout: the trunk
    # side broadcasts across rows (identical KV by construction — the
    # dedup the cascade exists for), the remainder transposes in, the
    # tail zero-pads exactly as prefill pads.
    pad = total_len - trunk_len - R

    def side(trunk, win):
        t = jnp.broadcast_to(trunk, (L, K, trunk_len, B, hd))
        w = win.transpose(0, 3, 2, 1, 4).astype(trunk.dtype)  # (L,K,R,B,hd)
        z = jnp.zeros((L, K, pad, B, hd), trunk.dtype)
        return jnp.concatenate([t, w, z], axis=2)

    return (side(tck, rk), side(tcv, rv)) + tuple(new[2:])


@jax.named_scope("lir.extend")
def verify_extend(params: Params, cfg: ModelConfig, cache,
                  chunk_tokens: jax.Array, cache_mask: jax.Array,
                  start_index: jax.Array, trunk_len: int = 0):
    """Teacher-forced VERIFY window (speculative decode): run the S-token
    draft window [current emission, drafts...] through the layers in one
    forward, writing its k/v at cache slots [start_index, start_index+S)
    and returning the logits at EVERY window position — the multi-query
    sibling of :func:`decode_step` that checks S sequential-scan steps in
    one dispatch.

    Every window row is real (teacher forcing; acceptance is decided by
    the caller from the returned logits), so the query mask is all-ones;
    ``cache_mask`` is the FULL cache validity with the window's S slots
    already set (rejected slots of earlier windows stay 0 — masked
    garbage, exactly the early-stop discipline). Positions derive from
    the mask's cumsum, so each query sits at its row's next logical
    position: the attention reduction runs over the same valid
    (token, position) set in the same slot order as the sequential
    decode_step, masked slots contributing exact zeros (the paged-path
    argument), and the fused route goes through the multi-query flash
    kernel whose per-query ops are the single-query kernel's
    (ops/flash_decode.flash_decode_mq). Results are argmax/top-k
    identical to the sequential step and logits-equal within float
    tolerance (the window cache is longer — T*spec_k decode slots — so
    XLA may group the reduction's masked-zero lanes differently; the
    same bar PR-7's fused-vs-dense kernels cleared), which is what the
    speculative tail needs: every CONSUMED readout (position-0 floats,
    the emitted token stream) stays bitwise.

    ``trunk_len`` (static) routes the window through the trunk-aware
    multi-query kernel on shared-trunk dispatches (cascade decode, gated
    by ``cfg.cascade_decode``): the verify window's trunk splits compute
    once per kv head for every row's queries, bitwise the flat kernel.

    Returns (logits (B, S, V) fp32, new_cache)."""
    refuse_recurrent(cfg, "a speculative verify window")
    B, S2 = chunk_tokens.shape
    key_positions = mask_positions(cache_mask)
    qpos = lax.dynamic_slice_in_dim(key_positions, start_index, S2, axis=1)
    x = _embed(params, cfg, chunk_tokens, qpos)
    sin = cos = None
    if cfg.pos_embedding == "rotary":
        sin, cos = _rope_sincos(qpos, cfg.rotary_dim, cfg.rope_theta)
    ones = jnp.ones((B, S2), jnp.int32)
    bias = _causal_bias(ones, qpos, cfg,
                        key_positions=key_positions, key_mask=cache_mask)
    x, new_cache = _scan_blocks(params, cfg, x, sin, cos, bias,
                                cache=cache, cache_index=start_index,
                                fused_ctx=(qpos, cache_mask,
                                           key_positions),
                                trunk_len=(int(trunk_len)
                                           if cfg.cascade_decode else 0))
    logits = _unembed(params, cfg, x)
    return logits, new_cache


@jax.named_scope("lir.decode")
def decode_step(params: Params, cfg: ModelConfig, cache, token: jax.Array,
                position: jax.Array, step_index: jax.Array,
                prompt_mask: jax.Array, trunk_len: int = 0):
    """One greedy-decode step.

    token: (B,) int32 current input; position: (B,) its mask-aware position;
    step_index: scalar slot in the cache where this token's k/v land (= S + t);
    prompt_mask: (B, T) validity mask over the FULL cache length T (prompt pads
    0, prompt tokens and generated slots 1 once written).
    ``trunk_len`` (static): on a shared-trunk dispatch with cascade
    decode on (``cfg.cascade_decode``), the cache's leading trunk slots
    are row-identical and the fused kernel's trunk splits read them once
    per kv head for all rows — bitwise the flat kernel.
    Returns (logits (B, V) fp32, new_cache).
    """
    if cfg.layer_kinds:
        return _mixed(cfg).decode_step(params, cfg, cache, token, position,
                                       step_index, prompt_mask)
    B = token.shape[0]
    x = _embed(params, cfg, token[:, None], position[:, None])
    sin = cos = None
    if cfg.pos_embedding == "rotary":
        sin, cos = _rope_sincos(position[:, None], cfg.rotary_dim, cfg.rope_theta)

    key_positions = mask_positions(prompt_mask)
    bias = _causal_bias(jnp.ones((B, 1), jnp.int32), position[:, None], cfg,
                        key_positions=key_positions, key_mask=prompt_mask)
    # The fused flash-decode route consumes the mask/positions directly
    # (the kernel owns causality + ALiBi); the bias tensor feeds only the
    # dense/int8 fallback and is dead code XLA drops when the kernel
    # engages.
    x, new_cache = _scan_blocks(params, cfg, x, sin, cos, bias,
                                cache=cache, cache_index=step_index,
                                fused_ctx=(position, prompt_mask,
                                           key_positions),
                                trunk_len=(int(trunk_len)
                                           if cfg.cascade_decode else 0))
    logits = _unembed(params, cfg, x)[:, 0, :]
    return logits, new_cache
