"""Plain reference for the Falcon-H1 block: a Mamba-2 mixer beside
grouped-query attention on ONE normed input, then a gated SiLU MLP.

With ``x`` the block input and ``n1``/``n2`` RMSNorm (keys of the
published ``config.json`` in backticks)::

    u = n1(x)
    x = x + attn(u * attention_in_multiplier) * attention_out_multiplier
          + mixer(u * ssm_in_multiplier) * ssm_out_multiplier
    x = x + mlp(n2(x))

* ``attn``: ``num_attention_heads`` query / ``num_key_value_heads``
  key-value heads of ``head_dim``, no bias, rotate-half rotary over the
  whole head at ``rope_theta``, the key projection's output times
  ``key_multiplier``, scores scaled by ``head_dim ** -0.5``, causal.
* ``mlp(h) = W_down(silu(W_gate h * mlp_multipliers[0]) * W_up h)
  * mlp_multipliers[1]``.
* ``mixer(h)`` (Mamba-2; ``mamba_n_heads`` heads of ``mamba_d_head``,
  state ``mamba_d_state``, ``mamba_n_groups`` groups, ``mamba_d_conv``
  taps with bias, no projection bias): ``[z | xBC | dt] = (W_in h) * mu``
  with ``mu`` the per-column vector of ``ssm_multipliers[0..4]`` over the
  z, x, B, C and dt columns; ``xBC = silu(causal_conv1d(xBC) + b)``
  depthwise; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; per
  head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t +
  D x_t``; then (``mamba_rms_norm`` true, ``mamba_norm_before_gate``
  false) ``y = groupRMSNorm(y * silu(z))`` with a learned scale, each of
  the groups normalised on its own; ``out = W_out y``.
* top: embedding rows times ``embedding_multiplier``; final RMSNorm;
  logits times ``lm_head_multiplier``; untied head.

Departures from the published model: none of the language model's
mathematics. What no key gives (``assumed`` in the configuration file):
``dt`` is not clamped after the softplus (the family's default limit is
``(0, inf)``); the seeded ``A`` is uniform in [1, 16] and the seeded
``dt_bias`` the inverse softplus of a step log-uniform in [0.001, 0.1]
(the Mamba-2 initialisation), so that over a 500-token row the fastest
heads forget within a token and the slowest keep ~60% of their first
token: the state neither dies nor overflows.

The recurrence is written as the recurrence: a ``lax.scan`` over tokens,
no chunks, no cache. Everything is float32 under
``jax.default_matmul_precision("highest")``. Weights are the served tree:
int8 matrices (``w_in`` and ``w_out`` among them) with a per-column
float32 scale, bfloat16 norms, conv, ``A_log``, ``D``, ``dt_bias``. Each
matrix's scale folds in the muP multiplier its branch is published with,
so that with seeded weights every branch's output is of order 1 (in the
trained model the weights' own size does that), attention scores have a
spread of order 1 and a wrong kernel shows in the logits.

``precision``: ``"float32"`` is the reference. CONTROLS, never computed
by the benchmark's own runs: ``"int8"`` / ``"fp8"`` round every matrix
product's activation operand, attention's q, k, v and probabilities and
the mixer's x, B and C as ``references/decoder.py`` rounds attention's;
``"bf16_state"`` rounds the SSM state to bfloat16 after every token and
nothing else (the configuration states a float32 state). The module
imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from .decoder import (INT8_STD, LOGIT_STD, _int8, _mm as _mm_rounded,
                      _rope, _round, seed_key)  # noqa: F401 (seed_key: the harness's)

VOCAB_BLOCKS = 8         # the head is applied in column blocks (5.3 GB whole)


@dataclasses.dataclass(frozen=True)
class HybridSpec:
    name: str
    preset: str              # the program's registry name for these sizes
    vocab: int
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    eps: float
    rope_theta: float
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    ssm_conv: int
    ssm_chunk: int
    embedding_multiplier: float
    lm_head_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    mlp_multipliers: tuple
    ssm_multipliers: tuple
    # What the harness's fixed list reads (builders.program_config).
    gated: bool = True
    act: str = "silu"
    norm: str = "rmsnorm"
    parallel: bool = False
    tied: bool = False

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def layer_matrices(self) -> dict:
        """name -> (d_in, d_out) of one layer's int8 matrices."""
        d, q, kv, f = (self.d, self.heads * self.head_dim,
                       self.kv_heads * self.head_dim, self.ffn)
        return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
                "w_up": (d, f), "w_gate": (d, f), "w_down": (f, d),
                "w_in": (d, self.ssm_inner + self.ssm_conv_dim
                         + self.ssm_heads),
                "w_out": (self.ssm_inner, d)}

    @property
    def layer_matmul_params(self) -> int:
        return sum(a * b for a, b in self.layer_matrices.values())

    @property
    def weight_bytes(self) -> int:
        """Bytes of the served tree: int8 matrices, bfloat16 embedding
        (the layers' small bfloat16 vectors are left out)."""
        return (self.layers * self.layer_matmul_params
                + self.d * self.vocab + 2 * self.vocab * self.d)

    @property
    def column_multipliers(self) -> tuple:
        """(width, multiplier) runs over ``w_in``'s output columns."""
        gn = self.ssm_groups * self.ssm_state
        return tuple(zip((self.ssm_inner, self.ssm_inner, gn, gn,
                          self.ssm_heads), self.ssm_multipliers))


def spec_from_config(name: str, raw: dict) -> HybridSpec:
    if raw["model_type"] != "falcon_h1":
        raise ValueError(f"{name}: model_type {raw['model_type']!r} is not "
                         "the Falcon-H1 block written down here")
    if (raw["attention_bias"] or raw["mamba_proj_bias"] or raw["mlp_bias"]
            or raw["projectors_bias"] or not raw["mamba_conv_bias"]
            or not raw["mamba_rms_norm"] or raw["mamba_norm_before_gate"]
            or raw["tie_word_embeddings"] or raw["rope_scaling"]
            or raw["hidden_act"] != "silu"
            or raw["mamba_d_ssm"] != raw["mamba_n_heads"] * raw["mamba_d_head"]):
        raise ValueError(f"{name}: only the published Falcon-H1-34B block "
                         "is written down here")
    return HybridSpec(
        name=name, preset=raw["lir_tpu"]["preset"], vocab=raw["vocab_size"],
        d=raw["hidden_size"], layers=raw["num_hidden_layers"],
        heads=raw["num_attention_heads"],
        kv_heads=raw["num_key_value_heads"], head_dim=raw["head_dim"],
        ffn=raw["intermediate_size"], eps=raw["rms_norm_eps"],
        rope_theta=float(raw["rope_theta"]),
        ssm_heads=raw["mamba_n_heads"], ssm_head_dim=raw["mamba_d_head"],
        ssm_state=raw["mamba_d_state"], ssm_groups=raw["mamba_n_groups"],
        ssm_conv=raw["mamba_d_conv"], ssm_chunk=raw["mamba_chunk_size"],
        embedding_multiplier=raw["embedding_multiplier"],
        lm_head_multiplier=raw["lm_head_multiplier"],
        attention_in_multiplier=float(raw["attention_in_multiplier"]),
        attention_out_multiplier=raw["attention_out_multiplier"],
        key_multiplier=raw["key_multiplier"],
        ssm_in_multiplier=raw["ssm_in_multiplier"],
        ssm_out_multiplier=raw["ssm_out_multiplier"],
        mlp_multipliers=tuple(raw["mlp_multipliers"]),
        ssm_multipliers=tuple(raw["ssm_multipliers"]))


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------

def _fold(spec: HybridSpec, name: str):
    """The multiplier(s) the matrix's output meets before it is used,
    folded into its seeded scale: a number, or (width, number) runs."""
    return {"wq": spec.attention_in_multiplier,
            "wk": spec.attention_in_multiplier * spec.key_multiplier,
            "wv": spec.attention_in_multiplier,
            "wo": spec.attention_out_multiplier,
            "w_up": 1.0, "w_gate": spec.mlp_multipliers[0],
            "w_down": spec.mlp_multipliers[1],
            "w_in": tuple((n, m * spec.ssm_in_multiplier)
                          for n, m in spec.column_multipliers),
            "w_out": spec.ssm_out_multiplier}[name]


def _scale(spec: HybridSpec, name: str, d_in: int, d_out: int):
    base = 1.0 / (INT8_STD * math.sqrt(d_in))
    fold = _fold(spec, name)
    if isinstance(fold, tuple):
        return jnp.concatenate([jnp.full((n,), base / m, jnp.float32)
                                for n, m in fold])
    return jnp.full((d_out,), base / fold, jnp.float32)


def _vector(key, shape, mean=0.0, std=0.1):
    return (mean + std * jax.random.normal(key, shape)).astype(jnp.bfloat16)


def layer_weights(spec: HybridSpec, key, layer) -> dict:
    """One layer's leaves as they are served, under the program's names:
    ``{name: {"q", "scale"}}`` for the matrices, ``{"scale"}`` for the two
    norms, plain bfloat16 arrays for the mixer's small tensors. ``layer``
    may be traced (the served tree is this function vmapped)."""
    lk = jax.random.fold_in(key, layer)
    out = {}
    for i, (name, (d_in, d_out)) in enumerate(
            sorted(spec.layer_matrices.items())):
        out[name] = {"q": _int8(jax.random.fold_in(lk, i), (d_in, d_out)),
                     "scale": _scale(spec, name, d_in, d_out)}
    f = lambda i: jax.random.fold_in(lk, 100 + i)  # noqa: E731
    out["ln1"] = {"scale": _vector(f(0), (spec.d,), 1.0)}
    out["ln2"] = {"scale": _vector(f(1), (spec.d,), 1.0)}
    Hs, C, taps = spec.ssm_heads, spec.ssm_conv_dim, spec.ssm_conv
    out["conv_w"] = _vector(f(2), (taps, C), 0.0, 1.0 / math.sqrt(taps))
    out["conv_b"] = _vector(f(3), (C,))
    out["a_log"] = jnp.log(jax.random.uniform(
        f(4), (Hs,), minval=1.0, maxval=16.0)).astype(jnp.bfloat16)
    out["ssm_d"] = _vector(f(5), (Hs,), 1.0)
    dt0 = jnp.exp(jax.random.uniform(f(6), (Hs,), minval=math.log(1e-3),
                                     maxval=math.log(1e-1)))
    out["dt_bias"] = (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(jnp.bfloat16)
    out["ssm_norm"] = _vector(f(7), (spec.ssm_inner,), 1.0)
    return out


def top_weights(spec: HybridSpec, key) -> dict:
    """Embedding, final norm and head, as served."""
    tk = jax.random.fold_in(key, 1_000_000)
    return {
        "tok_embed": (jax.random.normal(
            jax.random.fold_in(tk, 0), (spec.vocab, spec.d))
            / spec.embedding_multiplier).astype(jnp.bfloat16),
        "final_ln": {"scale": _vector(jax.random.fold_in(tk, 1),
                                      (spec.d,), 1.0)},
        "lm_head": {
            "q": _int8(jax.random.fold_in(tk, 2), (spec.d, spec.vocab)),
            "scale": jnp.full(
                (spec.vocab,), LOGIT_STD / (INT8_STD * math.sqrt(spec.d)
                                            * spec.lm_head_multiplier),
                jnp.float32)}}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _mm(x, w, precision):
    return _mm_rounded(x, w, "float32" if precision == "bf16_state"
                       else precision)


def _rnd(x, precision):
    return x if precision == "bf16_state" else _round(x, precision)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps
                             ) * scale.astype(jnp.float32)


def _attention(spec, h, w, precision):
    n, t, _ = h.shape
    H, K, hd = spec.heads, spec.kv_heads, spec.head_dim
    q = _rope(_mm(h, w["wq"], precision).reshape(n, t, H, hd),
              spec.rope_theta)
    k = _rope((_mm(h, w["wk"], precision) * spec.key_multiplier
               ).reshape(n, t, K, hd), spec.rope_theta)
    v = _mm(h, w["wv"], precision).reshape(n, t, K, hd)
    q, k, v = _rnd(q, precision), _rnd(k, precision), _rnd(v, precision)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", _rnd(p, precision), v)
    return _mm(o.reshape(n, t, H * hd), w["wo"], precision)


def _mixer(spec, h, w, precision):
    n, t, _ = h.shape
    Hs, P, N, G = (spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state,
                   spec.ssm_groups)
    inner, gn, taps = spec.ssm_inner, G * spec.ssm_state, spec.ssm_conv
    f32 = jnp.float32
    mu = jnp.concatenate([jnp.full((k,), m, f32)
                          for k, m in spec.column_multipliers])
    proj = _mm(h, w["w_in"], precision) * mu
    z, xbc, dt = (proj[..., :inner], proj[..., inner:inner + inner + 2 * gn],
                  proj[..., inner + inner + 2 * gn:])
    # Depthwise causal conv: tap k weighs the input taps - 1 - k back.
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = w["conv_b"].astype(f32) + sum(
        padded[:, k:k + t] * w["conv_w"][k].astype(f32)
        for k in range(taps))
    xbc = jax.nn.silu(conv)
    x = _rnd(xbc[..., :inner].reshape(n, t, Hs, P), precision)
    b = _rnd(xbc[..., inner:inner + gn].reshape(n, t, G, N), precision)
    c = _rnd(xbc[..., inner + gn:].reshape(n, t, G, N), precision)
    b = jnp.repeat(b, Hs // G, axis=2)          # head h reads group h // (Hs/G)
    c = jnp.repeat(c, Hs // G, axis=2)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(f32))         # (n, t, Hs)
    a = -jnp.exp(w["a_log"].astype(f32))

    def token(s, xs):
        xt, bt, ct, dtt = xs
        s = (jnp.exp(dtt * a)[:, :, None, None] * s
             + (dtt[:, :, None] * xt)[..., None] * bt[:, :, None, :])
        if precision == "bf16_state":
            # reduce_precision, not a pair of converts: the TPU compiler
            # may drop an f32 -> bf16 -> f32 round trip as excess precision.
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("nhpk,nhk->nhp", s, ct)

    swap = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731
    _, y = jax.lax.scan(token, jnp.zeros((n, Hs, P, N), f32),
                        (swap(x), swap(b), swap(c), swap(dt)))
    y = swap(y) + w["ssm_d"].astype(f32)[:, None] * x
    y = y.reshape(n, t, inner) * jax.nn.silu(z)
    y = _rms(y.reshape(n, t, G, inner // G), jnp.ones((), f32), spec.eps
             ).reshape(n, t, inner) * w["ssm_norm"].astype(f32)
    return _mm(y, w["w_out"], precision)


def _mlp(spec, h, w, precision):
    gate = _mm(h, w["w_gate"], precision) * spec.mlp_multipliers[0]
    hidden = jax.nn.silu(gate) * _mm(h, w["w_up"], precision)
    return _mm(hidden, w["w_down"], precision) * spec.mlp_multipliers[1]


@functools.partial(jax.jit, static_argnums=(0, 4))
def block(spec: HybridSpec, key, layer, x, precision: str = "float32"):
    """One layer over x (N, T, d) float32; its weights made inside."""
    with jax.default_matmul_precision("highest"):
        w = layer_weights(spec, key, layer)
        u = _rms(x, w["ln1"]["scale"], spec.eps)
        x = (x + _attention(spec, u * spec.attention_in_multiplier, w,
                            precision) * spec.attention_out_multiplier
             + _mixer(spec, u * spec.ssm_in_multiplier, w, precision)
             * spec.ssm_out_multiplier)
        return x + _mlp(spec, _rms(x, w["ln2"]["scale"], spec.eps), w,
                        precision)


@functools.partial(jax.jit, static_argnums=(0,))
def embed(spec: HybridSpec, key, tokens):
    return jnp.take(top_weights(spec, key)["tok_embed"], tokens,
                    axis=0).astype(jnp.float32) * spec.embedding_multiplier


@functools.partial(jax.jit, static_argnums=(0, 3))
def unembed(spec: HybridSpec, key, x, precision: str = "float32"):
    """x (..., d) float32 -> logits (..., vocab) float32, the head applied
    in :data:`VOCAB_BLOCKS` column blocks, one after another."""
    with jax.default_matmul_precision("highest"):
        top = top_weights(spec, key)
        h = _rnd(_rms(x, top["final_ln"]["scale"], spec.eps), precision)
        blocks = VOCAB_BLOCKS if spec.vocab % VOCAB_BLOCKS == 0 else 1
        width = spec.vocab // blocks
        q, scale = top["lm_head"]["q"], top["lm_head"]["scale"]

        def part(i):
            cols = jax.lax.dynamic_slice_in_dim(q, i * width, width, axis=1)
            s = jax.lax.dynamic_slice_in_dim(scale, i * width, width)
            return h @ (cols.astype(jnp.float32) * s)

        parts = jax.lax.map(part, jnp.arange(blocks))    # (blocks, ..., width)
        logits = jnp.moveaxis(parts, 0, -2).reshape(*x.shape[:-1],
                                                    spec.vocab)
        return logits * spec.lm_head_multiplier


def logits_at(spec: HybridSpec, seed: int, tokens, positions,
              precision: str = "float32", rows_per_block: int = 8):
    """Logits of the reference at chosen positions; arguments and result
    as ``references/decoder.logits_at``. Right padding is inert: attention
    is causal and the recurrence runs left to right."""
    key = seed_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    blocks = [embed(spec, key, tokens[i:i + rows_per_block])
              for i in range(0, tokens.shape[0], rows_per_block)]
    for layer in range(spec.layers):
        blocks = [block(spec, key, layer, x, precision) for x in blocks]
    x = jnp.concatenate(blocks, axis=0)
    picked = jnp.take_along_axis(
        x, jnp.asarray(positions, jnp.int32)[:, :, None], axis=1)
    return unembed(spec, key, picked, precision)
