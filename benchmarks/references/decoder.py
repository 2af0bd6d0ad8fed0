"""Plain reference for decoder-only transformers of two block types.

* sequential: ``x += attn(rms(x)); x += mlp(rms(x))`` with grouped-query
  attention and a gated SiLU feed-forward (mistral);
* parallel: ``x += attn(ln(x)) + mlp(ln(x))`` under ONE LayerNorm, one
  key/value head, GELU feed-forward, logits through the embedding table
  (falcon-7b, ``new_decoder_architecture: false``).

Everything is straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``: no cache, no kernel, no
batching trick. Rotary embedding is the rotate-half convention over the
whole head, positions counted from 0. Weights are made here from the seed,
one layer at a time, so a 7B reference never holds more than one layer in
float32. The module imports nothing of the program.

``precision`` selects the arithmetic: ``"float32"`` is the reference;
``"int8"`` and ``"fp8"`` are CONTROLS, the step below the bfloat16 the
configurations state: every matrix product's activation operand, and the
keys and values attention reads, rounded to int8 (symmetric, per vector,
amax/127) or to float8_e4m3. The benchmark's own runs never compute them.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]          # benchmarks/


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    preset: str              # the program's registry name for these sizes
    vocab: int
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    gated: bool              # act(gate) * up, else act(up)
    act: str                 # "silu" | "gelu"
    norm: str                # "rmsnorm" | "layernorm"
    eps: float
    parallel: bool           # one norm feeds attention and mlp side by side
    tied: bool               # logits through the embedding table
    rope_theta: float

    @property
    def layer_matrices(self) -> dict:
        """name -> (d_in, d_out) of one layer's int8 matrices."""
        d, q, kv, f = (self.d, self.heads * self.head_dim,
                       self.kv_heads * self.head_dim, self.ffn)
        m = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
             "w_up": (d, f), "w_down": (f, d)}
        if self.gated:
            m["w_gate"] = (d, f)
        return m

    @property
    def layer_matmul_params(self) -> int:
        return sum(a * b for a, b in self.layer_matrices.values())

    @property
    def weight_bytes(self) -> int:
        """Bytes of the served tree: int8 matrices, bfloat16 embedding."""
        head = 0 if self.tied else self.d * self.vocab
        return (self.layers * self.layer_matmul_params + head
                + 2 * self.vocab * self.d)


def spec_from_config(name: str, raw: dict) -> ModelSpec:
    """The published keys of ``configs/<name>.json`` -> sizes. What the
    published file leaves to the family's code is under ``assumed`` in the
    file and filled in here."""
    kind = raw["model_type"]
    preset = raw["lir_tpu"]["preset"]
    if kind == "mistral":
        return ModelSpec(
            name=name, preset=preset, vocab=raw["vocab_size"],
            d=raw["hidden_size"], layers=raw["num_hidden_layers"],
            heads=raw["num_attention_heads"],
            kv_heads=raw["num_key_value_heads"],
            head_dim=raw["hidden_size"] // raw["num_attention_heads"],
            ffn=raw["intermediate_size"], gated=True, act=raw["hidden_act"],
            norm="rmsnorm", eps=raw["rms_norm_eps"], parallel=False,
            tied=raw["tie_word_embeddings"], rope_theta=raw["rope_theta"])
    if kind == "falcon":
        if (raw["alibi"] or raw["bias"] or raw["new_decoder_architecture"]
                or not raw["parallel_attn"] or not raw["multi_query"]):
            raise ValueError(f"{name}: only the falcon-7b block is written "
                             "down here")
        return ModelSpec(
            name=name, preset=preset, vocab=raw["vocab_size"],
            d=raw["hidden_size"], layers=raw["num_hidden_layers"],
            heads=raw["num_attention_heads"], kv_heads=1,
            head_dim=raw["hidden_size"] // raw["num_attention_heads"],
            ffn=4 * raw["hidden_size"], gated=False, act="gelu",
            norm="layernorm", eps=raw["layer_norm_epsilon"], parallel=True,
            tied=True, rope_theta=10000.0)
    raise ValueError(f"{name}: model_type {kind!r} has no block here; a new "
                     "family brings a reference module of its own")


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------
# int8 payloads are uniform in [-127, 127] (standard deviation 73.3); the
# per-column scale is constant and sets each matrix to a standard
# deviation of 1/sqrt(d_in), so every block's output is of order 1,
# attention scores have a spread of order 1 (neither uniform nor one-hot)
# and a wrong kernel shows in the logits. Logits get a spread of 2.

INT8_STD = 127.0 / math.sqrt(3.0)
LOGIT_STD = 2.0


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number (seeds run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _int8(key, shape):
    q = jax.lax.bitcast_convert_type(
        jax.random.bits(key, shape, jnp.uint8), jnp.int8)
    return jnp.maximum(q, jnp.int8(-127))


def _norm_leaves(spec: ModelSpec, key) -> dict:
    ks, kb = jax.random.split(key)
    p = {"scale": (1.0 + 0.1 * jax.random.normal(ks, (spec.d,))
                   ).astype(jnp.bfloat16)}
    if spec.norm == "layernorm":
        p["bias"] = (0.1 * jax.random.normal(kb, (spec.d,))
                     ).astype(jnp.bfloat16)
    return p


def layer_weights(spec: ModelSpec, key, layer) -> dict:
    """One layer's leaves as they are served: ``{name: {"q", "scale"}}``
    for the matrices, ``{"scale"[, "bias"]}`` in bfloat16 for the norms.
    ``layer`` may be traced (the served tree is this function vmapped)."""
    lk = jax.random.fold_in(key, layer)
    out = {}
    for i, (name, (d_in, d_out)) in enumerate(
            sorted(spec.layer_matrices.items())):
        out[name] = {
            "q": _int8(jax.random.fold_in(lk, i), (d_in, d_out)),
            "scale": jnp.full((d_out,), 1.0 / (INT8_STD * math.sqrt(d_in)),
                              jnp.float32)}
    out["ln1"] = _norm_leaves(spec, jax.random.fold_in(lk, 100))
    if not spec.parallel:
        out["ln2"] = _norm_leaves(spec, jax.random.fold_in(lk, 101))
    return out


def top_weights(spec: ModelSpec, key) -> dict:
    """Embedding, final norm and (untied) head, as served."""
    tk = jax.random.fold_in(key, 1_000_000)
    out = {"tok_embed": (LOGIT_STD / math.sqrt(spec.d) * jax.random.normal(
        jax.random.fold_in(tk, 0), (spec.vocab, spec.d))
        ).astype(jnp.bfloat16),
        "final_ln": _norm_leaves(spec, jax.random.fold_in(tk, 1))}
    if not spec.tied:
        out["lm_head"] = {
            "q": _int8(jax.random.fold_in(tk, 2), (spec.d, spec.vocab)),
            "scale": jnp.full((spec.vocab,),
                              LOGIT_STD / (INT8_STD * math.sqrt(spec.d)),
                              jnp.float32)}
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _round(x, precision: str):
    """The control's rounding of an activation operand (last axis = one
    vector); the identity for the reference."""
    if precision == "float32":
        return x
    if precision == "int8":
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        scale = jnp.maximum(amax, 1e-8) / 127.0
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(precision)


def _mm(x, w, precision):
    return _round(x, precision) @ (w["q"].astype(jnp.float32) * w["scale"])


def _norm(spec, x, p):
    scale = p["scale"].astype(jnp.float32)
    if spec.norm == "rmsnorm":
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + spec.eps) * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + spec.eps) * scale
            + p["bias"].astype(jnp.float32))


def _rope(x, theta):
    """x: (N, T, heads, hd); rotate-half over the whole head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(spec, h, w, precision):
    n, t, _ = h.shape
    H, K, hd = spec.heads, spec.kv_heads, spec.head_dim
    q = _rope(_mm(h, w["wq"], precision).reshape(n, t, H, hd),
              spec.rope_theta)
    k = _rope(_mm(h, w["wk"], precision).reshape(n, t, K, hd),
              spec.rope_theta)
    v = _mm(h, w["wv"], precision).reshape(n, t, K, hd)
    q, k, v = _round(q, precision), _round(k, precision), _round(v, precision)
    k = jnp.repeat(k, H // K, axis=2)       # head h reads kv head h // (H/K)
    v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", _round(p, precision), v)
    return _mm(o.reshape(n, t, H * hd), w["wo"], precision)


def _mlp(spec, h, w, precision):
    act = {"silu": jax.nn.silu,
           "gelu": functools.partial(jax.nn.gelu, approximate=False)
           }[spec.act]
    up = _mm(h, w["w_up"], precision)
    hidden = act(_mm(h, w["w_gate"], precision)) * up if spec.gated \
        else act(up)
    return _mm(hidden, w["w_down"], precision)


@functools.partial(jax.jit, static_argnums=(0, 4))
def block(spec: ModelSpec, key, layer, x, precision: str = "float32"):
    """One layer over x (N, T, d) float32; its weights made inside."""
    with jax.default_matmul_precision("highest"):
        w = layer_weights(spec, key, layer)
        h = _norm(spec, x, w["ln1"])
        if spec.parallel:
            return (x + _attention(spec, h, w, precision)
                    + _mlp(spec, h, w, precision))
        x = x + _attention(spec, h, w, precision)
        return x + _mlp(spec, _norm(spec, x, w["ln2"]), w, precision)


@functools.partial(jax.jit, static_argnums=(0,))
def embed(spec: ModelSpec, key, tokens):
    return jnp.take(top_weights(spec, key)["tok_embed"], tokens,
                    axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 3))
def unembed(spec: ModelSpec, key, x, precision: str = "float32"):
    """x (..., d) float32 -> logits (..., vocab) float32."""
    with jax.default_matmul_precision("highest"):
        top = top_weights(spec, key)
        h = _norm(spec, x, top["final_ln"])
        if spec.tied:
            return _round(h, precision) @ top["tok_embed"].astype(
                jnp.float32).T
        return _mm(h, top["lm_head"], precision)


def logits_at(spec: ModelSpec, seed: int, tokens, positions,
              precision: str = "float32", rows_per_block: int = 8):
    """Logits of the reference at chosen positions.

    tokens: (N, T) int32, each row a prompt with its served tokens,
    right-padded (causal attention never looks right, so the padding is
    inert). positions: (N, P) int32. Returns float32 (N, P, vocab).
    Layers outermost, rows in blocks, so one layer's float32 weights and
    one block's activations are all that is live."""
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


def load(name: str) -> tuple:
    """(ModelSpec, raw dict) of ``configs/<name>.json``."""
    raw = json.loads((ROOT / "configs" / f"{name}.json").read_text())
    return spec_from_config(name, raw), raw
