"""Plain reference for K-EXAONE (``model_type`` exaone_moe): grouped-query
attention with a window on three layers of four, norms on each branch's
output, a dense MLP on the first layer and routed experts beside a shared
one on every later layer.

With ``x`` the residual stream and ``n`` an RMSNorm (keys of the published
``config.json`` in backticks; what no key gives is ``assumed`` in the
configuration file)::

    x = x + n1(attn(x))
    x = x + n2(mlp(x))            (layer < first_k_dense_replace)
    x = x + n2(experts(x))        (every later layer)

* ``attn``: ``num_attention_heads`` query / ``num_key_value_heads``
  key-value heads of ``head_dim``, no bias; ``q`` and ``k`` each through a
  per-head RMSNorm with a learned scale; rotate-half rotary at
  ``rope_parameters.rope_theta`` on the ``sliding_attention`` layers, none
  on the ``full_attention`` ones; scores scaled by ``head_dim ** -0.5``;
  a query at position ``i`` attends keys ``j`` with ``i - W < j <= i``
  where ``W`` is the layer's ``sliding_windows`` entry, every ``j <= i``
  where it is 0.
* ``mlp(h) = W_down(silu(W_gate h) * W_up h)``, width
  ``intermediate_size``.
* ``experts(h)``: ``s = sigmoid(h W_r)`` over every expert the router
  scores (``published.num_experts``), in float32; the ``num_experts_per_tok``
  experts of the largest ``s + b`` (``b`` the per-expert selection bias;
  ``n_group`` = ``topk_group`` = 1: no group limit) are chosen; gate ``g_e
  = routed_scaling_factor * s_e / sum(s over the chosen)``
  (``norm_topk_prob``); ``experts(h) = shared(h) + sum over the chosen
  experts this chip holds of g_e expert_e(h)``, every expert a gated SiLU
  MLP of ``moe_intermediate_size``.
* top: embedding rows; final RMSNorm; untied head.

Everything is float32 under ``jax.default_matmul_precision("highest")``,
no kernel, no cache, no batching trick: each held expert is applied to
every token and its term kept where the token chose it. Weights are the
served tree: int8 matrices with a per-column float32 scale, the router's
weight and bias float32, bfloat16 norms. An expert's weights are made
from its GLOBAL index, so any slice of the experts is the same model's
(:func:`uncut_experts` applies them all: the share test).

The seeded router (:func:`router_weights`) chooses by the token more than
by its context, and its scores fall off fast past a token's best few:
every embedding row carries a unit vector of norm :data:`TOKEN_NORM` in a
:data:`ROUTER_DIMS`-dimensional subspace, which the router reads at a
logit spread of :data:`ROUTER_SPREAD`, and one shared direction of norm
:data:`SHARED_NORM`, which puts every logit :data:`ROUTER_OFFSET` below 0,
where the sigmoid is an exponential. bfloat16 carries the embedding rows
exactly and the branches add little along those directions, so served and
float32 scores rarely part; where they do, the experts that trade places
at the 8th choice carry a few percent of the gate, not an eighth. One
expert a layer has selection bias :data:`BOOST`, the rest 0: every token
chooses it at a score too small to gate much, and a gate computed from
score + bias hands it most of the weight.

``precision``: ``"float32"`` is the reference. CONTROLS, never computed
by the benchmark's own runs: ``"int8"`` / ``"fp8"`` round every matrix
product's activation operand (the router's among them) and attention's
q, k, v and probabilities, as ``references/decoder.py`` does. The module
imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from .decoder import (INT8_STD, LOGIT_STD, _int8, _round,
                      seed_key)  # noqa: F401 (seed_key: the harness's)

ROUTER_DIMS = 8          # the subspace of the embedding rows the router reads
TOKEN_NORM = 60.0        # each row's part there, against ~1 a branch adds
SHARED_NORM = 60.0       # each row's part along one shared direction
ROUTER_SPREAD = 6.0      # std of a token's router logits over the experts
ROUTER_OFFSET = 17.0     # every logit this far below 0
BOOST = 1.0              # one expert a layer: chosen by every token


@dataclasses.dataclass(frozen=True)
class MoESpec:
    name: str
    preset: str              # the program's registry name for these sizes
    vocab: int
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int                 # the dense layers' MLP width
    experts: int             # experts each router scores
    held: tuple              # (first, count) of the experts this chip holds
    top_k: int
    expert_ffn: int
    shared: int              # shared experts (of expert_ffn each)
    scale: float             # routed_scaling_factor
    dense_layers: int        # first_k_dense_replace
    windows: tuple           # per layer: its window, 0 for a global layer
    rope_theta: float
    eps: float

    @property
    def window(self) -> int:
        return max(self.windows)

    @property
    def attention_params(self) -> int:
        d, q, kv = self.d, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return 2 * d * q + 2 * d * kv

    @property
    def expert_params(self) -> int:
        return 3 * self.d * self.expert_ffn

    @property
    def routed_layer_params(self) -> int:
        """What one token multiplies in an expert layer HERE: attention,
        router, the shared expert(s), and ``top_k x held / experts`` of an
        expert (the routed experts this chip holds, under uniform
        routing)."""
        return (self.attention_params + self.d * self.experts
                + self.shared * self.expert_params
                + self.top_k * self.held[1] * self.expert_params
                // self.experts)

    @property
    def layer_costs(self) -> tuple:
        """Rows ``(layers, matmul_params, score_width, state_flops)`` for
        ``harness/flops.py``: the dense layers, the window layers with
        experts, the global layers with experts. ``score_width`` counts
        every causal key, the window layers' too (flops.tokens_flops has
        no window)."""
        width = 2 * self.heads * self.head_dim
        dense = self.attention_params + 3 * self.d * self.ffn
        routed = range(self.dense_layers, self.layers)
        n_win = sum(1 for i in routed if self.windows[i])
        n_glob = len(routed) - n_win
        return ((self.dense_layers, dense, width, 0),
                (n_win, self.routed_layer_params, width, 0),
                (n_glob, self.routed_layer_params, width, 0))

    @property
    def weight_bytes(self) -> int:
        held = self.held[1] * self.expert_params
        routed = (self.attention_params + 4 * self.d * self.experts + held
                  + self.shared * self.expert_params)
        dense = self.attention_params + 3 * self.d * self.ffn
        return (self.dense_layers * dense
                + (self.layers - self.dense_layers) * routed
                + 3 * self.d * self.vocab)


def spec_from_config(name: str, raw: dict) -> MoESpec:
    """The published keys of ``configs/<name>.json`` -> sizes. The file's
    ``num_experts`` is what this chip holds (experts 0 to num_experts - 1,
    ``assumed``); ``published.num_experts`` is what each router scores."""
    if raw["model_type"] != "exaone_moe":
        raise ValueError(f"{name}: model_type {raw['model_type']!r} has no "
                         "block here")
    if raw["n_group"] != 1 or raw["topk_group"] != 1 \
            or raw["scoring_func"] != "sigmoid" \
            or not raw["norm_topk_prob"] or raw["hidden_act"] != "silu":
        raise ValueError(f"{name}: only a sigmoid router with no group "
                         "limit and normalized gates is written down here")
    L = raw["num_hidden_layers"]
    windows = tuple(int(w) for w in raw["sliding_windows"][:L])
    kinds = raw["layer_types"][:L]
    assert all((w > 0) == (k == "sliding_attention")
               for w, k in zip(windows, kinds)), name
    dense = raw["first_k_dense_replace"]
    assert raw["mlp_layer_types"][:L] == (["dense"] * dense
                                          + ["sparse"] * (L - dense)), name
    scored = raw.get("published", {}).get("num_experts", raw["num_experts"])
    return MoESpec(
        name=name, preset=raw["lir_tpu"]["preset"], vocab=raw["vocab_size"],
        d=raw["hidden_size"], layers=L, heads=raw["num_attention_heads"],
        kv_heads=raw["num_key_value_heads"], head_dim=raw["head_dim"],
        ffn=raw["intermediate_size"], experts=scored,
        held=(0, raw["num_experts"]), top_k=raw["num_experts_per_tok"],
        expert_ffn=raw["moe_intermediate_size"],
        shared=raw["num_shared_experts"],
        scale=float(raw["routed_scaling_factor"]), dense_layers=dense,
        windows=windows,
        rope_theta=float(raw["rope_parameters"]["rope_theta"]),
        eps=raw["rms_norm_eps"])


def program_fields(spec: MoESpec) -> dict:
    """Every field of the program's ``ModelConfig`` its preset must match:
    field -> the value the configuration file gives."""
    return {"vocab_size": spec.vocab, "hidden_size": spec.d,
            "n_layers": spec.layers, "n_heads": spec.heads,
            "n_kv_heads": spec.kv_heads, "head_dim": spec.head_dim,
            "intermediate_size": spec.ffn, "gated_mlp": True,
            "activation": "silu", "norm": "rmsnorm", "norm_eps": spec.eps,
            "parallel_block": False, "shared_block_ln": False,
            "tie_embeddings": False, "rope_theta": spec.rope_theta,
            "pos_embedding": "rotary", "rotary_pct": 1.0,
            "kv_cache_int8": False, "qk_norm": True, "post_norm": True,
            "layer_windowed": tuple(w > 0 for w in spec.windows),
            "sliding_window": spec.window,
            "num_experts": spec.experts, "experts_held": tuple(spec.held),
            "expert_top_k": spec.top_k, "expert_size": spec.expert_ffn,
            "shared_experts": spec.shared, "router_scale": spec.scale,
            "first_dense_layers": spec.dense_layers}


def tiny(spec: MoESpec) -> MoESpec:
    """The sizes a CPU test can hold (``tests/tiny.py``): two LLLG periods,
    the first layer dense, a window of 8, 16 experts of which 4 held (a
    slice away from 0), top 4, every width small."""
    layers = 8
    return dataclasses.replace(
        spec, vocab=2048, d=64, layers=layers, heads=4, kv_heads=2,
        head_dim=16, ffn=128, experts=16, held=(4, 4), top_k=4,
        expert_ffn=32, shared=1,
        windows=tuple(0 if i % 4 == 3 else 8 for i in range(layers)))


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------

def _matrix(key, d_in: int, d_out: int, lead=()) -> dict:
    return {"q": _int8(key, lead + (d_in, d_out)),
            "scale": jnp.full(lead + (d_out,),
                              1.0 / (INT8_STD * math.sqrt(d_in)),
                              jnp.float32)}


def _vector(key, shape):
    return (1.0 + 0.1 * jax.random.normal(key, shape)).astype(jnp.bfloat16)


def _attention_weights(spec: MoESpec, lk) -> dict:
    d, q, kv = spec.d, spec.heads * spec.head_dim, \
        spec.kv_heads * spec.head_dim
    f = functools.partial(jax.random.fold_in, lk)
    return {"wq": _matrix(f(0), d, q), "wk": _matrix(f(1), d, kv),
            "wv": _matrix(f(2), d, kv), "wo": _matrix(f(3), q, d),
            "q_norm": {"scale": _vector(f(4), (spec.head_dim,))},
            "k_norm": {"scale": _vector(f(5), (spec.head_dim,))},
            "ln1": {"scale": _vector(f(6), (spec.d,))},
            "ln2": {"scale": _vector(f(7), (spec.d,))}}


def _mlp_weights(key, d: int, width: int) -> dict:
    f = functools.partial(jax.random.fold_in, key)
    return {"w_gate": _matrix(f(0), d, width), "w_up": _matrix(f(1), d, width),
            "w_down": _matrix(f(2), width, d)}


def _expert_weights(spec: MoESpec, lk, expert) -> dict:
    """Expert ``expert`` (its GLOBAL index, may be traced) of a layer."""
    return _mlp_weights(jax.random.fold_in(lk, 1000 + expert), spec.d,
                        spec.expert_ffn)


def _router_basis(key, d: int) -> tuple:
    """(the router's subspace (d, ROUTER_DIMS), the shared direction (d,)):
    orthonormal, one draw a seed."""
    q, _ = jnp.linalg.qr(jax.random.normal(
        jax.random.fold_in(key, 2_000_000), (d, ROUTER_DIMS + 1),
        jnp.float32))
    return q[:, :ROUTER_DIMS], q[:, ROUTER_DIMS]


def router_weights(spec: MoESpec, key, lk) -> dict:
    """A layer's router (``lk`` its key): weight (d, experts) in the
    router's subspace at logit spread ROUTER_SPREAD, less ROUTER_OFFSET
    along the shared direction; selection bias BOOST on one expert."""
    basis, shared = _router_basis(key, spec.d)
    f = functools.partial(jax.random.fold_in, lk)
    w = basis @ (ROUTER_SPREAD / TOKEN_NORM * jax.random.normal(
        f(20), (ROUTER_DIMS, spec.experts), jnp.float32))
    w = w - (ROUTER_OFFSET / SHARED_NORM) * shared[:, None]
    boosted = jax.random.randint(f(21), (), 0, spec.experts)
    bias = BOOST * jax.nn.one_hot(boosted, spec.experts, dtype=jnp.float32)
    return {"w": w, "bias": bias}


def layer_weights(spec: MoESpec, key, layer, experts) -> dict:
    """One expert layer's leaves (``layer`` may be traced): attention,
    norms, ``router`` ({"w", "bias"} float32), ``experts`` (the gated
    MLPs of the experts whose global indices ``experts`` lists, stacked
    over them) and ``shared``."""
    lk = jax.random.fold_in(key, layer)
    out = _attention_weights(spec, lk)
    f = functools.partial(jax.random.fold_in, lk)
    out["router"] = router_weights(spec, key, lk)
    out["experts"] = jax.vmap(lambda e: _expert_weights(spec, lk, e))(
        jnp.asarray(experts))
    out["shared"] = _mlp_weights(f(30), spec.d,
                                 spec.shared * spec.expert_ffn)
    return out


def _dense_layer(spec: MoESpec, key, layer) -> dict:
    lk = jax.random.fold_in(key, layer)
    return {**_attention_weights(spec, lk),
            **_mlp_weights(jax.random.fold_in(lk, 10), spec.d, spec.ffn)}


def top_weights(spec: MoESpec, key) -> dict:
    """Embedding rows (each with its part in the router's subspace and
    along the shared direction, :func:`router_weights`), final norm,
    head."""
    tk = jax.random.fold_in(key, 1_000_000)
    basis, shared = _router_basis(key, spec.d)
    token = jax.random.normal(jax.random.fold_in(tk, 3),
                              (spec.vocab, ROUTER_DIMS), jnp.float32)
    token = TOKEN_NORM * token / jnp.linalg.norm(token, axis=-1,
                                                 keepdims=True)
    rows = (LOGIT_STD / math.sqrt(spec.d) * jax.random.normal(
        jax.random.fold_in(tk, 0), (spec.vocab, spec.d))
        + token @ basis.T + SHARED_NORM * shared)
    return {"tok_embed": rows.astype(jnp.bfloat16),
        "final_ln": {"scale": _vector(jax.random.fold_in(tk, 1),
                                      (spec.d,))},
        "lm_head": {
            "q": _int8(jax.random.fold_in(tk, 2), (spec.d, spec.vocab)),
            "scale": jnp.full((spec.vocab,),
                              LOGIT_STD / (INT8_STD * math.sqrt(spec.d)),
                              jnp.float32)}}


def _served_layer(tree: dict) -> dict:
    """The program's layout of a layer: norms' scales as bare vectors
    where the program reads them so (q_norm, k_norm)."""
    out = dict(tree)
    out["q_norm"] = tree["q_norm"]["scale"]
    out["k_norm"] = tree["k_norm"]["scale"]
    return out


def weights(spec: MoESpec, key) -> dict:
    """The whole served tree in the program's layout: the top leaves,
    ``dense_layers`` (the first layers) and ``layers`` (the expert
    layers), each stacked over its own layers along a leading axis."""
    dense = jax.vmap(lambda i: _served_layer(_dense_layer(spec, key, i)))(
        jnp.arange(spec.dense_layers))
    routed = jax.vmap(lambda i: _served_layer(layer_weights(
        spec, key, i, jnp.arange(spec.held[0],
                                 spec.held[0] + spec.held[1]))))(
        jnp.arange(spec.dense_layers, spec.layers))
    return {**top_weights(spec, key), "dense_layers": dense,
            "layers": routed}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _mm(x, w, precision):
    return _round(x, precision) @ (w["q"].astype(jnp.float32) * w["scale"])


def _rms(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _rope(x, theta):
    """x: (N, T, heads, hd); rotate-half over the whole head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(spec, h, w, window, precision):
    """``window``: the layer's window (traced), 0 for a global layer,
    which also rotates nothing."""
    n, t, _ = h.shape
    H, K, hd = spec.heads, spec.kv_heads, spec.head_dim
    q = _rms(_mm(h, w["wq"], precision).reshape(n, t, H, hd),
             w["q_norm"]["scale"], spec.eps)
    k = _rms(_mm(h, w["wk"], precision).reshape(n, t, K, hd),
             w["k_norm"]["scale"], spec.eps)
    v = _mm(h, w["wv"], precision).reshape(n, t, K, hd)
    q = jnp.where(window > 0, _rope(q, spec.rope_theta), q)
    k = jnp.where(window > 0, _rope(k, spec.rope_theta), k)
    q, k, v = _round(q, precision), _round(k, precision), _round(v, precision)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / math.sqrt(hd)
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    allowed = (j <= i) & ((window == 0) | (j > i - window))
    p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", _round(p, precision), v)
    return _mm(o.reshape(n, t, H * hd), w["wo"], precision)


def _mlp(h, w, precision):
    hidden = jax.nn.silu(_mm(h, w["w_gate"], precision)) * _mm(
        h, w["w_up"], precision)
    return _mm(hidden, w["w_down"], precision)


def route(spec: MoESpec, h, router, precision: str = "float32"):
    """(chosen experts (..., top_k), gates (..., top_k)) of tokens h."""
    s = jax.nn.sigmoid(_round(h, precision) @ router["w"])
    _, idx = jax.lax.top_k(s + router["bias"], spec.top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, spec.scale * chosen / jnp.sum(chosen, -1, keepdims=True)


def routed_terms(spec: MoESpec, h, w, experts, precision: str = "float32"):
    """Sum over the tokens' chosen experts among ``experts`` (global
    indices, ``w["experts"]`` stacked over them) of g_e expert_e(h): each
    expert applied to every token, its term kept where chosen."""
    idx, gates = route(spec, h, w["router"], precision)

    def one(total, e_w):
        e, ew = e_w
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)[..., None]
        return total + g * _mlp(h, ew, precision), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (jnp.asarray(experts), w["experts"]))
    return out


def uncut_experts(spec: MoESpec, key, layer: int, h):
    """An expert layer's output with EVERY expert applied (what the whole
    deployment computes for ``h``): the share test's uncut layer."""
    w = layer_weights(spec, key, layer, jnp.arange(spec.experts))
    return _mlp(h, w["shared"], "float32") + routed_terms(
        spec, h, w, jnp.arange(spec.experts))


@functools.partial(jax.jit, static_argnums=(0, 4))
def dense_block(spec: MoESpec, key, layer, x, precision: str = "float32"):
    with jax.default_matmul_precision("highest"):
        w = _dense_layer(spec, key, layer)
        window = jnp.asarray(spec.windows)[layer]
        x = x + _rms(_attention(spec, x, w, window, precision),
                     w["ln1"]["scale"], spec.eps)
        return x + _rms(_mlp(x, w, precision), w["ln2"]["scale"], spec.eps)


@functools.partial(jax.jit, static_argnums=(0, 4))
def expert_block(spec: MoESpec, key, layer, x, precision: str = "float32"):
    """One expert layer over x (N, T, d) float32; its weights made
    inside, the held experts' only."""
    with jax.default_matmul_precision("highest"):
        held = jnp.arange(spec.held[0], spec.held[0] + spec.held[1])
        w = layer_weights(spec, key, layer, held)
        window = jnp.asarray(spec.windows)[layer]
        x = x + _rms(_attention(spec, x, w, window, precision),
                     w["ln1"]["scale"], spec.eps)
        m = _mlp(x, w["shared"], precision) + routed_terms(
            spec, x, w, held, precision)
        return x + _rms(m, w["ln2"]["scale"], spec.eps)


@functools.partial(jax.jit, static_argnums=(0,))
def embed(spec: MoESpec, key, tokens):
    return jnp.take(top_weights(spec, key)["tok_embed"], tokens,
                    axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 3))
def unembed(spec: MoESpec, key, x, precision: str = "float32"):
    with jax.default_matmul_precision("highest"):
        top = top_weights(spec, key)
        return _mm(_rms(x, top["final_ln"]["scale"], spec.eps),
                   top["lm_head"], precision)


def logits_at(spec: MoESpec, seed: int, tokens, positions,
              precision: str = "float32", rows_per_block: int = 8):
    """Logits of the reference at chosen positions. tokens: (N, T) int32,
    each row a prompt with its served tokens, right-padded (causal
    attention never looks right, so the padding is inert). positions:
    (N, P) int32. Returns float32 (N, P, vocab). Layers outermost, rows
    in blocks, so one layer's float32 weights and one block's
    activations are all that is live."""
    key = seed_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    blocks = [embed(spec, key, tokens[i:i + rows_per_block])
              for i in range(0, tokens.shape[0], rows_per_block)]
    for layer in range(spec.layers):
        block = dense_block if layer < spec.dense_layers else expert_block
        blocks = [block(spec, key, layer, x, precision) for x in blocks]
    x = jnp.concatenate(blocks, axis=0)
    picked = jnp.take_along_axis(
        x, jnp.asarray(positions, jnp.int32)[:, :, None], axis=1)
    return unembed(spec, key, picked, precision)
