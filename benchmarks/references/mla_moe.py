"""Plain reference for Kimi-K2 (``model_type`` kimi_k2, the DeepSeek-V3
layout): latent attention with YaRN rotation in every layer, a dense MLP
on the first layer and routed experts beside a shared one on every later
layer.

With ``x`` the residual stream and ``n`` an RMSNorm with a learned scale
(keys of the published ``config.json`` in backticks; what no key gives is
``assumed`` in the configuration file)::

    x = x + attn(n1(x))
    x = x + mlp(n2(x))            (layer < first_k_dense_replace)
    x = x + experts(n2(x))        (every later layer)

* ``attn(h)``, EXPANDED form only: ``c_q = n_q(h W_qa)`` (``q_lora_rank``),
  ``[q_nope | q_pe]_h = c_q W_qb`` (``qk_nope_head_dim`` +
  ``qk_rope_head_dim`` for each of ``num_attention_heads`` heads); ``[c |
  k_pe] = h W_kva`` (``kv_lora_rank`` + ``qk_rope_head_dim``), ``c =
  n_kv(c)``, ``[k_nope | v]_h = c W_kvb`` (``qk_nope_head_dim`` +
  ``v_head_dim``); ``k_h = [k_nope,h | k_pe]``, one ``k_pe`` for all
  heads; ``q_pe`` and ``k_pe`` rotated at ``rope_theta`` with YaRN's
  frequencies (``rope_scaling``: ``factor``,
  ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``; the
  ramp between the correction dims ``floor(d ln(orig / (2 pi beta_fast)) /
  (2 ln theta))`` and the ``ceil`` of the same at ``beta_slow``, clipped to
  ``[0, d - 1]``) and sin, cos times ``mscale(factor, mscale) /
  mscale(factor, mscale_all_dim)``, ``mscale(f, m) = 0.1 m ln f + 1``;
  causal softmax at ``s = (qk_nope + qk_rope) ** -0.5 * mscale(factor,
  mscale_all_dim) ** 2``; ``attn = concat_h(o_h) W_o``.
* ``mlp(h) = W_down(silu(W_gate h) * W_up h)``, width ``intermediate_size``.
* ``experts(h)``: ``s = sigmoid(h W_r)`` over every expert the router
  scores (``published.n_routed_experts``) in float32; the
  ``num_experts_per_tok`` experts of the largest ``s + b`` (``b`` the
  ``e_score_correction_bias``; ``topk_method`` noaux_tc with ``n_group`` =
  ``topk_group`` = 1: no group limit) are chosen; gate ``g_e =
  routed_scaling_factor * s_e / sum(s over the chosen)``
  (``norm_topk_prob``); ``experts(h) = shared(h) + sum over the chosen
  experts this chip holds of g_e expert_e(h)``, every expert a gated SiLU
  MLP of ``moe_intermediate_size``, ``n_shared_experts`` shared.
* top: embedding rows; final RMSNorm; untied head.

Departures from the published model, each also in the configuration
file:

* Rotation pairs channel ``i`` with ``i + qk_rope / 2`` (rotate-half);
  the published checkpoint pairs ``2i`` with ``2i + 1``. Over seeded
  weights that is a fixed permutation of the rotated columns of ``W_qb``
  and ``W_kva``, the same model.
* The router seeding, the embedding rows and the experts' weights are
  ``references/exaone_moe.py``'s (a router that reads the token more than
  its context, so served and float32 routing rarely part); every output
  projection (``W_o``, the MLPs' ``W_down``) is seeded at
  :data:`OUT_SCALE` of a unit-variance map, so that in this pre-norm block
  the residual stays the embedding's along the router's directions.

Everything is float32 under ``jax.default_matmul_precision("highest")``,
no kernel, no cache, no absorbed form (a product with an int8 matrix as
``references/sala._mm`` takes it: the activation's three bfloat16 terms
against the payload, which bfloat16 holds exactly, to float32 accuracy); attention in blocks of
:data:`QUERY_BLOCK` queries so that a 16k-token row fits, and rows that
share a document pass it once (``references/sala.shared_documents``): the
model is causal, so a row's logits past the shared part depend on it only
through its keys and values. Weights are the served tree: int8 matrices
with a per-column float32 scale, the router's weight and bias float32,
bfloat16 norms; an expert's weights are made from its GLOBAL index, so
any slice of the experts is the same model's.

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
import numpy as np

from . import exaone_moe as ex
from .decoder import _round, seed_key  # noqa: F401 (seed_key: the harness's)
from .sala import _mm, shared_documents

QUERY_BLOCK = 256        # queries whose scores are live at a time
OUT_SCALE = 0.25         # the output projections' gain (see the docstring)
SHARE_UNIT = 16          # the grid a shared document's length is cut to


@dataclasses.dataclass(frozen=True)
class MLASpec:
    name: str
    preset: str              # the program's registry name for these sizes
    vocab: int
    d: int
    layers: int
    heads: int
    q_rank: int
    kv_rank: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    ffn: int                 # the dense layers' MLP width
    experts: int             # experts each router scores
    held: tuple              # (first, count) of the experts this chip holds
    top_k: int
    expert_ffn: int
    shared: int              # shared experts (of expert_ffn each)
    scale: float             # routed_scaling_factor
    dense_layers: int        # first_k_dense_replace
    rope_theta: float
    yarn: tuple              # (factor, original, beta_fast, beta_slow,
    #                           mscale, mscale_all_dim)
    eps: float

    @property
    def qk_dim(self) -> int:
        return self.qk_nope + self.qk_rope

    @property
    def matrices(self) -> dict:
        """name -> (d_in, d_out) of one layer's attention matrices."""
        H = self.heads
        return {"wq_a": (self.d, self.q_rank),
                "wq_b": (self.q_rank, H * self.qk_dim),
                "wkv_a": (self.d, self.kv_rank + self.qk_rope),
                "wkv_b": (self.kv_rank, H * (self.qk_nope + self.v_dim)),
                "wo": (H * self.v_dim, self.d)}

    @property
    def attention_params(self) -> int:
        return sum(a * b for a, b in self.matrices.values())

    @property
    def expert_params(self) -> int:
        return 3 * self.d * self.expert_ffn

    @property
    def routed_layer_params(self) -> int:
        """What one token multiplies in an expert layer HERE: attention,
        router, the shared expert(s), and ``top_k x held / experts`` of an
        expert (under uniform routing)."""
        return (self.attention_params + self.d * self.experts
                + self.shared * self.expert_params
                + self.top_k * self.held[1] * self.expert_params
                // self.experts)

    @property
    def layer_costs(self) -> tuple:
        """Rows ``(layers, matmul_params, score_width, state_flops)`` for
        ``harness/flops.py``: the dense layers, the expert layers. The
        score width is the expanded form's, heads x (query-key width +
        value width); expanding the latent is the program's way of
        attending, not work the traffic needs."""
        width = self.heads * (self.qk_dim + self.v_dim)
        dense = self.attention_params + 3 * self.d * self.ffn
        return ((self.dense_layers, dense, width, 0),
                (self.layers - self.dense_layers, self.routed_layer_params,
                 width, 0))

    @property
    def weight_bytes(self) -> int:
        held = self.held[1] * self.expert_params
        routed = (self.attention_params + 4 * self.d * self.experts + held
                  + self.shared * self.expert_params)
        dense = self.attention_params + 3 * self.d * self.ffn
        return (self.dense_layers * dense
                + (self.layers - self.dense_layers) * routed
                + 3 * self.d * self.vocab)


def spec_from_config(name: str, raw: dict) -> MLASpec:
    """The published keys of ``configs/<name>.json`` -> sizes. The file's
    ``n_routed_experts`` is what this chip holds (experts 0 to
    n_routed_experts - 1); ``published.n_routed_experts`` is what each
    router scores."""
    if raw["model_type"] != "kimi_k2":
        raise ValueError(f"{name}: model_type {raw['model_type']!r} has no "
                         "block here")
    if raw["n_group"] != 1 or raw["topk_group"] != 1 \
            or raw["scoring_func"] != "sigmoid" \
            or raw["topk_method"] != "noaux_tc" \
            or not raw["norm_topk_prob"] or raw["hidden_act"] != "silu" \
            or raw["attention_bias"] or raw["tie_word_embeddings"] \
            or raw["moe_layer_freq"] != 1:
        raise ValueError(f"{name}: only a sigmoid router with no group "
                         "limit, normalized gates and an expert layer after "
                         "every dense one is written down here")
    y = raw["rope_scaling"]
    if y["type"] != "yarn":
        raise ValueError(f"{name}: rope_scaling {y['type']!r}")
    scored = raw.get("published", {}).get("n_routed_experts",
                                          raw["n_routed_experts"])
    return MLASpec(
        name=name, preset=raw["lir_tpu"]["preset"], vocab=raw["vocab_size"],
        d=raw["hidden_size"], layers=raw["num_hidden_layers"],
        heads=raw["num_attention_heads"], q_rank=raw["q_lora_rank"],
        kv_rank=raw["kv_lora_rank"], qk_nope=raw["qk_nope_head_dim"],
        qk_rope=raw["qk_rope_head_dim"], v_dim=raw["v_head_dim"],
        ffn=raw["intermediate_size"], experts=scored,
        held=(0, raw["n_routed_experts"]),
        top_k=raw["num_experts_per_tok"],
        expert_ffn=raw["moe_intermediate_size"],
        shared=raw["n_shared_experts"],
        scale=float(raw["routed_scaling_factor"]),
        dense_layers=raw["first_k_dense_replace"],
        rope_theta=float(raw["rope_theta"]),
        yarn=(float(y["factor"]), int(y["original_max_position_embeddings"]),
              float(y["beta_fast"]), float(y["beta_slow"]),
              float(y["mscale"]), float(y["mscale_all_dim"])),
        eps=raw["rms_norm_eps"])


def program_fields(spec: MLASpec) -> dict:
    """Every field of the program's ``ModelConfig`` its preset must match:
    field -> the value the configuration file gives."""
    return {"vocab_size": spec.vocab, "hidden_size": spec.d,
            "n_layers": spec.layers, "n_heads": spec.heads,
            "n_kv_heads": 1, "head_dim": spec.qk_dim,
            "intermediate_size": spec.ffn, "gated_mlp": True,
            "activation": "silu", "norm": "rmsnorm", "norm_eps": spec.eps,
            "parallel_block": False, "shared_block_ln": False,
            "tie_embeddings": False, "rope_theta": spec.rope_theta,
            "pos_embedding": "rotary", "kv_cache_int8": False,
            "qk_norm": False, "post_norm": False, "layer_windowed": (),
            "q_lora_rank": spec.q_rank, "kv_lora_rank": spec.kv_rank,
            "qk_nope_dim": spec.qk_nope, "qk_rope_dim": spec.qk_rope,
            "v_head_dim": spec.v_dim, "rope_scaling": tuple(spec.yarn),
            "num_experts": spec.experts, "experts_held": tuple(spec.held),
            "expert_top_k": spec.top_k, "expert_size": spec.expert_ffn,
            "shared_experts": spec.shared, "router_scale": spec.scale,
            "first_dense_layers": spec.dense_layers}


def tiny(spec: MLASpec) -> MLASpec:
    """The sizes a CPU test can hold (``tests/tiny.py``): the dense layer
    and two expert layers, 16 experts of which 4 held (a slice away from
    0), top 4, every width small, YaRN over 32 original positions."""
    return dataclasses.replace(
        spec, vocab=2048, d=64, layers=3, heads=4, q_rank=32, kv_rank=16,
        qk_nope=16, qk_rope=8, v_dim=16, ffn=128, experts=16, held=(4, 4),
        top_k=4, expert_ffn=32, shared=1,
        yarn=(spec.yarn[0], 32) + tuple(spec.yarn[2:]))


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------

def _quiet(w: dict) -> dict:
    """An output projection at OUT_SCALE."""
    return {"q": w["q"], "scale": w["scale"] * OUT_SCALE}


def _mlp_weights(key, d: int, width: int) -> dict:
    w = ex._mlp_weights(key, d, width)
    return {**w, "w_down": _quiet(w["w_down"])}


def _expert_weights(spec: MLASpec, lk, expert) -> dict:
    """Expert ``expert`` (its GLOBAL index, may be traced) of a layer."""
    w = ex._expert_weights(spec, lk, expert)
    return {**w, "w_down": _quiet(w["w_down"])}


def _attention_weights(spec: MLASpec, lk) -> dict:
    f = functools.partial(jax.random.fold_in, lk)
    m = {name: ex._matrix(f(i), *shape)
         for i, (name, shape) in enumerate(spec.matrices.items())}
    m["wo"] = _quiet(m["wo"])
    return {**m, "q_a_norm": {"scale": ex._vector(f(5), (spec.q_rank,))},
            "kv_a_norm": {"scale": ex._vector(f(6), (spec.kv_rank,))},
            "ln1": {"scale": ex._vector(f(7), (spec.d,))},
            "ln2": {"scale": ex._vector(f(8), (spec.d,))}}


def layer_weights(spec: MLASpec, key, layer, experts) -> dict:
    """One expert layer's leaves (``layer`` may be traced): attention,
    norms, ``router`` ({"w", "bias"} float32), ``experts`` (the gated MLPs
    of the experts whose global indices ``experts`` lists, stacked over
    them) and ``shared``."""
    lk = jax.random.fold_in(key, layer)
    out = _attention_weights(spec, lk)
    out["router"] = ex.router_weights(spec, key, lk)
    out["experts"] = jax.vmap(lambda e: _expert_weights(spec, lk, e))(
        jnp.asarray(experts))
    out["shared"] = _mlp_weights(jax.random.fold_in(lk, 30), spec.d,
                                 spec.shared * spec.expert_ffn)
    return out


def _dense_layer(spec: MLASpec, key, layer) -> dict:
    lk = jax.random.fold_in(key, layer)
    return {**_attention_weights(spec, lk),
            **_mlp_weights(jax.random.fold_in(lk, 10), spec.d, spec.ffn)}


def top_weights(spec: MLASpec, key) -> dict:
    return ex.top_weights(spec, key)


def _served_layer(tree: dict) -> dict:
    """The program's layout of a layer: the latents' norm scales as bare
    vectors."""
    out = dict(tree)
    out["q_a_norm"] = tree["q_a_norm"]["scale"]
    out["kv_a_norm"] = tree["kv_a_norm"]["scale"]
    return out


def weights(spec: MLASpec, key) -> dict:
    """The whole served tree in the program's layout: the top leaves,
    ``dense_layers`` (the first layers) and ``layers`` (the expert
    layers), each stacked over its own layers along a leading axis."""
    held = jnp.arange(spec.held[0], spec.held[0] + spec.held[1])
    dense = jax.vmap(lambda i: _served_layer(_dense_layer(spec, key, i)))(
        jnp.arange(spec.dense_layers))
    routed = jax.vmap(lambda i: _served_layer(layer_weights(
        spec, key, i, held)))(jnp.arange(spec.dense_layers, spec.layers))
    return {**top_weights(spec, key), "dense_layers": dense,
            "layers": routed}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

_rms = ex._rms


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(spec: MLASpec) -> np.ndarray:
    """YaRN's frequencies of the rotated pairs (float64)."""
    factor, orig, beta_fast, beta_slow = spec.yarn[:4]
    d, theta = spec.qk_rope, spec.rope_theta
    extra = theta ** -(np.arange(0, d, 2) / d)

    def dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def softmax_scale(spec: MLASpec) -> float:
    return spec.qk_dim ** -0.5 * yarn_mscale(spec.yarn[0], spec.yarn[5]) ** 2


def _rope(x, positions, spec: MLASpec):
    """x: (n, t, heads, qk_rope) at positions (t,); rotate-half."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq(spec), jnp.float32)
    m = yarn_mscale(spec.yarn[0], spec.yarn[4]) / yarn_mscale(spec.yarn[0],
                                                             spec.yarn[5])
    sin = (jnp.sin(ang) * m)[None, :, None, :]
    cos = (jnp.cos(ang) * m)[None, :, None, :]
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(spec: MLASpec, h, w, start: int, past, precision):
    """h: (n, t, d) at positions ``start + [0, t)``; ``past``: (k (Tp, H,
    qk), v (Tp, H, v)) of the ``start`` positions before, which every row
    shares. Returns (attention output, (k, v) of the window)."""
    n, t, _ = h.shape
    H, dn, r = spec.heads, spec.qk_nope, spec.kv_rank
    pos = start + jnp.arange(t)
    cq = _rms(_mm(h, w["wq_a"], precision), w["q_a_norm"]["scale"], spec.eps)
    q = _mm(cq, w["wq_b"], precision).reshape(n, t, H, spec.qk_dim)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, spec)], -1)
    kv = _mm(h, w["wkv_a"], precision)
    c = _rms(kv[..., :r], w["kv_a_norm"]["scale"], spec.eps)
    k_pe = _rope(kv[..., None, r:], pos, spec)
    kvb = _mm(c, w["wkv_b"], precision).reshape(n, t, H, dn + spec.v_dim)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_pe, (n, t, H, spec.qk_rope))], -1)
    v = kvb[..., dn:]
    q, k, v = (_round(a, precision) for a in (q, k, v))
    scale = softmax_scale(spec)
    kpos = jnp.arange(start + t)
    step = min(QUERY_BLOCK, t)
    pad = -t % step

    def row(args):
        qr, kr, vr = args
        ka = jnp.concatenate([past[0], kr], axis=0)            # (Tk, H, qk)
        va = jnp.concatenate([past[1], vr], axis=0)
        qb = jnp.pad(qr, ((0, pad), (0, 0), (0, 0)))
        qp = jnp.pad(pos, (0, pad), constant_values=start + t - 1)

        def queries(args):
            qs, ps = args
            s = jnp.einsum("shd,jhd->hsj", qs, ka) * scale
            s = jnp.where(kpos[None, None, :] <= ps[None, :, None], s,
                          -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("hsj,jhd->shd", _round(p, precision), va)

        o = jax.lax.map(queries, (qb.reshape((-1, step) + qb.shape[1:]),
                                  qp.reshape(-1, step)))
        return o.reshape(t + pad, H * spec.v_dim)[:t]

    o = jax.lax.map(row, (q, k, v))
    return _mm(o, w["wo"], precision), (k, v)


def _mlp(h, w, precision):
    hidden = jax.nn.silu(_mm(h, w["w_gate"], precision)) * _mm(
        h, w["w_up"], precision)
    return _mm(hidden, w["w_down"], precision)


def _routed_terms(spec: MLASpec, key, lk, u, precision):
    """The shared expert and the sum over each token's chosen experts
    among the held ones of g_e expert_e(u), u (N, d): each held expert
    applied to every token (one scan over them, its weights made inside),
    its term kept where the token chose it."""
    idx, gates = ex.route(spec, u, ex.router_weights(spec, key, lk),
                          precision)
    held = jnp.arange(spec.held[0], spec.held[0] + spec.held[1])

    def one(total, e):
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)[:, None]
        return total + g * _mlp(u, _expert_weights(spec, lk, e),
                                precision), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), held)
    return _mlp(u, _mlp_weights(jax.random.fold_in(lk, 30), spec.d,
                                spec.shared * spec.expert_ffn),
                precision) + routed


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 7))
def layer_block(spec: MLASpec, key, routed: bool, start: int, layer, x,
                past, precision: str = "float32"):
    """One layer over x (n, t, d) float32 at positions ``start + [0, t)``,
    its weights made inside: (x, (k, v) of the window)."""
    with jax.default_matmul_precision("highest"):
        lk = jax.random.fold_in(key, layer)
        w = _attention_weights(spec, lk)
        a, kv = _attention(spec, _rms(x, w["ln1"]["scale"], spec.eps), w,
                           start, past, precision)
        x = x + a
        u = _rms(x, w["ln2"]["scale"], spec.eps)
        n, t, d = u.shape
        if routed:
            m = _routed_terms(spec, key, lk, u.reshape(n * t, d),
                              precision).reshape(n, t, d)
        else:
            m = _mlp(u, _mlp_weights(jax.random.fold_in(lk, 10), spec.d,
                                     spec.ffn), precision)
        return x + m, kv


def _layer(spec: MLASpec, key, layer: int, start: int, x, past, precision):
    """One layer over x (n, t, d) float32; returns (x, (k, v))."""
    return layer_block(spec, key, layer >= spec.dense_layers, start,
                       jnp.int32(layer), x, past, precision)


top_made = jax.jit(top_weights, static_argnums=(0,))


@functools.partial(jax.jit, static_argnums=(0,))
def embed(spec: MLASpec, top, tokens):
    return jnp.take(top["tok_embed"], tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 3))
def unembed(spec: MLASpec, top, x, precision: str = "float32"):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(x, top["final_ln"]["scale"], spec.eps),
                   top["lm_head"], precision)


def _empty(spec: MLASpec):
    return (jnp.zeros((0, spec.heads, spec.qk_dim), jnp.float32),
            jnp.zeros((0, spec.heads, spec.v_dim), jnp.float32))


def forward_rows(spec: MLASpec, key, tokens, precision="float32",
                 top=None):
    """x (n, t, d) after the last layer, every row on its own."""
    x = embed(spec, top_made(spec, key) if top is None else top, tokens)
    for layer in range(spec.layers):
        x, _ = _layer(spec, key, layer, 0, x, _empty(spec), precision)
    return x


def forward_shared(spec: MLASpec, key, tokens, shared: int, cluster: list,
                   precision="float32", top=None):
    """x (n, t - shared, d) after the last layer for the tokens past
    ``shared``: each document's first ``shared`` tokens pass once, its
    rows continue from that pass's keys and values (layers outermost)."""
    tokens = np.asarray(tokens)
    top = top_made(spec, key) if top is None else top
    docs = sorted(set(cluster))
    rows_of = {c: [r for r, cr in enumerate(cluster) if cr == c]
               for c in docs}
    # Every document's rows are filled up to the number of all rows with
    # its last one again, so that the continuation is ONE shape.
    fill = {c: rows_of[c] + rows_of[c][-1:] * (len(cluster) - len(rows_of[c]))
            for c in docs}
    heads = {c: embed(spec, top, jnp.asarray(tokens[rows_of[c][:1], :shared]))
             for c in docs}
    tails = {c: embed(spec, top, jnp.asarray(tokens[fill[c], shared:]))
             for c in docs}
    for layer in range(spec.layers):
        for c in docs:
            heads[c], (k, v) = _layer(spec, key, layer, 0, heads[c],
                                      _empty(spec), precision)
            tails[c], _ = _layer(spec, key, layer, shared, tails[c],
                                 (k[0], v[0]), precision)
    out = [None] * len(cluster)
    for c in docs:
        for j, r in enumerate(rows_of[c]):
            out[r] = tails[c][j]
    return jnp.stack(out)


def logits_at(spec: MLASpec, seed: int, tokens, positions,
              precision: str = "float32"):
    """Logits of the reference at chosen positions. tokens: (N, T) int32,
    each row a prompt with its served tokens, right-padded (the model is
    causal, so the padding is inert); positions: (N, P) int32. Returns
    float32 (N, P, vocab). Rows that share a document pass it once
    (:func:`forward_shared`) where every position asked for lies past the
    shared part."""
    key = seed_key(seed)
    top = top_made(spec, key)
    tokens = np.asarray(tokens, np.int32)
    positions = np.asarray(positions, np.int32)
    cluster, shared = shared_documents(tokens, SHARE_UNIT)
    if shared and positions.min() >= shared:
        x = forward_shared(spec, key, tokens, shared, cluster, precision,
                           top)
        positions = positions - shared
    else:
        x = forward_rows(spec, key, jnp.asarray(tokens), precision, top)
    picked = jnp.take_along_axis(x, jnp.asarray(positions)[:, :, None],
                                 axis=1)
    return unembed(spec, top, picked, precision)
