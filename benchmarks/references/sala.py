"""Plain reference for MiniCPM-SALA: lightning linear-attention layers
among softmax layers that select blocks, in the published order.

Keys of the published ``config.json`` in backticks; what it does not give
is under ``assumed`` in ``configs/minicpm-sala.json`` and marked (assumed)
here.

* model: ``x = embed(tokens) * scale_emb``; every block is pre-norm
  RMSNorm (``rms_norm_eps``): ``x += r * mixer(n1(x))``, ``x += r *
  mlp(n2(x))`` with ``r = scale_depth / sqrt(num_hidden_layers)`` and
  ``mlp(h) = W_down(silu(W_gate h) * W_up h)``; final RMSNorm; logits =
  ``head(x / (hidden_size / dim_model_base))``, untied head.
* lightning layer (``mixer_types[i] == "lightning-attn"``): ``q, k, v = h
  Wq, h Wk, h Wv``, ``lightning_nh`` heads of ``lightning_head_dim``
  (``lightning_nkv`` equal: no grouping); RMSNorm with a learned scale
  over each head of q and of k (``qk_norm``); rotate-half rotary over the
  whole head at ``rope_theta`` (``lightning_use_rope``); per head ``S_t =
  lambda_h S_{t-1} + k_t^T v_t``, ``o_t = (q_t / sqrt(head_dim)) S_t``
  (``lightning_scale``), ``lambda_h = exp(-2^(-8 (h + 1) / heads))`` the
  same in every layer (assumed: Lightning Attention's slopes); RMSNorm
  with a learned scale over the whole of ``o`` (``use_output_norm``;
  assumed: over all heads at once); ``y = (o * sigmoid(h Wg)) Wo``
  (``use_output_gate``).
* softmax layer (``"minicpm4"``): ``num_attention_heads`` query heads
  over ``num_key_value_heads`` kv heads of ``head_dim``, RMSNorm over
  each head of q and k, no rotation (``attn_use_rope`` false), causal
  softmax at ``1 / sqrt(head_dim)``, ``y = (o * sigmoid(h Wg)) Wo``
  (``attn_use_output_gate``). A query at position ``p`` with ``p + 1 <=
  dense_len`` attends every key before it. Past that (assumed: MiniCPM4's
  ``sparse_config``, InfLLM-v2) the keys are pooled, the mean of k over
  each ``kernel_size`` tokens at ``kernel_stride``, per kv head; the
  query scores the kernels that lie wholly before it, ``softmax(q .
  pooled / sqrt(head_dim))`` over them, summed over the query heads of
  its kv group; a block of ``block_size`` tokens takes the best score of
  the kernels that overlap it; the query keeps the first ``init_blocks``
  blocks, every block that reaches into its last ``window_size``
  positions (``p - window_size + 1 .. p``), and the ``topk`` best of the
  others (all of them where fewer exist; of equal scores the earlier block
  first), causally, one softmax over the tokens of the blocks kept.

Everything is float32 under ``jax.default_matmul_precision("highest")``,
the recurrence a ``lax.scan`` over tokens, the attention in blocks of
queries so that a 16k-token row fits. Weights are the served tree: int8
matrices with a per-column float32 scale, bfloat16 norms; the embedding's
rows are seeded at ``1 / scale_emb`` and the head's scale folds in
``hidden_size / dim_model_base`` so that activations and logits are of
order 1 with random weights (in the trained model the weights' own size
does that).

:func:`logits_at` may pass a document that several rows share ONCE: the
model is causal, so a row's logits past the shared part depend on that
part only through its keys and values (softmax layers) and its state
(lightning layers). ``tests/test_sala_reference.py`` holds the shared
pass equal to the row-by-row forward.

``precision``: ``"float32"`` is the reference. CONTROLS, never computed by
the benchmark's own runs: ``"int8"`` / ``"fp8"`` round every matrix
product's activation operand, the softmax layers' q, k, v and
probabilities and the lightning layers' q, k and v. The module imports
nothing of the program.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .decoder import (INT8_STD, LOGIT_STD, _int8, _round,
                      seed_key)  # noqa: F401 (seed_key: the harness's)

VOCAB_BLOCKS = 8          # the head is applied in column blocks
QUERY_BLOCK = 256         # queries whose scores are live at a time
SHARE_FROM = 1024         # tokens rows must share for a pass of their own
KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


@dataclasses.dataclass(frozen=True)
class SalaSpec:
    name: str
    preset: str
    vocab: int
    d: int
    kinds: tuple              # per layer "sparse" | "lightning"
    heads: int
    kv_heads: int
    head_dim: int
    l_heads: int
    l_head_dim: int
    ffn: int
    eps: float
    rope_theta: float
    attn_rope: bool
    lightning_rope: bool
    scale_emb: float
    scale_depth: float
    dim_model_base: int
    block: int
    kernel: int
    stride: int
    topk: int
    init_blocks: int
    window: int
    dense_len: int

    @property
    def layers(self) -> int:
        return len(self.kinds)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.layers)

    @property
    def logit_divisor(self) -> float:
        return self.d / self.dim_model_base

    def matrices(self, kind: str) -> dict:
        """name -> (d_in, d_out) of one layer's int8 matrices."""
        d, f = self.d, self.ffn
        if kind == "sparse":
            q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        else:
            q = kv = self.l_heads * self.l_head_dim
        return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
                "wg": (d, q), "w_up": (d, f), "w_gate": (d, f),
                "w_down": (f, d)}

    def count(self, kind: str) -> int:
        return sum(k == kind for k in self.kinds)

    @property
    def layer_costs(self) -> tuple:
        """Rows ``(layers, matmul_params, score_width, state_flops)`` for
        ``harness/flops.py``, one a kind of layer. The softmax row's
        ``score_width`` is the published one, heads x (query-key width +
        value width): ``flops.tokens_flops`` multiplies it by every causal
        key, so past ``dense_len`` it counts keys the model does not keep
        (PERF.md §7). The lightning row keeps no keys; its recurrence costs
        five operations a state element a token."""
        params = {k: sum(a * b for a, b in self.matrices(k).values())
                  for k in ("sparse", "lightning")}
        return ((self.count("sparse"), params["sparse"],
                 self.heads * 2 * self.head_dim, 0),
                (self.count("lightning"), params["lightning"], 0,
                 5 * self.l_heads * self.l_head_dim * self.l_head_dim))


def spec_from_config(name: str, raw: dict) -> SalaSpec:
    if raw["model_type"] != "minicpm_sala":
        raise ValueError(f"{name}: model_type {raw['model_type']!r} is not "
                         "the MiniCPM-SALA block written down here")
    if (raw["attention_bias"] or raw["hidden_act"] != "silu"
            or not raw["qk_norm"] or not raw["use_output_gate"]
            or not raw["use_output_norm"] or not raw["attn_use_output_gate"]
            or raw["tie_word_embeddings"]
            or raw["lightning_nkv"] != raw["lightning_nh"]
            or raw["lightning_scale"] != "1/sqrt(d)"
            or len(raw["mixer_types"]) != raw["num_hidden_layers"]):
        raise ValueError(f"{name}: only the published MiniCPM-SALA block is "
                         "written down here")
    sparse = raw["assumed"]["sparse_config"]["value"]
    return SalaSpec(
        name=name, preset=raw["lir_tpu"]["preset"], vocab=raw["vocab_size"],
        d=raw["hidden_size"],
        kinds=tuple(KINDS[m] for m in raw["mixer_types"]),
        heads=raw["num_attention_heads"],
        kv_heads=raw["num_key_value_heads"], head_dim=raw["head_dim"],
        l_heads=raw["lightning_nh"], l_head_dim=raw["lightning_head_dim"],
        ffn=raw["intermediate_size"], eps=raw["rms_norm_eps"],
        rope_theta=float(raw["rope_theta"]),
        attn_rope=bool(raw["attn_use_rope"]),
        lightning_rope=bool(raw["lightning_use_rope"]),
        scale_emb=float(raw["scale_emb"]),
        scale_depth=float(raw["scale_depth"]),
        dim_model_base=raw["dim_model_base"],
        block=sparse["block_size"], kernel=sparse["kernel_size"],
        stride=sparse["kernel_stride"], topk=sparse["topk"],
        init_blocks=sparse["init_blocks"], window=sparse["window_size"],
        dense_len=sparse["dense_len"])


def program_fields(spec: SalaSpec) -> dict:
    """Every field of the program's ``ModelConfig`` its preset must match,
    each under the program's own name."""
    return {"vocab_size": spec.vocab, "hidden_size": spec.d,
            "n_layers": spec.layers, "layer_kinds": spec.kinds,
            "n_heads": spec.heads, "n_kv_heads": spec.kv_heads,
            "head_dim": spec.head_dim, "lightning_heads": spec.l_heads,
            "lightning_head_dim": spec.l_head_dim,
            "intermediate_size": spec.ffn, "gated_mlp": True,
            "activation": "silu", "norm": "rmsnorm", "norm_eps": spec.eps,
            "parallel_block": False, "tie_embeddings": False,
            "rope_theta": spec.rope_theta, "attn_rope": spec.attn_rope,
            "lightning_rope": spec.lightning_rope, "qk_norm": True,
            "output_gate": True, "kv_cache_int8": False,
            "residual_scale": spec.residual_scale,
            "embedding_multiplier": spec.scale_emb,
            "lm_head_multiplier": 1.0 / spec.logit_divisor,
            "sparse_block": spec.block, "sparse_kernel": spec.kernel,
            "sparse_stride": spec.stride, "sparse_topk": spec.topk,
            "sparse_init_blocks": spec.init_blocks,
            "sparse_window": spec.window,
            "sparse_dense_len": spec.dense_len}


def tiny(spec: SalaSpec, kinds=("sparse", "lightning", "lightning",
                                "sparse")) -> SalaSpec:
    """The sizes a CPU test can hold (``tests/tiny.py``): a layer of each
    kind in both orders, every width small, and a selection that is live
    on rows of ~300 tokens while a dispatch's own slots behind its trunk
    (~150) stay inside the local window: blocks of 8, the best 2 of the
    others kept past 64 tokens. The model's own tests shrink the window
    further (``dataclasses.replace``). The published scales stay."""
    return dataclasses.replace(
        spec, vocab=2048, d=64, kinds=tuple(kinds), heads=4, kv_heads=2,
        head_dim=16, l_heads=4, l_head_dim=16, ffn=128, block=8, kernel=4,
        stride=2, topk=2, init_blocks=1, window=192, dense_len=64)


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------

def _vector(key, shape, mean=1.0, std=0.1):
    return (mean + std * jax.random.normal(key, shape)).astype(jnp.bfloat16)


def layer_weights(spec: SalaSpec, key, kind: str, layer,
                  payload=_int8) -> dict:
    """One layer's leaves as served; ``layer`` is its index in its KIND's
    own stack and may be traced (the served group is this vmapped).
    ``payload(key, shape)`` makes a matrix's int8 payload."""
    lk = jax.random.fold_in(jax.random.fold_in(
        key, 1 + sorted(KINDS.values()).index(kind)), layer)
    out = {}
    for i, (name, (d_in, d_out)) in enumerate(
            sorted(spec.matrices(kind).items())):
        out[name] = {"q": payload(jax.random.fold_in(lk, i), (d_in, d_out)),
                     "scale": jnp.full((d_out,),
                                       1.0 / (INT8_STD * math.sqrt(d_in)),
                                       jnp.float32)}
    f = lambda i: jax.random.fold_in(lk, 100 + i)  # noqa: E731
    hd = spec.head_dim if kind == "sparse" else spec.l_head_dim
    out["ln1"] = {"scale": _vector(f(0), (spec.d,))}
    out["ln2"] = {"scale": _vector(f(1), (spec.d,))}
    out["q_norm"] = _vector(f(2), (hd,))
    out["k_norm"] = _vector(f(3), (hd,))
    if kind == "lightning":
        out["o_norm"] = _vector(f(4), (spec.l_heads * spec.l_head_dim,))
    return out


_payload = jax.jit(_int8, static_argnums=(1,))      # one program a shape


@functools.partial(jax.jit, static_argnums=(0, 2))
def _but_payloads(spec: SalaSpec, key, kind: str, layer) -> dict:
    return layer_weights(spec, key, kind, layer, payload=lambda k, _: k)


def layer_made(spec: SalaSpec, key, kind: str, layer: int) -> dict:
    """:func:`layer_weights` of one layer, on the device, for the passes
    below: the same leaves from the same keys, each payload from a program
    compiled once a shape of matrix (a layer's whole tree in one program
    is seventeen seconds of compiling wherever it is inlined)."""
    w = _but_payloads(spec, key, kind, layer)
    for name, shape in spec.matrices(kind).items():
        w[name]["q"] = _payload(w[name]["q"], shape)
    return w


def top_weights(spec: SalaSpec, key) -> dict:
    tk = jax.random.fold_in(key, 1_000_000)
    return {
        "tok_embed": (jax.random.normal(
            jax.random.fold_in(tk, 0), (spec.vocab, spec.d))
            / spec.scale_emb).astype(jnp.bfloat16),
        "final_ln": {"scale": _vector(jax.random.fold_in(tk, 1),
                                      (spec.d,))},
        "lm_head": {
            "q": _int8(jax.random.fold_in(tk, 2), (spec.d, spec.vocab)),
            "scale": jnp.full(
                (spec.vocab,), LOGIT_STD * spec.logit_divisor
                / (INT8_STD * math.sqrt(spec.d)), jnp.float32)}}


def weights(spec: SalaSpec, key) -> dict:
    """The whole served tree in the program's layout: the top leaves, and
    under ``"layers"`` one group a kind, each stacked over its own
    layers."""
    return {**top_weights(spec, key), "layers": {
        kind: jax.vmap(lambda i, kind=kind: layer_weights(spec, key, kind, i)
                       )(jnp.arange(spec.count(kind)))
        for kind in ("sparse", "lightning")}}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _mm(x, w, precision):
    """x (float32, rounded first under a control) times an int8 matrix with
    its per-column scale, to float32 accuracy. The payload is exact in
    bfloat16, so the product needs the activation's three bfloat16 terms
    (hi + mid + lo is x to 24 bits) against it, each product exact and
    summed in float32: what ``highest`` computes, in half of the six
    passes it spends on two float32 operands."""
    x = _round(x, precision)
    q = w["q"].astype(jnp.bfloat16)
    acc = 0.0
    for _ in range(3):
        # reduce_precision and not a cast there and back: a compiler that
        # allows excess precision may drop the pair, never this.
        term = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        acc = acc + jnp.matmul(term.astype(jnp.bfloat16), q,
                               preferred_element_type=jnp.float32)
        x = x - term
    return acc * w["scale"]


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps
                             ) * scale.astype(jnp.float32)


def _rope(x, positions, theta):
    """x: (n, t, heads, hd); positions: (t,); rotate-half, whole head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def slopes(heads: int):
    return jnp.exp2(-8.0 * (jnp.arange(heads, dtype=jnp.float32) + 1.0)
                    / heads)


def _lightning(spec, h, w, start, past, precision):
    """h: (n, t, d) at positions ``start + [0, t)``; ``past``: the state
    (n, H, P, P) on entry (key width, value width). Returns (y, state)."""
    n, t, _ = h.shape
    H, P = spec.l_heads, spec.l_head_dim
    pos = start + jnp.arange(t)
    heads = lambda name: _mm(h, w[name], precision).reshape(n, t, H, P)  # noqa: E731
    q = _rms(heads("wq"), w["q_norm"], spec.eps)
    k = _rms(heads("wk"), w["k_norm"], spec.eps)
    v = heads("wv")
    if spec.lightning_rope:
        q, k = _rope(q, pos, spec.rope_theta), _rope(k, pos, spec.rope_theta)
    q, k, v = (_round(a, precision) for a in (q / math.sqrt(P), k, v))
    decay = jnp.exp(-slopes(H))[None, :, None, None]

    def token(s, xs):
        qt, kt, vt = xs                                       # (n, H, P)
        s = decay * s + kt[..., :, None] * vt[..., None, :]
        return s, jnp.einsum("nhk,nhkv->nhv", qt, s)

    swap = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
    state, o = jax.lax.scan(token, past, (swap(q), swap(k), swap(v)))
    o = _rms(swap(o).reshape(n, t, H * P), w["o_norm"], spec.eps)
    gate = jax.nn.sigmoid(_mm(h, w["wg"], precision))
    return _mm(o * gate, w["wo"], precision), state


def _kept(spec, q, pooled, qpos, n_keys):
    """The blocks a row's queries keep. q: (t, K, G, hd); pooled: (NK, K,
    hd) over the row's first ``n_keys`` keys; qpos: (t,). Returns (t, K,
    NB) bool."""
    hd = q.shape[-1]
    nb = -(-n_keys // spec.block)
    b = jnp.arange(nb)
    valid = b * spec.block <= qpos[:, None]                   # (t, NB)
    local = (b + 1) * spec.block >= (qpos - spec.window + 2)[:, None]
    fixed = valid & ((qpos + 1 <= spec.dense_len)[:, None]
                     | (b < spec.init_blocks) | local)
    nk = pooled.shape[0]
    if nk == 0:
        return jnp.broadcast_to(fixed[:, None], (q.shape[0], q.shape[1], nb))
    ends = jnp.arange(nk) * spec.stride + spec.kernel - 1
    seen = ends[None, :] < qpos[:, None]                      # (t, NK)
    s = jnp.einsum("tkgd,jkd->tkgj", q, pooled) / math.sqrt(hd)
    s = jnp.where(seen[:, None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(seen[:, None, None],
                  jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    p = (p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)).sum(axis=2)
    # Block b takes the best of the kernels that overlap it.
    starts = jnp.arange(nk) * spec.stride
    overlap = ((starts[None, :] < (b[:, None] + 1) * spec.block)
               & (starts[None, :] + spec.kernel > b[:, None] * spec.block))
    score = jnp.max(jnp.where(overlap[None, None], p[:, :, None, :], 0.0),
                    axis=-1)                                  # (t, K, NB)
    others = (valid & ~fixed)[:, None]
    score = jnp.where(others, score, -1.0)
    # The ``topk`` best of the others, a tie going to the lower index.
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return fixed[:, None] | (others & (rank < spec.topk))


def _sparse(spec, h, w, start, past, precision):
    """h: (n, t, d) at positions ``start + [0, t)``; ``past``: (k, v), each
    (start, K, hd), the keys and values of the ``start`` tokens before,
    which every row shares. Returns (y, (k, v) of the window)."""
    n, t, _ = h.shape
    H, K, hd = spec.heads, spec.kv_heads, spec.head_dim
    G = H // K
    pos = start + jnp.arange(t)
    q = _rms(_mm(h, w["wq"], precision).reshape(n, t, H, hd), w["q_norm"],
             spec.eps)
    k = _rms(_mm(h, w["wk"], precision).reshape(n, t, K, hd), w["k_norm"],
             spec.eps)
    v = _mm(h, w["wv"], precision).reshape(n, t, K, hd)
    if spec.attn_rope:
        q, k = _rope(q, pos, spec.rope_theta), _rope(k, pos, spec.rope_theta)
    q, k, v = (_round(a, precision) for a in (q, k, v))
    n_keys = start + t
    kpos = jnp.arange(n_keys)
    step = min(QUERY_BLOCK, t)
    pad = -t % step

    def row(args):
        qr, kr, vr = args                     # (t,H,hd) (t,K,hd) (t,K,hd)
        ka = jnp.concatenate([past[0], kr], axis=0)           # (Tk, K, hd)
        va = jnp.concatenate([past[1], vr], axis=0)
        nk = max((n_keys - spec.kernel) // spec.stride + 1, 0)
        idx = (jnp.arange(nk)[:, None] * spec.stride
               + jnp.arange(spec.kernel)[None, :])
        pooled = ka[idx].mean(axis=1) if nk else ka[:0]       # (NK, K, hd)
        qg = jnp.pad(qr.reshape(t, K, G, hd), ((0, pad),) + ((0, 0),) * 3)
        qp = jnp.pad(pos, (0, pad), constant_values=n_keys - 1)

        def queries(args):
            qb, pb = args                                     # (s,K,G,hd) (s,)
            keep = _kept(spec, qb, pooled, pb, n_keys)        # (s, K, NB)
            ok = (jnp.repeat(keep, spec.block, axis=-1)[..., :n_keys]
                  & (kpos[None, None, :] <= pb[:, None, None]))
            s = jnp.einsum("skgd,jkd->skgj", qb, ka) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(ok[:, :, None], s, -jnp.inf), -1)
            return jnp.einsum("skgj,jkd->skgd", _round(p, precision), va)

        o = jax.lax.map(queries, (qg.reshape((-1, step) + qg.shape[1:]),
                                  qp.reshape(-1, step)))
        return o.reshape(t + pad, H * hd)[:t]

    o = jax.lax.map(row, (q, k, v))
    gate = jax.nn.sigmoid(_mm(h, w["wg"], precision))
    return _mm(o * gate, w["wo"], precision), (k, v)


def _mlp(h, w, precision):
    hidden = jax.nn.silu(_mm(h, w["w_gate"], precision)) * _mm(
        h, w["w_up"], precision)
    return _mm(hidden, w["w_down"], precision)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 6))
def block(spec: SalaSpec, kind: str, start: int, x, past, w,
          precision: str = "float32"):
    """One layer of weights ``w`` (:func:`layer_made`) over x (n, t, d)
    float32 at positions ``start + [0, t)``. ``past`` is what the layer
    carries from the ``start`` tokens before: the lightning state (n, H, P,
    P), or the softmax layer's shared (k, v). Returns (x, what the window
    hands on)."""
    with jax.default_matmul_precision("highest"):
        r = spec.residual_scale
        mixer = _sparse if kind == "sparse" else _lightning
        mix, carry = mixer(spec, _rms(x, w["ln1"]["scale"], spec.eps), w,
                           start, past, precision)
        x = x + r * mix
        return x + r * _mlp(_rms(x, w["ln2"]["scale"], spec.eps), w,
                            precision), carry


top_made = jax.jit(top_weights, static_argnums=(0,))


@functools.partial(jax.jit, static_argnums=(0,))
def embed(spec: SalaSpec, top, tokens):
    return jnp.take(top["tok_embed"], tokens,
                    axis=0).astype(jnp.float32) * spec.scale_emb


@functools.partial(jax.jit, static_argnums=(0, 3))
def unembed(spec: SalaSpec, top, x, precision: str = "float32"):
    """x (..., d) float32 -> logits (..., vocab) float32 under the top
    leaves ``top`` (:data:`top_made`), the head applied in
    :data:`VOCAB_BLOCKS` column blocks, one after another."""
    h = _rms(x, top["final_ln"]["scale"], spec.eps) / spec.logit_divisor
    blocks = VOCAB_BLOCKS if spec.vocab % VOCAB_BLOCKS == 0 else 1
    width = spec.vocab // blocks
    q, scale = top["lm_head"]["q"], top["lm_head"]["scale"]

    def part(i):
        return _mm(h, {
            "q": jax.lax.dynamic_slice_in_dim(q, i * width, width, axis=1),
            "scale": jax.lax.dynamic_slice_in_dim(scale, i * width, width)},
            precision)

    parts = jax.lax.map(part, jnp.arange(blocks))
    return jnp.moveaxis(parts, 0, -2).reshape(*x.shape[:-1], spec.vocab)


def _empty(spec: SalaSpec, kind: str, n: int):
    if kind == "lightning":
        return jnp.zeros((n, spec.l_heads, spec.l_head_dim, spec.l_head_dim),
                         jnp.float32)
    kv = jnp.zeros((0, spec.kv_heads, spec.head_dim), jnp.float32)
    return kv, kv


def _stack_index(spec: SalaSpec):
    """Per layer (kind, index within the kind's own stack)."""
    seen, out = {}, []
    for kind in spec.kinds:
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = out[-1][1] + 1
    return out


def forward_rows(spec, key, tokens, precision="float32", top=None):
    """x (n, t, d) after the last layer, every row on its own."""
    x = embed(spec, top_made(spec, key) if top is None else top, tokens)
    for kind, i in _stack_index(spec):
        x, _ = block(spec, kind, 0, x, _empty(spec, kind, x.shape[0]),
                     layer_made(spec, key, kind, i), precision)
    return x


def shared_documents(tokens: np.ndarray, unit: int) -> tuple:
    """(cluster of each row, tokens every row of every cluster shares with
    its cluster's first row, on a grid of ``unit``): rows whose first
    :data:`SHARE_FROM` tokens are equal are one document. 0 shared where
    some row shares less than :data:`SHARE_FROM` with its document."""
    firsts, cluster = [], []
    shared = tokens.shape[1]
    for row in tokens:
        for c, f in enumerate(firsts):
            if np.array_equal(row[:SHARE_FROM], tokens[f][:SHARE_FROM]):
                differ = np.nonzero(row != tokens[f])[0]
                shared = min(shared, int(differ[0]) if differ.size
                             else tokens.shape[1])
                cluster.append(c)
                break
        else:
            firsts.append(len(cluster))
            cluster.append(len(firsts) - 1)
    if len(firsts) == len(cluster) or shared < SHARE_FROM:
        return cluster, 0
    return cluster, shared // unit * unit


def forward_shared(spec, key, tokens, shared: int, cluster: list,
                   precision="float32", top=None):
    """x (n, t - shared, d) after the last layer for the tokens past
    ``shared``: each document's first ``shared`` tokens pass once, its
    rows continue from that pass's keys, values and states. A layer's
    weights are made once and serve every document."""
    tokens = np.asarray(tokens)
    top = top_made(spec, key) if top is None else top
    docs = sorted(set(cluster))
    rows_of = {c: [r for r, cr in enumerate(cluster) if cr == c]
               for c in docs}
    # Every document's rows are filled up to the number of all rows with
    # its last one again, so that the continuation is ONE shape whatever
    # way the sample falls over the documents (a shape is a compile; the
    # rows added are a question long, beside a document's 16,000 tokens).
    fill = {c: rows_of[c] + rows_of[c][-1:] * (len(cluster) - len(rows_of[c]))
            for c in docs}
    heads = {c: embed(spec, top, jnp.asarray(tokens[rows_of[c][:1], :shared]))
             for c in docs}
    tails = {c: embed(spec, top, jnp.asarray(tokens[fill[c], shared:]))
             for c in docs}
    for kind, i in _stack_index(spec):
        w = layer_made(spec, key, kind, i)
        for c in docs:
            heads[c], carry = block(spec, kind, 0, heads[c],
                                    _empty(spec, kind, 1), w, precision)
            n = len(cluster)
            past = (jnp.broadcast_to(carry, (n,) + carry.shape[1:])
                    if kind == "lightning" else
                    (carry[0][0], carry[1][0]))
            tails[c], _ = block(spec, kind, shared, tails[c], past, w,
                                precision)
    out = [None] * len(cluster)
    for c in docs:
        for j, r in enumerate(rows_of[c]):
            out[r] = tails[c][j]
    return jnp.stack(out)


def logits_at(spec: SalaSpec, seed: int, tokens, positions,
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
    cluster, shared = shared_documents(tokens, spec.block)
    if shared and positions.min() >= shared:
        x = forward_shared(spec, key, tokens, shared, cluster, precision,
                           top)
        positions = positions - shared
    else:
        x = forward_rows(spec, key, jnp.asarray(tokens), precision, top)
    picked = jnp.take_along_axis(x, jnp.asarray(positions)[:, :, None],
                                 axis=1)
    return unembed(spec, top, picked, precision)
