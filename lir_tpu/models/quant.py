"""Weight-only int8 quantization for the decoder's linear layers.

The reference's 8-bit mode is bitsandbytes
``BitsAndBytesConfig(load_in_8bit=True)`` (compare_base_vs_instruct.py:
431-435), used so a 7B model fits one GPU. The TPU-native equivalent:
symmetric per-output-channel int8 weights with fp32 scales, dequantized
inside the matmul (``(x @ q) * scale``) — HBM for the big matrices halves
versus bf16, so a 7B model (~7 GB int8) fits a single v5e chip without
tensor parallelism. Activations stay bf16/fp32; the readout's fp32 softmax
path is unchanged.

A ``QuantTensor`` is a registered pytree node, so quantized layer stacks
ride ``lax.scan`` (the leading L axis slices both payload and scales) and
``jax.tree`` utilities transparently.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantTensor:
    """Symmetric per-output-channel int8 weight: w ≈ q * scale.

    q: int8, original shape (..., D_in, D_out); scale: fp32 (..., D_out).

    ``dynamic`` (static pytree metadata): when True, ``matmul`` quantizes
    the ACTIVATIONS per token on the fly and runs the dot s8 x s8 -> s32 on
    the MXU (int8 peak = 2x bf16 on v5e; no bf16 dequant copy of the weight
    ever materializes). This is the TPU-native analogue of bitsandbytes
    LLM.int8() vector-wise quantization — the mode the reference actually
    runs (compare_base_vs_instruct.py:431-435) — without the fp16
    outlier-column decomposition, so it is opt-in (--int8-dynamic).
    """

    q: jax.Array
    scale: jax.Array
    dynamic: bool = dataclasses.field(
        default=False, metadata=dict(static=True))

    @property
    def shape(self):
        return self.q.shape

    def dequant(self, dtype=jnp.float32) -> jax.Array:
        return (self.q.astype(dtype) * self.scale[..., None, :].astype(dtype))


def quantize(w: jax.Array) -> QuantTensor:
    """Quantize a (..., D_in, D_out) weight to int8 with per-output-column
    scales (amax / 127, zero-safe)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(w.astype(jnp.float32) / scale[..., None, :]), -127, 127
    ).astype(jnp.int8)
    return QuantTensor(q=q, scale=scale)


def dynamic_quant(x: jax.Array):
    """Symmetric per-vector int8 quantization over the LAST axis:
    x (..., D) -> (int8 payload (..., D), fp32 scale (...)), amax/127 with
    a zero-safe floor. The single source of the dynamic rule — used for
    activations (matmul), the int8 KV cache (models/decoder._quant_kv),
    and decode attention probabilities."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantActivation:
    """A pre-quantized activation: int8 payload + per-vector fp32 scale
    (the ``dynamic_quant`` pair) + the source dtype as static metadata.
    :func:`matmul` accepts it wherever a dynamic QuantTensor is the
    weight, so a call site multiplying ONE activation against SEVERAL
    dynamic int8 matrices (the decoder's wq/wk/wv triple, the gated
    MLP's w_up/w_gate pair) quantizes it once via :func:`shared_quant`
    instead of once per matrix — bit-identical results (the same
    amax/127 rule on the same tensor), N-1 fewer VPU quantization passes
    per site."""

    q: jax.Array      # int8 (..., D)
    scale: jax.Array  # fp32 (...)
    out_dtype: str = dataclasses.field(default="float32",
                                       metadata=dict(static=True))

    @classmethod
    def make(cls, x: jax.Array) -> "QuantActivation":
        xq, xs = dynamic_quant(x)
        return cls(q=xq, scale=xs, out_dtype=str(x.dtype))


def shared_quant(x: jax.Array, *weights):
    """Pre-quantize ``x`` once when EVERY weight it will multiply is a
    dynamic QuantTensor (the fused s8 x s8 path); pass it through
    untouched otherwise. The single entry point decoder.py/encdec.py use
    so no call site quantizes an activation it immediately re-quantizes."""
    if weights and all(isinstance(w, QuantTensor) and w.dynamic
                       for w in weights):
        return QuantActivation.make(x)
    return x


def _dot(x: jax.Array, w: jax.Array, accum_dtype) -> jax.Array:
    """(..., D_in) x (D_in, D_out) contraction as ONE lax.dot_general
    with an explicit accumulator dtype — the s8 x s8 -> s32 form the MXU
    runs at double rate (v5e/v5p/v6e) and the weight-only form XLA fuses
    the int8 -> activation-dtype convert into."""
    return jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=accum_dtype)


def matmul(x, w) -> jax.Array:
    """x @ w for dense or QuantTensor weights: (..., D_in) x (D_in, D_out).

    Every quantized branch issues a single ``lax.dot_general`` with int8
    inputs — no call site dequantizes a weight it immediately multiplies:

    - **dynamic** QuantTensors run the fused s8 x s8 -> s32 dot on the
      MXU (int8 peak = 2x bf16 on v5e); activations quantize per token
      (symmetric amax / 127, the LLM.int8() vector-wise rule) unless the
      caller already holds a :class:`QuantActivation` (shared_quant —
      the wq/wk/wv and w_up/w_gate call sites), whose payload feeds the
      dot directly. Scales apply on the narrow s32 output:
      y32 * x_scale * w_scale.
    - **static** (weight-only) QuantTensors contract the int8 payload
      with the convert fused INTO the dot — no bf16 copy of the weight
      ever materializes in HBM — and the per-output-column scale applies
      on the output side: (x @ q) * scale == x @ (q * scale).

    Measured on v5e: 1.5x prefill-shape matmul throughput vs the
    bf16-dequant path, and the per-step bf16 weight copy disappears from
    the decode loop's HBM traffic.
    """
    if isinstance(w, QuantTensor):
        if isinstance(x, QuantActivation):
            assert w.dynamic, "QuantActivation requires a dynamic weight"
            y = _dot(x.q, w.q, jnp.int32)
            return (y.astype(jnp.float32) * x.scale[..., None]
                    * w.scale).astype(x.out_dtype)
        if w.dynamic:
            xq, xs = dynamic_quant(x)
            y = _dot(xq, w.q, jnp.int32)
            return (y.astype(jnp.float32) * xs[..., None]
                    * w.scale).astype(x.dtype)
        y = _dot(x, w.q.astype(x.dtype), x.dtype)
        return y * w.scale.astype(x.dtype)
    if isinstance(x, QuantActivation):
        # A dense weight paired with a pre-quantized activation only
        # happens if a call site mis-grouped its weights; dequantize
        # rather than silently changing that weight's semantics.
        x = (x.q.astype(jnp.float32)
             * x.scale[..., None]).astype(x.out_dtype)
    return jnp.einsum("...d,de->...e", x, w)


# The per-layer matrices worth quantizing (biases/norms stay dense).
_LAYER_MATRICES = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down")


def _quantize_block(blk: Params, names, dynamic: bool) -> Params:
    """Shallow-copy a param subtree, int8-quantizing the named matrices
    (optionally tagged dynamic). The single quantize-a-stack rule shared by
    the decoder and T5 paths."""
    out = dict(blk)
    for name in names:
        if name in out:
            out[name] = dataclasses.replace(quantize(out[name]),
                                            dynamic=dynamic)
    return out


def quantize_decoder_params(params: Params, dynamic: bool = False) -> Params:
    """Quantize the big linear weights of a converted decoder param tree
    (stacked layer matrices + lm_head); everything else passes through.

    ``dynamic`` tags the LAYER matrices for on-the-fly activation
    quantization (see QuantTensor); the lm_head stays weight-only
    regardless — its fp32 activations feed the C13 logit readout directly,
    where activation-quantization noise would land on the measured
    probabilities."""
    out = dict(params)
    out["layers"] = _quantize_block(params["layers"], _LAYER_MATRICES,
                                    dynamic)
    if "lm_head" in params:
        out["lm_head"] = quantize(params["lm_head"])
    return out


# T5-family per-layer matrices (models/encdec.py stacks; biases/norms and
# the relative-position embeddings stay dense).
_ENCDEC_MATRICES = ("wq", "wk", "wv", "wo", "wi", "wi_0", "wi_1", "wo_mlp",
                    "cq", "ck", "cv", "co")


def quantize_encdec_params(params: Params, dynamic: bool = False) -> Params:
    """int8-quantize a converted T5 param tree (models/encdec.py layout) —
    the reference loads its t5/T0/tk-instruct models through the same 8-bit
    config as the decoders (compare_base_vs_instruct.py:431-435 via
    AutoModelForSeq2SeqLM :444-455). Same rules as the decoder path:
    per-output-channel scales, optional dynamic activation mode, lm_head
    weight-only (tied v1.0 embeddings stay dense entirely)."""
    out = dict(params)
    for side in ("encoder", "decoder"):
        out[side] = _quantize_block(params[side], _ENCDEC_MATRICES, dynamic)
    if "lm_head" in params:
        out["lm_head"] = quantize(params["lm_head"])
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _random_int8(key: jax.Array, shape) -> jax.Array:
    """Uniform int8 payload in [-127, 127], ONE fused program per leaf,
    drawn as 8-bit words so nothing wider than the payload is ever a
    device buffer. An eager ``randint(..., int8)`` draws int32s op by op
    — a 7.5 GB transient for a 7B MLP leaf, which took the build of a
    6.9 GiB tree to a 13.2 GB peak on a 16 GB chip; this draw peaks at
    8.2 GB (both seen on the v5e, PR 21)."""
    q = jax.lax.bitcast_convert_type(
        jax.random.bits(key, shape, jnp.uint8), jnp.int8)
    return jnp.maximum(q, jnp.int8(-127))


def random_quantized_params(cfg, key: jax.Array, dtype=jnp.bfloat16,
                            dynamic: bool = False) -> Params:
    """Random param tree at FULL size with the big matrices born int8.

    For real-size throughput/fit work (a 7B tree) the bf16 intermediate of
    init_params -> quantize would transiently double HBM; here each
    QuantTensor is generated directly (int8 payload + constant scale), so
    peak memory is the final int8 footprint. Layout matches
    decoder.init_params exactly (quantize_decoder_params of it would give
    the same tree structure)."""
    from . import decoder

    shapes = jax.eval_shape(lambda k: decoder.init_params(cfg, k, dtype=dtype),
                            key)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    quant_names = set(_LAYER_MATRICES) | {"lm_head"}

    leaves = []
    for i, (path, leaf) in enumerate(flat):
        leaf_key = jax.random.fold_in(key, i)
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in quant_names:
            q = _random_int8(leaf_key, tuple(leaf.shape))
            scale = jnp.full(leaf.shape[:-2] + leaf.shape[-1:],
                             0.02 / 127.0, jnp.float32)
            leaves.append(QuantTensor(q=q, scale=scale,
                                      dynamic=dynamic and name != "lm_head"))
        else:
            leaves.append((0.02 * jax.random.normal(leaf_key, leaf.shape))
                          .astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def param_bytes(params) -> int:
    """Total payload bytes of a param tree (QuantTensor-aware)."""
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.size * leaf.dtype.itemsize
    return total
