"""Pipeline parallelism (GPipe-style) over a ``pipe`` mesh axis.

The reference runs on one GPU and has no pipeline story at all; this is
the TPU-native completion of the parallelism matrix (dp x tp x sp x PP):
the layer stack is split into P contiguous stages (the stacked (L, ...)
param arrays shard on axis 0), the batch splits into M microbatches, and
activations flow stage-to-stage with ``lax.ppermute`` — ONE (B/M, S, D)
transfer per stage boundary per microbatch, instead of tensor
parallelism's two all-reduces per LAYER. That trade makes PP the right
axis when interconnect is the scarce resource (multi-slice DCN, or long
chains of chips), while TP stays right within an ICI-rich slice; the two
compose (a stage can itself be TP-sharded) but v1 keeps the pipe mesh
one-dimensional.

Scope: the full-sequence FORWARD (prefill / capture scoring path). The
KV-cached decode loop stays on the dp/tp/sp axes — a token-level decode
pipeline would add a bubble per generated token, which at our 4-16-token
decode budgets can never amortize (the classic GPipe bubble argument:
utilization = M / (M + P - 1) needs M >> P, and decode's M is 1).

Schedule: plain GPipe fill-drain over M + P - 1 ticks. Every stage runs
its layer chunk every tick (bubble ticks compute on garbage and are
discarded — on SPMD hardware predicating the work away saves nothing),
stage 0 injects microbatch t, stage P-1 collects microbatch t-(P-1).
Utilization M/(M+P-1); pick n_micro >= ~4x the stage count.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import decoder
from ..models.registry import ModelConfig

Params = Any


def build_pipe_mesh(n_stages: int, devices=None) -> Mesh:
    """A 1-axis ('pipe',) mesh of n_stages devices."""
    if devices is None:
        devices = jax.devices()
    if n_stages > len(devices):
        raise ValueError(f"pipeline needs {n_stages} devices, "
                         f"have {len(devices)}")
    return Mesh(np.asarray(devices[:n_stages]), ("pipe",))


def _layer_spec_tree(layer_params: Params):
    """PartitionSpec tree: every stacked (L, ...) leaf shards its LAYER
    axis over 'pipe' (QuantTensor payload/scale leaves included — the
    layer axis leads both)."""
    return jax.tree.map(
        lambda leaf: P("pipe", *([None] * (leaf.ndim - 1))), layer_params)


def shard_params_pipelined(params: Params, cfg: ModelConfig,
                           mesh: Mesh) -> Params:
    """Place the param tree for pipeline execution: layer stacks split
    across stages (axis 0 over 'pipe'), embeddings/norms/head replicated
    (stage 0 embeds, stage P-1 unembeds; replication keeps v1 simple and
    costs one vocab matrix per chip)."""
    P_ = mesh.shape["pipe"]
    if cfg.n_layers % P_:
        raise ValueError(
            f"n_layers={cfg.n_layers} must divide into {P_} pipeline stages")
    placed = {}
    for key, sub in params.items():
        if key == "layers":
            placed[key] = jax.tree.map(
                lambda leaf, spec: jax.device_put(
                    leaf, NamedSharding(mesh, spec)),
                sub, _layer_spec_tree(sub))
        else:
            placed[key] = jax.tree.map(
                lambda leaf: jax.device_put(leaf, NamedSharding(mesh, P())),
                sub)
    return placed


def forward_pipelined(params: Params, cfg: ModelConfig, tokens: jax.Array,
                      attn_mask: Optional[jax.Array] = None,
                      mesh: Optional[Mesh] = None,
                      n_micro: int = 4) -> jax.Array:
    """Pipeline-parallel full-sequence causal forward.

    Semantics match ``decoder.forward`` exactly (left-pad masks, RoPE /
    learned / ALiBi positions, fp32 logits (B, S, V)); parity is pinned in
    tests/test_pipeline_parallel.py. ``tokens``/``attn_mask``: (B, S) with
    B % n_micro == 0.
    """
    if mesh is None:
        mesh = build_pipe_mesh(jax.device_count())
    n_stages = mesh.shape["pipe"]
    B, S = tokens.shape
    if B % n_micro:
        raise ValueError(f"batch {B} must divide into {n_micro} microbatches")
    if cfg.n_layers % n_stages:
        raise ValueError(f"n_layers={cfg.n_layers} must divide into "
                         f"{n_stages} pipeline stages")
    if attn_mask is None:
        attn_mask = jnp.ones_like(tokens)
    Bm = B // n_micro

    layer_params = params["layers"]
    other = {k: v for k, v in params.items() if k != "layers"}

    def kernel(layers_local, other_p, toks, mask):
        stage = lax.axis_index("pipe")
        last = n_stages - 1
        full = dict(other_p)
        full["layers"] = layers_local

        # Per-microbatch views: (M, Bm, S)
        toks_mb = toks.reshape(n_micro, Bm, S)
        mask_mb = mask.reshape(n_micro, Bm, S)

        def chunk(x, mb_idx):
            """Run this stage's layer chunk on activations x (Bm, S, D)
            for microbatch mb_idx (positions/bias derived per microbatch —
            every stage needs them, not just stage 0)."""
            m = lax.dynamic_index_in_dim(mask_mb, mb_idx, 0, keepdims=False)
            positions = decoder.mask_positions(m)
            sin = cos = None
            if cfg.pos_embedding == "rotary":
                sin, cos = decoder._rope_sincos(positions, cfg.rotary_dim,
                                                cfg.rope_theta)
            bias = decoder._causal_bias(m, positions, cfg)
            x, _ = decoder._scan_blocks(full, cfg, x, sin, cos, bias,
                                        key_mask=m)
            return x

        def embed_mb(mb_idx):
            t = lax.dynamic_index_in_dim(toks_mb, mb_idx, 0, keepdims=False)
            m = lax.dynamic_index_in_dim(mask_mb, mb_idx, 0, keepdims=False)
            return decoder._embed(full, cfg, t, decoder.mask_positions(m))

        # Embeddings are never quantized (quant.py excludes tok_embed), so
        # the leaf's own shape/dtype describe the activations directly.
        D = full["tok_embed"].shape[-1]
        act_dtype = full["tok_embed"].dtype

        def tick(carry, t):
            buf, outs = carry
            # Which microbatch this stage processes at tick t (clamped in
            # the bubble; the result is discarded then).
            mb = jnp.clip(t - stage, 0, n_micro - 1)
            x_in = jnp.where(stage == 0, embed_mb(mb), buf)
            y = chunk(x_in, mb)
            # Hand to the next stage. No (last -> 0) edge: stage 0's
            # incoming buffer is zeros, and it never reads it.
            buf = lax.ppermute(y, "pipe",
                               [(i, i + 1) for i in range(n_stages - 1)])
            # Last stage banks finished microbatches (valid ticks only).
            out_idx = jnp.clip(t - last, 0, n_micro - 1)
            valid = (stage == last) & (t >= last)
            outs = jnp.where(
                valid,
                lax.dynamic_update_slice(outs, y[None],
                                         (out_idx, 0, 0, 0)),
                outs)
            return (buf, outs), None

        buf0 = jnp.zeros((Bm, S, D), act_dtype)
        outs0 = jnp.zeros((n_micro, Bm, S, D), act_dtype)
        (_, outs), _ = lax.scan(tick, (buf0, outs0),
                                jnp.arange(n_micro + n_stages - 1))

        # psum the (B, S, D) HIDDEN STATES (non-last stages contribute
        # zeros), then unembed on every stage: the collective moves D-wide
        # activations, not the V-wide fp32 logits — ~V/D (often 10-70x)
        # less traffic on exactly the slow links PP is chosen for. The
        # redundant unembed compute is replicated work XLA already
        # schedules locally.
        hidden = lax.psum(
            jnp.where(stage == last, outs, jnp.zeros_like(outs)), "pipe")
        return decoder._unembed(full, cfg, hidden.reshape(B, S, -1))

    in_specs = (_layer_spec_tree(layer_params),
                jax.tree.map(lambda _: P(), other), P(), P())
    return shard_map(kernel, mesh=mesh, in_specs=in_specs, out_specs=P(),
                     check_vma=False)(layer_params, other, tokens, attn_mask)
