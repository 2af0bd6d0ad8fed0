"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference never exceeds ~700-token prompts (SURVEY.md §5 "long-context:
absent"), but this framework treats long-context as first-class: when a
sequence no longer fits one chip's HBM, shard it over the mesh's ``seq``
axis and compute exact attention with either

  - ``ring_attention``: K/V blocks rotate around the ring via
    ``lax.ppermute`` while each device holds its Q shard, accumulating with
    an online (flash-style) softmax — communication overlaps compute and
    peak memory is O(S/N) per device. (Liu et al., Ring Attention with
    Blockwise Transformers, 2023.)
  - ``ulysses_attention``: two ``lax.all_to_all`` reshards (seq-sharded ->
    head-sharded and back) around a plain local attention — cheaper when
    n_heads >= n_seq_shards and the full sequence fits once per device.
    (Jacobs et al., DeepSpeed-Ulysses, 2023.)

Both are exact: outputs match single-device softmax attention to float
tolerance (verified against ``reference_attention`` in tests on a virtual
8-device mesh). Layout matches models/decoder.py: (B, S, H, hd), with the S
axis sharded over ``seq``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def reference_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, causal: bool = True,
    q_positions: jnp.ndarray | None = None,
    kv_positions: jnp.ndarray | None = None,
    key_mask: jnp.ndarray | None = None,
    alibi_slopes: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Plain softmax attention, (B, S, H, hd) layout — the single-device
    ground truth the parallel kernels must match.

    Optional mask semantics mirror ``models/decoder._causal_bias``: causality
    compares mask-aware positions (``kv_positions <= q_positions``), pads are
    excluded via ``key_mask``, and ALiBi adds ``slope * kv_position``.
    """
    B, S, H, hd = q.shape
    scale = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if q_positions is not None or key_mask is not None:
        if q_positions is None:
            q_positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        if kv_positions is None:
            kv_positions = q_positions
        allowed = jnp.ones((B, S, k.shape[1]), bool)
        if causal:
            allowed = kv_positions[:, None, :] <= q_positions[:, :, None]
        if key_mask is not None:
            allowed = allowed & (key_mask[:, None, :] > 0)
        s = jnp.where(allowed[:, None], s, -jnp.inf)
    elif causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    if alibi_slopes is not None:
        kp = (kv_positions if kv_positions is not None
              else jnp.broadcast_to(jnp.arange(k.shape[1]), (B, k.shape[1])))
        s = s + (alibi_slopes[None, :, None, None]
                 * kp.astype(jnp.float32)[:, None, None, :])
    # Fully-masked rows (query pads): softmax over all -inf is NaN; zero them.
    finite = jnp.isfinite(s).any(axis=-1, keepdims=True)
    p = jax.nn.softmax(jnp.where(finite, s, 0.0), axis=-1)
    p = jnp.where(finite, p, 0.0).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _repeat_kv(q, k, v):
    """Repeat K/V heads up to the query head count (GQA/MQA callers)."""
    H, K = q.shape[2], k.shape[2]
    if K != H:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    return k, v


def _ring_kernel(q, k, v, q_index, axis_name: str, axis_size: int,
                 causal: bool, q_pos=None, k_pos=None, k_valid=None,
                 slopes=None):
    """Per-device ring body. q/k/v: (B, Sl, H, hd) local shards; q_index is
    this device's position on the ring (its global block offset / Sl).

    Optional mask-aware mode (all shapes (B, Sl), local shards): ``q_pos`` /
    ``k_pos`` are positions with decoder._causal_bias semantics (causality =
    ``k_pos <= q_pos``), ``k_valid`` masks out pad keys, ``slopes`` (H,) adds
    ALiBi ``slope * k_pos``. The k-side arrays rotate around the ring with
    their K/V blocks.
    """
    B, Sl, H, hd = q.shape
    scale = 1.0 / np.sqrt(hd)
    qf = q.astype(jnp.float32) * scale

    o0 = jnp.zeros((B, Sl, H, hd), jnp.float32)
    m0 = jnp.full((B, H, Sl), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sl), jnp.float32)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    masked = q_pos is not None
    if not masked:
        q_pos = jnp.broadcast_to(q_index * Sl + jnp.arange(Sl), (B, Sl))
        k_pos = jnp.broadcast_to(
            (q_index * Sl + jnp.arange(Sl))[None], (B, Sl))
    if k_valid is None:
        k_valid = jnp.ones((B, Sl), jnp.int32)

    def step(j, carry):
        o, m, l, k_blk, v_blk, kp_blk, kv_blk = carry
        src = (q_index - j) % axis_size          # block's origin device
        if not masked:
            # Dense mode: block positions are derivable from the ring index;
            # recompute instead of rotating (saves two ppermutes' latency).
            kp = jnp.broadcast_to(src * Sl + jnp.arange(Sl)[None], (B, Sl))
        else:
            kp = kp_blk

        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32))
        allowed = kv_blk[:, None, :] > 0
        if causal:
            allowed = allowed & (kp[:, None, :] <= q_pos[:, :, None])
        s = jnp.where(allowed[:, None], s, -jnp.inf)
        if slopes is not None:
            s = s + (slopes[None, :, None, None]
                     * kp.astype(jnp.float32)[:, None, None, :])

        m_new = jnp.maximum(m, s.max(axis=-1))
        # exp(-inf - -inf) guard: a fully-masked row keeps m = -inf.
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)

        l = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
        o = o * alpha.transpose(0, 2, 1)[..., None] + pv

        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        if masked:
            kp_blk = lax.ppermute(kp_blk, axis_name, perm)
            kv_blk = lax.ppermute(kv_blk, axis_name, perm)
        return (o, m_new, l, k_blk, v_blk, kp_blk, kv_blk)

    o, m, l, *_ = lax.fori_loop(
        0, axis_size, step, (o0, m0, l0, k, v, k_pos, k_valid))
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    mesh: Mesh, causal: bool = True, axis_name: str = "seq",
    q_positions: jnp.ndarray | None = None,
    kv_positions: jnp.ndarray | None = None,
    key_mask: jnp.ndarray | None = None,
    alibi_slopes: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Exact attention with the sequence axis sharded over `axis_name`.

    q/k/v: (B, S, H, hd) GLOBAL shapes (S divisible by the axis size).
    GQA/MQA K/V (fewer heads than q) are repeated internally. Returns
    (B, S, H, hd) with the same sharding as q.

    Mask-aware mode (for the seq-sharded MODEL forward, parallel/seq_forward):
    ``q_positions``/``kv_positions``/``key_mask`` are (B, S) global arrays
    sharded like the sequence axis, with decoder._causal_bias semantics;
    ``alibi_slopes`` (H,) enables bloom's position bias in-ring.
    """
    k, v = _repeat_kv(q, k, v)
    axis_size = mesh.shape[axis_name]
    spec = P(None, axis_name, None, None)
    pspec = P(None, axis_name)

    if q_positions is not None:
        kv_positions = q_positions if kv_positions is None else kv_positions

        def kernel(q, k, v, qp, kp, kvalid):
            idx = lax.axis_index(axis_name)
            return _ring_kernel(q, k, v, idx, axis_name, axis_size, causal,
                                q_pos=qp, k_pos=kp, k_valid=kvalid,
                                slopes=alibi_slopes)

        if key_mask is None:
            key_mask = jnp.ones(q.shape[:2], jnp.int32)
        return shard_map(
            kernel, mesh=mesh,
            in_specs=(spec, spec, spec, pspec, pspec, pspec), out_specs=spec,
            check_vma=False,
        )(q, k, v, q_positions, kv_positions, key_mask)

    def kernel(q, k, v):
        idx = lax.axis_index(axis_name)
        return _ring_kernel(q, k, v, idx, axis_name, axis_size, causal,
                            slopes=alibi_slopes)

    return shard_map(
        kernel, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def ulysses_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    mesh: Mesh, causal: bool = True, axis_name: str = "seq",
    q_positions: jnp.ndarray | None = None,
    kv_positions: jnp.ndarray | None = None,
    key_mask: jnp.ndarray | None = None,
    alibi_slopes: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """All-to-all sequence parallelism: reshard (S/N, H) -> (S, H/N), run
    plain local attention over the full sequence, reshard back.

    Requires H % axis_size == 0. Same global layout and mask contract as
    ring_attention; per-head ALiBi slopes are sliced to each device's head
    shard after the all-to-all.
    """
    k, v = _repeat_kv(q, k, v)
    axis_size = mesh.shape[axis_name]
    H = q.shape[2]
    if H % axis_size != 0:
        raise ValueError(
            f"ulysses needs n_heads ({H}) divisible by seq shards ({axis_size})"
        )
    spec = P(None, axis_name, None, None)
    pspec = P(None, axis_name)
    masked = q_positions is not None
    if masked:
        kv_positions = q_positions if kv_positions is None else kv_positions
        if key_mask is None:
            key_mask = jnp.ones(q.shape[:2], jnp.int32)

    def kernel(q, k, v, *pos):
        # (B, Sl, H, hd) -> (B, S, H/N, hd): split heads, gather sequence.
        def to_heads(x):
            return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

        def to_seq(x):
            return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

        slopes = alibi_slopes
        if slopes is not None:
            # Heads are sharded after the all-to-all: take this device's rows.
            idx = lax.axis_index(axis_name)
            h_local = H // axis_size
            slopes = lax.dynamic_slice_in_dim(slopes, idx * h_local, h_local)
        qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
        if masked:
            qp, kp, kvalid = (
                lax.all_gather(x, axis_name, axis=1, tiled=True) for x in pos)
            out = reference_attention(
                qh, kh, vh, causal=causal, q_positions=qp, kv_positions=kp,
                key_mask=kvalid, alibi_slopes=slopes)
        else:
            out = reference_attention(qh, kh, vh, causal=causal,
                                      alibi_slopes=slopes)
        return to_seq(out)

    in_specs = (spec, spec, spec) + ((pspec, pspec, pspec) if masked else ())
    args = (q, k, v) + ((q_positions, kv_positions, key_mask) if masked else ())
    return shard_map(
        kernel, mesh=mesh,
        in_specs=in_specs, out_specs=spec,
        check_vma=False,
    )(*args)


def seq_sharded(mesh: Mesh, axis_name: str = "seq") -> NamedSharding:
    """NamedSharding for (B, S, H, hd) activations with S over `axis_name`."""
    return NamedSharding(mesh, P(None, axis_name, None, None))
