#!/usr/bin/env python3
"""How often the served (bfloat16) selection and the reference's (float32)
keep different blocks, on the chip, at the cell's own sizes.

    python3 benchmarks/tests/selection_probe.py --seed <n> [--queries 512]

One softmax layer of ``minicpm-sala`` (its seeded weights, stack index 0),
one row of 16,128 tokens of unit-variance activations: q and k are made
as the program makes them (bfloat16 activations, the weight-only int8
matmul, the per-head norm) and as the reference does (float32,
``highest``); each side pools its own keys and picks its own blocks for
the same queries past ``dense_len``. Prints one JSON line: the share of
(query, kv head) pairs whose choice differs at all, the mean and the most
blocks that differ of the 64 chosen. What a swapped block costs in logits is inside the
cell's own ``logprob_gap`` / ``token_gap`` (the served path ran with its
own choice); this says how often it happens. Never run by the benchmark.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tokens", type=int, default=16128)
    ap.add_argument("--queries", type=int, default=512)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import run as bench_run
    from harness import builders
    from references import sala as ref

    from lir_tpu.models import mixed, quant
    from lir_tpu.ops import sparse_attention as sparse

    files = bench_run.load_files(
        bench_run.load_cell("minicpm-sala.sweep-doc16k")[1])
    spec = files["spec"]
    key = ref.seed_key(args.seed)
    w = jax.jit(lambda k: ref.layer_weights(spec, k, "sparse", 0))(key)
    T, H, K, hd = args.tokens, spec.heads, spec.kv_heads, spec.head_dim
    G = H // K
    h = jax.random.normal(jax.random.fold_in(key, 7), (1, T, spec.d))
    rng = np.random.default_rng([args.seed, 3])
    qpos = np.sort(rng.choice(np.arange(spec.dense_len, T), args.queries,
                              replace=False)).astype(np.int32)
    nb = -(-T // spec.block)
    sizes = dict(n_blocks=nb, block=spec.block, kernel=spec.kernel,
                 stride=spec.stride, topk=spec.topk,
                 init_blocks=spec.init_blocks, window=spec.window,
                 dense_len=spec.dense_len)

    @jax.jit
    def served(h):
        wq = builders.wrap_quantized(w)
        hb = h.astype(jnp.bfloat16)
        q = mixed._head_norm(quant.matmul(hb, wq["wq"]).reshape(1, T, H, hd),
                             w["q_norm"], spec.eps)
        k = mixed._head_norm(quant.matmul(hb, wq["wk"]).reshape(1, T, K, hd),
                             w["k_norm"], spec.eps)
        qg = q[:, qpos].reshape(1, -1, K, G, hd).transpose(0, 2, 3, 1, 4)
        pooled = sparse.pool_keys(k.transpose(0, 2, 1, 3), spec.kernel,
                                  spec.stride)
        keep, _ = sparse.select_blocks(qg, pooled, jnp.asarray(qpos)[None],
                                       jnp.full((1,), T, jnp.int32), **sizes)
        return keep[0]                                        # (K, n, NB)

    @jax.jit
    def exact(h):
        with jax.default_matmul_precision("highest"):
            mm = lambda name: h @ (w[name]["q"].astype(jnp.float32)  # noqa: E731
                                   * w[name]["scale"])
            q = ref._rms(mm("wq").reshape(1, T, H, hd), w["q_norm"],
                         spec.eps)[0]
            k = ref._rms(mm("wk").reshape(1, T, K, hd), w["k_norm"],
                         spec.eps)[0]
            nk = (T - spec.kernel) // spec.stride + 1
            idx = (jnp.arange(nk)[:, None] * spec.stride
                   + jnp.arange(spec.kernel)[None, :])
            pooled = k[idx].mean(axis=1)                      # (NK, K, hd)
            keep = ref._kept(spec, q[qpos].reshape(-1, K, G, hd), pooled,
                             jnp.asarray(qpos), T)
            return jnp.moveaxis(keep, 0, 1)                   # (K, n, NB)

    a, b = np.asarray(served(h)), np.asarray(exact(h))
    assert a.shape == b.shape == (K, args.queries, nb)
    differ = (a != b).sum(-1) // 2                  # blocks swapped a choice
    print(json.dumps({
        "seed": args.seed, "tokens": T, "queries": args.queries,
        "kept_served": float(a.sum(-1).mean()),
        "kept_exact": float(b.sum(-1).mean()),
        "choices_that_differ_share": float((differ > 0).mean()),
        "blocks_swapped_mean": float(differ.mean()),
        "blocks_swapped_max": int(differ.max()),
        "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
