"""In-scan sweep-bucket profile at the CURRENT budgets (VERDICT r4 #3).

The r3 "definitive sweep-bucket profile" (SCALE.md) was measured at the
old conf=16 budget; r4 cut the confidence decode to 8 tokens and the
profile went stale — nothing measured said where the e2e-vs-isolated gap
(31.7 vs 41.0 p/s) now comes from or what the new device-bound ceiling
is. This tool re-measures the components of one production sweep bucket
(the shared-prefix two-format scorer, generate.greedy_decode_dispatch)
with repeats INSIDE one jitted lax.scan, so per-iteration time contains
zero host/dispatch overhead. Differencing two scan lengths cancels the fixed entry cost.

Components reported:
- full bucket (prefill 256 + 2 suffix extends + bin and conf fused tails)
  at the production budgets -> the device-work floor and p/s ceiling
- the same bucket at conf+8 -> ms per confidence decode step (slope)
- the same bucket at bin+4 -> ms per binary decode step (slope)
- shared prefill alone
- residual = extends + in-scan readout overhead

Run on the TPU:  python tools/bucket_profile.py [--batch 40] [--no-record]
"""

from __future__ import annotations

import argparse
import datetime
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

SCALE_MD = REPO / "SCALE.md"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=40)
    ap.add_argument("--bucket", type=int, default=256)
    ap.add_argument("--sfx", type=int, default=16)
    ap.add_argument("--model", default="llama2_7b")
    ap.add_argument("--bin-tokens", type=int, default=4)
    ap.add_argument("--conf-tokens", type=int, default=8)
    ap.add_argument("--reps", type=int, default=8,
                    help="long scan length (short is 2; per-iter = diff/6)")
    ap.add_argument("--no-record", action="store_true")
    args = ap.parse_args()

    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from lir_tpu.engine import generate
    from lir_tpu.models import decoder, quant

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print("# no accelerator: tiny CPU smoke variant")
        from lir_tpu.models.registry import ModelConfig
        cfg = ModelConfig(name="profile-smoke", vocab_size=512,
                          hidden_size=64, n_layers=2, n_heads=4,
                          intermediate_size=128, max_seq_len=1024)
        params = decoder.init_params(cfg, jax.random.PRNGKey(0))
        mode = "0.2M-smoke fp32"
    else:
        import dataclasses
        from tools.scale_validation import resolve_preset
        cfg = dataclasses.replace(resolve_preset(args.model),
                                  kv_cache_int8=True)
        params = quant.random_quantized_params(
            cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16, dynamic=True)
        mode = f"{cfg.name} int8-dyn+kvq8"

    B, S, S2 = args.batch, args.bucket, args.sfx
    rng = np.random.default_rng(0)
    prefix = jnp.asarray(rng.integers(5, cfg.vocab_size - 5, (B, S)),
                         jnp.int32)
    pmask = jnp.ones((B, S), jnp.int32)
    sfx = jnp.asarray(rng.integers(5, cfg.vocab_size - 5, (B, S2)),
                      jnp.int32)
    smask = jnp.ones((B, S2), jnp.int32)
    yes_ids = jnp.full((B,), 7, jnp.int32)
    no_ids = jnp.full((B,), 9, jnp.int32)
    digit_ids = jnp.asarray(rng.integers(5, cfg.vocab_size - 5, (32,)),
                            jnp.int32)
    digit_vals = jnp.asarray(np.linspace(0, 100, 32), jnp.float32)

    # params MUST be a traced argument: closing over a 7B tree embeds it
    # as multi-GB compile-time constants.
    def _vary(prefix, carry):
        # The body must be LOOP-CARRIED or XLA hoists the (otherwise
        # loop-invariant) model computation out of the scan and every
        # length times the same single execution. A carry-dependent token
        # offset (0 on iter 0, 1 after — cost-identical) forces true
        # per-iteration execution.
        off = jnp.clip(jnp.abs(carry).astype(jnp.int32), 0, 1)
        return jnp.minimum(prefix + off, cfg.vocab_size - 1)

    @functools.partial(jax.jit, static_argnames=("reps", "bin_t", "conf_t"))
    def scan_full(params, prefix, reps, bin_t, conf_t):
        def body(carry, _):
            (out_a, out_b), _, _ = generate.greedy_decode_dispatch(
                params, cfg, generate.Program(max_new=(bin_t, conf_t)),
                generate.DispatchArgs(
                    prefix=_vary(prefix, carry), prefix_mask=pmask,
                    sfx=(sfx, sfx), sfx_mask=(smask, smask),
                    yes_ids=yes_ids, no_ids=no_ids, digit_ids=digit_ids,
                    digit_vals=digit_vals))
            # Consume every output so nothing is dead-code-eliminated.
            chk = (out_a.p_yes.sum() + out_b.weighted_confidence.sum()
                   + out_a.generated.sum() + out_b.generated.sum())
            return carry + chk.astype(jnp.float32), ()
        total, _ = lax.scan(body, jnp.float32(0), None, length=reps)
        return total

    @functools.partial(jax.jit, static_argnames=("reps",))
    def scan_prefill(params, prefix, reps):
        T0 = S + S2 + 16
        def body(carry, _):
            logits, cache, pos = decoder.prefill(
                params, cfg, _vary(prefix, carry), pmask, T0)
            chk = logits.sum() + jax.tree_util.tree_leaves(cache)[0].sum(
                dtype=jnp.float32)
            return carry + chk.astype(jnp.float32), ()
        total, _ = lax.scan(body, jnp.float32(0), None, length=reps)
        return total

    def per_iter_ms(fn, *static) -> float:
        short, long_ = 2, args.reps
        for reps in (short, long_):          # compile both lengths
            fn(params, prefix, reps, *static).block_until_ready()
        t = {}
        for reps in (short, long_):
            t0 = time.perf_counter()
            fn(params, prefix, reps, *static).block_until_ready()
            t[reps] = time.perf_counter() - t0
        return (t[long_] - t[short]) / (long_ - short) * 1000.0

    bt, ct = args.bin_tokens, args.conf_tokens
    full_ms = per_iter_ms(scan_full, bt, ct)
    full_conf_ms = per_iter_ms(scan_full, bt, ct + 8)
    full_bin_ms = per_iter_ms(scan_full, bt + 4, ct)
    prefill_ms = per_iter_ms(scan_prefill)

    conf_step = (full_conf_ms - full_ms) / 8.0
    bin_step = (full_bin_ms - full_ms) / 4.0
    decode_ms = bt * bin_step + ct * conf_step
    resid_ms = full_ms - prefill_ms - decode_ms
    ceiling = B / (full_ms / 1000.0)

    stamp = datetime.date.today().isoformat()
    lines = [
        "",
        f"## r4-budget sweep-bucket profile — TPU v5 lite, {stamp} "
        "(in-scan timed)",
        "",
        f"{mode}, batch {B}, bucket {S}, suffixes {S2}, budgets "
        f"bin={bt}/conf={ct} (tools/bucket_profile.py; per-iter = scan-"
        f"length differencing, zero dispatch overhead):",
        "",
        "| component | ms/bucket | share |",
        "|---|---|---|",
        f"| shared prefill ({S} tok) | {prefill_ms:.0f} | "
        f"{prefill_ms / full_ms:.0%} |",
        f"| {bt} binary decode steps ({bin_step:.1f} ms/step) | "
        f"{bt * bin_step:.0f} | {bt * bin_step / full_ms:.0%} |",
        f"| {ct} confidence decode steps ({conf_step:.1f} ms/step) | "
        f"{ct * conf_step:.0f} | {ct * conf_step / full_ms:.0%} |",
        f"| 2 suffix extends + in-scan readouts (residual) | "
        f"{resid_ms:.0f} | {resid_ms / full_ms:.0%} |",
        f"| **device-work floor** | **{full_ms:.0f}** | -> "
        f"{ceiling:.1f} p/s ceiling |",
        "",
    ]
    print("\n".join(lines))
    if not args.no_record and dev.platform != "cpu":
        with SCALE_MD.open("a") as f:
            f.write("\n".join(lines) + "\n")
        print(f"# appended to {SCALE_MD}")


if __name__ == "__main__":
    main()
