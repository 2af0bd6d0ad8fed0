#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the scoring path starts on a TPU.

One process, the entry points a user calls, one model at full width:
mistral-7b (published widths, all 32 layers), int8 weights made in the
process from ``--seed``, bf16 KV cache, batch 40, the 512 bucket.

    python chip_smoke.py              # one chip   (what the driver runs)
    python chip_smoke.py --chips 4    # four chips (the mesh + replicas
                                      # paths and nothing else)

Phases with no arguments:

  A  sweep    ScoringEngine -> engine.sweep.run_perturbation_sweep over
              the real LEGAL_PROMPTS with seeded word-level rephrasings
              sized for the 512 bucket.
  B  serve    serve.ScoringServer over the same engine (prefix cache,
              speculative decode, precompile at their defaults): a few
              dozen requests, some repeated, some sharing a trunk.
  C  kernels  the same rows through an engine with the Pallas kernels
              off: probabilities agree, decisions agree, and the default
              run really dispatched the kernels.
  D  cli      lir_tpu.cli.main(["perturb", ...]) on a tiny HF checkpoint
              written into the output directory.

It refuses to start without a TPU, sets no platform, starts no child
process, and wraps no phase: any exception ends the run non-zero with
its traceback. Every second it prints is a SMOKE timing (one run, compile
included or apart as labelled) — never a metric. The last line of
standard output is the one the driver reads:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
OUT_DIR = HERE / ".chip_smoke"          # git-ignored, inside the checkout

BATCH = 40
MAX_SEQ = 512
HEAD_WORDS = 64          # words every rephrasing of a prompt shares (trunk)
SWEEP_WORDS = 420        # rephrasing length: lands in the 512 bucket
SWEEP_REPHRASINGS = 40   # per legal prompt: one full batch each
SERVE_WORDS = 200        # serve rephrasing length: the 256 bucket
SERVE_TRUNK_REQUESTS = 24
KERNEL_ROWS = 7          # + the prompt as published: 8 cells, batch 8
# Agreement bars on each probability, in log space (0.01 ~ 1% relative).
# Kernel path (fp32-accumulated scores, bf16 probabilities) vs the dense
# path (bf16 scores) on one chip: seen on the v5e at 1.1e-4 (smoke).
BF16_LOG_TOL = 0.01
# One chip vs the model=4 mesh: every matmul's reduction is re-ordered
# across chips in bf16, and the mesh engine runs attention dense.
MESH_LOG_TOL = 0.05
# A cold 7B executable compiles inside its first dispatch; the dispatch
# watchdog's floor must outlast that, or a compile reads as a hang.
WATCHDOG_FLOOR_S = 600.0


def emit(**record) -> None:
    print(json.dumps(record), flush=True)


def peak_bytes() -> dict:
    import jax

    out = {}
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        out[str(d.id)] = {k: int(s[k]) for k in
                          ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                          if k in s}
    return out


# Seconds JAX spent compiling (or loading from the persistent cache), as
# its own monitoring events report them; summed over threads, so a phase
# that compiles in parallel can show more than its wall time.
_COMPILE = {"seconds": 0.0}


def count_compile_seconds() -> None:
    import jax

    def on_duration(event: str, seconds: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            _COMPILE["seconds"] += seconds

    jax.monitoring.register_event_duration_secs_listener(on_duration)


class Phase:
    """Times one phase and prints its line. Not a guard: an exception
    inside the block propagates and ends the run."""

    def __init__(self, name: str):
        self.name, self.fields = name, {}

    def __enter__(self):
        from lir_tpu.utils import compile_cache

        self.t0 = time.perf_counter()
        self.cache0 = compile_cache.persistent_cache_counters()
        self.compile0 = _COMPILE["seconds"]
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        from lir_tpu.utils import compile_cache

        c1 = compile_cache.persistent_cache_counters()
        emit(phase=self.name,
             smoke_seconds=round(time.perf_counter() - self.t0, 2),
             smoke_compile_or_load_seconds=round(
                 _COMPILE["seconds"] - self.compile0, 2),
             persistent_cache={k: c1[k] - self.cache0[k] for k in c1},
             peak_bytes=peak_bytes(), **self.fields)
        return False


def compile_seconds(engine) -> float:
    """Seconds the engine's compile plan spent in XLA (AOT threads), as
    its own CompileStats recorded them per shape."""
    return round(sum(engine.compile_stats.summary()
                     .get("per_shape_compile_s", {}).values()), 2)


# ---------------------------------------------------------------------------
# Model, tokenizer, data — all from the seed and constants in the tree
# ---------------------------------------------------------------------------

def build_params(cfg, seed: int):
    import jax

    from lir_tpu.models import quant

    params = quant.random_quantized_params(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return params


def build_engine(params, cfg, **rt_overrides):
    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.engine.runner import ScoringEngine

    rt = RuntimeConfig(batch_size=BATCH, max_seq_len=MAX_SEQ,
                       watchdog_floor_s=WATCHDOG_FLOOR_S, **rt_overrides)
    # A 32000-row head emits ids the 1024-row in-tree BPE cannot decode;
    # the word tokenizer covers the whole vocabulary with no network.
    return ScoringEngine(params, cfg, FakeTokenizer(vocab=cfg.vocab_size),
                         rt)


def rephrasings(prompt, n: int, n_words: int, rng) -> list:
    """Word-level variations of ``prompt.main``: the first HEAD_WORDS
    words verbatim (the trunk every row of a dispatch shares), then words
    resampled from the prompt's own vocabulary up to ``n_words``."""
    words = prompt.main.split()
    head = words[:HEAD_WORDS]
    return [" ".join(head + list(rng.choice(words, n_words - len(head))))
            for _ in range(n)]


def serve_request(prompt, main: str, rid: str):
    from lir_tpu.serve import ServeRequest

    return ServeRequest(
        binary_prompt=prompt.rephrased_binary(main),
        confidence_prompt=prompt.rephrased_confidence(main),
        targets=tuple(prompt.target_tokens), request_id=rid,
        deadline_s=WATCHDOG_FLOOR_S)       # cold compiles ride requests


def assert_no_recovery(engine, where: str) -> None:
    f, g = engine.fault_stats, engine.guard_stats
    assert f.recovered_dispatches == 0, (where, f.summary())
    assert f.degraded_dispatches == 0 and f.degraded_rows == 0, (
        where, f.summary())
    assert not sum(g.stalls.values()), (where, g.stalls)
    assert not sum(g.quarantined.values()), (where, g.quarantined)
    gov = engine.governor
    assert gov.stats.oom_reclaims == 0 and gov.stats.oom_exhausted == 0, (
        where, gov.stats.summary())


# ---------------------------------------------------------------------------
# Phases (one chip)
# ---------------------------------------------------------------------------

def phase_sweep(engine, prompts, perts, out: Path) -> None:
    import numpy as np

    from lir_tpu.engine.sweep import run_perturbation_sweep

    with Phase("A:sweep") as ph:
        rows = run_perturbation_sweep(engine, engine.cfg.name, prompts,
                                      perts, out / "sweep" / "results.csv")
        n_cells = sum(1 + len(p) for p in perts)
        assert len(rows) == n_cells, (len(rows), n_cells)
        for r in rows:
            assert r.token_1_prob is not None and r.token_2_prob is not None
            assert np.isfinite(r.token_1_prob) and np.isfinite(r.token_2_prob)
        assert_no_recovery(engine, "sweep")
        ph.fields.update(
            rows=len(rows), compile_seconds=compile_seconds(engine),
            compile_plan=engine.compile_stats.summary(),
            cascade=engine.cascade_stats.summary(),
            occupancy=(engine.occupancy.summary()
                       if engine.occupancy is not None else None))


def phase_serve(engine, prompts, rng):
    from lir_tpu.serve import ScoringServer

    with Phase("B:serve") as ph:
        server = ScoringServer(engine, engine.cfg.name).start()
        # Wave 1: every legal prompt as published, plus rephrasings of
        # the first that share its 64-word trunk.
        wave1 = [serve_request(p, p.main, f"orig-{i}")
                 for i, p in enumerate(prompts)]
        trunk = rephrasings(prompts[0], SERVE_TRUNK_REQUESTS, SERVE_WORDS,
                            rng)
        wave1 += [serve_request(prompts[0], m, f"trunk-{i}")
                  for i, m in enumerate(trunk)]
        res1 = [f.result() for f in [server.submit(r) for r in wave1]]
        # Wave 2: repeats (result cache) and new rephrasings whose trunk
        # is now resident in the radix prefix cache.
        again = rephrasings(prompts[0], 8, SERVE_WORDS, rng)
        wave2 = ([serve_request(prompts[0], m, f"repeat-{i}")
                  for i, m in enumerate(trunk[:8])]
                 + [serve_request(prompts[0], m, f"warm-{i}")
                    for i, m in enumerate(again)])
        res2 = [f.result() for f in [server.submit(r) for r in wave2]]
        emit(op="metrics", metrics=server.metrics.snapshot())
        server.stop()
        results = res1 + res2
        bad = [(r.request_id, r.status, r.note) for r in results
               if r.status != "ok"]
        assert not bad, bad
        stats = server.stats.summary()
        assert not stats.get("shed", 0), stats
        assert sum(r.cached for r in res2) >= 8, "repeats missed the cache"
        assert server.healthy and server.program_error is None
        sf = server.faults
        assert (sf.recovered_dispatches == 0 and sf.degraded_dispatches == 0
                and sf.degraded_rows == 0 and sf.breaker_opens == 0), (
            sf.summary())
        assert_no_recovery(engine, "serve")
        ph.fields.update(requests=len(results), serve=stats,
                         prefix_cache=engine.prefix_stats.summary(),
                         spec=engine.spec_stats.summary())


def score_rows(engine, prompt, mains, out: Path, tag: str) -> dict:
    """One prompt's rows through the sweep entry point, keyed by text."""
    from lir_tpu.engine.sweep import run_perturbation_sweep

    rows = run_perturbation_sweep(engine, engine.cfg.name, (prompt,),
                                  (list(mains),),
                                  out / tag / "results.csv")
    assert len(rows) == 1 + len(mains), len(rows)
    assert_no_recovery(engine, tag)
    return {r.rephrased_main: r for r in rows}


def agreement(ref: dict, got: dict, tol: float = BF16_LOG_TOL) -> dict:
    """Every probability within ``tol`` of its reference in log space,
    and the decision identical wherever the reference's own margin
    |log P1 - log P2| exceeds twice that."""
    import math

    assert ref.keys() == got.keys()
    devs, flipped, close_calls = [], [], 0
    for key, a in ref.items():
        b = got[key]
        devs += [abs(math.log(p) - math.log(q))
                 for p, q in ((a.token_1_prob, b.token_1_prob),
                              (a.token_2_prob, b.token_2_prob))]
        if (abs(math.log(a.token_1_prob) - math.log(a.token_2_prob))
                <= 2 * tol):
            close_calls += 1
        elif ((a.token_1_prob > a.token_2_prob)
              != (b.token_1_prob > b.token_2_prob)):
            flipped.append(key[:40])
    out = {"rows": len(ref), "log_tolerance": tol,
           "worst_log_deviation": round(max(devs), 5),
           "median_log_deviation": round(sorted(devs)[len(devs) // 2], 5),
           "rows_inside_tolerance_margin": close_calls,
           "decisions_flipped": flipped,
           # Reported, not asserted: random weights give nearly flat
           # logits, so a greedy token may turn on less than ``tol``.
           "greedy_responses_identical": sum(
               a.model_response == got[k].model_response
               for k, a in ref.items())}
    emit(agreement=out)              # seen even when the asserts below fail
    assert max(devs) <= tol and not flipped, out
    return out


def phase_kernels(engine, params, cfg, prompt, mains, out: Path):
    """The same handful of rows scored twice: by the default engine and
    by one with the Pallas kernels off. (A handful, padded to batch 8:
    a second batch-40 cache beside the first engine's would not fit the
    chip.)"""
    from lir_tpu.engine import compile_plan

    with Phase("C:kernels") as ph:
        cs = engine.cascade_stats
        before = (cs.cascade_dispatches, cs.cascade_decode_dispatches)
        on = score_rows(engine, prompt, mains, out, "kernels-on")
        # The default run really dispatched the kernels ...
        assert cs.cascade_dispatches > before[0], cs.summary()
        assert cs.cascade_decode_dispatches > before[1], cs.summary()
        dense = build_engine(params, cfg, fused_decode=False,
                             cascade_prefill=False, cascade_decode=False)
        off = score_rows(dense, prompt, mains, out, "kernels-off")
        assert dense.cascade_stats.cascade_dispatches == 0
        assert dense.cascade_stats.cascade_decode_dispatches == 0
        ph.fields.update(agreement(off, on))
        # ... and the executables it dispatched carry them.
        with compile_plan._EXEC_CACHE_LOCK:
            cached = list(compile_plan._EXEC_CACHE.items())
        calls = {spec.label: compiled.as_text().count("tpu_custom_call")
                 for (key, spec), compiled in cached
                 if key == engine.cache_manifest_key
                 and spec.kind == "shared_cascade"}
        assert calls and all(n > 0 for n in calls.values()), calls
        ph.fields.update(tpu_custom_calls=calls,
                         compile_seconds=compile_seconds(dense))


def phase_cli(out: Path, prompts, rng):
    """Argument parsing -> engine_factory -> loader -> sweep -> writer,
    on the chip once. Tiny on purpose: Phase A carries the width."""
    import csv

    sys.path.insert(0, str(HERE / "tools"))
    from tiny_checkpoints import build_bpe_gpt2

    from lir_tpu import cli
    from lir_tpu.data import schemas

    with Phase("D:cli") as ph:
        ckpt = out / "checkpoints"
        build_bpe_gpt2(ckpt / "tiny-gpt2")
        entries = [((p.main, p.response_format, tuple(p.target_tokens),
                     p.confidence_format),
                    [" ".join(rng.permutation(p.main.split()[:40]))
                     for _ in range(3)]) for p in prompts]
        pert_path = out / "cli" / "perturbations.json"
        schemas.save_perturbations(pert_path, entries)
        results = out / "cli" / "results.csv"
        cli.main(["perturb", "--checkpoints", str(ckpt), "--model",
                  "tiny-gpt2", "--perturbations", str(pert_path),
                  "--out", str(results), "--batch-size", "8"])
        with open(results, newline="") as fh:
            n = sum(1 for _ in csv.DictReader(fh))
        assert n == len(prompts) * 4, n
        ph.fields.update(rows=n)


def run_one_chip(seed: int, out: Path) -> None:
    import jax
    import numpy as np

    from lir_tpu.data import LEGAL_PROMPTS
    from lir_tpu.models import registry
    from lir_tpu.utils import profiling

    rng = np.random.default_rng(seed)
    cfg = registry.mistral_7b()
    assert not cfg.kv_cache_int8                # bf16 KV: kernels eligible
    with Phase("build") as ph:
        params = build_params(cfg, seed)
        engine = build_engine(params, cfg)
        from lir_tpu.models import quant

        ph.fields.update(
            model=cfg.name, layers=cfg.n_layers, weights=(
                "quant.random_quantized_params (int8, from --seed); "
                "tokenizer backends.fake.FakeTokenizer(vocab=32000)"),
            param_gib=round(quant.param_bytes(params) / 2**30, 3),
            governor_budget_bytes=engine.governor.budget_bytes,
            peak_bf16_flops=profiling.chip_peak_flops(),
            kernels={"fused_decode": engine.cfg.fused_decode,
                     "cascade_prefill": engine.cascade_supported(),
                     "cascade_decode": engine.cascade_decode_supported(),
                     "cascade_fused_suffix": engine.cfg.cascade_fused_suffix})
    prompts = tuple(LEGAL_PROMPTS)
    perts = tuple(rephrasings(p, SWEEP_REPHRASINGS, SWEEP_WORDS, rng)
                  for p in prompts)
    phase_sweep(engine, prompts, perts, out)
    phase_serve(engine, prompts, rng)
    phase_kernels(engine, params, cfg, prompts[0], perts[0][:KERNEL_ROWS], out)
    phase_cli(out, prompts, rng)
    emit(block_until_ready=sync_check(jax))


def sync_check(jax) -> dict:
    """Does ``block_until_ready`` wait for the device? Time a chain of
    matmuls three ways: enqueue only, enqueue + block_until_ready, and
    enqueue + host read of a scalar. If blocking waits, the second and
    third agree and the first is far shorter."""
    import jax.numpy as jnp

    x = jnp.ones((4096, 4096), jnp.bfloat16)

    @jax.jit
    def chain(a):
        for _ in range(64):
            a = (a @ a) * (1.0 / 4096)
        return a

    float(jax.block_until_ready(chain(x))[0, 0])   # compile + warm both

    def timed(finish):
        t0 = time.perf_counter()
        finish(chain(x))
        return time.perf_counter() - t0

    enqueue = timed(lambda y: None)
    jax.block_until_ready(chain(x))
    blocked = timed(jax.block_until_ready)
    host = timed(lambda y: float(y[0, 0]))
    flops = 64 * 2 * 4096 ** 3
    return {"smoke_enqueue_s": round(enqueue, 5),
            "smoke_block_until_ready_s": round(blocked, 5),
            "smoke_host_read_s": round(host, 5),
            "implied_tflops_if_blocked": round(flops / blocked / 1e12, 1),
            "waits": bool(blocked > 0.5 * host and blocked > 2 * enqueue)}


# ---------------------------------------------------------------------------
# --chips 4: the mesh and the replicas, and what they are compared with
# ---------------------------------------------------------------------------

def phase_mesh(seed: int, out: Path, rng) -> None:
    """mistral-7b sharded MeshConfig(data=1, model=4) against the same
    rows on a one-device engine on device 0 of the same process."""
    import jax

    from lir_tpu.config import MeshConfig
    from lir_tpu.data import LEGAL_PROMPTS
    from lir_tpu.models import quant, registry
    from lir_tpu.parallel import sharding

    cfg = registry.mistral_7b()
    prompt = LEGAL_PROMPTS[0]
    mains = rephrasings(prompt, KERNEL_ROWS, SWEEP_WORDS, rng)
    with Phase("mesh:one-chip reference") as ph:
        params = build_params(cfg, seed)          # whole, on device 0
        ref = score_rows(build_engine(params, cfg), prompt, mains, out,
                         "mesh-ref")
        ph.fields.update(rows=len(ref))
    with Phase("mesh:model=4") as ph:
        mesh = sharding.build_mesh(MeshConfig(data=1, model=4))
        sharded = sharding.shard_params(params, cfg, mesh)
        jax.block_until_ready(sharded)
        del params
        total = quant.param_bytes(sharded)
        per_dev = {d.id: 0 for d in mesh.devices.flat}
        for leaf in jax.tree.leaves(sharded):
            for shard in leaf.addressable_shards:
                per_dev[shard.device.id] += (shard.data.size
                                             * shard.data.dtype.itemsize)
        shares = {k: round(v / total, 4) for k, v in per_dev.items()}
        # Matrices split four ways; norms and scales replicate.
        assert all(0.20 <= s <= 0.30 for s in shares.values()), shares
        engine = build_engine(sharded, cfg)
        got = score_rows(engine, prompt, mains, out, "mesh-tp4")
        ph.fields.update(agreement(ref, got, MESH_LOG_TOL))
        ph.fields.update(weight_bytes=total, weight_share_per_device=shares,
                         kernels_under_model_axis={
                             "fused_decode": engine.cfg.fused_decode,
                             "cascade_prefill": engine.cascade_supported(),
                             "cascade_decode":
                                 engine.cascade_decode_supported()})


def phase_replicas(seed: int, out: Path, rng) -> None:
    """Four one-chip replicas behind serve.ReplicaRouter, built the way
    cli._run_router_serve builds them: replica i on
    ``sharding.replica_devices(i, 1)``."""
    import jax

    from lir_tpu.data import LEGAL_PROMPTS
    from lir_tpu.models import registry
    from lir_tpu.parallel import sharding
    from lir_tpu.serve import ReplicaRouter, ScoringServer

    cfg = registry.mistral_7b()
    with Phase("replicas:4") as ph:
        servers, homes = [], []
        for i in range(4):
            (dev,) = sharding.replica_devices(i, 1)
            with jax.default_device(dev):
                params = jax.device_put(build_params(cfg, seed), dev)
                engine = build_engine(params, cfg)
                servers.append(ScoringServer(engine, cfg.name).start())
            homes.append({d.id for leaf in jax.tree.leaves(engine.params)
                          for d in leaf.devices()})
        assert homes == [{d.id} for d in jax.devices()[:4]], homes
        router = ReplicaRouter(
            [(f"r{i}", s) for i, s in enumerate(servers)]).start()
        p = LEGAL_PROMPTS[0]
        mains = rephrasings(p, 32, SERVE_WORDS, rng)
        futs = [router.submit(serve_request(p, m, f"rep-{i}"))
                for i, m in enumerate(mains)]
        results = [f.result() for f in futs]
        summary = router.stats_summary()
        router.stop()
        for s in servers:
            s.stop()
        bad = [(r.request_id, r.status, r.note) for r in results
               if r.status != "ok"]
        assert not bad, bad
        answered = [s.stats.summary().get("completed", 0) for s in servers]
        assert all(n > 0 for n in answered), answered
        for s in servers:
            assert_no_recovery(s.engine, "replica")
        ph.fields.update(requests=len(results), answered=answered,
                         replica_devices=[sorted(h) for h in homes],
                         router=summary)


def run_four_chips(seed: int, out: Path) -> None:
    import numpy as np

    rng = np.random.default_rng(seed)
    phase_mesh(seed, out, rng)
    phase_replicas(seed, out, rng)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax.devices()[0].platform == "
                 f"{dev.platform!r}); refusing to start")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX reports {len(devices)}")

    from lir_tpu.utils import compile_cache

    cache_dir = compile_cache.enable_persistent_cache()
    count_compile_seconds()
    out = OUT_DIR / f"chips{args.chips}"
    if out.exists():
        import shutil

        shutil.rmtree(out)                   # results are made anew each run
    out.mkdir(parents=True)
    emit(device={"platform": dev.platform, "kind": dev.device_kind,
                 "count": len(devices)},
         jax=jax.__version__, seed=args.seed, chips=args.chips,
         compile_cache_dir=str(cache_dir), out=str(out))
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args.seed, out)
    else:
        run_one_chip(args.seed, out)
    emit(smoke_total_seconds=round(time.perf_counter() - t0, 1),
         persistent_cache=compile_cache.persistent_cache_counters(),
         peak_bytes=peak_bytes())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
