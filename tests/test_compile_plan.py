"""Compile-plan tests (engine/compile_plan.py + utils/compile_cache.py).

Pins the cache-keying contract the cold-start tentpole relies on:
- the manifest key separates every input that changes an executable
  (model config, quant mode, mesh, bucket ladder, runtime budgets) — no
  stale-executable reuse is possible across configurations;
- plan_specs follows the runner's padding and cache-handoff variant
  selection exactly, so every planned executable is the one dispatched;
- same-shape dispatches reuse ONE registry executable (and the donated
  variant is a distinct one);
- precompiled-vs-lazy sweep results are bitwise identical;
- the persistent disk cache round-trips a recompile after
  jax.clear_caches() into a cache hit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lir_tpu.backends.fake import FakeTokenizer
from lir_tpu.config import RuntimeConfig
from lir_tpu.engine import compile_plan, scheduler as sched_mod
from lir_tpu.engine import tokens as tok
from lir_tpu.utils import compile_cache
from lir_tpu.utils.profiling import CompileStats, OccupancyStats

from dispatch_helpers import grouped_spec, plan_specs, shared_spec


@pytest.fixture(autouse=True)
def _cache_dir_not_placed_from_outside(monkeypatch):
    """These tests enable private cache directories; a directory placed
    from outside would (by the rule under test) override them."""
    monkeypatch.delenv(compile_cache.ENV_JAX_CACHE_DIR, raising=False)


# ---------------------------------------------------------------------------
# Manifest key: every configuration input separates the key space
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Cfg:
    name: str = "m"
    hidden_size: int = 64
    n_layers: int = 2
    vocab_size: int = 1000


def test_manifest_key_deterministic_and_sensitive():
    cfg, rt = _Cfg(), RuntimeConfig()
    base = dict(buckets=(64, 128, 256), quant="fp",
                mesh={"devices": 8, "platform": "cpu"})
    key = compile_cache.manifest_key(cfg, rt, **base)
    # Deterministic: same inputs, same key (stable across processes too —
    # sha256 over canonical JSON, no id()/hash() randomness).
    assert key == compile_cache.manifest_key(cfg, rt, **base)
    assert len(key) == 16

    # Each input that changes compiled programs changes the key.
    variants = [
        compile_cache.manifest_key(
            dataclasses.replace(cfg, hidden_size=128), rt, **base),
        compile_cache.manifest_key(
            cfg, dataclasses.replace(rt, sweep_decode_tokens=6), **base),
        compile_cache.manifest_key(
            cfg, rt, **{**base, "quant": "int8-dyn"}),
        compile_cache.manifest_key(
            cfg, rt, **{**base, "mesh": {"devices": 1, "platform": "cpu"}}),
        compile_cache.manifest_key(
            cfg, rt, **{**base, "buckets": (64, 96, 128, 256)}),
    ]
    assert len({key, *variants}) == 1 + len(variants)


def test_quant_mode_fingerprint():
    from lir_tpu.models.quant import QuantTensor

    fp = {"w": jnp.zeros((4, 4), jnp.float32)}
    q8 = {"w": QuantTensor(q=jnp.zeros((4, 4), jnp.int8),
                           scale=jnp.ones((4,), jnp.float32))}
    q8d = {"w": QuantTensor(q=jnp.zeros((4, 4), jnp.int8),
                            scale=jnp.ones((4,), jnp.float32),
                            dynamic=True)}
    modes = {compile_cache.quant_mode(p) for p in (fp, q8, q8d)}
    assert len(modes) == 3  # fp32 / int8 / int8-dyn all distinct


# ---------------------------------------------------------------------------
# plan_specs follows the engine's routes: padding + handoff variants
# ---------------------------------------------------------------------------

def _items(lengths, fmt_len=6):
    items = []
    for i, n in enumerate(lengths):
        base = [100 + i] * n
        items.append(sched_mod.SweepItem(
            cell=("cell", i), bin_ids=tuple(base + [7] * fmt_len),
            conf_ids=tuple(base + [9] * fmt_len), lcp=n))
    return items


def test_plan_specs_variants_and_order():
    # 12 same-bucket cells at batch 4 -> 3 shared dispatches of one
    # shape: spec 1 scratchless (first of the handoff chain), spec 2 the
    # donated variant serving dispatches 2 AND 3 — exactly two
    # executables, in first-use order.
    buckets = tok.bucket_ladder(256)
    planner = sched_mod.RaggedScheduler(buckets, 4, group_cells=False,
                                        stats=OccupancyStats())
    dispatches = planner.schedule(_items([30] * 12))
    assert len(dispatches) == 3
    engine = _tiny_engine(RuntimeConfig(batch_size=4, max_seq_len=256,
                                        spec_decode=False,
                                        piggyback_prefill=False))
    specs = plan_specs(engine, dispatches, new_tokens=4, conf_tokens=8,
                       stops_armed=False)
    assert len(specs) == 2
    assert [s.scratch for s in specs] == [False, True]
    assert all(s.kind == "shared" and s.batch == 4 for s in specs)
    assert specs[0] == dataclasses.replace(specs[1], scratch=False)

    # The padded tail dispatch (13th cell -> power-of-two pad) is its own
    # shape; stops_armed flips every spec (different traced pytree).
    d13 = planner.schedule(_items([30] * 13))
    specs13 = plan_specs(engine, d13, 4, 8, stops_armed=False)
    assert {s.batch for s in specs13} == {4, 1}
    armed = plan_specs(engine, d13, 4, 8, stops_armed=True)
    assert set(armed).isdisjoint(specs13)


def test_plan_specs_padded_rows_match_runner_tail():
    from lir_tpu.engine.runner import _tail_batch

    planner = sched_mod.RaggedScheduler(tok.bucket_ladder(256), 8,
                                        group_cells=False,
                                        stats=OccupancyStats())
    for n in (1, 3, 5, 8, 11):
        dispatches = planner.schedule(_items([40] * n))
        for d in dispatches:
            rows = d.padded_rows(8)
            expect = (8 if len(d.items) == 8
                      else _tail_batch(len(d.items), 8))
            assert rows == (expect, expect)


def test_sweep_specs_for_ladder_covers_every_edge():
    engine = _tiny_engine(RuntimeConfig(batch_size=4, max_seq_len=256))
    specs = compile_plan.sweep_specs_for_ladder(engine, sfx_buckets=(8, 16))
    # Every (edge, sfx, handoff) combination plans BOTH the sequential
    # executable and its speculative sibling (spec_k-keyed).
    seq = [s for s in specs if not s.spec_k]
    spec = [s for s in specs if s.spec_k]
    assert len(seq) == len(engine.buckets) * 2 * 2
    assert len(spec) == len(seq)
    assert all(s.spec_k == engine.rt.spec_k for s in spec)
    assert {s.bucket for s in specs} == set(engine.buckets)
    assert all(s.batch == 4 and s.kind == "shared" for s in specs)
    # FakeTokenizer exposes no per-token strings -> stops can't arm.
    assert not any(s.stops_armed for s in specs)


# ---------------------------------------------------------------------------
# Engine-level: registry reuse + bitwise parity with the lazy path
# ---------------------------------------------------------------------------

def _tiny_engine(rt, seed=2):
    from lir_tpu.engine.runner import ScoringEngine
    from lir_tpu.models import decoder
    from lir_tpu.models.registry import ModelConfig

    cfg = ModelConfig(name="cp-smoke", vocab_size=FakeTokenizer.VOCAB,
                      hidden_size=32, n_layers=1, n_heads=2,
                      intermediate_size=64, max_seq_len=256)
    params = decoder.init_params(cfg, jax.random.PRNGKey(seed))
    return ScoringEngine(params, cfg, FakeTokenizer(), rt)


def _grid(n_cells, words_each=12, seed=5):
    from lir_tpu.data.prompts import LegalPrompt

    rng = np.random.default_rng(seed)
    words = ("coverage policy flood water damage claim insurer "
             "premium exclusion endorsement").split()

    def text():
        return " ".join(rng.choice(words) for _ in range(words_each)) + " ?"

    lp = (LegalPrompt(main=text(), response_format="Answer Yes or No .",
                      target_tokens=("Yes", "No"),
                      confidence_format="Give a number from 0 to 100 ."),)
    return lp, ([text() for _ in range(n_cells - 1)],)


def test_same_shape_dispatches_reuse_one_executable(tmp_path):
    """12 equal-length cells at batch 4 = 3 dispatches of one shape: with
    piggybacking OFF the registry compiles exactly three executables
    (fresh + donated handoff variants, plus the streaming-stats fold for
    the one fold width) and serves every dispatch AND every fold — zero
    lazy misses."""
    from lir_tpu.engine.sweep import run_perturbation_sweep

    compile_plan.exec_cache_clear()  # order-independence: force compiles
    engine = _tiny_engine(RuntimeConfig(batch_size=4, max_seq_len=256,
                                        piggyback_prefill=False))
    lp, perts = _grid(12)
    rows = run_perturbation_sweep(engine, "cp", lp, perts,
                                  tmp_path / "r.xlsx",
                                  checkpoint_every=100)
    assert len(rows) == 12
    reg = engine.exec_registry
    # fresh + donated handoff variants of the sequential AND speculative
    # shared executables, plus the streaming-stats fold.
    assert reg is not None and len(reg) == 5
    assert {s.kind for s in reg._futures} == {"shared", "stream_fold"}
    # 3 dispatch hits + 3 accumulator-fold hits.
    assert engine.compile_stats.aot_hits == 6
    assert engine.compile_stats.lazy_misses == 0
    assert len(engine.compile_stats.shapes) == 5
    assert all(t > 0 for t in engine.compile_stats.shapes.values())
    # Registry is namespaced by the engine's manifest key.
    assert reg.manifest_key == engine.cache_manifest_key


def test_donate_first_plans_and_runs_one_variant_a_shape(tmp_path):
    """``RuntimeConfig.donate_first``: the first dispatch of a shape is
    handed an empty cache to donate, so the same 3-dispatch plan holds the
    donated variants alone (sequential + speculative + the fold: three
    executables where the default compiles five), every dispatch runs
    precompiled, and the rows are the default's bit for bit."""
    from lir_tpu.engine.sweep import run_perturbation_sweep

    lp, perts = _grid(12)

    def run(donate_first, sub):
        compile_plan.exec_cache_clear()
        engine = _tiny_engine(RuntimeConfig(
            batch_size=4, max_seq_len=256, piggyback_prefill=False,
            donate_first=donate_first))
        rows = run_perturbation_sweep(engine, "cp", lp, perts,
                                      tmp_path / sub / "r.xlsx",
                                      checkpoint_every=100)
        return rows, engine

    rows, engine = run(True, "one")
    shared = [s for s in engine.exec_registry._futures if s.kind == "shared"]
    assert len(shared) == 2 and all(s.scratch for s in shared)
    assert len(engine.exec_registry) == 3
    assert engine.compile_stats.aot_hits == 6
    assert engine.compile_stats.lazy_misses == 0
    assert all("/donated" in label or label.startswith("stream_fold")
               for label in engine.compile_stats.shapes)
    both, _ = run(False, "two")
    key = lambda r: (r.original_main, r.rephrased_main)  # noqa: E731
    by_key = {key(r): r for r in both}
    assert len(rows) == 12 and set(map(key, rows)) == set(by_key)
    for r in rows:
        other = by_key[key(r)]
        assert r.token_1_prob == other.token_1_prob
        assert r.weighted_confidence == other.weighted_confidence
        assert r.log_probabilities == other.log_probabilities
    # Where no executable is at hand the lazy function runs scratchless.
    assert compile_plan.empty_scratch(lambda *a, **k: None) is None


def test_piggyback_chain_runs_precompiled(tmp_path):
    """With piggybacking ON (the default), the same 3-dispatch plan chains
    through the piggyback executables: the plan additionally covers the
    opener/step/drain stages, every chain call is served by the registry,
    and nothing falls back to lazy jit."""
    from lir_tpu.engine.sweep import run_perturbation_sweep

    compile_plan.exec_cache_clear()
    engine = _tiny_engine(RuntimeConfig(batch_size=4, max_seq_len=256))
    lp, perts = _grid(12)
    rows = run_perturbation_sweep(engine, "cp-piggy", lp, perts,
                                  tmp_path / "r.xlsx",
                                  checkpoint_every=100)
    assert len(rows) == 12
    reg = engine.exec_registry
    # 2 plain + 2 speculative (fresh + donated each, kept for the
    # unchained/recovery fallback) + the piggyback chain's 3 stages +
    # the streaming-stats fold width.
    assert reg is not None and len(reg) == 8
    kinds = {s.kind for s in reg._futures}
    assert {"piggy_prefill", "piggy_step", "piggy_drain",
            "stream_fold"} <= kinds
    # opener + 2 steps + drain + 3 accumulator folds, all registry-served.
    assert engine.compile_stats.aot_hits == 7
    assert engine.compile_stats.lazy_misses == 0
    assert engine.kernel_stats.counters.get("piggybacked_steps") == 2


@pytest.mark.parametrize("piggyback", [False, True])
def test_tight_edge_plan_compiles_the_shapes_it_dispatches(tmp_path,
                                                           piggyback):
    """Rows of ~140 tokens queue under the 256 bucket and run at the 192
    edge. The plan's executables are lowered from that edge and the
    dispatch hands the runner the same one, so every dispatch (and every
    stage of a piggyback chain) is served by a planned executable: a
    plan at the ladder's shapes would miss (lazy) or be called with
    other avals (raise)."""
    from lir_tpu.engine.sweep import run_perturbation_sweep
    from lir_tpu.engine import generate

    compile_plan.exec_cache_clear()
    engine = _tiny_engine(RuntimeConfig(batch_size=4, max_seq_len=256,
                                        piggyback_prefill=piggyback))
    lp, perts = _grid(12, words_each=140)
    rows = run_perturbation_sweep(engine, "cp-tight", lp, perts,
                                  tmp_path / "r.xlsx",
                                  checkpoint_every=100)
    assert len(rows) == 12
    occ = engine.occupancy
    assert set(occ.buckets) == {256} and occ.trimmed_slots == 12 * 64
    reg = engine.exec_registry
    programs = [s for s in reg._futures if s.kind != "stream_fold"]
    assert programs and {s.bucket for s in programs} == {192}
    assert engine.compile_stats.lazy_misses == 0
    # 3 dispatches (plain: 3 calls; chained: opener + 2 steps + drain)
    # + 3 accumulator folds, all from the registry.
    assert engine.compile_stats.aot_hits == (7 if piggyback else 6)
    # The handoff buffer the plan lowered the donated variant against is
    # the dispatched one: prefix edge + suffix edges + budget, on the
    # decode kernel's grid.
    spec = next(s for s in programs if s.kind == "shared" and s.scratch
                and not s.spec_k)
    cache = compile_plan._lower(engine, spec).out_info[-1]
    need = spec.bucket + max(spec.sfx_a + spec.new_tokens,
                             spec.sfx_b + spec.conf_tokens)
    assert {leaf.shape[2] for leaf in jax.tree.leaves(cache)} == {
        generate.cache_extent(engine.cfg, need, spec.batch)}


@pytest.mark.parametrize("kind", ["shared", "grouped"])
def test_donated_scratch_cache_really_aliases_the_output(kind):
    """The KV handoff donates the previous dispatch's cache as a scratch
    argument the program never READS — memory reuse only. jit prunes
    unused arguments by default, and a pruned donation aliases nothing:
    on the v5e the "donated" 7B executable held the old cache AND a new
    one (PR 21, RESOURCE_EXHAUSTED at batch 40 / bucket 512 / bf16 KV).
    The decode programs keep unused arguments, so the compiled donated
    variant must carry an input/output alias for the cache pair."""
    engine = _tiny_engine(RuntimeConfig(batch_size=4, max_seq_len=128))
    spec = (shared_spec(64, 4, 8, 8, 4, 8, False, True)
            if kind == "shared" else
            grouped_spec(64, 2, 4, 8, 4, False, True))
    text = compile_plan._lower_compile(engine, spec).as_text()
    assert "input_output_alias" in text
    assert text[text.index("input_output_alias"):][:400].count(
        "alias)") >= 2                      # k and v


def test_engines_with_different_configs_get_different_manifest_keys():
    e1 = _tiny_engine(RuntimeConfig(batch_size=4, max_seq_len=256))
    e2 = _tiny_engine(RuntimeConfig(batch_size=8, max_seq_len=256))
    e3 = _tiny_engine(RuntimeConfig(batch_size=4, max_seq_len=512))
    keys = {e.cache_manifest_key for e in (e1, e2, e3)}
    assert len(keys) == 3  # batch and ladder both separate the key space


@pytest.mark.slow
def test_precompiled_matches_lazy_bitwise(tmp_path):
    """AOT-precompiled and lazily-jitted sweeps hash to the same HLO, so
    their rows must agree BITWISE (with the persistent cache enabled the
    lazy path literally deserializes the executable the AOT path wrote)."""
    from lir_tpu.engine.sweep import run_perturbation_sweep

    compile_cache.enable_persistent_cache(tmp_path / "xla")
    try:
        lp, perts = _grid(13, seed=9)

        def run(aot, sub):
            rt = RuntimeConfig(batch_size=4, max_seq_len=256,
                               aot_precompile=aot)
            engine = _tiny_engine(rt)
            return run_perturbation_sweep(
                engine, "cp-bitwise", lp, perts,
                tmp_path / sub / "r.xlsx", checkpoint_every=100), engine

        rows_a, eng_a = run(True, "aot")
        jax.clear_caches()
        rows_l, _ = run(False, "lazy")
        assert eng_a.compile_stats.aot_hits > 0

        key = lambda r: (r.original_main, r.rephrased_main)  # noqa: E731
        by_key = {key(r): r for r in rows_l}
        assert set(map(key, rows_a)) == set(by_key)
        for r in rows_a:
            l = by_key[key(r)]
            assert r.token_1_prob == l.token_1_prob
            assert r.token_2_prob == l.token_2_prob
            assert r.weighted_confidence == l.weighted_confidence
            assert r.model_response == l.model_response
            assert r.model_confidence_response == l.model_confidence_response
            assert r.log_probabilities == l.log_probabilities
    finally:
        compile_cache.disable_persistent_cache()


# ---------------------------------------------------------------------------
# Persistent disk cache round-trip + observability counters
# ---------------------------------------------------------------------------

def test_persistent_cache_roundtrip_and_counters(tmp_path):
    cache_dir = compile_cache.enable_persistent_cache(tmp_path / "xla")
    try:
        assert cache_dir == tmp_path / "xla"

        @jax.jit
        def f(x):
            return jnp.tanh(x @ x.T).sum()

        x = jnp.ones((64, 64))
        before = compile_cache.persistent_cache_counters()
        float(f(x))
        mid = compile_cache.persistent_cache_counters()
        assert mid["requests"] > before["requests"]
        assert any(cache_dir.iterdir())  # executable serialized to disk

        # A "restarted worker": in-memory executables dropped, disk warm.
        jax.clear_caches()
        float(f(x))
        after = compile_cache.persistent_cache_counters()
        assert after["hits"] > mid["hits"]

        # CompileStats scopes the process-global counters to a window.
        stats = CompileStats()
        stats.snapshot_persistent()
        jax.clear_caches()
        float(f(x))
        stats.finish_persistent()
        assert stats.persistent_hits >= 1
        summ = stats.summary()
        assert summ["persistent_cache_hits"] >= 1
        assert summ["persistent_cache_misses"] >= 0
    finally:
        compile_cache.disable_persistent_cache()


def test_manifest_written_next_to_cache(tmp_path):
    compile_cache.enable_persistent_cache(tmp_path / "xla")
    try:
        path = compile_cache.write_manifest(
            "abc123", {"model": _Cfg(), "buckets": (64, 128)})
        assert path is not None and path.exists()
        import json

        payload = json.loads(path.read_text())
        assert payload["key"] == "abc123"
        assert payload["buckets"] == [64, 128]
        # Idempotent: second write returns the same file.
        assert compile_cache.write_manifest("abc123", {}) == path
    finally:
        compile_cache.disable_persistent_cache()
    # No cache enabled -> no-op, not an error.
    assert compile_cache.write_manifest("zzz", {}) is None


# ---------------------------------------------------------------------------
# Compile cache placed from outside (resolve_cache_dir is the one rule)
# ---------------------------------------------------------------------------

def test_cache_dir_unset_is_one_fixed_path_in_the_checkout(monkeypatch):
    import os
    from pathlib import Path

    import lir_tpu

    monkeypatch.delenv(compile_cache.ENV_JAX_CACHE_DIR, raising=False)
    first = compile_cache.resolve_cache_dir()
    # Another process's worth of os.getpid() (and a later clock) resolves
    # the very same path: nothing of the process or the moment is in it.
    monkeypatch.setattr(os, "getpid", lambda: 424242)
    second = compile_cache.resolve_cache_dir()
    assert first == second == compile_cache.REPO_CACHE_DIR
    repo = Path(lir_tpu.__file__).resolve().parent.parent
    assert first == repo / ".jax_cache"
    assert "tmp" not in first.parts and "424242" not in str(first)
    # ... and git ignores it.
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()


def test_cache_dir_explicit_argument_survives_only_when_unset(
        monkeypatch, tmp_path):
    monkeypatch.delenv(compile_cache.ENV_JAX_CACHE_DIR, raising=False)
    assert compile_cache.resolve_cache_dir(tmp_path / "x") == tmp_path / "x"
    monkeypatch.setenv(compile_cache.ENV_JAX_CACHE_DIR, str(tmp_path / "j"))
    assert compile_cache.resolve_cache_dir() is None
    assert compile_cache.resolve_cache_dir(tmp_path / "x") is None


def test_enable_sets_no_directory_when_jax_variable_is_set(
        monkeypatch, tmp_path):
    """With $JAX_COMPILATION_CACHE_DIR set JAX already has the directory:
    the code sets none (thresholds, listener and manifest location only)."""
    monkeypatch.setenv(compile_cache.ENV_JAX_CACHE_DIR, str(tmp_path / "j"))
    updates = []
    real_update = jax.config.update

    def spy(name, value):
        updates.append(name)
        return real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    min_size = jax.config.jax_persistent_cache_min_entry_size_bytes
    try:
        got = compile_cache.enable_persistent_cache(tmp_path / "ignored")
        assert got == tmp_path / "j"
        assert compile_cache.enabled_cache_dir() == tmp_path / "j"
        assert "jax_compilation_cache_dir" not in updates
        assert "jax_persistent_cache_min_compile_time_secs" in updates
        assert not (tmp_path / "ignored").exists()
    finally:
        monkeypatch.undo()
        compile_cache.disable_persistent_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          min_size)


def test_no_moving_parts_in_any_cache_path():
    """No mkdtemp, pid or clock anywhere a cache directory is chosen."""
    from pathlib import Path

    import lir_tpu

    repo = Path(lir_tpu.__file__).resolve().parent.parent
    src = (repo / "lir_tpu" / "utils" / "compile_cache.py").read_text()
    for word in ("mkdtemp", "getpid", "time.time", "LIR_TPU_COMPILE_CACHE"):
        assert word not in src, word
    bench = (repo / "bench.py").read_text()
    assert "mkdtemp" not in bench
    assert "compile_cache.enable_persistent_cache()" in bench
