"""The one dispatch program (generate.greedy_decode_dispatch), its one
argument builder (compile_plan.dispatch_args) and the one routing rule
(runner.ScoringEngine.route):

- every program the engine asks the registry for, in a ragged sweep and
  in a serve session, is in the plan built beforehand;
- for every front x layout x tail the engine can route to, the builder's
  ShapeDtypeStructs are the avals of the arrays the runner passes, and
  the plan's lowering is the dispatch's;
- the plan of the benchmark cells' dispatch shapes is a listed set of
  programs, so a pruning of the routing rule shows as a diff here;
- the program keeps the name and the phase scopes the device-trace
  readers find it by.
"""

import dataclasses
import re

import jax
import numpy as np
import pytest

from lir_tpu.backends.fake import FakeTokenizer
from lir_tpu.config import RuntimeConfig, ServeConfig
from lir_tpu.engine import compile_plan, generate, scheduler as sched
from lir_tpu.engine.runner import ScoringEngine
from lir_tpu.models import decoder, registry
from lir_tpu.models.registry import ModelConfig
from lir_tpu.utils.profiling import OccupancyStats

from dispatch_helpers import plan_specs

CFG = ModelConfig(name="dispatch-t", vocab_size=FakeTokenizer.VOCAB,
                  hidden_size=32, n_layers=1, n_heads=2,
                  intermediate_size=64, max_seq_len=256)
PARAMS = decoder.init_params(CFG, jax.random.PRNGKey(2))


def _engine(**rt):
    rt.setdefault("batch_size", 4)
    rt.setdefault("max_seq_len", 256)
    return ScoringEngine(PARAMS, CFG, FakeTokenizer(), RuntimeConfig(**rt))


@pytest.fixture(autouse=True)
def _fresh_exec_cache():
    compile_plan.exec_cache_clear()
    yield
    compile_plan.exec_cache_clear()


@pytest.fixture()
def kernels_interpreted(monkeypatch):
    """Arm the cascade front and the trunk-aware decode on the CPU."""
    monkeypatch.setattr(decoder, "CASCADE_INTERPRET_ON_CPU", True)
    monkeypatch.setattr(decoder, "FUSED_DECODE_INTERPRET_ON_CPU", True)


# ---------------------------------------------------------------------------
# (a) nothing the engine asks for is outside the plan
# ---------------------------------------------------------------------------

def _ragged_grid(seed=5):
    """Twelve cells of three lengths: two buckets, a padded tail."""
    from lir_tpu.data.prompts import LegalPrompt

    rng = np.random.default_rng(seed)
    words = ("coverage policy flood water damage claim insurer "
             "premium exclusion endorsement").split()

    def text(n):
        return " ".join(rng.choice(words) for _ in range(n)) + " ?"

    lp = (LegalPrompt(main=text(20), response_format="Answer Yes or No .",
                      target_tokens=("Yes", "No"),
                      confidence_format="Give a number from 0 to 100 ."),)
    return lp, ([text(n) for n in [20] * 6 + [70] * 5],)


@pytest.mark.parametrize("spec_on", [False, True])
@pytest.mark.parametrize("prefix", [False, True])
def test_a_ragged_sweep_asks_only_for_planned_programs(tmp_path, prefix,
                                                       spec_on):
    """Cold, then once more on the warm engine (with the prefix cache the
    second pass resumes from pages: the paged fronts)."""
    from lir_tpu.engine.sweep import run_perturbation_sweep

    engine = _engine(prefix_cache=prefix, prefix_cache_pages=128,
                     spec_decode=spec_on)
    lp, perts = _ragged_grid()
    for i in range(2):
        rows = run_perturbation_sweep(engine, "plan", lp, perts,
                                      tmp_path / f"r{i}.xlsx",
                                      checkpoint_every=100)
        assert len(rows) == 12
        assert engine.compile_stats.lazy_misses == 0
    stats = engine.compile_stats
    assert stats.aot_hits > 0
    if prefix:
        assert engine.prefix_stats.hit_tokens > 0
        assert any("/win" in s.label for s in engine.exec_registry._handed)
    if spec_on:
        assert engine.spec_stats.spec_dispatches > 0


@pytest.mark.parametrize("spec_on", [False, True])
@pytest.mark.parametrize("prefix", [False, True])
def test_a_serve_session_asks_only_for_planned_programs(prefix, spec_on):
    """The boot plan (compile_plan.sweep_specs_for_ladder) covers what the
    batcher's dispatches route to, repeats (warm, paged) included."""
    from lir_tpu.serve import ScoringServer, ServeRequest

    engine = _engine(batch_size=2, max_seq_len=64, spec_decode=spec_on,
                     prefix_cache_pages=64)
    cfg = ServeConfig(queue_depth=16, classes=(("t", 600.0),),
                      default_class="t", linger_s=0.005, pad_full=True,
                      prefix_cache=prefix, cache_entries=0)
    server = ScoringServer(engine, "dispatch-t", cfg, precompile=True)
    assert engine.exec_registry is not None
    body = "coverage policy flood water damage claim insurer premium " * 4
    server.start()
    try:
        for round_ in range(2):
            futures = [server.submit(ServeRequest(
                binary_prompt=f"{body} case {i} Answer Yes or No .",
                confidence_prompt=f"{body} case {i} Give a number .",
                klass="t", request_id=f"{round_}-{i}")) for i in range(4)]
            assert all(f.result(timeout=300).status == "ok"
                       for f in futures)
    finally:
        server.stop()
    assert engine.compile_stats.aot_hits > 0
    assert engine.compile_stats.lazy_misses == 0
    if prefix:
        assert engine.prefix_stats.hit_tokens > 0


# ---------------------------------------------------------------------------
# (b) the builder's avals are the dispatch's arrays; one lowering
# ---------------------------------------------------------------------------

def _trunk_rows(n=4, trunk=48, tail=10, seed=3):
    rng = np.random.default_rng(seed)
    head = rng.integers(8, 200, trunk).tolist()
    return [head + rng.integers(8, 200, tail).tolist() for _ in range(n)]


def _shared_call(engine, rows):
    t = np.full((len(rows),), 5, np.int32)
    return engine.decode_fused_shared(
        [""] * len(rows), [""] * len(rows), t, t, new_tokens=2,
        conf_tokens=3, pretokenized_a=[r + [5, 6] for r in rows],
        pretokenized_b=[r + [7, 8, 9] for r in rows], bucket=64,
        sfx_buckets_ab=(8, 8), reuse_cache=True, n_real=len(rows))


def _grouped_call(engine, rows):
    items = tuple(sched.SweepItem(cell=None, bin_ids=tuple(r + [5, 6]),
                                  conf_ids=tuple(r + [7, 8, 9]),
                                  lcp=len(r)) for r in rows)
    t = np.full((len(rows),), 5, np.int32)
    return engine.decode_fused_grouped(
        [sched.PrefixGroup(items=items[:2], plen=48),
         sched.PrefixGroup(items=items[2:], plen=48)], t, t, 2, 3,
        early_stop=False, bucket=64, sfx_bucket=8, reuse_cache=True)


# name: (runtime knobs, the dispatch, how often, (front, layout, tail))
ROUTES = {
    "prefill-pair": (dict(spec_decode=False), _shared_call, 1,
                     ("prefill", "pair", 0)),
    "prefill-pair-spec": (dict(spec_decode=True), _shared_call, 1,
                          ("prefill", "pair", 4)),
    "paged-pair": (dict(spec_decode=False, prefix_cache=True), _shared_call,
                   2, ("paged", "pair", 0)),
    "paged-pair-spec": (dict(spec_decode=True, prefix_cache=True),
                        _shared_call, 2, ("paged", "pair", 4)),
    "cascade-pair": (dict(cascade=True), _shared_call, 1,
                     ("cascade", "pair", 0)),
    "cascade_paged-pair": (dict(cascade=True, prefix_cache=True),
                           _shared_call, 2, ("cascade_paged", "pair", 0)),
    "prefill-grouped": (dict(), _grouped_call, 1,
                        ("prefill", "grouped", 0)),
    "paged-grouped": (dict(prefix_cache=True), _grouped_call, 2,
                      ("paged", "grouped", 0)),
}


def _avals(tree):
    return jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), tree)


@pytest.mark.parametrize("name", sorted(ROUTES))
def test_the_plans_arguments_are_the_dispatchs(name, monkeypatch):
    knobs, call, times, want = ROUTES[name]
    knobs = dict(knobs)
    if knobs.pop("cascade", False):
        monkeypatch.setattr(decoder, "CASCADE_INTERPRET_ON_CPU", True)
    engine = _engine(prefix_cache_pages=64, piggyback_prefill=False,
                     **knobs)
    seen = []
    real_hit, real_call = engine._hit_or_lazy, compile_plan.registry_call
    monkeypatch.setattr(
        engine, "_hit_or_lazy",
        lambda spec, lazy: seen.append([spec]) or real_hit(spec, lazy))

    def spy(run, params, args, scratch_cache):
        seen[-1].append(_avals(args))
        seen[-1].append(generate.greedy_decode_dispatch.lower(
            params, engine.cfg, compile_plan.dispatch_program(
                engine, seen[-1][0]), args,
            scratch_cache=scratch_cache).as_text())
        return real_call(run, params, args, scratch_cache)

    monkeypatch.setattr(compile_plan, "registry_call", spy)
    engine.fresh_handoff()
    for _ in range(times):
        call(engine, _trunk_rows())
    spec, avals, text = seen[-1]
    program = compile_plan.dispatch_program(engine, spec)
    assert (program.front, program.layout, program.spec_k) == want
    assert spec.scratch == (times > 1)
    planned = compile_plan.dispatch_args(engine, spec)
    assert _avals(planned) == avals
    assert compile_plan._lower(engine, spec).as_text() == text


def test_an_array_of_another_shape_never_reaches_a_program():
    engine = _engine(spec_decode=False)
    spec = engine.route("shared", 64, 4, 0, 8, 8, 2, 3, False).spec()
    host = {k: np.zeros(s, np.int32) for k, s in dict(
        prefix=(4, 64), prefix_mask=(4, 64), sfx_a=(4, 8),
        sfx_a_mask=(4, 8), sfx_b=(4, 16), sfx_b_mask=(4, 8),
        yes_ids=(4,), no_ids=(4,)).items()}
    with pytest.raises(ValueError, match="sfx_b.*\\(4, 8\\)"):
        compile_plan.dispatch_args(engine, spec, host)


# ---------------------------------------------------------------------------
# (c) what the three benchmark cells plan
# ---------------------------------------------------------------------------

def _cell_items():
    """The shared-trunk sweep's shapes (benchmarks/traffic): five
    originals of ~100 tokens, and groups of 40 rephrasings of 420 tokens
    that keep their prompt's first 64, under 20- and 32-token format
    suffixes."""
    rng = np.random.default_rng(0)
    items = []

    def item(ids):
        a, b = [7] * 20, [9] * 32
        return sched.SweepItem(cell=None, bin_ids=tuple(ids + a),
                               conf_ids=tuple(ids + b), lcp=len(ids))

    heads = [rng.integers(8, 200, 64).tolist() for _ in range(5)]
    for head in heads:
        items.append(item(head + rng.integers(8, 200, 36).tolist()))
    for head in heads[:2]:
        for _ in range(40):
            items.append(item(head + rng.integers(8, 200, 356).tolist()))
    return items


def _dense(shape, tail=""):
    return [f"shared/{shape}/sfx32+32/new4-8{tail}/{var}"
            for var in ("fresh", "donated")]


CELL_PLANS = {
    # The never-run alternatives (the dense program and its speculative
    # sibling beside the cascade front) are still listed: ROADMAP S7.
    "mistral": ["stream_fold/b5x8/sfx0/new0-0/fresh",
                "stream_fold/b5x40/sfx0/new0-0/fresh",
                _dense("b128x8")[0], _dense("b128x8", "/spec4")[0],
                _dense("b448x40")[0], _dense("b448x40", "/spec4")[0],
                _dense("b448x40", "/trunk64")[0],
                _dense("b448x40")[1], _dense("b448x40", "/spec4")[1],
                _dense("b448x40", "/trunk64")[1]],
    "falcon-h1": ["stream_fold/b5x8/sfx0/new0-0/fresh",
                  "stream_fold/b5x40/sfx0/new0-0/fresh",
                  _dense("b128x8")[0], _dense("b448x40")[0],
                  _dense("b448x40", "/trunk64")[0], _dense("b448x40")[1],
                  _dense("b448x40", "/trunk64")[1]],
}
CELL_PLANS["falcon"] = CELL_PLANS["mistral"]


@pytest.mark.parametrize("family", sorted(CELL_PLANS))
def test_the_cells_plan_is_the_listed_programs(family, kernels_interpreted):
    cfg = dataclasses.replace(registry.tiny(family),
                              vocab_size=FakeTokenizer.VOCAB)
    engine = ScoringEngine(
        decoder.init_params(cfg, jax.random.PRNGKey(0)), cfg,
        FakeTokenizer(), RuntimeConfig(batch_size=40, max_seq_len=512))
    planner = sched.RaggedScheduler(engine.buckets, 40, group_cells=False,
                                    stats=OccupancyStats())
    dispatches = planner.schedule(_cell_items())
    assert [len(d.items) for d in dispatches] == [5, 40, 40]
    specs = plan_specs(engine, dispatches, 4, 8, False,
                       stream_shape=(5, 40, False))
    assert [s.label for s in specs] == CELL_PLANS[family]


# ---------------------------------------------------------------------------
# (d) the name and the scopes the trace readers find the program by
# ---------------------------------------------------------------------------

def test_the_program_keeps_its_name_and_its_phase_scopes():
    engine = _engine(spec_decode=False)
    spec = engine.route("shared", 64, 4, 0, 8, 8, 2, 3, False).spec()
    compiled = compile_plan._lower_compile(engine, spec)
    module, scopes, _ = compile_plan.scope_table(compiled.as_text())
    # benchmarks/harness/spans.py and the device_ms_per_dispatch reader
    # select the dispatch programs by this pattern.
    assert re.match(r"^jit_greedy_decode", module), module
    assert set(scopes.values()) == {"lir.prefill", "lir.extend",
                                    "lir.decode", "lir.readout"}
