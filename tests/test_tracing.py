"""The span record and the phase scopes (observe/tracing.py, the spans of
engine/sweep.py and engine/compile_plan.py, the ``lir.<phase>`` scopes of
models/decoder.py and engine/generate.py).

- a recorded span has an id, a parent (the innermost span open on its
  thread, or the one a worker adopted) and an optional cause; ids stay
  unique when the ring overflows; totals keep self time;
- ``clock_anchor`` ties the recorder's clock to a capturing profiler's;
- one tiny ``run_perturbation_sweep`` under a recorder: the call is the
  root, its children cover it, every drain names its dispatch;
- every dispatch program family lowers with its phase scopes, and the
  scope table of a compiled program is right on a warm persistent cache,
  also one that holds an executable built without the scopes.
"""

import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import pytest

from lir_tpu.backends.fake import FakeTokenizer
from lir_tpu.config import RuntimeConfig
from lir_tpu.engine import compile_plan
from lir_tpu.guard import watchdog
from lir_tpu.observe import registry as reg_mod
from lir_tpu.observe import tracing
from lir_tpu.utils.profiling import CompileStats

from dispatch_helpers import (grouped_paged_spec, grouped_spec,
                              shared_cascade_paged_spec, shared_cascade_spec,
                              shared_paged_spec, shared_spec)

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture()
def recorder():
    rec = tracing.TraceRecorder(capacity=1000)
    prev = tracing.set_recorder(rec)
    yield rec
    tracing.set_recorder(prev)


def _by_name(rec):
    out = {}
    for ev in rec.events():
        out.setdefault(ev["name"], []).append(ev)
    return out


# ---------------------------------------------------------------------------
# id / parent / cause
# ---------------------------------------------------------------------------

def test_nesting_on_one_thread_gives_parents(recorder):
    with tracing.span("sweep/outer") as outer:
        with tracing.span("sweep/inner") as inner:
            assert tracing.current_span() == inner
            tracing.add_span("sweep/stamped", 1.0, 2.0)
        with tracing.span("sweep/second") as second:
            pass
    assert tracing.current_span() is None
    ev = {e["name"]: e for e in recorder.events()}
    assert ev["sweep/outer"]["id"] == outer and "parent" not in ev[
        "sweep/outer"]
    assert ev["sweep/inner"]["parent"] == outer
    assert ev["sweep/second"]["parent"] == outer
    assert ev["sweep/stamped"]["parent"] == inner
    assert len({outer, inner, second, ev["sweep/stamped"]["id"]}) == 4


def test_siblings_on_two_threads_do_not_nest(recorder):
    ready, go = threading.Event(), threading.Event()

    def other():
        with tracing.span("sweep/other"):
            ready.set()
            go.wait(5)

    t = threading.Thread(target=other)
    t.start()
    ready.wait(5)
    with tracing.span("sweep/mine"):     # opened while the other is open
        pass
    go.set()
    t.join()
    ev = {e["name"]: e for e in recorder.events()}
    assert "parent" not in ev["sweep/mine"]
    assert "parent" not in ev["sweep/other"]
    assert ev["sweep/mine"]["thread"] != ev["sweep/other"]["thread"]


def test_cause_crosses_threads_and_reaches_the_export(recorder):
    box = {}
    with tracing.span("sweep/dispatch", dispatch=7) as sid:
        box["cause"] = sid

    def drain():
        with tracing.span("sweep/drain", cause=box["cause"], dispatch=7):
            pass

    t = threading.Thread(target=drain)
    t.start()
    t.join()
    ev = {e["name"]: e for e in recorder.events()}
    assert ev["sweep/drain"]["cause"] == ev["sweep/dispatch"]["id"]
    chrome = {e["name"]: e for e in recorder.export_chrome()["traceEvents"]
              if e["ph"] == "X"}
    assert chrome["sweep/drain"]["args"]["cause"] == sid
    assert chrome["sweep/drain"]["args"]["dispatch"] == 7
    assert chrome["sweep/dispatch"]["args"]["span"] == sid


def test_ring_overflow_keeps_ids_unique():
    rec = tracing.TraceRecorder(capacity=4)
    prev = tracing.set_recorder(rec)
    try:
        seen = []
        for i in range(9):
            with tracing.span(f"sweep/s{i}") as sid:
                seen.append(sid)
    finally:
        tracing.set_recorder(prev)
    assert len(set(seen)) == 9 and rec.dropped == 5
    assert [e["id"] for e in rec.events()] == seen[-4:]


def test_watched_call_hands_the_callers_span_to_its_worker(recorder):
    """guard/watchdog runs the dispatch on a ``watched:<site>`` thread;
    the spans it opens there must hang under the caller's open span."""

    def dispatch():
        with tracing.span("engine/compile_wait"):
            return threading.current_thread().name

    with tracing.span("sweep/dispatch") as sid:
        worker = watchdog.watch_call(dispatch, deadline_s=30.0,
                                     label="sweep")
    assert worker.startswith("watched:")
    ev = {e["name"]: e for e in recorder.events()}
    assert ev["engine/compile_wait"]["thread"] == worker
    assert ev["engine/compile_wait"]["parent"] == sid


def test_totals_keep_self_time_without_a_recorder():
    assert tracing.get_recorder() is None
    before = tracing.TOTALS.summary().get("sweep/t_outer",
                                          {"count": 0, "self_s": 0.0,
                                           "total_s": 0.0})
    with tracing.span("sweep/t_outer"):
        time.sleep(0.02)
        with tracing.span("sweep/t_inner"):
            time.sleep(0.03)
    now = tracing.TOTALS.summary()
    outer, inner = now["sweep/t_outer"], now["sweep/t_inner"]
    assert outer["count"] == before["count"] + 1
    total = outer["total_s"] - before["total_s"]
    self_s = outer["self_s"] - before["self_s"]
    assert total >= 0.05 and 0.02 <= self_s < total
    assert self_s == pytest.approx(total - inner["total_s"], abs=5e-3)


def test_totals_lose_no_span_under_contention():
    """More threads than cores, a shortened switch interval: every span
    closed on any thread is counted once, and self time never exceeds
    total time."""
    name = "sweep/t_contended"
    before = tracing.TOTALS.summary().get(name, {"count": 0})["count"]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work():
            for _ in range(400):
                with tracing.span(name):
                    with tracing.span(name):
                        pass

        threads = [threading.Thread(target=work) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    after = tracing.TOTALS.summary()[name]
    assert after["count"] - before == 16 * 400 * 2
    assert 0.0 <= after["self_s"] <= after["total_s"]


def test_annotate_reaches_the_open_span(recorder):
    with tracing.span("engine/compile_load", label="x"):
        tracing.annotate(persistent_cache_hit=True)
    tracing.annotate(lost=True)          # outside a span: nothing happens
    (ev,) = recorder.events()
    assert ev["args"] == {"label": "x", "persistent_cache_hit": True}


def test_span_totals_are_a_registry_source():
    with tracing.span("sweep/registered"):
        pass
    reg = reg_mod.MetricsRegistry()
    reg.register("spans", tracing.TOTALS)
    doc = reg.snapshot(device_memory=False)
    entry = doc["sources"]["spans"]["summary"]["sweep/registered"]
    assert entry["count"] >= 1 and entry["total_s"] >= entry["self_s"] >= 0
    json.dumps(doc)


def test_clock_anchor_lays_a_recorder_span_on_the_profilers_clock(
        recorder, tmp_path):
    """Two anchors bracket a traced window; the offset read from either
    maps a recorder span onto the profiler's timeline to well under a
    millisecond, and the two offsets agree (drift)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        tracing.clock_anchor()
        with tracing.span("sweep/probe"):
            time.sleep(0.05)
        tracing.clock_anchor()
    finally:
        jax.profiler.stop_trace()
    (pb,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = {}
    for plane in jax.profiler.ProfileData.from_file(str(pb)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in (tracing.CLOCK_ANCHOR, "sweep/probe"):
                    host.setdefault(e.name, []).append(
                        (e.start_ns / 1e9, e.duration_ns / 1e9))
    spans = _by_name(recorder)
    assert len(host[tracing.CLOCK_ANCHOR]) == 2
    offsets = [start + dur / 2 - (ev["t0"] + ev["t1"]) / 2
               for (start, dur), ev in zip(sorted(host[tracing.CLOCK_ANCHOR]),
                                           spans[tracing.CLOCK_ANCHOR])]
    assert abs(offsets[1] - offsets[0]) < 1e-3
    (start, dur), (probe,) = host["sweep/probe"][0], spans["sweep/probe"]
    assert probe["t0"] + offsets[0] == pytest.approx(start, abs=1e-3)
    assert probe["t1"] - probe["t0"] == pytest.approx(dur, abs=1e-3)


# ---------------------------------------------------------------------------
# CompileStats: distinct planned programs dispatched, wall seconds loading
# ---------------------------------------------------------------------------

def test_aot_shapes_hit_counts_a_label_once():
    stats = CompileStats()
    for label in ("a", "b", "c"):
        stats.record_shape(label, 0.1)
    for label in ("a", "a", "b", "a"):
        stats.hit(label)
    assert stats.aot_hits == 4 and stats.aot_shapes_hit == 2
    summary = stats.summary()
    assert summary["aot_shapes_hit"] <= summary["aot_shapes"] == 3
    assert "aot_shapes_hit" in reg_mod.STATS_SCHEMA["CompileStats"]
    assert "load_wall_s" in reg_mod.STATS_SCHEMA["CompileStats"]


def test_load_wall_seconds_is_the_union_of_parallel_loads():
    stats = CompileStats()

    def load():
        with stats.loading():
            time.sleep(0.1)

    threads = [threading.Thread(target=load) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with stats.loading():
        time.sleep(0.05)
    assert 0.14 <= stats.load_wall_s < 0.3       # not 3 x 0.1 + 0.05


# ---------------------------------------------------------------------------
# One tiny sweep under a recorder
# ---------------------------------------------------------------------------

def _tiny_engine(rt, **cfg_kw):
    from lir_tpu.engine.runner import ScoringEngine
    from lir_tpu.models import decoder
    from lir_tpu.models.registry import ModelConfig

    cfg = ModelConfig(name="trace-smoke", vocab_size=FakeTokenizer.VOCAB,
                      hidden_size=32, n_layers=1, n_heads=2,
                      intermediate_size=64, max_seq_len=256, **cfg_kw)
    params = decoder.init_params(cfg, jax.random.PRNGKey(2))
    return ScoringEngine(params, cfg, FakeTokenizer(), rt)


def _grid(n_cells, seed):
    import numpy as np

    from lir_tpu.data.prompts import LegalPrompt

    rng = np.random.default_rng(seed)
    words = ("coverage policy flood water damage claim insurer "
             "premium exclusion endorsement").split()

    def text():
        return " ".join(rng.choice(words) for _ in range(12)) + " ?"

    lp = (LegalPrompt(main=text(), response_format="Answer Yes or No .",
                      target_tokens=("Yes", "No"),
                      confidence_format="Give a number from 0 to 100 ."),)
    return lp, ([text() for _ in range(n_cells - 1)],)


@pytest.fixture(scope="module")
def traced_sweep(tmp_path_factory):
    """(events of the second call, the engine): the first call compiles
    the plan, the second is what a benchmark window looks like."""
    from lir_tpu.engine.sweep import run_perturbation_sweep

    tmp = tmp_path_factory.mktemp("traced")
    compile_plan.exec_cache_clear()
    engine = _tiny_engine(RuntimeConfig(batch_size=4, max_seq_len=256,
                                        piggyback_prefill=False))
    lp, perts = _grid(12, seed=5)
    run_perturbation_sweep(engine, "warm", lp, perts, tmp / "warm.csv")
    engine.exec_registry.wait()
    rec = tracing.TraceRecorder()
    prev = tracing.set_recorder(rec)
    try:
        lp, perts = _grid(12, seed=6)
        rows = run_perturbation_sweep(engine, "window", lp, perts,
                                      tmp / "window.csv")
    finally:
        tracing.set_recorder(prev)
    assert len(rows) == 12
    return rec.events(), engine


def test_the_call_is_the_root_and_its_children_cover_it(traced_sweep):
    events, _ = traced_sweep
    calls = [e for e in events if e["name"] == "sweep/call"]
    assert len(calls) == 1 and "parent" not in calls[0]
    call = calls[0]
    kids = [e for e in events if e.get("parent") == call["id"]]
    assert kids and all(e["thread"] == call["thread"] for e in kids)
    names = {e["name"] for e in kids}
    assert {"sweep/plan", "sweep/dispatch", "sweep/writer_wait",
            "sweep/flush", "sweep/finish", "sweep/tail"} <= names
    # sweep/tail overlaps its siblings by construction (explicit stamps)
    covered = sum(e["t1"] - e["t0"] for e in kids
                  if e["name"] != "sweep/tail")
    # 98% on an idle machine at this size (a 50 ms call; 99.99% of a 12 s
    # window on the chip, PERF.md); six loaded test workers stretch the
    # few hundred microseconds between spans, hence the room.
    assert covered >= 0.85 * (call["t1"] - call["t0"])
    assert all(call["t0"] <= e["t0"] and e["t1"] <= call["t1"] for e in kids)


def test_plan_ends_before_the_first_dispatch(traced_sweep):
    events, _ = traced_sweep
    plans = [e for e in events if e["name"] == "sweep/plan"]
    first = min(e["t0"] for e in events if e["name"] == "sweep/dispatch")
    assert {e["args"]["stage"] for e in plans} == {"grid", "schedule"}
    assert max(e["t1"] for e in plans) <= first
    # the process-wide plan was compiled by the first call: nothing loads
    assert not [e for e in events if e["name"] == "engine/compile_load"]


def test_every_drain_names_its_dispatch(traced_sweep):
    events, _ = traced_sweep
    by_id = {e["id"]: e for e in events}
    drains = [e for e in events if e["name"] == "sweep/drain"]
    dispatches = [e for e in events if e["name"] == "sweep/dispatch"]
    assert len(drains) == len(dispatches) == 3
    assert sorted(e["args"]["dispatch"] for e in dispatches) == [0, 1, 2]
    for d in drains:
        cause = by_id[d["cause"]]
        assert cause["name"] == "sweep/dispatch"
        assert cause["args"]["dispatch"] == d["args"]["dispatch"]
        assert cause["thread"] != d["thread"]
        assert cause["t0"] <= d["t0"]


def test_drain_wait_and_fold_lie_inside_their_drain(traced_sweep):
    events, _ = traced_sweep
    by_id = {e["id"]: e for e in events}
    waits = [e for e in events if e["name"] == "sweep/drain_wait"]
    folds = [e for e in events if e["name"] == "stream/fold"]
    assert len(waits) == 3 and len(folds) == 3
    for e in waits + folds:
        drain = by_id[e["parent"]]
        assert drain["name"] == "sweep/drain"
        assert drain["t0"] <= e["t0"] and e["t1"] <= drain["t1"]


def test_tail_runs_from_the_last_readback_to_the_return(traced_sweep):
    events, _ = traced_sweep
    (tail,) = [e for e in events if e["name"] == "sweep/tail"]
    (call,) = [e for e in events if e["name"] == "sweep/call"]
    last_wait = max(e["t1"] for e in events
                    if e["name"] == "sweep/drain_wait")
    assert tail["t0"] == pytest.approx(last_wait, abs=5e-3)
    assert 0.0 <= tail["t1"] - tail["t0"] and tail["t1"] <= call["t1"]


def test_planned_programs_dispatched_is_a_share_of_the_plan(traced_sweep):
    _, engine = traced_sweep
    stats = engine.compile_stats
    summary = stats.summary()
    assert 0 < stats.aot_shapes_hit <= summary["aot_shapes"]
    # two calls, three dispatches + three folds each; three programs ran
    assert stats.aot_hits == 12 and stats.aot_shapes_hit == 3
    assert 0.0 < stats.load_wall_s <= stats.compile_s
    doc = reg_mod.engine_registry(engine).snapshot(device_memory=False)
    spans = doc["sources"]["spans"]["summary"]
    assert spans["sweep/call"]["count"] >= 2
    assert spans["sweep/drain"]["self_s"] <= spans["sweep/drain"]["total_s"]
    assert doc["sources"]["compile"]["fields"]["aot_shapes_hit"] == 3


# ---------------------------------------------------------------------------
# Phase scopes in every dispatch program family
# ---------------------------------------------------------------------------

def _family_engine(family):
    from lir_tpu.models import decoder

    if family.startswith("cascade"):
        decoder.CASCADE_INTERPRET_ON_CPU = True
    rt = RuntimeConfig(batch_size=4, max_seq_len=256,
                       prefix_cache="paged" in family,
                       prefix_cache_pages=64)
    return _tiny_engine(rt)


ALL_PHASES = {"lir.prefill", "lir.extend", "lir.decode", "lir.readout"}
FAMILIES = {
    # family: (spec, exactly the scopes the lowered text must name)
    "shared": (lambda: shared_spec(
        64, 4, 8, 8, 4, 8, False, False), ALL_PHASES),
    "shared_donated": (lambda: shared_spec(
        64, 4, 8, 8, 4, 8, False, True), ALL_PHASES),
    "spec": (lambda: shared_spec(
        64, 4, 8, 8, 4, 8, False, False, spec_k=4), ALL_PHASES),
    "grouped": (lambda: grouped_spec(
        64, 2, 4, 8, 8, False, False), ALL_PHASES),
    "cascade": (lambda: shared_cascade_spec(
        64, 4, 32, 8, 8, 4, 8, False, False), ALL_PHASES),
    "paged": (lambda: shared_paged_spec(
        64, 4, 16, 8, 8, 4, 8, False, False), ALL_PHASES),
    "paged_spec": (lambda: shared_paged_spec(
        64, 4, 16, 8, 8, 4, 8, False, False, spec_k=4), ALL_PHASES),
    "cascade_paged": (lambda: shared_cascade_paged_spec(
        64, 4, 32, 16, 8, 8, 4, 8, False, False), ALL_PHASES),
    "grouped_paged": (lambda: grouped_paged_spec(
        64, 2, 4, 16, 8, 8, False, False), ALL_PHASES),
    "piggy_prefill": (lambda: compile_plan.piggy_prefill_spec(
        64, 4, 8, 8, 4, 8), {"lir.prefill", "lir.extend"}),
    "piggy_step": (lambda: compile_plan.piggy_step_spec(
        64, 4, 8, 8, 4, 8, False), ALL_PHASES),
    "piggy_drain": (lambda: compile_plan.piggy_drain_spec(
        64, 4, 8, 8, 4, 8, False), {"lir.decode", "lir.readout"}),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_program_family_lowers_with_its_phase_scopes(family):
    from lir_tpu.models import decoder

    make_spec, want = FAMILIES[family]
    was = decoder.CASCADE_INTERPRET_ON_CPU
    try:
        engine = _family_engine(family)
        text = compile_plan._lower(engine, make_spec()).as_text(
            debug_info=True)
    finally:
        decoder.CASCADE_INTERPRET_ON_CPU = was
    found = {s for s in ALL_PHASES
             if f"/{s}/" in text or f"/{s}\"" in text}
    assert found == want, (family, found)


def test_outermost_scope_decides():
    """The paged prefix window runs ``decoder.extend`` under
    ``lir.prefill``; the speculative verify window runs
    ``decoder.verify_extend`` under ``lir.decode``."""
    text = "\n".join([
        "HloModule jit_f, entry_computation_layout={()->f32[]}",
        '  %a.1 = f32[] add(%x, %y), metadata={op_name="jit(f)/jit(main)/'
        'lir.prefill/lir.extend/while/body/dot_general" source_file="g.py"}',
        '  ROOT %fusion.2 = f32[] fusion(%a.1), kind=kLoop, metadata={'
        'op_name="jit(f)/jit(main)/lir.decode/while/body/lir.extend/add"}',
        '  %copy.3 = f32[] copy(%a.1), metadata={op_name="jit(f)/copy"}',
        "  %bitcast.4 = f32[] bitcast(%copy.3)",
    ])
    module, scopes, n = compile_plan.scope_table(text)
    assert module == "jit_f" and n == 4
    assert scopes == {"a.1": "lir.prefill", "fusion.2": "lir.decode"}


# ---------------------------------------------------------------------------
# The scope table of a compiled program, cold and on a warm cache
# ---------------------------------------------------------------------------

_TABLE_SCRIPT = r"""
import contextlib, json, sys
from concurrent.futures import ThreadPoolExecutor
import jax
if sys.argv[2] == "unscoped":
    # a build of the program from before the scopes existed
    class _NoScope(contextlib.ContextDecorator):
        def __init__(self, name): pass
        def __enter__(self): return self
        def __exit__(self, *exc): return False
    jax.named_scope = _NoScope
sys.path.insert(0, sys.argv[3])
from test_tracing import _tiny_engine
from lir_tpu.config import RuntimeConfig
from lir_tpu.engine import compile_plan
from lir_tpu.utils import compile_cache
from dispatch_helpers import shared_spec
compile_cache.enable_persistent_cache(sys.argv[1])
engine = _tiny_engine(RuntimeConfig(batch_size=4, max_seq_len=256))
spec = shared_spec(64, 4, 8, 8, 4, 8, False, False)
registry = compile_plan.ExecutableRegistry(engine.cache_manifest_key,
                                           engine.compile_stats)
with ThreadPoolExecutor(1, thread_name_prefix="compile-plan") as pool:
    registry.submit(spec, engine, pool)
assert registry.get(spec) is not None
before = compile_cache.persistent_cache_counters()
(table,) = registry.scope_tables(engine)
counts = {}
for scope in table["scopes"].values():
    counts[scope] = counts.get(scope, 0) + 1
print(json.dumps({"counts": counts, "recompiled": table["recompiled"],
                  "module": table["module"],
                  "instructions": table["instructions"],
                  "hits_at_load": before["hits"]}))
"""


def _table_in_a_fresh_process(cache_dir, mode):
    out = subprocess.run(
        [sys.executable, "-c", _TABLE_SCRIPT, str(cache_dir), mode,
         str(REPO / "tests")],
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(REPO)})
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


SCOPES = ("lir.prefill", "lir.extend", "lir.decode", "lir.readout")


def test_scope_table_survives_a_warm_persistent_cache(tmp_path):
    """Cold process: the table comes from the executable it compiled.
    Second process: the executable comes from the disk and the table is
    the same."""
    cold = _table_in_a_fresh_process(tmp_path / "xla", "scoped")
    warm = _table_in_a_fresh_process(tmp_path / "xla", "scoped")
    assert cold["hits_at_load"] == 0 and warm["hits_at_load"] >= 1
    for table in (cold, warm):
        assert table["module"].startswith("jit_greedy_decode_dispatch")
        assert not table["recompiled"]
        assert all(table["counts"].get(s, 0) >= 1 for s in SCOPES), table
    assert warm["counts"] == cold["counts"]


def test_scope_table_is_right_over_an_executable_cached_without_scopes(
        tmp_path):
    """The persistent cache's key leaves metadata out: a cache warmed by
    a build without the scopes hands that build's executable back. The
    table must still be this build's."""
    old = _table_in_a_fresh_process(tmp_path / "xla", "unscoped")
    assert old["counts"] == {} and old["hits_at_load"] == 0
    new = _table_in_a_fresh_process(tmp_path / "xla", "scoped")
    assert new["hits_at_load"] >= 1          # the old executable came back
    assert new["recompiled"]
    assert all(new["counts"].get(s, 0) >= 1 for s in SCOPES), new
    assert new["instructions"] == old["instructions"]
