"""Fault-injection harness + self-healing dispatch tests (lir_tpu/faults).

Pins the robustness tentpole's contracts:
- FaultPlan schedules are deterministic and seeded (same seed -> same
  injections, at exact call indices, bounded by max_failures);
- the circuit breaker walks closed -> open -> half_open -> closed with
  lazy cooldown promotion, and every transition is recorded;
- the degradation ladder isolates poison rows by bisection without
  punishing their neighbors;
- retry_with_exponential_backoff never swallows KeyboardInterrupt /
  SystemExit, even under a broad retry_on tuple;
- SweepManifest tolerates (and truncates) a torn trailing line — the
  exact crash it exists to survive;
- the sweep's dispatch recovery outlives transient device faults with
  bitwise-identical rows, and a preempted sweep resumes with zero lost
  and zero duplicated rows;
- the serve breaker recovers to healthy via the half-open probe, the
  serve ladder isolates poison requests, and the shutdown checkpoint
  hands every pending request to a fresh server.
"""

import json
import time

import jax
import numpy as np
import pytest

from lir_tpu import faults
from lir_tpu.backends.fake import FakeTokenizer
from lir_tpu.config import RetryConfig, RuntimeConfig, ServeConfig
from lir_tpu.data.prompts import LegalPrompt
from lir_tpu.engine.runner import ScoringEngine
from lir_tpu.engine.sweep import run_perturbation_sweep
from lir_tpu.serve import ScoringServer, ServeRequest
from lir_tpu.utils.manifest import SweepManifest
from lir_tpu.utils.profiling import FaultStats
from lir_tpu.utils.retry import retry_with_exponential_backoff


# ---------------------------------------------------------------------------
# FaultPlan: deterministic seeded schedules
# ---------------------------------------------------------------------------

def test_fault_plan_explicit_schedule_and_bounds():
    plan = faults.FaultPlan(seed=0, schedules={
        "dispatch": faults.SiteSchedule(fail_calls=(1, 3),
                                        max_failures=1)})
    hits = []
    for i in range(5):
        try:
            plan.check("dispatch")
            hits.append("ok")
        except faults.InjectedFault:
            hits.append("fault")
    # Call 1 fails; call 3 would, but max_failures=1 already spent.
    assert hits == ["ok", "fault", "ok", "ok", "ok"]
    assert plan.injected("dispatch") == 1
    assert plan.calls("dispatch") == 5
    assert plan.stats.injected == {"dispatch": 1}
    # An unscheduled site never fails but still counts calls.
    plan.check("tokenize")
    assert plan.calls("tokenize") == 1


def test_fault_plan_rate_is_seed_deterministic():
    def draws(seed):
        plan = faults.FaultPlan(seed=seed, schedules={
            "dispatch": faults.SiteSchedule(rate=0.3)})
        out = []
        for _ in range(50):
            try:
                plan.check("dispatch")
                out.append(0)
            except faults.InjectedFault:
                out.append(1)
        return out

    a, b = draws(7), draws(7)
    assert a == b                       # same seed -> same schedule
    assert 0 < sum(a) < 50              # rate actually fires sometimes


def test_fault_plan_preemption_is_base_exception():
    plan = faults.FaultPlan(schedules={
        "preempt": faults.SiteSchedule.kill_at(0)})
    with pytest.raises(faults.InjectedPreemption):
        plan.check("preempt")
    assert not issubclass(faults.InjectedPreemption, Exception)
    assert plan.stats.preemptions == 1


def test_fault_plan_wrap_indexes_by_site_not_wrapper():
    plan = faults.FaultPlan(schedules={
        "dispatch": faults.SiteSchedule(fail_calls=(2,))})
    f = plan.wrap("dispatch", lambda: "a")
    g = plan.wrap("dispatch", lambda: "b")
    assert f() == "a"           # call 0
    assert g() == "b"           # call 1 — shared site counter
    with pytest.raises(faults.InjectedFault):
        f()                     # call 2


def test_replica_kill_and_lag_schedule_kinds():
    """The elastic chaos kinds: replica_kill raises InjectedReplicaKill
    (an ordinary Exception — the router is the recovery layer under
    test and must survive it); replica_lag delays the call and lets it
    COMPLETE (the straggler whose late payload must lose the race)."""
    plan = faults.FaultPlan(schedules={
        "replica": faults.SiteSchedule.replica_kill_at(1, "r1")})
    f = plan.wrap("replica", lambda: "ok")
    assert f() == "ok"
    with pytest.raises(faults.InjectedReplicaKill) as exc:
        f()
    assert exc.value.replica_id == "r1"
    assert isinstance(exc.value, Exception)   # NOT a BaseException kill
    assert plan.stats.injected == {"replica": 1}

    lag = faults.FaultPlan(schedules={
        "replica": faults.SiteSchedule.replica_lag_at(0, 0.02)})
    g = lag.wrap("replica", lambda: "late")
    t0 = time.monotonic()
    assert g() == "late"          # delayed, then completed
    assert time.monotonic() - t0 >= 0.02
    assert g() == "late"          # schedule exhausted -> instant
    assert lag.stats.injected == {"replica": 1}


def test_migration_stall_and_corrupt_schedule_kinds():
    """The disaggregation chaos kinds (serve/migrate.py seam):
    migration_stall sleeps then raises at the migrator's wire hop;
    migration_corrupt flips the export's chunk bytes UNDER its
    checksums and lets the transfer proceed — detection is the
    import-side verify's job. Both are counter-indexed at the
    'migrate' site like every other kind."""

    class _Migrator:
        def transfer(self, export):
            return export

    class _Export:
        def __init__(self):
            import numpy as np

            self.chunks = [(np.zeros((2, 2, 4), np.float32), 2)]
            self.checksums = [0]

    stall = faults.FaultPlan(schedules={
        "migrate": faults.SiteSchedule.migration_stall_at(
            1, seconds=0.02)})
    m = faults.wrap_migrator(_Migrator(), stall)
    e = _Export()
    assert m.transfer(e) is e            # call 0: clean
    t0 = time.monotonic()
    with pytest.raises(faults.InjectedFault, match="migration stall"):
        m.transfer(e)                    # call 1: sleeps then raises
    assert time.monotonic() - t0 >= 0.02
    assert stall.stats.injected == {"migrate": 1}

    corrupt = faults.FaultPlan(seed=9, schedules={
        "migrate": faults.SiteSchedule.migration_corrupt_at(0)})
    m2 = faults.wrap_migrator(_Migrator(), corrupt)
    e2 = _Export()
    before = e2.chunks[0][0].copy()
    assert m2.transfer(e2) is e2         # completes, mutated in place
    assert not (e2.chunks[0][0] == before).all()
    assert e2.checksums == [0]           # checksums left stale
    assert corrupt.stats.injected == {"migrate": 1}
    # the new kinds/site are registered
    assert "migration_stall" in faults.KINDS
    assert "migration_corrupt" in faults.KINDS
    assert "migrate" in faults.SITES


# ---------------------------------------------------------------------------
# CircuitBreaker lifecycle
# ---------------------------------------------------------------------------

def test_breaker_lifecycle_closed_open_half_open_closed():
    t = [0.0]
    stats = FaultStats()
    b = faults.CircuitBreaker(failure_threshold=2, cooldown_s=5.0,
                              clock=lambda: t[0], stats=stats)
    assert b.state == faults.CLOSED and b.allow()
    assert not b.record_failure()           # 1 of 2
    assert b.record_failure()               # opens
    assert b.state == faults.OPEN and not b.allow()
    t[0] += 4.9
    assert b.state == faults.OPEN           # cooldown not elapsed
    t[0] += 0.2
    assert b.state == faults.HALF_OPEN and b.allow()
    # Probe fails -> straight back to OPEN for another cooldown.
    assert b.record_failure()
    assert b.state == faults.OPEN
    t[0] += 5.1
    assert b.state == faults.HALF_OPEN
    b.record_success()                      # probe succeeds -> CLOSED
    assert b.state == faults.CLOSED
    assert b.consecutive_failures == 0
    assert stats.transitions == [
        (faults.CLOSED, faults.OPEN),
        (faults.OPEN, faults.HALF_OPEN),
        (faults.HALF_OPEN, faults.OPEN),
        (faults.OPEN, faults.HALF_OPEN),
        (faults.HALF_OPEN, faults.CLOSED)]
    assert stats.breaker_opens == 2
    assert stats.breaker_probes == 2
    assert stats.breaker_closes == 1


def test_breaker_cooldown_is_monotonic_not_wall_clock():
    """The cooldown must be timed on time.monotonic, never time.time:
    a wall-clock step (NTP correction, operator clock change) must not
    hold a per-replica breaker open past its cooldown or promote it
    early. Pinned by faking BOTH clocks: the breaker runs on an
    injected monotonic stand-in while the wall clock jumps around it —
    only monotonic elapsed time may move the state."""
    import time as _time

    # The default clock IS time.monotonic — the contract itself.
    assert faults.CircuitBreaker().clock is _time.monotonic

    mono = [100.0]
    wall = [1_700_000_000.0]
    b = faults.CircuitBreaker(failure_threshold=1, cooldown_s=5.0,
                              clock=lambda: mono[0])
    assert b.record_failure() and b.state == faults.OPEN

    # Wall clock leaps a day FORWARD; monotonic barely moves: a
    # wall-clocked breaker would promote immediately — ours must not.
    wall[0] += 86_400.0
    mono[0] += 0.5
    assert b.state == faults.OPEN

    # Wall clock steps BACKWARD an hour; monotonic crosses the
    # cooldown: a wall-clocked breaker would stay open ~an hour — ours
    # promotes on schedule.
    wall[0] -= 3_600.0
    mono[0] += 5.0
    assert b.state == faults.HALF_OPEN
    b.record_success()
    assert b.state == faults.CLOSED
    del wall  # the wall clock never entered a single comparison


def test_breaker_trip_forces_open_then_ordinary_recovery():
    """trip() (the router's replica-kill path) opens the breaker NOW
    regardless of the failure count, and recovery still runs the
    ordinary open -> half_open -> closed probe."""
    t = [0.0]
    stats = FaultStats()
    b = faults.CircuitBreaker(failure_threshold=3, cooldown_s=2.0,
                              clock=lambda: t[0], stats=stats)
    b.trip()
    assert b.state == faults.OPEN and not b.allow()
    b.trip()                                # idempotent while open
    assert stats.breaker_opens == 1
    t[0] += 2.1
    assert b.state == faults.HALF_OPEN
    b.record_success()
    assert b.state == faults.CLOSED


def test_breaker_success_resets_consecutive_count():
    b = faults.CircuitBreaker(failure_threshold=3, cooldown_s=1.0,
                              clock=lambda: 0.0)
    b.record_failure()
    b.record_failure()
    b.record_success()
    assert b.consecutive_failures == 0
    assert not b.record_failure()       # 1 of 3 again, stays CLOSED
    assert b.state == faults.CLOSED


# ---------------------------------------------------------------------------
# Degradation ladder: bisection isolates poison
# ---------------------------------------------------------------------------

def test_degrade_dispatch_isolates_poison_rows():
    poison = {3, 6}
    calls = []

    def score(rows):
        calls.append(list(rows))
        if any(r in poison for r in rows):
            raise RuntimeError("poison")
        return [{"row": r} for r in rows]

    rows = list(range(8))
    out = faults.degrade_dispatch(score, rows)
    for i, payload in enumerate(out):
        if i in poison:
            assert payload is None
        else:
            assert payload == {"row": i}
    # First call retries the whole batch (the AOT->lazy retry).
    assert calls[0] == rows


def test_degrade_dispatch_full_batch_retry_can_recover():
    """A transient full-batch failure (already retried upstream) that
    clears by the ladder's first re-call recovers every row."""
    state = {"failed": False}

    def score(rows):
        if not state["failed"]:
            state["failed"] = True
            raise RuntimeError("transient")
        return [{"row": r} for r in rows]

    out = faults.degrade_dispatch(score, [1, 2, 3])
    assert out == [{"row": 1}, {"row": 2}, {"row": 3}]


def test_degrade_dispatch_propagates_shutdown_signals():
    def score(rows):
        raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        faults.degrade_dispatch(score, [1, 2])


# ---------------------------------------------------------------------------
# Retry satellite: shutdown signals are never swallowed
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sig", [KeyboardInterrupt, SystemExit])
def test_retry_never_swallows_shutdown_signals(sig):
    calls, waits = [], []

    def fn():
        calls.append(1)
        raise sig()

    with pytest.raises(sig):
        retry_with_exponential_backoff(
            fn, retry_on=(BaseException,),
            config=RetryConfig(max_retries=5, initial_delay=60.0),
            sleep=waits.append, log=lambda m: None)
    assert len(calls) == 1          # no retry
    assert waits == []              # and no 60 s backoff sleep


# ---------------------------------------------------------------------------
# Manifest satellite: torn-tail tolerance
# ---------------------------------------------------------------------------

def test_manifest_torn_tail_is_skipped_and_truncated(tmp_path):
    path = tmp_path / "m.jsonl"
    m = SweepManifest(path, ("model", "reph"))
    m.mark_done_many([{"model": "m", "reph": f"r{i}"} for i in range(3)])
    faults.tear_jsonl_tail(path, '{"model": "m", "re')

    # The exact crash this file exists to survive must not kill resume.
    m2 = SweepManifest(path, ("model", "reph"))
    assert len(m2) == 3
    # The next append truncates the torn fragment first.
    m2.mark_done({"model": "m", "reph": "r3"})
    lines = [l for l in path.read_text().splitlines() if l.strip()]
    assert [json.loads(l)["reph"] for l in lines] == ["r0", "r1", "r2",
                                                      "r3"]
    assert len(SweepManifest(path, ("model", "reph"))) == 4


def test_manifest_torn_tail_with_valid_json_missing_keys(tmp_path):
    """A torn line can still parse as JSON (cut between fields) — the
    key check catches it."""
    path = tmp_path / "m.jsonl"
    m = SweepManifest(path, ("model", "reph"))
    m.mark_done({"model": "m", "reph": "r0"})
    faults.tear_jsonl_tail(path, '{"model": "m"}')
    m2 = SweepManifest(path, ("model", "reph"))
    assert len(m2) == 1
    m2.mark_done({"model": "m", "reph": "r1"})
    assert len(SweepManifest(path, ("model", "reph"))) == 2


def test_manifest_mid_file_corruption_still_raises(tmp_path):
    path = tmp_path / "m.jsonl"
    path.write_text('not json\n{"model": "m", "reph": "r0"}\n')
    with pytest.raises(json.JSONDecodeError):
        SweepManifest(path, ("model", "reph"))


def test_manifest_seed_from_results_with_column_map(tmp_path):
    import pandas as pd

    csv = tmp_path / "results.csv"
    pd.DataFrame({"Model": ["m"], "Original Main Part": ["o"],
                  "Rephrased Main Part": ["r"]}).to_csv(csv, index=False)
    m = SweepManifest.from_existing_results(
        tmp_path / "m.jsonl", csv, ("model", "original_main",
                                    "rephrased_main"),
        column_map={"model": "Model", "original_main":
                    "Original Main Part",
                    "rephrased_main": "Rephrased Main Part"})
    assert m.is_done({"model": "m", "original_main": "o",
                      "rephrased_main": "r"})


# ---------------------------------------------------------------------------
# Sweep: transient-fault recovery + preemption resume
# ---------------------------------------------------------------------------

def _tiny_engine(batch=2, seed=5):
    from lir_tpu.models import decoder
    from lir_tpu.models.registry import ModelConfig

    cfg = ModelConfig(name="faults-t", vocab_size=FakeTokenizer.VOCAB,
                      hidden_size=32, n_layers=1, n_heads=2,
                      intermediate_size=64, max_seq_len=128)
    params = decoder.init_params(cfg, jax.random.PRNGKey(seed))
    return ScoringEngine(params, cfg, FakeTokenizer(),
                         RuntimeConfig(batch_size=batch, max_seq_len=128))


def _tiny_grid(n_cells, seed=3):
    rng = np.random.default_rng(seed)
    words = "coverage policy flood water damage claim".split()

    def text():
        return " ".join(rng.choice(words) for _ in range(8)) + " ?"

    lp = (LegalPrompt(main=text(), response_format="Answer Yes or No .",
                      target_tokens=("Yes", "No"),
                      confidence_format="Number from 0 to 100 ."),)
    return lp, ([text() for _ in range(n_cells - 1)],)


def _values(r):
    return (r.token_1_prob, r.token_2_prob, r.confidence_value,
            r.weighted_confidence, r.model_response,
            r.model_confidence_response, r.log_probabilities)


def test_sweep_recovers_transient_fault_bitwise(tmp_path):
    lp, perts = _tiny_grid(6)
    clean = run_perturbation_sweep(_tiny_engine(), "f", lp, perts,
                                   tmp_path / "clean.csv",
                                   checkpoint_every=100)

    engine = _tiny_engine()
    plan = faults.FaultPlan(schedules={
        "dispatch": faults.SiteSchedule(fail_calls=(0, 2))})
    faults.wrap_engine(engine, plan)
    rows = run_perturbation_sweep(engine, "f", lp, perts,
                                  tmp_path / "chaos.csv",
                                  checkpoint_every=100)
    assert engine.fault_stats.recovered_dispatches >= 1
    assert plan.stats.injected_total == 2
    by_key = {r.rephrased_main: _values(r) for r in clean}
    assert len(rows) == 6
    for r in rows:
        assert _values(r) == by_key[r.rephrased_main]   # bitwise


def test_sweep_preemption_resume_zero_lost_zero_dup(tmp_path):
    from lir_tpu.data import schemas
    from lir_tpu.engine import grid as grid_mod

    lp, perts = _tiny_grid(6, seed=9)
    clean = run_perturbation_sweep(_tiny_engine(), "f", lp, perts,
                                   tmp_path / "clean.csv",
                                   checkpoint_every=2)

    out = tmp_path / "chaos.csv"
    plan = faults.FaultPlan(schedules={
        "manifest_write": faults.SiteSchedule.kill_at(1)})
    manifest = SweepManifest(out.with_suffix(".manifest.jsonl"),
                             grid_mod.RESUME_KEY_FIELDS)
    manifest.mark_done_many = plan.wrap("manifest_write",
                                        manifest.mark_done_many)
    with pytest.raises(faults.InjectedPreemption):
        run_perturbation_sweep(_tiny_engine(), "f", lp, perts, out,
                               manifest=manifest, checkpoint_every=2)
    # The kill landed AFTER the checkpoint's results-append, BEFORE its
    # manifest mark — the torn window — and left a torn manifest line.
    faults.tear_jsonl_tail(out.with_suffix(".manifest.jsonl"))

    run_perturbation_sweep(_tiny_engine(), "f", lp, perts, out,
                           checkpoint_every=2)
    df = schemas.read_results_frame(out)
    keys = list(df["Rephrased Main Part"])
    assert len(keys) == 6                       # zero lost
    assert len(set(keys)) == 6                  # zero duplicated
    by_key = {r.rephrased_main: r.token_1_prob for r in clean}
    for _, row in df.iterrows():
        assert float(row["Token_1_Prob"]) == pytest.approx(
            by_key[row["Rephrased Main Part"]], abs=0, rel=1e-12)


# ---------------------------------------------------------------------------
# Serve: breaker recovery, ladder isolation, checkpoint resume
# ---------------------------------------------------------------------------

_FAST_RETRY = RetryConfig(max_retries=1, initial_delay=0.001,
                          max_delay=0.002, full_jitter=True,
                          max_elapsed=0.5)


def _serve_cfg(**kw):
    base = dict(queue_depth=32, classes=(("t", 600.0),),
                default_class="t", linger_s=0.0,
                max_consecutive_failures=1, breaker_cooldown_s=0.15,
                retry=_FAST_RETRY)
    base.update(kw)
    return ServeConfig(**base)


def _req(i, rid=None):
    body = f"clause {i} covers hail damage under policy {i * 3}"
    return ServeRequest(binary_prompt=f"{body} Answer Yes or No .",
                        confidence_prompt=f"{body} Number 0 to 100 .",
                        klass="t", request_id=rid or str(i))


def test_server_breaker_opens_then_recovers_via_probe():
    server = ScoringServer(_tiny_engine(batch=2), "f",
                           _serve_cfg(degrade_ladder=False))
    # Outage: exactly one dispatch's retries (2 attempts), then healthy.
    plan = faults.FaultPlan(schedules={
        "dispatch": faults.SiteSchedule(rate=1.0, max_failures=2)})
    faults.wrap_server(server, plan)
    server.start()
    try:
        r = server.submit(_req(0)).result(timeout=60)
        assert r.status == "error"
        deadline = time.monotonic() + 10
        while server.healthy and time.monotonic() < deadline:
            time.sleep(0.005)
        assert not server.healthy               # breaker OPEN
        shed = server.submit(_req(1)).result(timeout=5)
        assert shed.status == "shed" and "unhealthy" in shed.note
        time.sleep(0.2)                         # cooldown -> half-open
        probe = server.submit(_req(2)).result(timeout=60)
        assert probe.status == "ok"             # probe served
        assert server.healthy                   # breaker CLOSED again
        ok = server.submit(_req(3)).result(timeout=60)
        assert ok.status == "ok"
    finally:
        server.stop()
    trans = server.faults.transitions
    assert (faults.CLOSED, faults.OPEN) in trans
    assert (faults.OPEN, faults.HALF_OPEN) in trans
    assert (faults.HALF_OPEN, faults.CLOSED) in trans


def test_server_ladder_isolates_poison_request():
    server = ScoringServer(_tiny_engine(batch=4), "f",
                           _serve_cfg(max_consecutive_failures=3))
    real_score = server.batcher.score

    def poisoned(bucket, rows):
        if any(p.request.request_id == "poison" for p in rows):
            raise RuntimeError("poison row crash")
        return real_score(bucket, rows)

    server.batcher.score = poisoned
    futs = [server.submit(_req(i)) for i in range(3)]
    bad = server.submit(_req(7, "poison"))
    server.start()
    try:
        results = [f.result(timeout=60) for f in futs]
        poison_res = bad.result(timeout=60)
    finally:
        server.stop()
    assert all(r.status == "ok" for r in results)   # neighbors survive
    assert poison_res.status == "error"
    assert "degradation ladder" in poison_res.note
    assert server.faults.degraded_rows == 1
    assert server.faults.recovered_dispatches >= 1
    assert server.healthy                           # no breaker trip


# ---------------------------------------------------------------------------
# A program the compiler refuses is not a transient fault
# ---------------------------------------------------------------------------

_REFUSAL = ("The Pallas TPU lowering currently requires that the last two "
            "dimensions of your block shape are divisible by 8 and 128 "
            "respectively")


class JaxRuntimeError(RuntimeError):
    """Stand-in carrying the runtime error class's NAME (the classifier
    reads the name so it needs no private jax import)."""


@pytest.mark.parametrize("err,expected", [
    (ValueError(_REFUSAL), True),
    (TypeError("unsupported operand"), True),
    (NotImplementedError("Unsupported vector.shape_cast"), True),
    (JaxRuntimeError("INTERNAL: Mosaic failed to compile TPU kernel"), True),
    (JaxRuntimeError("INVALID_ARGUMENT: bad layout"), True),
    (JaxRuntimeError("UNAVAILABLE: TPU halted, slice restarting"), False),
    (JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory"), False),
    (RuntimeError("device fell over"), False),
    (faults.InjectedFault("injected fault at dispatch call 0"), False),
])
def test_is_program_error_classification(err, expected):
    assert faults.is_program_error(err) is expected


def test_degrade_dispatch_never_bisects_a_program_error():
    calls = []

    def score(rows):
        calls.append(list(rows))
        raise ValueError(_REFUSAL)

    with pytest.raises(ValueError, match="Pallas TPU lowering"):
        faults.degrade_dispatch(score, list(range(8)))
    assert calls == [list(range(8))]        # one attempt, no halves


def test_sweep_attempts_a_refused_dispatch_exactly_once(tmp_path):
    """A lowering-style ValueError ends the sweep at once: no degrade to
    lazy jit, no DISPATCH_RETRY recompiles, nothing counted recovered."""
    from lir_tpu.engine.sweep import _dispatch_with_recovery

    engine = _tiny_engine()
    degraded = []
    engine.degrade_to_lazy = lambda: degraded.append(1)
    calls = []

    def call():
        calls.append(1)
        raise ValueError(_REFUSAL)

    with pytest.raises(ValueError, match="Pallas TPU lowering"):
        _dispatch_with_recovery(engine, call)
    assert len(calls) == 1 and not degraded
    assert engine.fault_stats.recovered_dispatches == 0

    # ... and through the whole sweep entry point too.
    lp, perts = _tiny_grid(4)
    engine = _tiny_engine()
    n = {"calls": 0}

    def refused(*a, **kw):
        n["calls"] += 1
        raise ValueError(_REFUSAL)

    engine.decode_fused_shared = refused
    with pytest.raises(ValueError, match="Pallas TPU lowering"):
        run_perturbation_sweep(engine, "f", lp, perts,
                               tmp_path / "refused.csv",
                               checkpoint_every=100)
    assert n["calls"] == 1
    assert engine.fault_stats.recovered_dispatches == 0


def test_server_does_not_bisect_a_refused_program():
    """The serve supervisor ends the dispatch with the error on every
    row — attempted once, no ladder, no "poison row" verdicts — and
    reads unhealthy; a transient fault right after still recovers."""
    server = ScoringServer(_tiny_engine(batch=4), "f",
                           _serve_cfg(max_consecutive_failures=3))
    real_score = server.batcher.score
    calls = []

    def refused(bucket, rows):
        calls.append(len(rows))
        raise ValueError(_REFUSAL)

    server.batcher.score = refused
    futs = [server.submit(_req(i)) for i in range(4)]
    server.start()
    try:
        results = [f.result(timeout=60) for f in futs]
        assert calls == [4]                     # exactly one attempt
        assert all(r.status == "error" for r in results)
        assert all("program error (not retried)" in r.note
                   and "Pallas TPU lowering" in r.note for r in results)
        assert not any("poison" in r.note for r in results)
        assert server.faults.degraded_dispatches == 0
        assert server.faults.degraded_rows == 0
        assert server.faults.recovered_dispatches == 0
        assert not server.healthy
        assert isinstance(server.program_error, ValueError)
        # Transient faults keep their recovery on the same server.
        flaky = {"n": 0}

        def transient(bucket, rows):
            flaky["n"] += 1
            if flaky["n"] == 1:
                raise RuntimeError("device hiccup")
            return real_score(bucket, rows)

        server.batcher.score = transient
        ok = server.submit(_req(9)).result(timeout=60)
        assert ok.status == "ok"
        assert server.faults.recovered_dispatches >= 1
    finally:
        server.stop()


def test_server_shutdown_checkpoint_resume_zero_lost(tmp_path):
    ckpt = tmp_path / "state.json"
    server = ScoringServer(_tiny_engine(), "f", _serve_cfg())
    futs = [server.submit(_req(i)) for i in range(5)]
    n = server.shutdown_checkpoint(ckpt)    # never started: all pending
    assert n == 5
    assert not any(f.done() for f in futs)  # neither served nor lost

    fresh = ScoringServer(_tiny_engine(), "f", _serve_cfg()).start()
    try:
        resumed = fresh.resume_from_checkpoint(ckpt)
        results = [f.result(timeout=60) for f in resumed]
    finally:
        fresh.stop()
    assert sorted(r.request_id for r in results) == [str(i)
                                                     for i in range(5)]
    assert all(r.status == "ok" for r in results)


def test_serve_request_record_roundtrip():
    r = ServeRequest(binary_prompt="b", confidence_prompt="c",
                     targets=("Covered", "Not"), klass="interactive",
                     deadline_s=2.5, request_id="x1")
    rec = r.to_record()
    assert json.loads(json.dumps(rec)) == rec       # JSON-safe
    assert ServeRequest.from_record(rec) == r
