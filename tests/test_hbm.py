"""Unified HBM governor tests (engine/hbm.py + the wiring around it).

Pins the contracts the memory-governance tentpole rides on:

- the ledger: register/update/unregister, pressure math, admission
  counters, and the gauges landing in the MetricsRegistry snapshot
  next to device_memory_stats;
- the degradation ladder: rungs engage only under SUSTAINED pressure,
  release with hysteresis in reverse order, and the flag rungs map to
  allows()/batch_cap()/should_shed() exactly;
- the seeded ``hbm_squeeze`` fault kind: budget shrinks at the
  scheduled tick, auto-restores, and the ladder walks down AND back up
  (rung_downs == rung_ups after the squeeze clears);
- OOM routing: a device OOM in the sweep path reclaims and retries
  once (run completes, rows intact), a persistent OOM raises
  HbmExhausted with the ledger arithmetic; a serve-path OOM never
  advances the circuit breaker (capacity != device death) and
  quarantines only the irreducible dispatch;
- fleet boot validation: a weight-cache budget smaller than the
  largest configured model fails construction with the sizing
  arithmetic instead of surfacing as WeightCacheOOM mid-sweep;
- WeightCache refcounts under concurrency: threaded acquire/release/
  evict stress holding the never-negative invariant and pinned/
  in-flight unevictability under contention;
- router placement: the replica pressure gauge penalizes squeezed
  replicas.
"""

import threading

import pytest

import jax

from lir_tpu import faults
from lir_tpu.backends.fake import FakeTokenizer
from lir_tpu.config import (GovernorConfig, RetryConfig, RouterConfig,
                            RuntimeConfig, ServeConfig)
from lir_tpu.engine import hbm
from lir_tpu.engine.fleet import ModelFleet
from lir_tpu.engine.runner import ScoringEngine
from lir_tpu.models import decoder, weights
from lir_tpu.models.registry import ModelConfig
from lir_tpu.serve import ScoringServer, ServeRequest
from lir_tpu.utils.profiling import MemStats

MB = 1 << 20


def _gov(budget_mb=100, engage=0.9, hyst=0.15, sustain=1, enabled=True):
    return hbm.HbmGovernor(
        GovernorConfig(enabled=enabled, engage_pressure=engage,
                       hysteresis=hyst, sustain_ticks=sustain),
        budget_bytes=budget_mb * MB)


def _tiny_cfg(name="hbm-test"):
    return ModelConfig(name=name, vocab_size=FakeTokenizer.VOCAB,
                       hidden_size=32, n_layers=1, n_heads=2,
                       intermediate_size=64, max_seq_len=256)


def _tiny_engine(name="hbm-test", seed=3, batch_size=4, **rt_kw):
    cfg = _tiny_cfg(name)
    return ScoringEngine(
        decoder.init_params(cfg, jax.random.PRNGKey(seed)), cfg,
        FakeTokenizer(),
        RuntimeConfig(batch_size=batch_size, max_seq_len=256, **rt_kw))


# ---------------------------------------------------------------------------
# ledger + pressure
# ---------------------------------------------------------------------------


def test_ledger_register_update_unregister():
    g = _gov(budget_mb=100)
    g.register("a", 30 * MB)
    g.register("b", 20 * MB)
    assert g.ledger_bytes == 50 * MB
    assert g.pressure() == pytest.approx(0.5)
    g.update("a", 10 * MB)          # replace, not accumulate
    assert g.ledger_bytes == 30 * MB
    g.unregister("b")
    assert g.ledger() == {"a": 10 * MB}
    assert g.headroom() == 90 * MB


def test_admit_counts_and_respects_budget():
    g = _gov(budget_mb=100)
    g.register("a", 60 * MB)
    assert g.admit("b", 30 * MB)            # 90 <= 100
    assert not g.admit("b", 50 * MB)        # 110 > 100
    assert g.admit("a", 90 * MB)            # replacing a: 90 <= 100
    assert g.stats.admits == 2
    assert g.stats.denials == 1


def test_unbounded_governor_is_inert():
    g = hbm.HbmGovernor(GovernorConfig(), budget_bytes=None)
    g.register("a", 10 ** 12)
    assert g.pressure() == 0.0
    assert g.headroom() is None
    for _ in range(20):
        g.tick()
    assert g.level == 0                     # nothing to press against


# ---------------------------------------------------------------------------
# the ladder
# ---------------------------------------------------------------------------


def test_ladder_requires_sustained_pressure():
    g = _gov(budget_mb=100, sustain=3)
    g.register("a", 95 * MB)                # pressure 0.95 > 0.9
    g.tick()
    g.tick()
    assert g.level == 0                     # 2 ticks < sustain 3
    g.tick()
    assert g.level == 1                     # third consecutive engages
    assert g.stats.rung_downs == {"evict_weights": 1}


def test_ladder_walks_down_in_order_and_back_up_in_reverse():
    g = _gov(budget_mb=100, sustain=1)
    g.register("a", 95 * MB)
    for _ in range(len(hbm.RUNGS)):
        g.tick()
    assert g.level == len(hbm.RUNGS)
    assert g.engaged_rungs() == list(hbm.RUNGS)
    assert not g.allows("piggyback")
    assert not g.allows("spec")
    assert g.batch_cap(32) == 16
    assert g.should_shed()
    g.update("a", 10 * MB)                  # pressure clears
    for _ in range(len(hbm.RUNGS)):
        g.tick()
    assert g.level == 0
    assert g.allows("piggyback") and g.allows("spec")
    assert g.batch_cap(32) == 32
    assert not g.should_shed()
    # every rung shows BOTH transitions — full reversibility
    for rung in hbm.RUNGS:
        assert g.stats.rung_downs.get(rung) == 1, rung
        assert g.stats.rung_ups.get(rung) == 1, rung


def test_hysteresis_band_is_quiet():
    g = _gov(budget_mb=100, engage=0.9, hyst=0.15, sustain=1)
    g.register("a", 95 * MB)
    g.tick()
    assert g.level == 1
    # 0.80 sits inside (0.75, 0.9): neither engages nor releases.
    g.update("a", 80 * MB)
    for _ in range(5):
        g.tick()
    assert g.level == 1
    g.update("a", 70 * MB)                  # 0.70 < 0.75 releases
    g.tick()
    assert g.level == 0


def test_rung_actions_fire_and_report_freed():
    g = _gov(budget_mb=100, sustain=1)
    calls = []
    g.set_action("evict_weights", engage=lambda: calls.append("w") or True)
    g.register("a", 95 * MB)
    g.tick()
    assert calls == ["w"]


# ---------------------------------------------------------------------------
# squeeze (the hbm_squeeze fault kind)
# ---------------------------------------------------------------------------


def test_squeeze_shrinks_and_auto_restores():
    g = _gov(budget_mb=100, sustain=1)
    g.register("a", 50 * MB)                # pressure 0.5 — calm
    g.squeeze(0.25, calls=4)                # budget -> 25 MB: pressure 2
    assert g.stats.squeezes == 1
    for _ in range(4):
        g.tick()
    assert g.level > 0                      # ladder walked down
    down_at_peak = dict(g.stats.rung_downs)
    for _ in range(len(hbm.RUNGS) + 2):
        g.tick()                            # squeeze expired: walk up
    assert g.level == 0
    assert g.budget_bytes == 100 * MB
    assert g.stats.rung_ups == down_at_peak  # fully reversible


def test_wrap_governor_fires_at_the_seeded_tick():
    g = _gov(budget_mb=100, sustain=1)
    g.register("a", 50 * MB)
    plan = faults.FaultPlan(seed=1, schedules={
        "hbm": faults.SiteSchedule.hbm_squeeze_at(2, frac=0.2, calls=3)})
    faults.wrap_governor(g, plan)
    g.tick()
    g.tick()
    assert g.stats.squeezes == 0            # calls 0 and 1: no squeeze
    g.tick()                                # call 2 fires
    assert g.stats.squeezes == 1
    assert plan.injected("hbm") == 1
    assert g.budget_bytes == 20 * MB


# ---------------------------------------------------------------------------
# OOM routing
# ---------------------------------------------------------------------------


def _oom():
    return RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating")


def test_handle_oom_force_engages_reclaim_rungs():
    g = _gov(budget_mb=100, sustain=10)     # sustain high: ticks alone
    freed = []                              # would never engage
    g.set_action("evict_weights", engage=lambda: freed.append(1) or True)
    assert g.handle_oom("sweep") is True
    assert g.engaged_rungs() == list(hbm.RECLAIM_RUNGS)
    assert freed == [1]
    assert g.stats.oom_reclaims == 1
    assert g.stats.oom_events == {"sweep": 1}
    # a second OOM with everything already engaged frees nothing
    assert g.handle_oom("sweep") is False
    assert g.stats.oom_exhausted == 1


def test_sweep_oom_reclaims_and_retries_once():
    from lir_tpu.engine.sweep import _dispatch_with_recovery

    engine = _tiny_engine()
    engine.governor = _gov(budget_mb=100)
    engine.governor.set_action("evict_weights", engage=lambda: True)
    state = {"n": 0}

    def flaky():
        state["n"] += 1
        if state["n"] == 1:
            raise _oom()
        return "scored"

    assert _dispatch_with_recovery(engine, flaky) == "scored"
    assert state["n"] == 2                  # exactly one retry
    assert engine.governor.stats.oom_reclaims == 1


def test_sweep_persistent_oom_raises_hbm_exhausted_with_arithmetic():
    from lir_tpu.engine.sweep import _dispatch_with_recovery

    engine = _tiny_engine()
    engine.governor = _gov(budget_mb=100)
    engine.governor.register("kv_pages:x", 40 * MB)
    engine.governor.set_action("evict_weights", engage=lambda: True)

    def always_oom():
        raise _oom()

    with pytest.raises(hbm.HbmExhausted) as ei:
        _dispatch_with_recovery(engine, always_oom)
    msg = str(ei.value)
    assert "ledger" in msg and "kv_pages:x" in msg and "budget" in msg


def test_sweep_oom_without_reclaim_reraises_raw():
    from lir_tpu.engine.sweep import _dispatch_with_recovery

    engine = _tiny_engine()
    engine.governor = hbm.HbmGovernor(GovernorConfig(enabled=False))

    def always_oom():
        raise _oom()

    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        _dispatch_with_recovery(engine, always_oom)


def _serve_cfg():
    return ServeConfig(
        queue_depth=64, classes=(("smoke", 600.0),),
        default_class="smoke", linger_s=0.0,
        max_consecutive_failures=2,
        retry=RetryConfig(max_retries=2, initial_delay=0.001,
                          max_delay=0.002, full_jitter=True,
                          max_elapsed=0.5))


def _request(i, rid=None):
    body = f"clause {i} covers wind damage under policy {i * 7}"
    return ServeRequest(
        binary_prompt=f"{body} Answer Yes or No .",
        confidence_prompt=f"{body} Give a number from 0 to 100 .",
        klass="smoke", request_id=rid or str(i))


def test_serve_oom_reclaim_retry_bypasses_breaker():
    engine = _tiny_engine()
    engine.governor = _gov(budget_mb=100)
    engine.governor.set_action("evict_weights", engage=lambda: True)
    server = ScoringServer(engine, "hbm-serve", _serve_cfg())
    real_score = server.batcher.score
    state = {"n": 0}

    def oom_once(bucket, rows):
        state["n"] += 1
        if state["n"] == 1:
            raise _oom()
        return real_score(bucket, rows)

    server.batcher.score = oom_once
    server.start()
    try:
        res = [server.submit(_request(i)).result(timeout=60)
               for i in range(2)]
    finally:
        server.stop()
    assert all(r.status == "ok" for r in res)
    assert state["n"] >= 2                   # reclaim retry ran
    assert engine.governor.stats.oom_reclaims == 1
    assert server.breaker.consecutive_failures == 0
    assert server.healthy


def test_serve_persistent_oom_quarantines_dispatch_not_breaker():
    engine = _tiny_engine()
    engine.governor = _gov(budget_mb=100)
    # nothing reclaimable: no evict action, flag rungs free no bytes
    server = ScoringServer(engine, "hbm-serve", _serve_cfg())
    state = {"n": 0}

    def always_oom(bucket, rows):
        state["n"] += 1
        raise _oom()

    real_score = server.batcher.score
    server.batcher.score = always_oom
    server.start()
    try:
        res = server.submit(_request(1)).result(timeout=60)
        assert res.status == "error"
        assert "ledger" in res.note          # the arithmetic, not a trace
        # capacity never advances the breaker — the server stays
        # healthy and serves the next request once memory "returns"
        assert server.breaker.consecutive_failures == 0
        assert server.healthy
        server.batcher.score = real_score
        ok = server.submit(_request(2)).result(timeout=60)
        assert ok.status == "ok"
    finally:
        server.stop()
    # the OOM skipped the generic retry loop: ONE attempt before the
    # governor's single reclaim-retry path took over
    assert state["n"] <= 2
    assert engine.governor.stats.oom_events.get("serve") == 1


def test_serve_shed_rung_resolves_shed():
    engine = _tiny_engine()
    engine.governor = _gov(budget_mb=100, sustain=1)
    engine.governor.register("big", 95 * MB)
    for _ in range(len(hbm.RUNGS)):
        engine.governor.tick()               # walk to the shed rung
    server = ScoringServer(engine, "hbm-serve", _serve_cfg())
    res = server.submit(_request(1)).result(timeout=5)
    assert res.status == "shed"
    assert "memory pressure" in res.note
    engine.governor.update("big", 5 * MB)
    for _ in range(len(hbm.RUNGS) + 1):
        engine.governor.tick()               # rungs re-arm
    server.start()
    try:
        ok = server.submit(_request(2)).result(timeout=60)
        assert ok.status == "ok"
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# metrics + engine wiring
# ---------------------------------------------------------------------------


def test_governor_gauges_in_metrics_snapshot():
    engine = _tiny_engine()
    server = ScoringServer(engine, "hbm-serve", _serve_cfg())
    snap = server.metrics.snapshot(device_memory=False)
    assert "mem" in snap["sources"]
    fields = snap["sources"]["mem"]["fields"]
    assert fields["ledger_bytes"] > 0        # params registered
    assert set(fields) >= {"pressure", "rung", "rung_downs", "rung_ups"}
    assert snap["sources"]["mem"]["type"] == "MemStats"


def test_engine_registers_params_and_pool_in_ledger():
    engine = _tiny_engine(prefix_cache=True, prefix_cache_pages=16)
    ledger = engine.governor.ledger()
    assert any(k.startswith("params:") for k in ledger)
    assert any(k.startswith("kv_pages:") and v > 0
               for k, v in ledger.items())


def test_mem_stats_schema_matches_dataclass():
    import dataclasses

    from lir_tpu.observe.registry import STATS_SCHEMA

    fields = {f.name for f in dataclasses.fields(MemStats)
              if not f.name.startswith("_")}
    assert fields == set(STATS_SCHEMA["MemStats"])


# ---------------------------------------------------------------------------
# fleet boot validation (satellite: budget < largest model fails loud)
# ---------------------------------------------------------------------------


def test_fleet_boot_rejects_budget_below_largest_model():
    engine = _tiny_engine("m0", seed=5)
    nbytes = weights.tree_bytes(engine.params)
    fleet = ModelFleet(cache_budget_bytes=nbytes // 2)
    with pytest.raises(ValueError) as ei:
        fleet.add_model("m0", engine=engine)
    msg = str(ei.value)
    assert "m0" in msg and "GiB" in msg and "headroom" in msg
    assert "weight-cache-gb" in msg


def test_fleet_boot_accepts_fitting_budget():
    engine = _tiny_engine("m0", seed=5)
    nbytes = weights.tree_bytes(engine.params)
    fleet = ModelFleet(cache_budget_bytes=2 * nbytes)
    fleet.add_model("m0", engine=engine)     # no raise
    assert fleet.resident("m0")


def test_attach_governor_revalidates_and_mirrors_weights():
    engine = _tiny_engine("m0", seed=5)
    nbytes = weights.tree_bytes(engine.params)
    fleet = ModelFleet(cache_budget_bytes=2 * nbytes)
    fleet.add_model("m0", engine=engine)
    gov = _gov(budget_mb=1000)
    fleet.attach_governor(gov)
    assert gov.ledger().get("weights") == fleet.cache.resident_bytes
    # evict_weights rung action drops the (idle) model
    assert fleet.evict_idle() is True
    assert not fleet.resident("m0")
    assert gov.ledger().get("weights") == 0


# ---------------------------------------------------------------------------
# WeightCache refcounts under concurrency (satellite: stress test)
# ---------------------------------------------------------------------------


def test_weight_cache_refcounts_threaded_stress():
    """Threaded acquire/release against a concurrent evictor: refcounts
    can never go negative (WeightCache asserts — any violation raises
    into the worker and fails the test), an in-flight or pinned model
    is never evicted mid-acquire, and the cache ends balanced."""
    cache = weights.WeightCache(budget_bytes=None)
    n_models = 4
    for i in range(n_models):
        cache.insert(f"m{i}", params={"w": i}, nbytes=MB)
    cache.pin("m0")
    errors = []
    stop = threading.Event()

    def worker(wid):
        try:
            for k in range(300):
                mid = f"m{(wid + k) % n_models}"
                try:
                    params = cache.acquire(mid)
                except KeyError:
                    continue        # evicted between choice and acquire
                assert params is not None
                # the model CANNOT be evicted while we hold it
                assert mid in cache, f"{mid} evicted while referenced"
                cache.release(mid)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)
            stop.set()

    def evictor():
        try:
            while not stop.is_set():
                evicted = cache.evict_idle()
                if evicted is not None:
                    assert evicted != "m0", "pinned model evicted"
                    # reinsert so workers keep finding work
                    cache.insert(evicted, params={"w": evicted},
                                 nbytes=MB)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(4)]
    ev = threading.Thread(target=evictor)
    for t in threads:
        t.start()
    ev.start()
    for t in threads:
        t.join(timeout=60)
    stop.set()
    ev.join(timeout=60)
    assert not errors, errors
    for i in range(n_models):
        assert cache.refcount(f"m{i}") == 0, "unbalanced refcount"
    assert "m0" in cache                     # pinned survived the storm

def test_weight_cache_release_below_zero_crashes():
    cache = weights.WeightCache()
    cache.insert("m", params={"w": 1}, nbytes=MB)
    cache.acquire("m")
    cache.release("m")
    with pytest.raises(AssertionError, match="negative"):
        cache.release("m")


# ---------------------------------------------------------------------------
# router pressure signal
# ---------------------------------------------------------------------------


def test_router_placement_penalizes_pressure():
    from lir_tpu.serve.router import ReplicaRouter

    class _Stub:
        def __init__(self, pressure):
            self.hbm_pressure = pressure
            self.queue_depth = 0
            self.stats = None

        def oldest_wait(self, now):
            return 0.0

        def submit(self, request):
            raise AssertionError("placement test never dispatches")

    calm, squeezed = _Stub(0.0), _Stub(2.0)
    router = ReplicaRouter(
        [("calm", calm), ("squeezed", squeezed)],
        config=RouterConfig(pressure_weight=6.0, cache_entries=0))
    # with equal depth, the squeezed replica must lose every pick
    for _ in range(6):
        h = router._pick("", exclude=set())
        assert h.replica_id == "calm"
    summary = router.stats_summary()
    assert summary["replicas"]["squeezed"]["hbm_pressure"] == 2.0


# ---------------------------------------------------------------------------
# A missing bytes_limit is the CPU's privilege, not an accelerator's
# ---------------------------------------------------------------------------

class _StatsDev:
    def __init__(self, platform, stats):
        self.platform, self.device_kind, self._stats = platform, "fake", stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("platform,stats,expected", [
    ("cpu", None, None),
    ("cpu", {}, None),
    ("tpu", {"bytes_limit": 16 * 2**30}, 16 * 2**30),
])
def test_device_bytes_limit(monkeypatch, platform, stats, expected):
    import jax

    from lir_tpu.engine import hbm

    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_StatsDev(platform, stats)])
    assert hbm.device_bytes_limit() == expected
    budget = hbm.device_budget_bytes(0.25)
    assert budget == (None if expected is None else int(expected * 0.75))


@pytest.mark.parametrize("stats", [None, {}, {"bytes_in_use": 5}])
def test_accelerator_without_bytes_limit_is_an_error(monkeypatch, stats):
    import jax

    from lir_tpu.engine import hbm

    monkeypatch.setattr(jax, "devices", lambda *a: [_StatsDev("tpu", stats)])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        hbm.device_budget_bytes()
