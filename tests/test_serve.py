"""Online serving layer tests (lir_tpu/serve + the retry/bucket_cost
satellites).

Pins the contracts the serving tentpole rides on:
- admission control: FIFO under capacity, deadline-aware shedding at the
  bound (the least-urgent request is the one shed);
- deadline expiry returns PARTIAL confidence-free results without
  failing the rest of the batch;
- the content-addressed dedup cache returns bitwise-identical results to
  a fresh score;
- continuous-batch per-request results equal the offline sweep's for the
  same cells (the dispatch path is the sweep's own, bit for bit);
- repeated device errors drain the queue and flip the health flag;
- retry_with_exponential_backoff's full jitter stays inside the delay
  envelope and the max-elapsed cap bounds total retry time.
"""

import random

import jax
import numpy as np
import pytest

from lir_tpu.backends.fake import FakeTokenizer
from lir_tpu.config import RetryConfig, RuntimeConfig, ServeConfig
from lir_tpu.engine import compile_plan
from lir_tpu.engine import scheduler as sched_mod
from lir_tpu.serve import (ResultCache, ScoringServer, ServeFuture,
                           ServeRequest, content_key)
from lir_tpu.serve.queue import Pending, RequestQueue
from lir_tpu.utils.profiling import ServeStats
from lir_tpu.utils.retry import retry_with_exponential_backoff


# ---------------------------------------------------------------------------
# RequestQueue: admission control + deadline-aware shedding (pure host)
# ---------------------------------------------------------------------------

def _pending(deadline: float, rid: str) -> Pending:
    return Pending(
        request=ServeRequest(binary_prompt="b", confidence_prompt="c",
                             request_id=rid),
        future=ServeFuture(), t_submit=0.0, t_deadline=deadline)


def test_queue_admission_and_shed_ordering():
    stats = ServeStats()
    q = RequestQueue(2, stats, clock=lambda: 0.0)
    a, b = _pending(10.0, "a"), _pending(5.0, "b")
    assert q.offer(a) and q.offer(b)

    # Full queue + a LESS urgent newcomer: the newcomer is shed.
    c = _pending(20.0, "c")
    assert not q.offer(c)
    assert c.future.result(0).status == "shed"

    # Full queue + a MORE urgent newcomer: the latest-deadline queued
    # request (a) is evicted instead.
    d = _pending(1.0, "d")
    assert q.offer(d)
    assert a.future.result(0).status == "shed"
    assert not b.future.done() and not d.future.done()

    # FIFO among survivors; the books balance.
    assert [p.request.request_id for p in q.drain()] == ["b", "d"]
    assert stats.shed == 2
    assert stats.admitted == 3
    assert stats.queue_depth_peak == 2


def test_queue_concurrent_shed_keeps_most_urgent_set():
    """The latest-deadline-shed invariant under CONCURRENT submitters
    (it was only pinned single-threaded before): with every offer
    serialized through the queue lock, the greedy policy keeps exactly
    the maxlen most-urgent requests seen so far — so after N threads
    race 200 distinct-deadline offers into a depth-16 queue, the
    survivors must be precisely the 16 earliest deadlines, every loser
    must hold a resolved shed future, and the books must balance."""
    import threading

    depth, n_threads, per_thread = 16, 8, 25
    stats = ServeStats()
    q = RequestQueue(depth, stats, clock=lambda: 0.0)
    # Distinct deadlines, dealt round-robin so every thread holds a mix
    # of urgent and lazy requests (maximizing eviction interleavings).
    deadlines = [float(d) for d in
                 np.random.default_rng(0).permutation(
                     n_threads * per_thread)]
    pendings = [_pending(d, str(i)) for i, d in enumerate(deadlines)]
    start = threading.Barrier(n_threads)

    def submitter(tid):
        start.wait()
        for p in pendings[tid::n_threads]:
            q.offer(p)

    threads = [threading.Thread(target=submitter, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    survivors = q.drain()
    assert len(survivors) == depth
    want = sorted(deadlines)[:depth]
    assert sorted(p.t_deadline for p in survivors) == want
    # Every non-survivor was resolved shed — no future leaks.
    kept = {id(p) for p in survivors}
    for p in pendings:
        if id(p) in kept:
            assert not p.future.done()
        else:
            assert p.future.result(0).status == "shed"
    assert stats.shed == len(pendings) - depth
    # admitted counts every entry that EVER joined the queue (evicted
    # ones included), so it must at least cover the survivors and never
    # exceed the offers.
    assert depth <= stats.admitted <= len(pendings)


def test_queue_flush_resolves_everything():
    q = RequestQueue(8, ServeStats(), clock=lambda: 0.0)
    ps = [_pending(9.0, str(i)) for i in range(3)]
    for p in ps:
        q.offer(p)
    assert q.flush("error", "drained") == 3
    assert all(p.future.result(0).status == "error" for p in ps)
    assert len(q) == 0


# ---------------------------------------------------------------------------
# ResultCache: content addressing + LRU bound
# ---------------------------------------------------------------------------

def test_result_cache_lru_and_keying():
    stats = ServeStats()
    cache = ResultCache(2, stats)
    r1 = ServeRequest(binary_prompt="p1 bin", confidence_prompt="p1 conf")
    r2 = ServeRequest(binary_prompt="p2 bin", confidence_prompt="p2 conf")
    r3 = ServeRequest(binary_prompt="p1 bin", confidence_prompt="p1 conf",
                      targets=("Covered", "Not"))
    k1, k2, k3 = (content_key("eng", r) for r in (r1, r2, r3))
    assert len({k1, k2, k3}) == 3            # prompts AND targets key
    assert content_key("other-engine", r1) != k1

    cache.put(k1, {"v": 1})
    cache.put(k2, {"v": 2})
    assert cache.get(k1) == {"v": 1}         # k1 now most-recent
    cache.put(k3, {"v": 3})                  # evicts k2 (LRU)
    assert cache.get(k2) is None
    assert cache.get(k1) == {"v": 1} and cache.get(k3) == {"v": 3}
    assert stats.dedup_hits == 3 and stats.dedup_misses == 1

    disabled = ResultCache(0, ServeStats())
    disabled.put(k1, {"v": 1})
    assert disabled.get(k1) is None and len(disabled) == 0


# ---------------------------------------------------------------------------
# Retry satellite: full jitter + max-elapsed cap
# ---------------------------------------------------------------------------

def test_retry_max_elapsed_cap_is_deterministic():
    calls, waits, t = [], [], [0.0]
    cfg = RetryConfig(max_retries=10, initial_delay=4.0, max_delay=300.0,
                      backoff_factor=2.0, jitter=(1.0, 1.0),
                      max_elapsed=5.0)

    def always_fails():
        calls.append(1)
        raise ValueError("nope")

    def sleep(s):
        waits.append(s)
        t[0] += s

    with pytest.raises(ValueError):
        retry_with_exponential_backoff(
            always_fails, (ValueError,), cfg, sleep=sleep,
            log=lambda s: None, clock=lambda: t[0])
    # First retry slept 4 s (inside the cap); the second would sleep 8 s,
    # crossing the 5 s cap -> the failure re-raises without sleeping.
    assert waits == [4.0]
    assert len(calls) == 2
    assert t[0] <= cfg.max_elapsed


def test_retry_gives_up_at_once_on_what_retrying_cannot_change():
    """``give_up``: a failure the predicate names re-raises on the first
    attempt, with no sleep, whatever ``retry_on`` says; other failures
    keep the whole policy."""
    cfg = RetryConfig(max_retries=5, initial_delay=1.0, max_delay=1.0,
                      jitter=(1.0, 1.0), max_elapsed=None)
    calls, waits = [], []

    def refused():
        calls.append(1)
        raise ValueError("refused by the compiler")

    with pytest.raises(ValueError):
        retry_with_exponential_backoff(
            refused, (Exception,), cfg, sleep=waits.append,
            log=lambda s: None,
            give_up=lambda e: isinstance(e, ValueError))
    assert len(calls) == 1 and waits == []

    def flaky():
        calls.append(1)
        raise RuntimeError("transient")

    calls.clear()
    with pytest.raises(RuntimeError):
        retry_with_exponential_backoff(
            flaky, (Exception,), cfg, sleep=waits.append,
            log=lambda s: None,
            give_up=lambda e: isinstance(e, ValueError))
    assert len(calls) == 6 and len(waits) == 5


def test_retry_full_jitter_stays_inside_the_envelope():
    random.seed(0)
    waits, t = [], [0.0]
    cfg = RetryConfig(max_retries=6, initial_delay=1.0, max_delay=4.0,
                      backoff_factor=2.0, full_jitter=True,
                      max_elapsed=1000.0)

    def always_fails():
        raise ValueError("nope")

    def sleep(s):
        waits.append(s)
        t[0] += s

    with pytest.raises(ValueError):
        retry_with_exponential_backoff(
            always_fails, (ValueError,), cfg, sleep=sleep,
            log=lambda s: None, clock=lambda: t[0])
    assert len(waits) == 6
    caps = [1.0, 2.0, 4.0, 4.0, 4.0, 4.0]    # delay doubles, capped at 4
    assert all(0.0 <= w <= c for w, c in zip(waits, caps))


# ---------------------------------------------------------------------------
# bucket_cost satellite: one price model for planner and batcher
# ---------------------------------------------------------------------------

def test_bucket_cost_matches_the_planner_rule():
    # The helper IS the planner's keep-the-tail price: padded
    # power-of-two batch x (prefill edge + fixed decode scan).
    assert sched_mod.bucket_cost(3, 64, 8, 12) == 4 * (64 + 12)
    assert sched_mod.bucket_cost(8, 64, 8, 12) == 8 * (64 + 12)
    assert sched_mod.bucket_cost(9, 64, 8, 12) == 8 * (64 + 12)  # capped
    # Promotion fires exactly when riding the next bucket is cheaper.
    B, edge, nxt, dc = 8, 64, 96, 12
    for n in range(1, B + 1):
        promote = n * nxt < sched_mod.bucket_cost(n, edge, B, dc)
        assert promote == (n * nxt < sched_mod._tail_batch(n, B)
                           * (edge + dc))


def test_serve_batches_and_ladder_specs():
    assert compile_plan.serve_batches(32) == (1, 2, 4, 8, 16, 32)
    assert compile_plan.serve_batches(1) == (1,)
    # The serve boot precompile warms every (edge, sfx, padded batch)
    # shared executable in both handoff variants.
    engine = _tiny_setup()()
    specs = compile_plan.sweep_specs_for_ladder(
        engine, sfx_buckets=(8,), batches=(1, 2, 4))
    # Sequential + speculative sibling per (edge, sfx, batch, handoff).
    seq = [s for s in specs if not s.spec_k]
    assert len(seq) == len(engine.buckets) * 1 * 3 * 2
    assert len(specs) == 2 * len(seq)
    assert {s.batch for s in specs} == {1, 2, 4}
    assert {s.bucket for s in specs} == set(engine.buckets)


def test_online_promotion_rides_the_next_buckets_dispatch():
    """An underfull ripe bucket with work waiting above it promotes —
    the offline slot-refill rule run incrementally. A lone bucket never
    promotes into an empty queue (nothing to ride)."""
    from lir_tpu.serve.batcher import ContinuousBatcher

    engine = _tiny_setup()()          # buckets: ladder up to 256
    stats = ServeStats()
    b = ContinuousBatcher(engine, stats, linger_s=0.0, pad_full=True)
    small, big = engine.buckets[0], engine.buckets[1]

    def pend(bucket, rid):
        p = _pending(600.0, rid)
        p.bucket = bucket
        return p

    # 2 rows at the small edge + 2 at the next: promotion merges them
    # into ONE full dispatch at the bigger edge.
    for i in range(2):
        b.admit(pend(small, f"s{i}"))
        b.admit(pend(big, f"b{i}"))
    edge, rows = b.next_dispatch(now=10.0)
    assert edge == big and len(rows) == 4
    assert stats.promoted == 2
    # Lone underfull bucket, empty ladder above: dispatches in place.
    b2 = ContinuousBatcher(engine, stats, linger_s=0.0, pad_full=True)
    b2.admit(pend(small, "alone"))
    edge2, rows2 = b2.next_dispatch(now=10.0)
    assert edge2 == small and len(rows2) == 1


# ---------------------------------------------------------------------------
# Server-level: scoring parity, dedup, deadlines, health
# ---------------------------------------------------------------------------

def _tiny_setup(batch_size=4, seed=2):
    from lir_tpu.engine.runner import ScoringEngine
    from lir_tpu.models import decoder
    from lir_tpu.models.registry import ModelConfig

    cfg = ModelConfig(name="serve-t", vocab_size=FakeTokenizer.VOCAB,
                      hidden_size=32, n_layers=1, n_heads=2,
                      intermediate_size=64, max_seq_len=256)
    params = decoder.init_params(cfg, jax.random.PRNGKey(seed))
    rt = RuntimeConfig(batch_size=batch_size, max_seq_len=256)

    def engine():
        return ScoringEngine(params, cfg, FakeTokenizer(), rt)

    return engine


def _grid(n_cells, words_each=12, seed=5):
    """Uniform-length cells (every prompt the same token count) so the
    offline planner and the online batcher form IDENTICAL dispatch
    shapes — the precondition for bitwise equality across the paths."""
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


def _request_for(cell, rid):
    return ServeRequest(binary_prompt=cell.binary_prompt,
                        confidence_prompt=cell.confidence_prompt,
                        targets=cell.target_tokens, klass="t",
                        request_id=rid)


_SERVE_CFG = ServeConfig(queue_depth=64, classes=(("t", 600.0),),
                         default_class="t", linger_s=0.01)


def test_continuous_batching_matches_offline_sweep_bitwise(tmp_path):
    """The acceptance pin: per-request serve results equal the offline
    sweep's for the same cells, bit for bit. Same cells, same batch
    size, same bucket/suffix snapping, same handoff chain -> the serve
    path dispatches the sweep's own executables on identical inputs."""
    from lir_tpu.engine import grid as grid_mod
    from lir_tpu.engine.sweep import run_perturbation_sweep

    make_engine = _tiny_setup(batch_size=4)
    lp, perts = _grid(12)

    rows = run_perturbation_sweep(
        make_engine(), "serve-t", lp, perts, tmp_path / "off.xlsx",
        checkpoint_every=100)
    by_prompt = {r.rephrased_main: r for r in rows}
    assert len(by_prompt) == 12

    cells = grid_mod.build_grid("serve-t", lp, perts)
    server = ScoringServer(make_engine(), "serve-t", _SERVE_CFG)
    futures = [(c, server.submit(_request_for(c, str(i))))
               for i, c in enumerate(cells)]
    server.start()
    try:
        for cell, fut in futures:
            res = fut.result(timeout=300)
            off = by_prompt[cell.rephrased_main]
            assert res.status == "ok" and not res.cached
            # Bitwise: exact float equality, not allclose.
            assert res.token_1_prob == off.token_1_prob
            assert res.token_2_prob == off.token_2_prob
            assert res.weighted_confidence == off.weighted_confidence
            assert res.confidence_value == off.confidence_value
            assert res.model_response == off.model_response
            assert (res.model_confidence_response
                    == off.model_confidence_response)
            assert res.log_probabilities == off.log_probabilities
    finally:
        server.stop()
    assert server.stats.completed == 12
    assert server.stats.shed == 0 and server.stats.expired == 0


def test_dedup_cache_hit_is_bitwise_identical_to_fresh_score():
    make_engine = _tiny_setup()
    lp, perts = _grid(4, seed=9)
    from lir_tpu.engine import grid as grid_mod

    cells = grid_mod.build_grid("serve-t", lp, perts)
    server = ScoringServer(make_engine(), "serve-t", _SERVE_CFG).start()
    try:
        fresh = [server.submit(_request_for(c, str(i))).result(timeout=300)
                 for i, c in enumerate(cells)]
        assert all(r.status == "ok" and not r.cached for r in fresh)
        dispatches_after_fresh = server.stats.dispatches
        hits = [server.submit(_request_for(c, f"again{i}"))
                .result(timeout=60) for i, c in enumerate(cells)]
    finally:
        server.stop()
    for a, b in zip(fresh, hits):
        assert b.cached and b.status == "ok"
        assert b.token_1_prob == a.token_1_prob
        assert b.token_2_prob == a.token_2_prob
        assert b.weighted_confidence == a.weighted_confidence
        assert b.log_probabilities == a.log_probabilities
        assert b.model_response == a.model_response
    assert server.stats.dedup_hits == len(cells)
    # A hit never touched the device: dispatch count didn't grow.
    assert server.stats.dispatches == dispatches_after_fresh


def test_deadline_expired_rows_return_partial_without_failing_batch():
    make_engine = _tiny_setup()
    lp, perts = _grid(4, seed=3)
    from lir_tpu.engine import grid as grid_mod

    cells = grid_mod.build_grid("serve-t", lp, perts)
    server = ScoringServer(make_engine(), "serve-t", _SERVE_CFG)
    # Submit BEFORE start: the expired row sits queued past its deadline
    # while the live rows ride the same bucket.
    doomed = server.submit(ServeRequest(
        binary_prompt=cells[0].binary_prompt,
        confidence_prompt=cells[0].confidence_prompt,
        deadline_s=0.0, request_id="doomed"))
    live = [server.submit(_request_for(c, str(i)))
            for i, c in enumerate(cells[1:])]
    server.start()
    try:
        d = doomed.result(timeout=300)
        results = [f.result(timeout=300) for f in live]
    finally:
        server.stop()
    # Partial, confidence-free result — not an exception, not a dropped
    # request, and the batch it would have ridden still completed.
    assert d.status == "deadline_exceeded"
    assert d.token_1_prob is None and d.token_2_prob is None
    assert d.confidence_value is None and d.weighted_confidence is None
    assert all(r.status == "ok" for r in results)
    assert server.stats.expired == 1
    assert server.stats.completed == len(results)


def test_expired_request_resolves_partial_during_watched_dispatch():
    """Satellite pin (guard layer): deadline enforcement actually
    CANCELS. A request whose deadline passes while its dispatch is on
    the device resolves its partial result immediately — the watched
    executor's tick callback — instead of waiting out the device call.
    Pre-guard behavior was to block until the dispatch returned, which
    made deadlines advisory whenever the device was slow or hung."""
    import time as _time

    make_engine = _tiny_setup()
    lp, perts = _grid(4, seed=7)
    from lir_tpu.engine import grid as grid_mod

    cells = grid_mod.build_grid("serve-t", lp, perts)
    server = ScoringServer(make_engine(), "serve-t", _SERVE_CFG)
    real_score = server.batcher.score
    slow_s = 1.5

    def slow_score(bucket, rows):
        _time.sleep(slow_s)         # a slow (not hung) device call
        return real_score(bucket, rows)

    server.batcher.score = slow_score
    doomed = server.submit(ServeRequest(
        binary_prompt=cells[0].binary_prompt,
        confidence_prompt=cells[0].confidence_prompt,
        deadline_s=0.2, request_id="doomed"))
    live = [server.submit(_request_for(c, str(i)))
            for i, c in enumerate(cells)]
    server.start()
    try:
        t0 = _time.monotonic()
        d = doomed.result(timeout=60)
        waited = _time.monotonic() - t0
        results = [f.result(timeout=300) for f in live]
    finally:
        server.stop()
    assert d.status == "deadline_exceeded"
    assert d.token_1_prob is None and d.weighted_confidence is None
    assert "mid-dispatch" in d.note
    # The whole point: resolved BEFORE the device call finished.
    assert waited < slow_s, (
        f"expired request waited out the {slow_s}s dispatch "
        f"({waited:.2f}s)")
    # Its batch still completed for every live neighbor, and the late
    # payload for the cancelled row was dropped, not double-resolved.
    assert all(r.status == "ok" for r in results)
    eng_stats = server.engine.guard_stats
    assert eng_stats.inflight_cancelled >= 1
    assert server.stats.expired >= 1


def test_repeated_device_errors_drain_queue_and_flip_health():
    make_engine = _tiny_setup()
    cfg = ServeConfig(
        queue_depth=16, classes=(("t", 600.0),), default_class="t",
        linger_s=0.0, max_consecutive_failures=1,
        retry=RetryConfig(max_retries=1, initial_delay=0.001,
                          max_delay=0.002, full_jitter=True,
                          max_elapsed=1.0))
    server = ScoringServer(make_engine(), "serve-t", cfg)
    boom = RuntimeError("device on fire")

    def exploding_score(bucket, rows):
        raise boom

    server.batcher.score = exploding_score
    lp, perts = _grid(4, seed=4)
    from lir_tpu.engine import grid as grid_mod

    cells = grid_mod.build_grid("serve-t", lp, perts)
    futures = [server.submit(_request_for(c, str(i)))
               for i, c in enumerate(cells)]
    server.start()
    try:
        results = [f.result(timeout=60) for f in futures]
    finally:
        server.stop()
    assert all(r.status == "error" for r in results)
    assert not server.healthy
    assert server.stats.errors == len(cells)
    # Post-trip submits shed immediately instead of queueing.
    shed = server.submit(_request_for(cells[0], "post")).result(timeout=5)
    assert shed.status == "shed" and "unhealthy" in shed.note
