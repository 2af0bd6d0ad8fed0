"""Disaggregated prefill/decode serving: KV-page migration + the
cluster-wide prefix index (serve/migrate.py, engine/prefix_tree.
ClusterPrefixIndex, serve/router.py roles).

Pins the PR's load-bearing claims:

- device legs: pages extracted from one pool re-inserted into ANOTHER
  pool (different size — the different-mesh stand-in the CPU suite can
  exercise) come back bitwise through the slot gather;
- the prefill-only dispatch (engine.prefill_insert) produces page
  VALUES bitwise-identical to the pages a full scoring dispatch of the
  same bucket inserts — the property that makes remote prefill
  transparent;
- export/import round-trip: chunked, double-buffered, checksummed;
  a corrupted chunk is refused with the destination tree/refcounts
  rolled back untouched; a cancelled transfer leaves refcounts sane;
- cluster index: insert/evict listener events maintain the router-side
  match, eviction prunes it;
- the headline: migrated-page decode == colocated local-prefill decode
  BITWISE — cold, warm, early-stop, and int8-KV flavors;
- router integration: page residency wins placement, the disagg chain
  serves end-to-end with scoring only on decode replicas, and the
  migration_stall / migration_corrupt chaos kinds fall back to local
  re-prefill with payloads still bitwise.
"""

import dataclasses

import jax
import numpy as np
import pytest

from lir_tpu import faults
from lir_tpu.backends.fake import FakeTokenizer
from lir_tpu.config import (MigrationConfig, RouterConfig, RuntimeConfig,
                            ServeConfig)
from lir_tpu.engine import prefix_tree
from lir_tpu.engine import tokens as tok
from lir_tpu.engine.runner import ScoringEngine
from lir_tpu.models import decoder, paged
from lir_tpu.models.registry import ModelConfig, tiny
from lir_tpu.serve import migrate as mig
from lir_tpu.serve import (ReplicaRouter, ScoringServer, ServeRequest)

from dispatch_helpers import assert_paged_equals_cold

CFG = tiny("llama")
PARAMS = decoder.init_params(CFG, jax.random.PRNGKey(1))
TOKZ = FakeTokenizer(vocab=CFG.vocab_size)

FUSED_FIELDS = ("generated", "p_yes", "p_no", "top2_ids", "topk_logprobs",
                "topk_ids", "weighted_confidence")

PAYLOAD_FIELDS = ("model_response", "model_confidence_response",
                  "token_1_prob", "token_2_prob", "log_probabilities",
                  "confidence_value", "weighted_confidence")


def _engine(prefix: bool, pages: int = 64, params=PARAMS, cfg=CFG,
            **kw):
    rt = RuntimeConfig(batch_size=4, max_seq_len=128,
                       aot_precompile=False, prefix_cache=prefix,
                       prefix_cache_pages=pages, **kw)
    return ScoringEngine(params, cfg, TOKZ, rt)


def _prompts(n, trunk_words=70, seed=0):
    words = ("coverage policy flood water damage claim insurer premium "
             "exclusion endorsement peril deductible adjuster").split()
    rng = np.random.default_rng(seed)
    base = " ".join(rng.choice(words) for _ in range(trunk_words))
    bps = [f"{base} case {i} Answer Yes or No ." for i in range(n)]
    cps = [f"{base} case {i} Give a number 0 to 100 ." for i in range(n)]
    return bps, cps


def _prefixes(bps, cps):
    bin_ids = [TOKZ(p).input_ids for p in bps]
    conf_ids = [TOKZ(p).input_ids for p in cps]
    lcps = [tok.shared_prefix_len(a, b)
            for a, b in zip(bin_ids, conf_ids)]
    return [list(a[:n]) for a, n in zip(bin_ids, lcps)]


def _shared(engine, bps, cps, use, early_stop=False):
    engine.fresh_handoff()
    yes = np.full((len(bps),), TOKZ.YES, np.int32)
    no = np.full((len(bps),), TOKZ.NO, np.int32)
    return engine.decode_fused_shared(
        bps, cps, yes, no, new_tokens=4, conf_tokens=6,
        early_stop=early_stop, bucket=128, sfx_buckets_ab=(16, 16),
        reuse_cache=True, use_prefix_cache=use, n_real=len(bps))


def assert_fused_bitwise(a, b):
    for f in FUSED_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"fused field {f}")


def _assert_pins_released(engine):
    pool = engine.prefix_cache.pool
    assert (pool.refcount >= 0).all()
    assert pool.refcount[1:].sum() == pool.pages_in_use


def _migrate_all(src, dst, bucket, prefixes, config=None):
    cfg = config or MigrationConfig(chunk_pages=2)
    moved = 0
    for ids in prefixes:
        e = mig.export_prefix(src, bucket, ids, config=cfg)
        if e is not None:
            moved += mig.import_prefix(dst, e, config=cfg).pages
    return moved


# ---------------------------------------------------------------------------
# Device legs (models/paged.extract_pages / insert_pages)
# ---------------------------------------------------------------------------

def test_extract_insert_roundtrip_between_pools_bitwise():
    """Pages written into one pool come back bitwise after an
    extract -> insert hop into a DIFFERENT-sized pool (the
    different-mesh pool stand-in CPU can exercise: leaf shapes differ
    in n_pages, sharding is re-derived at device_put)."""
    aval = jax.eval_shape(
        lambda k: jax.random.normal(k, (2, 2, 32, 4, 8)),
        jax.random.PRNGKey(0))
    cache = jax.random.normal(jax.random.PRNGKey(3), (2, 2, 32, 4, 8))
    src = paged.KVPagePool(16, page_size=4)
    src.ensure(aval)
    src.scatter(cache, [(1, 0, 0), (2, 0, 4), (3, 1, 8)])
    blocks = src.extract([1, 2, 3])
    dst = paged.KVPagePool(8, page_size=4)
    dst.ensure(aval)
    dst.insert(blocks, [5, 6, 7])
    got = dst.extract([5, 6, 7])
    for a, b in zip(jax.tree.leaves(blocks), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the block contents really are the cache slices
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(got)[0])[:, :, 0],
        np.asarray(cache)[:, :, 0:4, 0])


# ---------------------------------------------------------------------------
# Prefill-only dispatch parity (the disaggregation keystone)
# ---------------------------------------------------------------------------

def test_prefill_insert_pages_bitwise_vs_dispatch_pages():
    """engine.prefill_insert's pages are BITWISE the pages a full
    scoring dispatch of the same bucket inserts — remote prefill is
    transparent by construction."""
    bps, cps = _prompts(4)
    prefixes = _prefixes(bps, cps)
    eng_a = _engine(True)
    _shared(eng_a, bps, cps, True)        # dispatch-produced pages
    eng_b = _engine(True)
    covered = eng_b.prefill_insert(128, prefixes)
    ps = eng_b.prefix_cache.page_size
    assert covered == (len(prefixes[0]) // ps) * ps
    for ids in prefixes:
        ma = eng_a.prefix_cache.lookup(128, ids, record=False)
        mb = eng_b.prefix_cache.lookup(128, ids, record=False)
        assert ma.tokens == mb.tokens > 0
        ba = eng_a.prefix_cache.pool.extract(ma.pages)
        bb = eng_b.prefix_cache.pool.extract(mb.pages)
        for x, y in zip(jax.tree.leaves(ba), jax.tree.leaves(bb)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        eng_a.prefix_cache.release(ma)
        eng_b.prefix_cache.release(mb)
    _assert_pins_released(eng_b)


def test_prefill_insert_skips_already_cached_rows():
    bps, cps = _prompts(2)
    prefixes = _prefixes(bps, cps)
    eng = _engine(True)
    eng.prefill_insert(128, prefixes)
    inserted = eng.prefix_stats.inserted_pages
    covered = eng.prefill_insert(128, prefixes)   # repeat: no new pages
    assert eng.prefix_stats.inserted_pages == inserted
    assert covered > 0


# ---------------------------------------------------------------------------
# Export / import round-trip
# ---------------------------------------------------------------------------

def test_export_import_roundtrip_bitwise_different_pool():
    """Exported pages re-imported on a different-sized pool are
    bitwise, chunked at a stable width with per-chunk checksums."""
    bps, cps = _prompts(3)
    prefixes = _prefixes(bps, cps)
    src = _engine(True, pages=64)
    src.prefill_insert(128, prefixes)
    dst = _engine(True, pages=24)
    cfg = MigrationConfig(chunk_pages=2)
    e = mig.export_prefix(src, 128, prefixes[0], config=cfg)
    assert e is not None and e.n_pages > 0
    assert len(e.checksums) == len(e.chunks) >= 2
    assert e.nbytes == src.prefix_cache.pool.page_nbytes() * e.n_pages
    r = mig.import_prefix(dst, e, config=cfg)
    assert r.pages == e.n_pages
    ms = src.prefix_cache.lookup(128, prefixes[0], record=False)
    md = dst.prefix_cache.lookup(128, prefixes[0], record=False)
    assert ms.tokens == md.tokens
    bs = src.prefix_cache.pool.extract(ms.pages)
    bd = dst.prefix_cache.pool.extract(md.pages)
    for x, y in zip(jax.tree.leaves(bs), jax.tree.leaves(bd)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    src.prefix_cache.release(ms)
    dst.prefix_cache.release(md)
    _assert_pins_released(src)
    _assert_pins_released(dst)


def test_import_is_idempotent_and_partial_pulls_align():
    """Re-importing an already-held prefix lands zero pages; an export
    taken from a partial offset fills exactly the destination's gap."""
    bps, cps = _prompts(1, trunk_words=80)
    prefixes = _prefixes(bps, cps)
    src = _engine(True)
    src.prefill_insert(128, prefixes)
    dst = _engine(True)
    cfg = MigrationConfig(chunk_pages=2)
    ps = src.prefix_cache.page_size
    # destination already holds the first 2 pages (local prefill of a
    # shorter prefix sharing the trunk)
    dst.prefill_insert(128, [prefixes[0][:2 * ps]])
    have = dst.prefix_cache.match_len(128, prefixes[0])
    assert have == 2 * ps
    e = mig.export_prefix(src, 128, prefixes[0], from_token=have,
                          config=cfg)
    assert e.start_tokens == have
    r = mig.import_prefix(dst, e, config=cfg)
    want = (len(prefixes[0]) // ps) * ps
    assert dst.prefix_cache.match_len(128, prefixes[0]) == want
    assert r.pages == (want - have) // ps
    # idempotent: nothing more to land
    e2 = mig.export_prefix(src, 128, prefixes[0], config=cfg)
    assert mig.import_prefix(dst, e2, config=cfg).pages == 0
    _assert_pins_released(dst)


def test_corrupt_chunk_refused_and_rolled_back():
    """A chunk corrupted in flight fails the checksum verify: NO page
    lands, the destination tree gains no nodes, refcounts and the free
    list are exactly as before — then a clean retry succeeds."""
    bps, cps = _prompts(2)
    prefixes = _prefixes(bps, cps)
    src = _engine(True)
    src.prefill_insert(128, prefixes)
    dst = _engine(True, pages=24)
    cfg = MigrationConfig(chunk_pages=2)
    e = mig.export_prefix(src, 128, prefixes[0], config=cfg)
    faults.corrupt_export_chunks(e, seed="t")
    free_before = dst.prefix_cache.pool.free_pages
    nodes_before = len(dst.prefix_cache)
    with pytest.raises(mig.MigrationError, match="checksum"):
        mig.import_prefix(dst, e, config=cfg)
    assert dst.prefix_cache.pool.free_pages == free_before
    assert len(dst.prefix_cache) == nodes_before
    assert (dst.prefix_cache.pool.refcount >= 0).all()
    # a clean export still lands afterwards
    e2 = mig.export_prefix(src, 128, prefixes[0], config=cfg)
    assert mig.import_prefix(dst, e2, config=cfg).pages == e2.n_pages


def test_cancelled_transfer_keeps_refcounts_sane():
    """A transfer that dies mid-import (device-put failure stand-in)
    rolls back: fresh nodes removed, their pages freed, no leaked
    pins."""
    bps, cps = _prompts(1)
    prefixes = _prefixes(bps, cps)
    src = _engine(True)
    src.prefill_insert(128, prefixes)
    dst = _engine(True)
    cfg = MigrationConfig(chunk_pages=1, verify=False)
    e = mig.export_prefix(src, 128, prefixes[0], config=cfg)
    # poison the second chunk's host tree so the import's device_put
    # raises after the first chunk already queued
    e.chunks[1] = (None, e.chunks[1][1])
    free_before = dst.prefix_cache.pool.free_pages
    with pytest.raises(Exception):
        mig.import_prefix(dst, e, config=cfg)
    assert dst.prefix_cache.pool.free_pages == free_before
    assert len(dst.prefix_cache) == 0
    assert (dst.prefix_cache.pool.refcount >= 0).all()


# ---------------------------------------------------------------------------
# Cluster prefix index
# ---------------------------------------------------------------------------

def test_cluster_index_follows_insert_and_evict_events():
    """Tree listener events maintain the router-side index; evicting
    pages on the replica PRUNES the cluster match."""
    bps, cps = _prompts(2)
    prefixes = _prefixes(bps, cps)
    eng = _engine(True)
    idx = prefix_tree.ClusterPrefixIndex(eng.prefix_cache.page_size)
    import functools
    eng.prefix_cache.add_listener(
        functools.partial(idx.on_event, "r0"))
    eng.prefill_insert(128, prefixes)
    ps = eng.prefix_cache.page_size
    want = len(prefixes[0]) // ps
    assert idx.match_pages(128, prefixes[0]) == {"r0": want}
    assert idx.best_holder(128, prefixes[0]) == ("r0", want)
    assert idx.best_holder(128, prefixes[0],
                           exclude=("r0",)) == (None, 0)
    # evict everything: the index must end empty
    eng.prefix_cache.evict(eng.prefix_cache.pool.n_pages)
    assert idx.match_pages(128, prefixes[0]) == {}


def test_cluster_index_bucket_namespaces_and_partial_match():
    idx = prefix_tree.ClusterPrefixIndex(4)
    idx.on_event("a", "insert", 64, tuple(range(8)))
    idx.on_event("b", "insert", 64, tuple(range(4)))
    idx.on_event("b", "insert", 32, tuple(range(8)))
    probe = tuple(range(8))
    assert idx.match_pages(64, probe) == {"a": 2, "b": 1}
    assert idx.best_holder(64, probe) == ("a", 2)
    assert idx.match_pages(32, probe) == {"b": 2}
    # divergent tail matches only the shared leading pages
    assert idx.match_pages(64, (0, 1, 2, 3, 9, 9, 9, 9)) \
        == {"a": 1, "b": 1}
    idx.drop_replica("a")
    assert idx.match_pages(64, probe) == {"b": 1}


def test_forget_tail_rolls_back_and_notifies():
    eng = _engine(True)
    bps, cps = _prompts(1)
    prefixes = _prefixes(bps, cps)
    events = []
    eng.prefix_cache.add_listener(
        lambda ev, b, ids: events.append((ev, b, len(ids))))
    eng.prefill_insert(128, prefixes)
    n = len(eng.prefix_cache)
    assert events and events[0][0] == "insert"
    removed = eng.prefix_cache.forget_tail(128, prefixes[0], 2)
    assert removed == 2
    assert len(eng.prefix_cache) == n - 2
    assert [e for e in events if e[0] == "evict"]
    assert (eng.prefix_cache.pool.refcount >= 0).all()


# ---------------------------------------------------------------------------
# Migrated decode == colocated decode (dispatch_helpers
# .assert_paged_equals_cold: tokens exact, floats to the CPU's ulp bar;
# the int8 flavor compares paged with paged and stays bitwise)
# ---------------------------------------------------------------------------

def _migrated_vs_colocated(early_stop=False, params=PARAMS, cfg=CFG):
    bps, cps = _prompts(4, seed=3)
    prefixes = _prefixes(bps, cps)
    src = _engine(True, params=params, cfg=cfg)
    src.prefill_insert(128, prefixes)
    dst = _engine(True, pages=32, params=params, cfg=cfg)
    moved = _migrate_all(src, dst, 128, prefixes)
    assert moved > 0
    got = _shared(dst, bps, cps, True, early_stop=early_stop)
    assert dst.prefix_stats.hit_tokens > 0, "decode did not resume warm"
    ref = _engine(False, params=params, cfg=cfg)
    want = _shared(ref, bps, cps, False, early_stop=early_stop)
    for k in (0, 1):
        assert_paged_equals_cold(got[k], want[k])
    _assert_pins_released(dst)


def test_migrated_decode_bitwise_cold():
    """Decode resuming from migrated pages == the colocated unpaged
    run (the destination never prefilled this prefix)."""
    _migrated_vs_colocated()


def test_migrated_decode_bitwise_warm_repeat():
    """Second dispatch on the destination (fully warm, migrated pages
    now mixed with locally-inserted ones) stays equal."""
    bps, cps = _prompts(4, seed=5)
    prefixes = _prefixes(bps, cps)
    src = _engine(True)
    src.prefill_insert(128, prefixes)
    dst = _engine(True)
    _migrate_all(src, dst, 128, prefixes)
    first = _shared(dst, bps, cps, True)
    second = _shared(dst, bps, cps, True)
    ref = _engine(False)
    want = _shared(ref, bps, cps, False)
    for got in (first, second):
        for k in (0, 1):
            assert_paged_equals_cold(got[k], want[k])


def test_migrated_decode_bitwise_early_stop():
    _migrated_vs_colocated(early_stop=True)


def test_migrated_decode_bitwise_int8_kv():
    """int8-KV flavor: migrated-page decode == LOCAL-prefill paged
    decode, bitwise. The reference is the colocated PAGED engine (its
    own prefill_insert warmed it): int8 pages are payload+scale pairs
    and the warm window-recompute attends over their dequantized
    values, so paged-warm was never bitwise against the UNPAGED
    prefill (which attends over unquantized in-flight k/v) — that
    pre-existing quantization property is orthogonal to migration,
    whose contract is that migrated pages behave exactly like locally
    produced ones."""
    cfg_q = dataclasses.replace(CFG, kv_cache_int8=True)
    params_q = decoder.init_params(cfg_q, jax.random.PRNGKey(7))
    bps, cps = _prompts(4, seed=3)
    prefixes = _prefixes(bps, cps)
    src = _engine(True, params=params_q, cfg=cfg_q)
    src.prefill_insert(128, prefixes)
    dst = _engine(True, pages=32, params=params_q, cfg=cfg_q)
    assert _migrate_all(src, dst, 128, prefixes) > 0
    got = _shared(dst, bps, cps, True)
    assert dst.prefix_stats.hit_tokens > 0
    ref = _engine(True, params=params_q, cfg=cfg_q)
    ref.prefill_insert(128, prefixes)         # local prefill, same pages
    want = _shared(ref, bps, cps, True)
    assert ref.prefix_stats.hit_tokens > 0
    for k in (0, 1):
        assert_fused_bitwise(got[k], want[k])
    _assert_pins_released(dst)


# ---------------------------------------------------------------------------
# Router integration
# ---------------------------------------------------------------------------

_SERVE_CFG = ServeConfig(classes=(("t", 600.0),), default_class="t",
                         linger_s=0.002, cache_entries=0)


def _tiny_server(seed=2, batch=4):
    mcfg = ModelConfig(name="migrate-t", vocab_size=FakeTokenizer.VOCAB,
                       hidden_size=32, n_layers=1, n_heads=2,
                       intermediate_size=64, max_seq_len=256)
    params = decoder.init_params(mcfg, jax.random.PRNGKey(seed))
    rt = RuntimeConfig(batch_size=batch, max_seq_len=256)
    engine = ScoringEngine(params, mcfg, FakeTokenizer(), rt)
    return ScoringServer(engine, "migrate-t", _SERVE_CFG)


def _req(body, rid):
    return ServeRequest(
        binary_prompt=f"{body} Answer Yes or No .",
        confidence_prompt=f"{body} Give a number from 0 to 100 .",
        klass="t", request_id=rid)


def _trunk(seed, words=55):
    rng = np.random.default_rng(seed)
    vocab = ("coverage policy flood water damage claim insurer "
             "premium").split()
    return " ".join(rng.choice(vocab) for _ in range(words))


def test_page_op_queue_runs_on_supervisor_and_propagates_errors():
    server = _tiny_server().start()
    try:
        fut = server.submit_page_op(lambda eng: eng.prefix_cache.page_size)
        assert fut.result(30) == server.engine.prefix_cache.page_size

        def boom(eng):
            raise ValueError("page op boom")

        fut2 = server.submit_page_op(boom)
        with pytest.raises(ValueError, match="page op boom"):
            fut2.result(30)
    finally:
        server.stop()


def test_router_disagg_end_to_end_bitwise_and_decode_only():
    """1 prefill + 2 decode replicas: every request ok, scoring lands
    ONLY on decode replicas, pages migrate, payloads bitwise a
    colocated single server's."""
    reqs = [_req(f"{_trunk(9)} case {i}", str(i)) for i in range(5)]
    colo = _tiny_server().start()
    base = [colo.submit(r).result(120) for r in reqs]
    colo.stop()
    servers = [_tiny_server().start() for _ in range(3)]
    router = ReplicaRouter(
        [("pre", servers[0]), ("d0", servers[1]), ("d1", servers[2])],
        config=RouterConfig(cache_entries=0, tick_s=0.01),
        roles={"pre": "prefill", "d0": "decode", "d1": "decode"},
        migrate=MigrationConfig(min_prefix_tokens=16,
                                chunk_pages=2)).start()
    try:
        res = [router.submit(r).result(120) for r in reqs]
        assert all(r.status == "ok" for r in res)
        for got, want in zip(res, base):
            for f in PAYLOAD_FIELDS:
                assert getattr(got, f) == getattr(want, f), f
        assert router.migrate_stats.pages_migrated > 0
        assert router.migrate_stats.prefill_ops > 0
        assert router.stats.per_replica.get("pre", 0) == 0
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_migration_stall_falls_back_to_local_reprefill():
    """migration_stall past the chain deadline: the request resolves ok
    and bitwise via LOCAL re-prefill; stalls/fallbacks counted."""
    req = _req(f"{_trunk(13)} case 0", "s0")
    colo = _tiny_server().start()
    want = colo.submit(req).result(120)
    colo.stop()
    servers = [_tiny_server().start() for _ in range(3)]
    router = ReplicaRouter(
        [("pre", servers[0]), ("d0", servers[1]), ("d1", servers[2])],
        config=RouterConfig(cache_entries=0, tick_s=0.01),
        roles={"pre": "prefill", "d0": "decode", "d1": "decode"},
        migrate=MigrationConfig(min_prefix_tokens=16, chunk_pages=2,
                                timeout_s=0.3)).start()
    plan = faults.FaultPlan(seed=5, schedules={
        "migrate": faults.SiteSchedule.migration_stall_at(
            0, seconds=0.8)})
    faults.wrap_migrator(router.migrator, plan)
    try:
        got = router.submit(req).result(120)
        assert got.status == "ok"
        for f in PAYLOAD_FIELDS:
            assert getattr(got, f) == getattr(want, f), f
        assert plan.injected("migrate") == 1
        assert router.migrate_stats.refetch_fallbacks == 1
        assert router.migrate_stats.stalls >= 1
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_migration_corrupt_falls_back_to_local_reprefill():
    """migration_corrupt: checksum verify refuses the pages, the
    destination rolls back untouched, the request resolves ok and
    bitwise via local re-prefill."""
    req = _req(f"{_trunk(17)} case 0", "c0")
    colo = _tiny_server().start()
    want = colo.submit(req).result(120)
    colo.stop()
    servers = [_tiny_server().start() for _ in range(3)]
    router = ReplicaRouter(
        [("pre", servers[0]), ("d0", servers[1]), ("d1", servers[2])],
        config=RouterConfig(cache_entries=0, tick_s=0.01),
        roles={"pre": "prefill", "d0": "decode", "d1": "decode"},
        migrate=MigrationConfig(min_prefix_tokens=16, chunk_pages=2,
                                timeout_s=5.0)).start()
    plan = faults.FaultPlan(seed=6, schedules={
        "migrate": faults.SiteSchedule.migration_corrupt_at(0)})
    faults.wrap_migrator(router.migrator, plan)
    try:
        got = router.submit(req).result(120)
        assert got.status == "ok"
        for f in PAYLOAD_FIELDS:
            assert getattr(got, f) == getattr(want, f), f
        assert router.migrate_stats.corrupt_chunks == 1
        assert router.migrate_stats.refetch_fallbacks == 1
        for s in servers:
            assert (s.engine.prefix_cache.pool.refcount >= 0).all()
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_kill_mid_migration_recovers_on_survivor():
    """The SOURCE replica dying mid-chain fails the migration over:
    the request re-prefills locally on a survivor, resolves ok and
    bitwise, nothing dropped."""
    req = _req(f"{_trunk(21)} case 0", "k0")
    colo = _tiny_server().start()
    want = colo.submit(req).result(120)
    colo.stop()
    servers = [_tiny_server().start() for _ in range(3)]
    router = ReplicaRouter(
        [("pre", servers[0]), ("d0", servers[1]), ("d1", servers[2])],
        config=RouterConfig(cache_entries=0, tick_s=0.01),
        roles={"pre": "prefill", "d0": "decode", "d1": "decode"},
        migrate=MigrationConfig(min_prefix_tokens=16, chunk_pages=2,
                                timeout_s=5.0)).start()
    plan = faults.FaultPlan(seed=7, schedules={
        "migrate": faults.SiteSchedule.migration_stall_at(
            0, seconds=0.6)})
    faults.wrap_migrator(router.migrator, plan)
    try:
        fut = router.submit(req)
        router.kill_replica("pre")
        got = fut.result(120)
        assert got.status == "ok"
        for f in PAYLOAD_FIELDS:
            assert getattr(got, f) == getattr(want, f), f
        assert router.migrate_stats.refetch_fallbacks >= 1
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_transfer_buffers_ride_the_hbm_ledger():
    """Export/import staging registers `migrate_buf:<model>` bytes in
    the PR-14 HBM governor's ledger for the transfer's duration and
    unregisters after — a squeeze accounts for in-flight migrations
    next to the pool reservation."""
    from lir_tpu.config import GovernorConfig

    bps, cps = _prompts(1)
    prefixes = _prefixes(bps, cps)
    rt = RuntimeConfig(batch_size=4, max_seq_len=128,
                       aot_precompile=False, prefix_cache=True,
                       prefix_cache_pages=64)
    src = ScoringEngine(PARAMS, CFG, TOKZ, rt,
                        governor_config=GovernorConfig())
    src.prefill_insert(128, prefixes)
    seen = []
    real_register = src.governor.register

    def spy(name, nbytes):
        seen.append((name, nbytes))
        real_register(name, nbytes)

    src.governor.register = spy
    cfg = MigrationConfig(chunk_pages=2)
    e = mig.export_prefix(src, 128, prefixes[0], config=cfg)
    key = f"migrate_buf:{CFG.name}"
    assert any(n == key and b > 0 for n, b in seen)
    assert key not in src.governor.ledger()       # unregistered after
    dst = ScoringEngine(PARAMS, CFG, TOKZ, rt,
                        governor_config=GovernorConfig())
    seen_d = []
    real_d = dst.governor.register
    dst.governor.register = lambda n, b: (seen_d.append((n, b)),
                                          real_d(n, b))
    mig.import_prefix(dst, e, config=cfg)
    assert any(n == key and b > 0 for n, b in seen_d)
    assert key not in dst.governor.ledger()


def test_migration_stats_schema_mirror():
    """Every MigrationStats public field rides STATS_SCHEMA (and hence
    the metrics endpoint) — the metrics-drift contract, mirrored here
    so a drift fails next to the feature too."""
    import dataclasses as dc

    from lir_tpu.observe.registry import STATS_SCHEMA
    from lir_tpu.utils.profiling import MigrationStats

    fields = {f.name for f in dc.fields(MigrationStats)
              if not f.name.startswith("_")}
    assert fields == set(STATS_SCHEMA["MigrationStats"])
    s = MigrationStats()
    s.add_transfer(pages=3, nbytes=100, chunks=2, exposed_s=0.5,
                   hidden_s=0.2)
    s.count("refetch_fallbacks")
    summ = s.summary()
    assert summ["pages_migrated"] == 3 and summ["migrations"] == 1
    assert summ["refetch_fallbacks"] == 1
