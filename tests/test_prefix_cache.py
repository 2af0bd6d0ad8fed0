"""Cross-request radix prefix cache over the paged KV allocator.

Pins the PR's load-bearing claims:

- allocator invariants: page refcounts never go negative, the free list
  only holds unreferenced pages, eviction can never free a page an
  in-flight dispatch has pinned;
- radix semantics: page-granular insert/lookup/match, LRU eviction with
  parent cascade, per-bucket namespace isolation (KV is only
  bitwise-reproducible within one bucket shape);
- gather/scatter: a page written from a cache comes back bit-identical
  through the slot gather;
- the headline guarantee: paged decode results — shared, grouped, and
  the serve path — equal the contiguous-cache (unpaged) path's, cold
  and warm, including cross-length trunk reuse (the canonical
  right-padded slot == position layout is what makes a page produced
  under one row length valid for another): every token and decision
  exact, and on the CPU every float within the few ulps that a W-row
  window extension and an S-row prefill differ by there
  (dispatch_helpers.assert_paged_equals_cold names the first tensor
  that moves); paths that run the SAME program twice stay bitwise.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lir_tpu.backends.fake import FakeTokenizer
from lir_tpu.config import RuntimeConfig, ServeConfig
from lir_tpu.engine import prefix_tree, scheduler as sched
from lir_tpu.engine.runner import ScoringEngine
from lir_tpu.models import decoder, paged
from lir_tpu.models.registry import tiny

from dispatch_helpers import assert_paged_equals_cold, shared_paged_spec


FUSED_FIELDS = ("generated", "p_yes", "p_no", "top2_ids", "topk_logprobs",
                "topk_ids", "weighted_confidence")


def assert_fused_bitwise(a, b):
    for f in FUSED_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"fused field {f}")


# ---------------------------------------------------------------------------
# Allocator (models/paged.KVPagePool)
# ---------------------------------------------------------------------------

def test_pool_alloc_refcount_roundtrip():
    pool = paged.KVPagePool(8, page_size=4)
    assert pool.free_pages == 7          # page 0 reserved
    pages = [pool.alloc() for _ in range(7)]
    assert 0 not in pages and pool.alloc() is None
    pool.incref(pages)
    pool.decref(pages[:3])
    assert pool.free_pages == 3 and pool.pages_in_use == 4
    # freed pages are reallocatable; referenced ones are not in the list
    again = [pool.alloc() for _ in range(3)]
    assert sorted(again) == sorted(pages[:3])


def test_pool_decref_below_zero_is_a_crash():
    pool = paged.KVPagePool(4, page_size=4)
    p = pool.alloc()
    pool.incref([p])
    pool.decref([p])
    with pytest.raises(AssertionError):
        pool.decref([p])


def test_window_edges_and_pick():
    assert paged.window_edges(256, 16) == (16, 32, 64, 128)
    assert paged.pick_window(10, 256, 16) == 16
    assert paged.pick_window(100, 256, 16) == 128
    # a needed window >= bucket means nothing useful is cached
    assert paged.pick_window(200, 256, 16) is None
    assert paged.pick_window(1, 16, 16) is None


# ---------------------------------------------------------------------------
# Radix tree (engine/prefix_tree.RadixPrefixCache)
# ---------------------------------------------------------------------------

def _tree(n_pages=16, ps=4):
    return prefix_tree.RadixPrefixCache(paged.KVPagePool(n_pages, ps))


def test_radix_insert_lookup_match_roundtrip():
    t = _tree()
    ids = list(range(11))                 # 2 full pages + a 3-token tail
    start, pages = t.plan_insert(64, ids)
    assert start == 0 and len(pages) == 2
    assert t.match_len(64, ids) == 8      # the tail never caches
    m = t.lookup(64, ids)
    assert m.tokens == 8 and m.pages == tuple(pages)
    t.release(m)
    # extending the sequence caches only the NEW full page
    start2, pages2 = t.plan_insert(64, list(range(14)))
    assert start2 == 8 and len(pages2) == 1
    # an unrelated sequence shares nothing
    assert t.match_len(64, [99, 98, 97, 96]) == 0


def test_radix_partial_match_stops_at_divergence():
    t = _tree()
    a = list(range(12))
    b = list(range(8)) + [77, 78, 79, 80]
    t.plan_insert(64, a)
    assert t.match_len(64, b) == 8        # shares the first two pages
    start, fresh = t.plan_insert(64, b)
    assert start == 8 and len(fresh) == 1


def test_radix_per_bucket_namespaces_are_isolated():
    t = _tree()
    ids = list(range(8))
    t.plan_insert(64, ids)
    assert t.match_len(64, ids) == 8
    assert t.match_len(128, ids) == 0     # other bucket: other namespace
    t.plan_insert(128, ids)
    assert t.pool.pages_in_use == 4       # cached twice, once per bucket


def test_radix_lru_eviction_and_parent_cascade():
    t = _tree(n_pages=16, ps=4)
    old = list(range(8))
    t.plan_insert(64, old)
    new = [50 + i for i in range(8)]
    t.plan_insert(64, new)
    t.lookup(64, new).pages  # touch `new` so `old` is stalest
    freed = t.evict(1)
    assert freed >= 1
    assert t.match_len(64, old) < 8       # oldest leaf went first
    assert t.match_len(64, new) == 8
    # evicting everything evictable cascades leaf -> parent
    t.evict(100)
    assert t.match_len(64, old) == 0


def test_eviction_never_frees_inflight_pinned_pages():
    t = _tree(n_pages=6, ps=4)            # 5 usable pages
    ids = list(range(8))
    t.plan_insert(64, ids)
    m = t.lookup(64, ids)                 # dispatch pin
    assert t.evict(100) == 0              # everything pinned: nothing freed
    assert t.match_len(64, ids) == 8
    # filling the pool forces plan_insert to TRY evicting; pinned pages
    # survive and the insert degrades to a shorter cached prefix
    t.plan_insert(64, [90 + i for i in range(12)])
    assert t.match_len(64, ids) == 8
    t.release(m)
    assert t.evict(100) >= 1              # unpinned now


def test_release_then_evict_returns_page_to_free_list():
    t = _tree(n_pages=4, ps=4)            # 3 usable pages
    ids = list(range(4))
    t.plan_insert(64, ids)
    m = t.lookup(64, ids)
    # while the dispatch pins the page, the node is unevictable BY
    # CONSTRUCTION and the free list can never see the page
    assert t.evict(100) == 0
    assert t.match_len(64, ids) == 4
    free_before = t.pool.free_pages
    t.release(m)                          # drop the dispatch pin
    assert t.pool.free_pages == free_before   # tree still holds its ref
    assert t.evict(100) == 1              # now evictable: page goes free
    assert t.pool.free_pages == free_before + 1
    assert (t.pool.refcount >= 0).all()


# ---------------------------------------------------------------------------
# Gather / scatter
# ---------------------------------------------------------------------------

def test_scatter_then_gather_roundtrip_bitwise():
    cfg = tiny("llama")
    rng = np.random.default_rng(0)
    cache = decoder.init_cache(cfg, batch=2, max_len=32, dtype=jnp.float32)
    cache = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype), cache)
    pool = paged.KVPagePool(8, page_size=8)
    pool.ensure(cache)
    p1, p2 = pool.alloc(), pool.alloc()
    pool.incref([p1, p2])
    # page p1 <- row 0 slots [0, 8); page p2 <- row 1 slots [8, 16)
    pool.scatter(cache, [(p1, 0, 0), (p2, 1, 8)])
    slot_src = np.zeros((1, 16), np.int32)
    slot_src[0, :8] = p1 * 8 + np.arange(8)
    slot_src[0, 8:] = p2 * 8 + np.arange(8)
    out = paged.gather_slots(pool.leaves, jnp.asarray(slot_src))
    for o, c in zip(jax.tree.leaves(out), jax.tree.leaves(cache)):
        np.testing.assert_array_equal(np.asarray(o)[:, :, :8, 0],
                                      np.asarray(c)[:, :, :8, 0])
        np.testing.assert_array_equal(np.asarray(o)[:, :, 8:16, 0],
                                      np.asarray(c)[:, :, 8:16, 1])


# ---------------------------------------------------------------------------
# Price model
# ---------------------------------------------------------------------------

def test_bucket_cost_cached_tokens_discount_and_floor():
    base = sched.bucket_cost(4, 128, 4, 10)
    assert base == 4 * (128 + 10)
    assert sched.bucket_cost(4, 128, 4, 10, cached_tokens=100) == base - 100
    # the decode scan is the floor: cached prefill can never go negative
    assert sched.bucket_cost(4, 128, 4, 10, cached_tokens=10_000) == 4 * 10


# ---------------------------------------------------------------------------
# Engine: paged == unpaged, bitwise
# ---------------------------------------------------------------------------

CFG = tiny("llama")
PARAMS = decoder.init_params(CFG, jax.random.PRNGKey(1))
TOKZ = FakeTokenizer(vocab=CFG.vocab_size)


def _engine(prefix: bool, pages: int = 64, **kw):
    rt = RuntimeConfig(batch_size=4, max_seq_len=128, aot_precompile=False,
                       prefix_cache=prefix, prefix_cache_pages=pages, **kw)
    return ScoringEngine(PARAMS, CFG, TOKZ, rt)


def _legal_prompts(n, trunk_words=70, rng_seed=0):
    words = ("coverage policy flood water damage claim insurer premium "
             "exclusion endorsement peril deductible adjuster settle").split()
    rng = np.random.default_rng(rng_seed)
    base = " ".join(rng.choice(words) for _ in range(trunk_words))
    bps = [f"{base} case {i} Answer Yes or No ." for i in range(n)]
    cps = [f"{base} case {i} Give a number 0 to 100 ." for i in range(n)]
    return bps, cps


def _shared(engine, bps, cps, use):
    engine.fresh_handoff()
    yes = np.full((len(bps),), TOKZ.YES, np.int32)
    no = np.full((len(bps),), TOKZ.NO, np.int32)
    return engine.decode_fused_shared(
        bps, cps, yes, no, new_tokens=4, conf_tokens=6, early_stop=False,
        bucket=128, sfx_buckets_ab=(16, 16), reuse_cache=True,
        use_prefix_cache=use, n_real=len(bps))


def test_shared_paged_bitwise_cold_and_warm():
    bps, cps = _legal_prompts(4)
    ref = _engine(False)
    eng = _engine(True)
    r_ref = _shared(ref, bps, cps, False)
    r_cold = _shared(eng, bps, cps, True)     # cold: unpaged + insert
    assert eng.prefix_stats.inserted_pages > 0
    assert eng.prefix_stats.hit_tokens == 0
    r_warm = _shared(eng, bps, cps, True)     # warm: paged resume
    assert eng.prefix_stats.hit_tokens > 0
    for got in (r_cold, r_warm):
        for k in (0, 1):
            assert_paged_equals_cold(got[k], r_ref[k])
    assert (eng.prefix_cache.pool.refcount >= 0).all()
    # all dispatch pins released: only the tree's own references remain
    in_use = eng.prefix_cache.pool.pages_in_use
    assert (eng.prefix_cache.pool.refcount[1:].sum() == in_use)


def test_shared_paged_cross_length_trunk_reuse_bitwise():
    """Rows of DIFFERENT prefix lengths sharing one trunk reuse pages
    within a bucket namespace (the canonical slot == position layout's
    raison d'être): warming the 72-token rows caches the trunk, then
    both LONGER rows extending the same trunk and SHORTER rows that are
    a pure truncation of it resume the cached pages, paying prefill
    only for their unshared tails — the remainder window anchors at the
    dispatch's longest real row, so short rows never force a
    bucket-wide recompute."""
    bps, cps = _legal_prompts(4, trunk_words=70)
    tail = ("under the flood exclusion endorsement riders and the "
            "binding arbitration clause")
    long_b = [b.replace(" Answer", f" {tail} Answer") for b in bps]
    long_c = [c.replace(" Give", f" {tail} Give") for c in cps]
    ref = _engine(False)
    eng = _engine(True)
    _shared(eng, bps, cps, True)              # warm the trunk pages
    stats_before = eng.prefix_stats.hit_tokens
    r_ref = _shared(ref, long_b, long_c, False)
    r_warm = _shared(eng, long_b, long_c, True)
    assert eng.prefix_stats.hit_tokens > stats_before
    for k in (0, 1):
        assert_paged_equals_cold(r_warm[k], r_ref[k])
    # 40-word rows whose WHOLE prefix is the warm trunk's first half:
    # the max-row-anchored window reaches their tails, so they resume
    # the trunk pages too (with the old bucket-end anchor these could
    # only fall back to the unpaged prefill).
    short_b = [" ".join(bps[0].split()[:40]) + " Answer Yes or No ."]
    short_c = [" ".join(bps[0].split()[:40]) + " Give a number 0 to 100 ."]
    stats_mid = eng.prefix_stats.hit_tokens
    r_ref_s = _shared(ref, short_b * 4, short_c * 4, False)
    r_s = _shared(eng, short_b * 4, short_c * 4, True)
    assert eng.prefix_stats.hit_tokens > stats_mid
    for k in (0, 1):
        assert_paged_equals_cold(r_s[k], r_ref_s[k])


def test_shared_paged_bitwise_with_early_stop():
    bps, cps = _legal_prompts(4)
    ref = _engine(False)
    eng = _engine(True)

    def call(engine, use):
        engine.fresh_handoff()
        yes = np.full((4,), TOKZ.YES, np.int32)
        no = np.full((4,), TOKZ.NO, np.int32)
        return engine.decode_fused_shared(
            bps, cps, yes, no, new_tokens=4, conf_tokens=6,
            early_stop=True, bucket=128, sfx_buckets_ab=(16, 16),
            reuse_cache=True, use_prefix_cache=use, n_real=4)

    r_ref = call(ref, False)
    call(eng, True)
    r_warm = call(eng, True)
    for k in (0, 1):
        assert_paged_equals_cold(r_warm[k], r_ref[k])


def _groups(n_groups=2, per=2, plen_words=40, seed=5):
    words = ("levee breach flood policy water claim exclusion peril "
             "statute meaning binding interpret").split()
    rng = np.random.default_rng(seed)
    groups = []
    for g in range(n_groups):
        base = [int(TOKZ(w).input_ids[0]) for w in
                rng.choice(words, plen_words)]
        items = []
        for i in range(per):
            sfx = rng.integers(3, CFG.vocab_size, 4).tolist()
            items.append(sched.SweepItem(
                cell=None, bin_ids=tuple(base + sfx + [7]),
                conf_ids=tuple(base + sfx + [9, 11]),
                lcp=plen_words + 4))
        groups.append(sched.PrefixGroup(items=tuple(items),
                                        plen=plen_words))
    return groups


def test_grouped_paged_bitwise_cold_and_warm():
    groups = _groups()
    n = sum(len(g.items) for g in groups)
    yes = np.full((n,), TOKZ.YES, np.int32)
    no = np.full((n,), TOKZ.NO, np.int32)
    ref = _engine(False)
    eng = _engine(True)

    def call(engine, use):
        engine.fresh_handoff()
        out, m = engine.decode_fused_grouped(
            groups, yes, no, new_tokens=4, conf_tokens=6,
            early_stop=False, bucket=64, sfx_bucket=8, reuse_cache=True,
            use_prefix_cache=use)
        return out

    r_ref = call(ref, False)
    r_cold = call(eng, True)
    r_warm = call(eng, True)
    assert eng.prefix_stats.hit_tokens > 0
    assert_paged_equals_cold(r_cold, r_ref)
    assert_paged_equals_cold(r_warm, r_ref)


def test_aot_paged_executable_matches_lazy_bitwise():
    """The block-table (paged) executables the compile plan precompiles
    bind (pool, slot_src, win_start, ...) in exactly the order the
    runner passes them: a warm dispatch must HIT the registry (no lazy
    fallback) and return results bitwise-identical to the lazy-jit
    paged path."""
    from lir_tpu.engine import compile_plan

    bps, cps = _legal_prompts(4)
    eng_lazy = _engine(True, spec_decode=False)
    _shared(eng_lazy, bps, cps, True)
    r_lazy = _shared(eng_lazy, bps, cps, True)

    # Pin the SEQUENTIAL paged executables specifically — speculative
    # dispatches look up their own spec_k-keyed registry entries
    # (tests/test_spec_decode.py covers those).
    eng = _engine(True, spec_decode=False)
    _shared(eng, bps, cps, True)              # warm the radix cache
    specs = [shared_paged_spec(128, 4, w, 16, 16, 4, 6,
                                            stops_armed=False,
                                            scratch=False)
             for w in paged.window_edges(128, 16)]
    reg = compile_plan.precompile_async(eng, specs, max_workers=2)
    reg.wait()
    eng.exec_registry = reg
    aot_before = eng.compile_stats.aot_hits
    r_aot = _shared(eng, bps, cps, True)
    assert eng.compile_stats.aot_hits == aot_before + 1
    for k in (0, 1):
        assert_fused_bitwise(r_aot[k], r_lazy[k])


def test_tight_pool_evicts_but_never_corrupts():
    """A pool far smaller than the working set churns through eviction;
    results stay bitwise-identical and refcounts sane."""
    ref = _engine(False)
    eng = _engine(True, pages=6)              # 5 usable pages, ~1 row's worth
    for seed in range(3):
        bps, cps = _legal_prompts(4, rng_seed=seed)
        r_ref = _shared(ref, bps, cps, False)
        r_paged = _shared(eng, bps, cps, True)
        for k in (0, 1):
            assert_fused_bitwise(r_paged[k], r_ref[k])
        assert (eng.prefix_cache.pool.refcount >= 0).all()
    assert eng.prefix_stats.evicted_pages > 0 or \
        eng.prefix_stats.inserted_pages <= 5


# ---------------------------------------------------------------------------
# Serve path
# ---------------------------------------------------------------------------

def _serve_once(prefix: bool, reqs):
    from lir_tpu.serve import ScoringServer, ServeRequest

    engine = _engine(prefix)
    cfgs = ServeConfig(queue_depth=64, prefix_cache=prefix,
                       classes=(("bench", 120.0),), default_class="bench")
    payloads = []
    for _ in range(2):                        # pass 2 is the warm pass
        server = ScoringServer(engine, "prefix-test", cfgs).start()
        futs = [server.submit(ServeRequest(
            binary_prompt=b, confidence_prompt=c, klass="bench",
            request_id=str(i))) for i, (b, c) in enumerate(reqs)]
        payloads = [f.result(timeout=120) for f in futs]
        server.stop()
    return engine, payloads


@pytest.mark.slow
def test_serve_prefix_cache_bitwise_and_counts():
    bps, cps = _legal_prompts(6)
    reqs = list(zip(bps, cps))
    eng_off, base = _serve_once(False, reqs)
    eng_on, warm = _serve_once(True, reqs)
    assert eng_on.prefix_stats.hit_tokens > 0
    assert eng_off.prefix_cache is None
    fields = ("status", "token_1_prob", "token_2_prob",
              "log_probabilities", "confidence_value",
              "weighted_confidence", "model_response",
              "model_confidence_response")
    for a, b in zip(base, warm):
        for f in fields:
            assert getattr(a, f, None) == getattr(b, f, None), f


def test_fake_tokenizer_vocab_clamp():
    t = FakeTokenizer(vocab=256)
    ids = t("flood levee coverage exclusion peril deductible").input_ids
    assert max(ids) < 256
    # default keeps the historical 1000-id behavior
    assert FakeTokenizer().VOCAB == 1000
    with pytest.raises(ValueError):
        FakeTokenizer(vocab=2)
