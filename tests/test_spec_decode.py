"""Speculative scoring decode (engine/spec.py +
generate.greedy_decode_fused_shared_spec): acceptance edge cases pinned
against the sequential path.

The parity contract under test: every CONSUMED result — the emitted
token streams, position-0 probabilities, top-2 stream, top-20 logprob
map, weighted confidence, and hence every sweep row and serve payload —
is bitwise-identical to the sequential scan's, for ANY draft quality
(zero-accept, full-accept, ragged per-row accepts, stop conditions
inside the draft window, corrupted drafts). Interior per-step float
rows match within float tolerance (the verify window's longer cache
extent regroups reduction lanes — the same bar PR-7's fused-vs-dense
kernels cleared).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lir_tpu.backends.fake import FakeTokenizer
from lir_tpu.config import RuntimeConfig
from lir_tpu.engine import generate, scheduler as sched, spec as spec_mod
from lir_tpu.engine import tokens as tok
from lir_tpu.engine.runner import ScoringEngine
from lir_tpu.models import decoder, paged
from lir_tpu.models.registry import ModelConfig

from dispatch_helpers import fused_shared, fused_shared_spec, plan_specs

VOCAB = 256
CFG = ModelConfig(name="spec-tiny", vocab_size=VOCAB, hidden_size=32,
                  n_layers=1, n_heads=2, n_kv_heads=2,
                  intermediate_size=64, max_seq_len=512)
PARAMS = decoder.init_params(CFG, jax.random.PRNGKey(3))
TOKZ = FakeTokenizer(vocab=VOCAB)

CONSUMED_FIELDS = ("generated", "top2_ids", "topk_logprobs", "topk_ids",
                   "weighted_confidence")


def _assert_consumed_bitwise(spec_out, seq_out):
    """Every consumed readout bitwise; per-step floats to tolerance."""
    for f in CONSUMED_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(spec_out, f)),
            np.asarray(getattr(seq_out, f)), err_msg=f)
    for f in ("p_yes", "p_no"):
        a = np.asarray(getattr(spec_out, f))
        b = np.asarray(getattr(seq_out, f))
        np.testing.assert_array_equal(a[:, 0], b[:, 0],
                                      err_msg=f"{f}[pos0]")
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-7,
                                   err_msg=f)


# ---------------------------------------------------------------------------
# generate-level: controlled drafts straight into the spec executable
# ---------------------------------------------------------------------------

def _rows(seed=0, B=3, plen=24, sfx=4):
    rng = np.random.default_rng(seed)
    # Reserved low ids (pad etc.) excluded; distinct tokens so the
    # n-gram drafter has no accidental matches unless a test wants them.
    ids = rng.choice(np.arange(8, VOCAB), size=(B, plen + 2 * sfx),
                     replace=False if B * (plen + 2 * sfx) < VOCAB - 8
                     else True)
    prefixes = [list(map(int, ids[r, :plen])) for r in range(B)]
    sfx_a = [list(map(int, ids[r, plen:plen + sfx])) for r in range(B)]
    sfx_b = [list(map(int, ids[r, plen + sfx:])) for r in range(B)]
    return prefixes, sfx_a, sfx_b


def _shared_args(prefixes, sfx_a_ids, sfx_b_ids, bucket=32, sb=8):
    pad = 0
    prefix, prefix_mask = tok.right_pad_ids(prefixes, bucket, pad)
    sfx_a, sfx_a_mask = tok.right_pad_ids(sfx_a_ids, sb, pad)
    sfx_b, sfx_b_mask = tok.right_pad_ids(sfx_b_ids, sb, pad)
    B = len(prefixes)
    yes = np.full((B,), 7, np.int32)
    no = np.full((B,), 9, np.int32)
    digit_ids = np.arange(10, 16, dtype=np.int32)
    digit_vals = np.arange(6, dtype=np.float32) * 10.0
    return (jnp.asarray(prefix), jnp.asarray(prefix_mask),
            jnp.asarray(sfx_a), jnp.asarray(sfx_a_mask),
            jnp.asarray(sfx_b), jnp.asarray(sfx_b_mask),
            jnp.asarray(yes), jnp.asarray(no), jnp.asarray(digit_ids),
            jnp.asarray(digit_vals))


def _seq(args, Ta=4, Tb=8, **kw):
    return jax.device_get(fused_shared(
        PARAMS, CFG, *args, max_new_a=Ta, max_new_b=Tb, **kw))


def _spec_inputs(prefixes, sfx_a_ids, sfx_b_ids, Ta, Tb, bucket=32, sb=8,
                 draft_a=None, draft_b=None):
    B = len(prefixes)

    def ctx_of(sfx_ids, budget):
        rows = [p + s for p, s in zip(prefixes, sfx_ids)]
        width = bucket + sb + budget
        ctx = np.zeros((B, width), np.int32)
        lens = np.zeros((B,), np.int32)
        for r, row in enumerate(rows):
            ctx[r, :len(row)] = row
            lens[r] = len(row)
        return jnp.asarray(ctx), jnp.asarray(lens)

    def drafts(d, budget):
        toks = np.zeros((B, budget), np.int32)
        lens = np.zeros((B,), np.int32)
        if d is not None:
            for r, row in enumerate(d):
                n = min(len(row), budget)
                toks[r, :n] = row[:n]
                lens[r] = n
        return jnp.asarray(toks), jnp.asarray(lens)

    ca, cal = ctx_of(sfx_a_ids, Ta)
    cb, cbl = ctx_of(sfx_b_ids, Tb)
    da, dal = drafts(draft_a, Ta)
    db, dbl = drafts(draft_b, Tb)
    return (ca, cal, da, dal, cb, cbl, db, dbl)


def _spec(args, spec_inputs, Ta=4, Tb=8, k=4, **kw):
    out = fused_shared_spec(
        PARAMS, CFG, *args, *spec_inputs, max_new_a=Ta, max_new_b=Tb,
        spec_k=k, **kw)
    return jax.device_get(out)


def test_zero_accept_bitwise_and_forward_parity():
    """Deterministically-wrong tree drafts (sequential stream + 1): the
    verifier rejects everything, results stay bitwise, and the window
    scan runs exactly as many forwards as the sequential scan."""
    prefixes, sa, sb = _rows(seed=1)
    args = _shared_args(prefixes, sa, sb)
    seq_a, seq_b = _seq(args)
    wrong_a = (np.asarray(seq_a.generated) + 1) % VOCAB
    wrong_b = (np.asarray(seq_b.generated) + 1) % VOCAB
    si = _spec_inputs(prefixes, sa, sb, 4, 8, draft_a=wrong_a,
                      draft_b=wrong_b)
    out_a, out_b, sp_a, sp_b = _spec(args, si)
    _assert_consumed_bitwise(out_a, seq_a)
    _assert_consumed_bitwise(out_b, seq_b)
    for sp, T in ((sp_a, 4), (sp_b, 8)):
        assert int(np.sum(sp.accepted)) == 0
        assert int(sp.chunks) == int(sp.seq_steps) == T


def test_full_accept_bitwise_and_2x_fewer_forwards():
    """Perfect tree drafts (the sequential stream itself): every window
    accepts whole, the confidence scan retires in ceil(T/k) forwards —
    >= 2x fewer than sequential — and results stay bitwise."""
    prefixes, sa, sb = _rows(seed=2)
    args = _shared_args(prefixes, sa, sb)
    seq_a, seq_b = _seq(args)
    si = _spec_inputs(prefixes, sa, sb, 4, 8,
                      draft_a=np.asarray(seq_a.generated),
                      draft_b=np.asarray(seq_b.generated))
    out_a, out_b, sp_a, sp_b = _spec(args, si)
    _assert_consumed_bitwise(out_a, seq_a)
    _assert_consumed_bitwise(out_b, seq_b)
    assert int(np.sum(sp_b.accepted)) == int(np.sum(sp_b.drafted))
    assert int(sp_b.seq_steps) == 8
    assert int(sp_b.chunks) * 2 <= int(sp_b.seq_steps)
    assert int(sp_b.chunks) == 2           # ceil(8 / 4)
    # All accepted drafts came from the tree lane.
    assert int(sp_b.accepted[0]) == int(np.sum(sp_b.accepted))


def test_ragged_per_row_accept_lengths_in_one_batch():
    """Row 1 drafts garbage while rows 0/2 draft perfectly: per-row
    accept lengths diverge inside one window scan and every row's
    results still match the sequential batch bitwise."""
    prefixes, sa, sb = _rows(seed=3)
    args = _shared_args(prefixes, sa, sb)
    seq_a, seq_b = _seq(args)
    da = np.asarray(seq_a.generated).copy()
    db = np.asarray(seq_b.generated).copy()
    da[1] = (da[1] + 3) % VOCAB
    db[1] = (db[1] + 3) % VOCAB
    out_a, out_b, sp_a, sp_b = _spec(
        args, _spec_inputs(prefixes, sa, sb, 4, 8, draft_a=da, draft_b=db))
    _assert_consumed_bitwise(out_a, seq_a)
    _assert_consumed_bitwise(out_b, seq_b)
    # Mixed accepts: more than zero, fewer than everything.
    acc = int(np.sum(sp_b.accepted))
    assert 0 < acc < int(np.sum(sp_b.drafted))
    # The slow row gates the window scan: forwards land between the
    # full-accept floor and the sequential count.
    assert 2 <= int(sp_b.chunks) <= 8


def _eos_stop_case(digit_stop: bool):
    """Arm a stop rule chosen so it triggers INSIDE a draft window: run
    the unstopped sequential scan, pick the confidence branch's step-1
    emission of row 0 as eos/digit-terminator, then compare stopped
    sequential vs stopped speculative (perfect drafts) bitwise."""
    prefixes, sa, sb = _rows(seed=4)
    args = _shared_args(prefixes, sa, sb)
    free_a, free_b = _seq(args)
    eos_id = int(np.asarray(free_b.generated)[0, 1])
    cls = np.zeros((VOCAB,), np.int32)
    if digit_stop:
        # Step-0 emissions open a standalone digit run; anything
        # non-pure terminates it -> rows stop after their "integer".
        for t in np.asarray(free_b.generated)[:, 0]:
            cls[int(t)] = tok.STOP_PURE | tok.STOP_PREFIX | tok.STOP_ENDS_WORD
    stop = jnp.asarray(cls)
    kw = dict(stop_mask_a=stop, stop_mask_b=stop,
              eos_id=jnp.int32(eos_id))
    seq_a, seq_b = _seq(args, **kw)
    # Draft the STOPPED stream (what a warm tree would have recorded).
    out_a, out_b, sp_a, sp_b = _spec(
        args, _spec_inputs(prefixes, sa, sb, 4, 8,
                           draft_a=np.asarray(seq_a.generated),
                           draft_b=np.asarray(seq_b.generated)),
        **kw)
    _assert_consumed_bitwise(out_a, seq_a)
    _assert_consumed_bitwise(out_b, seq_b)
    # The stop actually engaged: EOS fill appears in the stream.
    gen = np.asarray(seq_b.generated)
    assert (gen[0] == eos_id).any()
    return sp_b


def test_eos_inside_draft_window_bitwise():
    sp = _eos_stop_case(digit_stop=False)
    # Early stop saves sequential forwards too; speculation must not
    # run more than the sequential scan.
    assert int(sp.chunks) <= int(sp.seq_steps) + 1


def test_digit_stop_inside_draft_window_bitwise():
    _eos_stop_case(digit_stop=True)


def test_spec_out_accounting_identity():
    prefixes, sa, sb = _rows(seed=5)
    args = _shared_args(prefixes, sa, sb)
    seq_a, seq_b = _seq(args)
    out = _spec(args, _spec_inputs(prefixes, sa, sb, 4, 8,
                                   draft_a=np.asarray(seq_a.generated),
                                   draft_b=np.asarray(seq_b.generated)))
    _, _, sp_a, sp_b = out
    from lir_tpu.utils.profiling import SpecStats

    st = SpecStats()
    for sp in (sp_a, sp_b):
        st.add_branch(sp.drafted, sp.accepted, int(sp.chunks),
                      int(sp.seq_steps))
    assert st.drafted_tokens == st.accepted_tokens + st.rejected_tokens
    assert st.dispatches_saved == st.seq_forwards - st.decode_forwards
    assert 0.0 < st.accept_rate <= 1.0


# ---------------------------------------------------------------------------
# engine-level: drafting sources, warm repeats, fleet, faults
# ---------------------------------------------------------------------------

def _engine(spec_on=True, prefix=False, k=4, **kw):
    rt = RuntimeConfig(batch_size=4, max_seq_len=256, spec_decode=spec_on,
                       spec_k=k, piggyback_prefill=False,
                       prefix_cache=prefix, prefix_cache_pages=64, **kw)
    return ScoringEngine(PARAMS, CFG, TOKZ, rt)


def _prompts(n=4, seed=11):
    rng = np.random.default_rng(seed)
    words = ("coverage policy flood water damage claim insurer premium "
             "exclusion endorsement peril deductible").split()
    base = " ".join(rng.choice(words) for _ in range(30))
    bps = [f"{base} case {i} Answer Yes or No ." for i in range(n)]
    cps = [f"{base} case {i} Give a number 0 to 100 ." for i in range(n)]
    return bps, cps


def _dispatch(eng, bps, cps):
    B = len(bps)
    yes = np.full((B,), eng.yes_id, np.int32)
    no = np.full((B,), eng.no_id, np.int32)
    return jax.device_get(eng.decode_fused_shared(
        bps, cps, yes, no, new_tokens=4, conf_tokens=8, reuse_cache=True))


def test_radix_miss_ngram_fallback_bitwise():
    """No prefix cache -> no tree: drafts come from the n-gram lane
    only, and engine-level consumed results stay bitwise vs OFF."""
    bps, cps = _prompts(seed=13)
    on = _engine(True, prefix=False)
    off = _engine(False, prefix=False)
    r_on = _dispatch(on, bps, cps)
    r_off = _dispatch(off, bps, cps)
    for k in (0, 1):
        _assert_consumed_bitwise(r_on[k], r_off[k])
    on.spec_flush()
    s = on.spec_stats
    assert s.spec_dispatches == 1
    assert s.draft_tree == 0
    assert s.draft_ngram > 0


def test_warm_repeat_tree_drafts_2x_fewer_dispatches():
    """The headline: an identical repeat dispatch on a warm tree drafts
    every row's whole reply and verifies it in >= 2x fewer forwards,
    results bitwise vs the sequential engine warm AND cold."""
    bps, cps = _prompts(seed=17)
    on = _engine(True, prefix=True)
    off = _engine(False, prefix=True)
    with on._tok_lock:
        bin_ids = [TOKZ(p).input_ids for p in bps]
        conf_ids = [TOKZ(p).input_ids for p in cps]
    lcp = [tok.shared_prefix_len(a, b) for a, b in zip(bin_ids, conf_ids)]
    bucket = tok.pick_bucket([max(n, 1) for n in lcp], on.buckets)

    r1 = _dispatch(on, bps, cps)
    on.spec_record(bucket, bin_ids, np.asarray(r1[0].generated), len(bps))
    on.spec_record(bucket, conf_ids, np.asarray(r1[1].generated), len(bps))
    on.spec_flush()
    fwd1 = on.spec_stats.decode_forwards
    r2 = _dispatch(on, bps, cps)
    on.spec_flush()
    s = on.spec_stats
    warm_fwd = s.decode_forwards - fwd1
    warm_seq = s.seq_forwards - fwd1
    assert s.accepted_tree > 0
    assert warm_seq >= 2 * warm_fwd, (warm_seq, warm_fwd)

    o1 = _dispatch(off, bps, cps)
    o2 = _dispatch(off, bps, cps)
    for k in (0, 1):
        _assert_consumed_bitwise(r1[k], o1[k])
        _assert_consumed_bitwise(r2[k], o2[k])


def test_fleet_draft_parity_with_self_draft_and_sequential():
    """A fleet draft model (any weights) only changes SPEED: results are
    bitwise the sequential path's and the self-draft path's, and the
    draft tokens count into the fleet lane. A perfect drafter (the
    verifier itself) accepts everything."""
    dcfg = dataclasses.replace(CFG, name="spec-draft", n_layers=1)
    dparams = decoder.init_params(dcfg, jax.random.PRNGKey(23))
    bps, cps = _prompts(seed=19)

    off = _engine(False)
    self_draft = _engine(True)
    fleet = _engine(True, spec_draft_model="drafty")
    fleet.set_spec_draft(dparams, dcfg, "drafty")
    r_off = _dispatch(off, bps, cps)
    r_self = _dispatch(self_draft, bps, cps)
    r_fleet = _dispatch(fleet, bps, cps)
    for k in (0, 1):
        _assert_consumed_bitwise(r_fleet[k], r_off[k])
        _assert_consumed_bitwise(r_self[k], r_off[k])
    fleet.spec_flush()
    assert fleet.spec_stats.draft_fleet > 0
    assert fleet.spec_stats.draft_ngram == 0

    perfect = _engine(True, spec_draft_model="self")
    perfect.set_spec_draft(PARAMS, CFG, "self")
    r_p = _dispatch(perfect, bps, cps)
    for k in (0, 1):
        _assert_consumed_bitwise(r_p[k], r_off[k])
    perfect.spec_flush()
    s = perfect.spec_stats
    assert s.accepted_fleet == s.draft_fleet > 0
    assert s.seq_forwards >= 2 * s.decode_forwards


def test_draft_model_vocab_mismatch_refused():
    bad = dataclasses.replace(CFG, vocab_size=VOCAB // 2)
    eng = _engine(True)
    with pytest.raises(ValueError, match="vocab"):
        eng.set_spec_draft(PARAMS, bad, "bad")


def test_draft_corrupt_fault_costs_only_reverification():
    """Seeded draft_corrupt: corrupted tree drafts are rejected by the
    verifier — results bitwise vs the uncorrupted warm dispatch, and
    the rejection counter records the injection."""
    from lir_tpu import faults

    bps, cps = _prompts(seed=29)

    def warm_engine():
        eng = _engine(True, prefix=True)
        with eng._tok_lock:
            bin_ids = [TOKZ(p).input_ids for p in bps]
            conf_ids = [TOKZ(p).input_ids for p in cps]
        lcp = [tok.shared_prefix_len(a, b)
               for a, b in zip(bin_ids, conf_ids)]
        bucket = tok.pick_bucket([max(n, 1) for n in lcp], eng.buckets)
        r1 = _dispatch(eng, bps, cps)
        eng.spec_record(bucket, bin_ids, np.asarray(r1[0].generated),
                        len(bps))
        eng.spec_record(bucket, conf_ids, np.asarray(r1[1].generated),
                        len(bps))
        return eng

    clean = warm_engine()
    r_clean = _dispatch(clean, bps, cps)
    clean.spec_flush()
    assert clean.spec_stats.accepted_tree > 0  # warm drafts DID land

    eng = warm_engine()
    plan = faults.FaultPlan(seed=5, schedules={
        "draft": faults.SiteSchedule.draft_corrupt_at(0, rows=(0, 1))})
    faults.wrap_engine(eng, plan)
    r_bad = _dispatch(eng, bps, cps)
    eng.spec_flush()
    assert plan.injected("draft") == 1
    assert eng.spec_stats.rejected_tokens > 0
    for k in (0, 1):
        _assert_consumed_bitwise(r_bad[k], r_clean[k])


def test_fused_interpret_mode_parity():
    """The Pallas multi-query verify kernel (flash_decode_mq) under the
    interpreter: consumed results match the sequential fused path — the
    CPU proof of the route that runs compiled on the chip."""
    fcfg = dataclasses.replace(CFG, fused_decode=True)
    prev = decoder.FUSED_DECODE_INTERPRET_ON_CPU
    decoder.FUSED_DECODE_INTERPRET_ON_CPU = True
    try:
        bps, cps = _prompts(n=3, seed=31)
        yes = np.full((3,), 7, np.int32)
        no = np.full((3,), 9, np.int32)

        def run(spec_on):
            rt = RuntimeConfig(batch_size=4, max_seq_len=256,
                               spec_decode=spec_on, spec_k=3,
                               piggyback_prefill=False, fused_decode=True)
            eng = ScoringEngine(PARAMS, fcfg, TOKZ, rt)
            return jax.device_get(eng.decode_fused_shared(
                bps, cps, yes, no, new_tokens=3, conf_tokens=4,
                reuse_cache=True))

        r_on = run(True)
        r_off = run(False)
        for k in (0, 1):
            _assert_consumed_bitwise(r_on[k], r_off[k])
    finally:
        decoder.FUSED_DECODE_INTERPRET_ON_CPU = prev


# ---------------------------------------------------------------------------
# the radix tree's token history (continuation / record_tail)
# ---------------------------------------------------------------------------

def _tree(pages=32, ps=4):
    pool = paged.KVPagePool(pages, ps)
    from lir_tpu.engine.prefix_tree import RadixPrefixCache

    return RadixPrefixCache(pool)


def test_continuation_replays_recorded_tail():
    tree = _tree()
    ids = list(range(20, 30))                       # 10 tokens, ps=4
    tree.record_tail(0, ids, [51, 52, 53])
    assert tree.continuation(0, ids, 8) == (51, 52, 53)
    assert tree.continuation(0, ids, 2) == (51, 52)
    # Different remainder -> no match; different bucket -> namespace miss.
    assert tree.continuation(0, ids[:-1], 8) == ()
    assert tree.continuation(1, ids, 8) == ()
    # Most-recent record wins for the same remainder.
    tree.record_tail(0, ids, [60, 61])
    assert tree.continuation(0, ids, 8) == (60, 61)


def test_continuation_descends_cached_page_keys():
    """A longer sequence cached as pages makes the tree itself predict
    the shorter prompt's continuation — no tail record needed."""
    tree = _tree()
    long_ids = list(range(40, 56))                  # 4 full pages
    start, pages = tree.plan_insert(0, long_ids)
    assert start == 0 and len(pages) == 4
    probe = long_ids[:6]                            # 1 page + 2 remainder
    cont = tree.continuation(0, probe, 6)
    assert cont == tuple(long_ids[6:12])
    # Page descent composes with a recorded tail at the deep node.
    tree.record_tail(0, long_ids, [91, 92])
    assert tree.continuation(0, long_ids, 4) == (91, 92)


def test_record_tail_caps_and_refusals():
    tree = _tree()
    ids = list(range(8))
    assert not tree.record_tail(0, ids, [])         # nothing to record
    assert not tree.record_tail(0, ids, [1] * 600)  # overlong refusal
    root_ids = list(range(8, 12))
    for i in range(40):                             # LRU cap per node
        tree.record_tail(0, root_ids + [100 + i], [i], max_tails=8)
    node = tree._root(0)
    assert len(node.tails) <= 8


def test_continuation_probe_takes_no_references():
    tree = _tree()
    ids = list(range(70, 82))
    tree.plan_insert(0, ids)
    before = list(tree.pool.refcount)
    tree.record_tail(0, ids, [5, 6])
    tree.continuation(0, ids, 4)
    assert list(tree.pool.refcount) == before


# ---------------------------------------------------------------------------
# pricing + planning satellites
# ---------------------------------------------------------------------------

def test_scheduler_spec_pricing_and_headroom():
    # Default (non-spec) pricing is byte-identical to the pre-spec model.
    assert sched.decode_token_cost(True) == sched.DECODE_TOKEN_COST_FUSED
    assert sched.decode_token_cost(False) == sched.DECODE_TOKEN_COST_UNFUSED
    assert sched.decode_token_cost(True, True) == sched.DECODE_TOKEN_COST_SPEC
    base = sched.bucket_cost(4, 128, 8, 12)
    assert base == sched.bucket_cost(4, 128, 8, 12, spec_decode=False)
    spec_cost = sched.bucket_cost(4, 128, 8, 12, spec_decode=True)
    assert spec_cost < base
    assert (base - spec_cost) == 4 * 12 * (
        sched.DECODE_TOKEN_COST_FUSED - sched.DECODE_TOKEN_COST_SPEC)
    # Widened watchdog seed for SPECULATING engines: a zero-accept
    # dispatch that degenerates to the UNFUSED sequential cost stays
    # inside a spec-calibrated seed; non-spec engines keep the original
    # fused/unfused spread (their scenarios' deadlines are unchanged).
    assert (sched.watchdog_seed_headroom(spec_decode=True)
            == sched.DECODE_TOKEN_COST_UNFUSED / sched.DECODE_TOKEN_COST_SPEC)
    assert (sched.watchdog_seed_headroom()
            == sched.DECODE_TOKEN_COST_UNFUSED
            / sched.DECODE_TOKEN_COST_FUSED)
    assert (sched.watchdog_seed_headroom(True) * sched.DECODE_TOKEN_COST_SPEC
            >= sched.DECODE_TOKEN_COST_UNFUSED)
    # The engine's own watchdog picks the spec-aware seed.
    assert (_engine(True).watchdog.seed_headroom
            == sched.watchdog_seed_headroom(True))
    assert (_engine(False).watchdog.seed_headroom
            == sched.watchdog_seed_headroom(False))


def test_plan_specs_covers_spec_variants_per_bucket_batch_k():
    from lir_tpu.engine import compile_plan
    from lir_tpu.utils.profiling import OccupancyStats

    planner = sched.RaggedScheduler(tok.bucket_ladder(256), 4,
                                    group_cells=False,
                                    stats=OccupancyStats())
    items = []
    rng = np.random.default_rng(0)
    for n in (30, 30, 30, 30, 60, 60, 60, 60):
        ids = [int(x) for x in rng.integers(8, VOCAB, size=n)]
        items.append(sched.SweepItem(cell=None, bin_ids=tuple(ids + [1]),
                                     conf_ids=tuple(ids + [2]),
                                     lcp=n))
    dispatches = planner.schedule(items)
    specs = plan_specs(_engine(True), dispatches, 4, 8, False)
    spec_specs = [s for s in specs if s.spec_k]
    assert spec_specs, "no speculative executables planned"
    assert all(s.spec_k == 4 and not s.spec_draft for s in spec_specs)
    # One spec variant per planned sequential shared shape.
    seq_shared = [s for s in specs if s.kind == "shared" and not s.spec_k]
    assert len(spec_specs) == len(seq_shared)


def test_spec_stats_in_metrics_registry():
    from lir_tpu.observe.registry import STATS_SCHEMA, engine_registry
    from lir_tpu.utils.profiling import SpecStats

    eng = _engine(True)
    snap = engine_registry(eng).snapshot()
    assert "spec" in snap["sources"]
    assert snap["sources"]["spec"]["type"] == "SpecStats"
    schema = set(STATS_SCHEMA["SpecStats"])
    public = {f.name for f in dataclasses.fields(SpecStats)
              if not f.name.startswith("_")}
    assert schema == public


# ---------------------------------------------------------------------------
# sweep-level: kill/resume with speculation ON folds bitwise (PR-9)
# ---------------------------------------------------------------------------

def test_kill_resume_with_spec_on_accum_bitwise(tmp_path):
    """A mid-sweep kill with speculation ON: the resumed run's streaming
    accumulator is bitwise an uninterrupted spec-ON run's — and that
    one is bitwise a spec-OFF run's (speculation is invisible to the
    PR-9 lattice)."""
    from pathlib import Path

    from lir_tpu import faults
    from lir_tpu.data.prompts import LegalPrompt
    from lir_tpu.engine import stream_stats as stream_mod
    from lir_tpu.engine.sweep import run_perturbation_sweep

    rng = np.random.default_rng(43)
    words = ("coverage policy flood water damage claim insurer "
             "premium exclusion peril").split()

    def text(n):
        return " ".join(rng.choice(words) for _ in range(n)) + " ?"

    lp = (LegalPrompt(main=text(8), response_format="Answer Yes or No .",
                      target_tokens=("Yes", "No"),
                      confidence_format="Give a number from 0 to 100 ."),)
    perts = ([text(8) for _ in range(7)],)

    def engine(spec_on):
        return _engine(spec_on)

    def accum(path):
        return stream_mod.load_accum(
            Path(path).with_suffix(stream_mod.ACCUM_SUFFIX))

    run_perturbation_sweep(engine(True), "spec", lp, perts,
                           tmp_path / "on.csv", checkpoint_every=4)
    run_perturbation_sweep(engine(False), "spec", lp, perts,
                           tmp_path / "off.csv", checkpoint_every=4)
    acc_on, acc_off = accum(tmp_path / "on.csv"), accum(tmp_path / "off.csv")
    for f in ("filled", "rel", "conf", "dec"):
        np.testing.assert_array_equal(getattr(acc_on, f),
                                      getattr(acc_off, f), err_msg=f)

    eng = engine(True)
    plan = faults.FaultPlan(seed=13, schedules={
        "dispatch": faults.SiteSchedule.kill_at(1)},
        stats=eng.fault_stats)
    faults.wrap_engine(eng, plan)
    out = tmp_path / "killed.csv"
    with pytest.raises(faults.InjectedPreemption):
        run_perturbation_sweep(eng, "spec", lp, perts, out,
                               checkpoint_every=4)
    run_perturbation_sweep(engine(True), "spec", lp, perts, out,
                           checkpoint_every=4)
    acc = accum(out)
    for f in ("filled", "rel", "conf", "dec"):
        np.testing.assert_array_equal(getattr(acc, f),
                                      getattr(acc_on, f), err_msg=f)
