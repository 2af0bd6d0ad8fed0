"""The trunk as a value (ISSUE 32): a trunk that is at least half of
what a dispatch would prefill is prefilled by a program of its own
(generate.greedy_decode_trunk), held by the engine for as long as
consecutive dispatches start with it, and handed to each as an argument
(the ``"cascade_held"`` front).

What is pinned, at tiny sizes on the CPU: the dispatch behind the trunk
program answers as the dispatch that prefills the trunk itself, and at
ONE row as the dense one-row program, for every family that has a
cascade front; the plan of a doc16k-shaped call (one trunk program a
prompt, a held route for every dispatch behind it, the dense program for
a lone original) and of a trunk512-shaped one (nothing held, the plan
the cascade front always had); the held trunk's lifetime; its counters
and span.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dispatch_helpers import (FUSED_FIELDS, fused_shared, shared_cascade_spec,
                              shared_spec)

from lir_tpu.engine import compile_plan, generate
from lir_tpu.models import decoder, registry

FAMILIES = ("dense", "hybrid", "sala")


def _family(name, vocab=256):
    """(cfg, params, vocab) of a tiny model of the family, float32
    weights, at ``vocab`` where the family's tests let it be chosen."""
    if name == "dense":
        cfg = dataclasses.replace(registry.tiny("mistral"), vocab_size=vocab)
        return cfg, decoder.init_params(cfg, jax.random.PRNGKey(0),
                                        dtype=jnp.float32), vocab
    if name == "hybrid":
        import test_hybrid_model as hy

        spec = hy._spec()
        return hy._cfg(spec), hy._params(spec), hy.VOCAB
    import test_sala_model as sa

    spec = dataclasses.replace(sa._tiny("lightning-first"), window=96,
                               vocab=vocab)
    return sa._model(spec) + (vocab,)


def _inputs(rng, vocab, B, S, S2, trunk):
    """Right-padded prefixes that share their first ``trunk`` tokens, two
    format suffixes a row, target ids, a digit table."""
    lens = rng.integers(trunk + 2, S + 1, B)
    lens[0] = S
    prefix = rng.integers(3, vocab, (B, S))
    prefix[:, :trunk] = prefix[0, :trunk]
    pm = (np.arange(S)[None] < lens[:, None]).astype(np.int32)

    def sfx():
        n = rng.integers(2, S2 + 1, B)
        m = (np.arange(S2)[None] < n[:, None]).astype(np.int32)
        return rng.integers(3, vocab, (B, S2)) * m, m

    (sa, sam), (sb, sbm) = sfx(), sfx()
    ids = rng.integers(3, vocab, (2, B)).astype(np.int32)
    args = [jnp.asarray(v, jnp.int32)
            for v in (prefix * pm, pm, sa, sam, sb, sbm, ids[0], ids[1])]
    return args + [jnp.arange(3, 13, dtype=jnp.int32),
                   jnp.arange(10, dtype=jnp.float32)]


@pytest.mark.parametrize("rows", [3, 1], ids=["rows", "one-row"])
@pytest.mark.parametrize("family", FAMILIES)
def test_a_dispatch_behind_the_trunk_program_answers_as_the_one_that_prefills(
        family, rows, monkeypatch):
    """2 + rows: every output of the ``"cascade_held"`` dispatch fed by
    the trunk program equals the ``"cascade"`` dispatch's (the same trunk
    pass at one row, the same cascade_extend: one program cut in two).
    ONE row: the odd row of a group, which today runs the dense program
    over its whole prefix; behind the held trunk it runs its window
    only, and answers the same."""
    monkeypatch.setattr(decoder, "CASCADE_INTERPRET_ON_CPU", True)
    cfg, params, vocab = _family(family)
    S, S2, new, trunk = 80, 6, 3, 64
    args = _inputs(np.random.default_rng(11 + rows), vocab, rows, S, S2,
                   trunk)
    kw = dict(max_new_a=new, max_new_b=new, topk=5)
    with jax.default_matmul_precision("highest"):
        held_trunk = generate.greedy_decode_trunk(params, cfg,
                                                  args[0][:1, :trunk])
        _, want_trunk, _ = decoder.prefill(
            params, cfg, args[0][:1, :trunk],
            jnp.ones((1, trunk), jnp.int32), trunk)
        held = fused_shared(params, cfg, *args, trunk_len=trunk,
                            trunk_cache=held_trunk, return_cache=True, **kw)
        if rows > 1:
            want = fused_shared(params, cfg, *args, trunk_len=trunk,
                                return_cache=True, **kw)
        else:
            want = fused_shared(params, cfg, *args, return_cache=True, **kw)
    # The trunk program returns what prefill returns inside the front
    # (jitted against op by op: to the last bits, not bitwise, on a CPU).
    for got, ref in zip(jax.tree.leaves(held_trunk),
                        jax.tree.leaves(want_trunk)):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
    for h, w in zip(held[:2], want[:2]):
        for f in FUSED_FIELDS:
            a, b = np.asarray(getattr(h, f)), np.asarray(getattr(w, f))
            if a.dtype.kind in "iub":
                np.testing.assert_array_equal(a, b, err_msg=f)
            else:
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                           err_msg=f)
    if rows > 1:
        # One program cut in two: the caches it hands on are the same
        # leaves (a model whose layers differ in kind keeps the trunk at
        # ONE row in them: models/mixed.py).
        for a, b in zip(jax.tree.leaves(held[2]), jax.tree.leaves(want[2])):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-5, atol=1e-5)


# A recurrent state rides neither a grouped batch nor a speculative tail
# (decoder.refuse_recurrent; generate.greedy_decode_dispatch).
AVAL_CASES = [(family, front) for family in FAMILIES
              for front in ("prefill", "cascade", "cascade_held")] + [
                  ("dense", "grouped"), ("dense", "speculative")]


@pytest.mark.parametrize("family,front", AVAL_CASES)
def test_the_cache_avals_a_plan_lowers_over_are_the_programs_own(
        family, front, monkeypatch):
    """``generate.dispatch_cache_avals`` traces the front alone; what it
    says the program returns (what a donated variant is lowered over) is
    what the whole program returns, for every front, layout and tail."""
    monkeypatch.setattr(decoder, "CASCADE_INTERPRET_ON_CPU", True)
    cfg, params, vocab = _family(family)
    B, S, S2, trunk = 4, 80, 6, 64
    a = _inputs(np.random.default_rng(1), vocab, B, S, S2, trunk)
    kw = dict(front=front, max_new=(3, 3), topk=5, return_cache=True)
    more = {}
    if front.startswith("cascade"):
        kw.update(trunk=trunk)
    if front == "cascade_held":
        more["trunk_cache"] = generate.greedy_decode_trunk.eval_shape(
            params, cfg, a[0][:1, :trunk])
    if front == "grouped":
        kw.update(front="prefill", layout="grouped", max_new=(3,))
        more["group_idx"] = jnp.asarray([0, 0, 1, 1, 2, 3, 3, 3], jnp.int32)
        a = [a[0], a[1]] + [jnp.concatenate([x, x]) for x in a[2:8]] + a[8:]
    if front == "speculative":
        kw.update(front="prefill", spec_k=3)
        i32 = lambda *sh: jnp.zeros(sh, jnp.int32)  # noqa: E731
        more["drafts"] = generate.Drafts(
            ctx=(i32(B, S + S2 + 3),) * 2, ctx_len=(i32(B),) * 2,
            tokens=(i32(B, 3),) * 2, lens=(i32(B),) * 2)
    program = generate.Program(**kw)
    n = 1 if front == "grouped" else 2
    args = generate.DispatchArgs(
        prefix=a[0], prefix_mask=a[1], sfx=(a[2], a[4])[:n],
        sfx_mask=(a[3], a[5])[:n], yes_ids=a[6], no_ids=a[7],
        digit_ids=a[8], digit_vals=a[9], **more)
    want = generate.greedy_decode_dispatch.eval_shape(
        params, cfg, program, args)[2]
    got = generate.dispatch_cache_avals(params, cfg, program, args)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------

def _engine(family, **rt):
    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.engine.runner import ScoringEngine

    cfg, params, _ = _family(family, FakeTokenizer.VOCAB)
    return ScoringEngine(params, cfg, FakeTokenizer(),
                         RuntimeConfig(**{"batch_size": 4,
                                          "max_seq_len": 256, **rt}))


def _item(ids):
    from lir_tpu.engine import scheduler as sched

    ids = tuple(int(i) for i in ids)
    return sched.SweepItem(cell=None, bin_ids=ids + (1, 5),
                           conf_ids=ids + (2, 6), lcp=len(ids))


def _schedule(engine, rows, new=4, conf=8):
    from lir_tpu.engine import scheduler as sched

    return sched.RaggedScheduler(
        engine.buckets, engine.rt.batch_size, new_budget=max(new, conf),
        decode_cost=new + conf, group_cells=False,
        token_cap=engine.rt.dispatch_tokens).schedule(rows)


def test_a_doc16k_shaped_call_plans_one_trunk_program_a_prompt(monkeypatch):
    """Original + 2 groups + the odd row, then a lone original of another
    document: ONE trunk program for the first prompt, a held route for
    each dispatch behind it (the odd row's too: one row, no 160-token
    pass), the dense one-row program for the lone original; the compile
    plan lists exactly these."""
    monkeypatch.setattr(decoder, "CASCADE_INTERPRET_ON_CPU", True)
    engine = _engine("sala", dispatch_tokens=384, sweep_group_min_cells=0)
    doc_a, doc_b = list(range(10, 170)), list(range(300, 460))
    rows = ([_item(doc_a + [7] * 9)]
            + [_item(doc_a + [8 + i] * 12) for i in range(8)]
            + [_item(doc_b + [7] * 9)])
    plan = _schedule(engine, rows)
    assert [len(d.items) for d in plan] == [4, 4, 1, 1]
    routes = engine.route_plan(plan, 4, 8, False)
    assert [(r.trunk, r.held, r.trunk_run) for r in routes] == [
        (160, True, True), (160, True, False), (160, True, False),
        (0, False, False)]
    assert routes[2].shape.batch == 1 and routes[2].held_ids == tuple(doc_a)
    # The lone original changes nothing about what the engine holds.
    assert routes[3].held_ids == tuple(doc_a)
    edge, (sa, sb) = plan[0].edge, (plan[0].sfx_bucket_a,
                                    plan[0].sfx_bucket_b)
    assert 160 >= 4 * (edge - 160)             # the rule, on this traffic
    specs = compile_plan.plan_specs(plan, routes)
    held4 = shared_cascade_spec(edge, 4, 160, sa, sb, 4, 8, False, False)
    held4 = dataclasses.replace(held4, held=True)
    assert specs == [
        compile_plan.trunk_spec(160), held4,
        dataclasses.replace(held4, scratch=True),
        dataclasses.replace(held4, batch=1),
        shared_spec(edge, 1, sa, sb, 4, 8, False, False)]
    assert [s.label for s in specs[:2]] == [
        "trunk/t160x1",
        f"shared/b{edge}x4/sfx{sa}+{sb}/new4-8/trunk160+held/fresh"]
    # What a dispatch finds at run time is what the plan carried: the
    # same route from the engine's held trunk.
    assert engine.held_trunk is None
    again = engine.route_dispatch(plan[2], 4, 8, False, held=tuple(doc_a))
    assert again == routes[2]
    # An odd row with nothing held (a fault dropped the trunk) is the
    # dense one-row program, as it was before.
    alone = engine.route_dispatch(plan[2], 4, 8, False)
    assert (alone.trunk, alone.held) == (0, False)


def test_a_trunk512_shaped_call_holds_nothing_and_plans_what_it_did(
        monkeypatch):
    """A 32-token head on ~100-token rows at batch 4 is under half of
    what the dispatch prefills: the in-program cascade front, and
    ``Route.planned`` the list it always was (the dense program and its
    speculative sibling, then the cascade program)."""
    monkeypatch.setattr(decoder, "CASCADE_INTERPRET_ON_CPU", True)
    engine = _engine("dense")
    rng = np.random.default_rng(2)
    head = list(rng.integers(3, 200, 32))
    rows = [_item(head + list(rng.integers(3, 200, 70 - (i % 3))))
            for i in range(8)]
    plan = _schedule(engine, rows)
    routes = engine.route_plan(plan, 4, 8, False)
    assert [len(d.items) for d in plan] == [4, 4]
    for r in routes:
        assert (r.trunk, r.held, r.trunk_run, r.held_ids) == (32, False,
                                                              False, None)
    d = plan[0]
    rest = (d.sfx_bucket_a, d.sfx_bucket_b, 4, 8, False)
    k = engine.rt.spec_k
    assert engine.spec_supported() and k >= 2
    for scratch in (False, True):
        assert routes[0].planned(scratch) == [
            shared_spec(d.edge, 4, *rest, scratch),
            shared_spec(d.edge, 4, *rest, scratch, spec_k=k),
            shared_cascade_spec(d.edge, 4, 32, *rest, scratch)]
    specs = compile_plan.plan_specs(plan, routes)
    assert not [s for s in specs if s.held or s.kind == "trunk"]
    # Across a device mesh nothing lays a held trunk out: the rule does
    # not hold one however long it is.
    long_rows = [list(head) * 3 + [9 + i] for i in range(2)]
    held = engine.route("shared", 128, 2, 0, 8, 8, 4, 8, False, long_rows, 2)
    assert held.held and held.trunk == 96
    monkeypatch.setattr("lir_tpu.engine.runner._params_span", lambda p: 4)
    meshed = engine.route("shared", 128, 2, 0, 8, 8, 4, 8, False, long_rows,
                          2)
    assert (meshed.trunk, meshed.held) == (96, False)


# ---------------------------------------------------------------------------
# Lifetime, counters, span
# ---------------------------------------------------------------------------

def _rows_of(head, n, seed):
    rng = np.random.default_rng(seed)
    return [list(head) + [int(x) for x in rng.integers(3, 200, 6 + r % 3)]
            for r in range(n)]


def _dispatch(engine, rows):
    n = len(rows)
    t1 = np.asarray([5] * n, np.int32)
    t2 = np.asarray([9] * n, np.int32)
    return engine.decode_fused_shared(
        [""] * n, [""] * n, t1, t2, new_tokens=3, conf_tokens=4,
        pretokenized_a=[r + [5, 6] for r in rows],
        pretokenized_b=[r + [7, 8] for r in rows], bucket=64,
        sfx_buckets_ab=(8, 8), reuse_cache=True, n_real=n)


def test_one_trunk_is_held_and_let_go_when_it_should_be(monkeypatch):
    from lir_tpu.config import GovernorConfig, RuntimeConfig
    from lir_tpu.engine import hbm
    from lir_tpu.engine.sweep import _dispatch_with_recovery
    from lir_tpu.observe import registry as metrics_mod

    monkeypatch.setattr(decoder, "CASCADE_INTERPRET_ON_CPU", True)
    engine = _engine("dense")
    engine.governor = hbm.HbmGovernor(GovernorConfig(enabled=True),
                                      budget_bytes=1 << 30)
    stats = engine.cascade_stats
    alive = []                    # what was held whenever a trunk was run
    real = generate.greedy_decode_trunk
    monkeypatch.setattr(generate, "greedy_decode_trunk",
                        lambda *a, **k: (alive.append(engine.held_trunk),
                                         real(*a, **k))[1])
    head_x = [int(x) for x in np.random.default_rng(0).integers(3, 200, 48)]
    head_y = [int(x) for x in np.random.default_rng(1).integers(3, 200, 48)]
    ledger = f"trunk:{engine.cfg.name}"

    # The first dispatch has the trunk run for it; the second finds it.
    first = _dispatch(engine, _rows_of(head_x, 2, 0))
    assert engine.held_trunk == tuple(head_x)
    assert (stats.trunk_programs, stats.trunk_held_dispatches,
            stats.trunk_rows_deduped) == (1, 1, 1)
    assert engine.governor.ledger()[ledger] > 0
    _dispatch(engine, _rows_of(head_x, 2, 1))
    assert (stats.trunk_programs, stats.trunk_held_dispatches,
            stats.trunk_rows_deduped) == (1, 2, 3)
    # ... and so does ONE row that starts with it, which answers as the
    # dense one-row program does on an engine that holds nothing.
    odd = _rows_of(head_x, 1, 2)
    got = _dispatch(engine, odd)
    assert (stats.trunk_programs, stats.trunk_held_dispatches,
            stats.trunk_rows_deduped, stats.dense_fallbacks) == (1, 3, 4, 0)
    assert stats.trunk_tokens_prefilled == 48
    plain = _engine("dense", cascade_prefill=False)
    for g, w in zip(got, _dispatch(plain, odd)):
        assert (np.asarray(g.generated) == np.asarray(w.generated)).all()
        np.testing.assert_allclose(np.asarray(g.topk_logprobs),
                                   np.asarray(w.topk_logprobs), atol=5e-5)
    # One row of another document: dense, and the held trunk stays.
    _dispatch(engine, _rows_of(head_y, 1, 3))
    assert stats.dense_fallbacks == 1 and engine.held_trunk == tuple(head_x)
    # A different trunk: the old one is let go BEFORE the new one is run.
    _dispatch(engine, _rows_of(head_y, 2, 4))
    assert engine.held_trunk == tuple(head_y) and stats.trunk_programs == 2
    assert alive == [None, None]               # never two alive
    # fresh_handoff (the start of every sweep call) drops it, and its
    # ledger entry: nothing prefilled before a call is read inside it.
    engine.fresh_handoff()
    assert engine.held_trunk is None and ledger not in engine.governor.ledger()
    _dispatch(engine, _rows_of(head_y, 2, 5))
    assert stats.trunk_programs == 3
    # A fault the recovery ladder handles drops it too: the retried
    # dispatch has the trunk run again.
    state = {"n": 0}

    def flaky():
        state["n"] += 1
        if state["n"] == 1:
            raise RuntimeError("injected: device reset")
        return _dispatch(engine, _rows_of(head_y, 2, 6))

    engine.exec_registry = None
    _dispatch_with_recovery(engine, flaky)
    assert state["n"] == 2 and stats.trunk_programs == 4
    assert alive == [None] * 4
    assert len(first) == 2
    snap = metrics_mod.engine_registry(engine).snapshot(device_memory=False)
    fields = snap["sources"]["cascade"]["fields"]
    assert fields["trunk_programs"] == 4
    assert fields["trunk_held_dispatches"] == stats.trunk_held_dispatches == 6
    assert fields["trunk_rows_deduped"] == stats.trunk_rows_deduped
    assert snap["sources"]["spans"]["summary"]["sweep/trunk"]["count"] >= 4
