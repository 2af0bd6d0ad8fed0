"""Ragged sweep scheduler tests (engine/scheduler.py).

Pins the three properties the scheduler's callers rely on:
- planning is deterministic and TOTAL (every grid cell lands in exactly
  one dispatch, identical inputs plan identical schedules),
- slot refill / bucket-ladder dispatch composition changes ONLY the
  batching — per-cell sweep results are identical to the legacy
  todo-order path on the fake backend,
- the cross-cell prefix-group decode reproduces decode_fused_shared on
  its pairwise special case (one cell per group, [bin, conf] members).
"""

import json

import numpy as np
import pytest

from lir_tpu.backends.fake import FakeTokenizer
from lir_tpu.config import RuntimeConfig
from lir_tpu.engine import scheduler as sched_mod
from lir_tpu.engine import tokens as tok
from lir_tpu.utils.profiling import OccupancyStats


# ---------------------------------------------------------------------------
# Bucket ladder (tokens.bucket_ladder / assign_bucket) — pure host-side
# ---------------------------------------------------------------------------

def test_bucket_ladder_shape_and_alignment():
    edges = tok.bucket_ladder(1024)
    assert edges == tuple(sorted(set(edges)))          # strictly increasing
    assert edges[-1] == 1024                           # covers the ceiling
    for e in edges:
        # flash-eligibility: lane-friendly under one block, whole blocks
        # above it (tokens.FLASH_BLOCK) — a misaligned edge silently
        # drops every dispatch in its bucket to dense attention.
        assert e % (16 if e <= tok.FLASH_BLOCK else tok.FLASH_BLOCK) == 0
    # ~sqrt(2) spacing keeps worst-case padding bounded: no step doubles.
    for a, b in zip(edges, edges[1:]):
        assert b <= 2 * a
    # Tiny ceilings degenerate to a single bucket.
    assert tok.bucket_ladder(48) == (48,)


def test_assign_bucket_total_and_deterministic():
    edges = tok.bucket_ladder(512)
    for n in range(1, 600):
        b = tok.assign_bucket(n, edges)
        assert b in edges
        if n <= max(edges):
            # smallest covering edge
            assert b >= n and all(e < n for e in edges if e < b)
        else:
            # over-long: largest bucket (left-truncation semantics)
            assert b == max(edges)
        assert tok.assign_bucket(n, edges) == b


# ---------------------------------------------------------------------------
# Planning: totality, determinism, slot refill accounting
# ---------------------------------------------------------------------------

def _items(lengths, fmt_len=6):
    """SweepItems with distinct token contents: per-cell prompts share
    their first `n` tokens between formats (lcp == n)."""
    items = []
    for i, n in enumerate(lengths):
        base = [100 + i] * n
        items.append(sched_mod.SweepItem(
            cell=("cell", i), bin_ids=tuple(base + [7] * fmt_len),
            conf_ids=tuple(base + [9] * fmt_len), lcp=n))
    return items


def test_schedule_is_total_and_deterministic():
    rng = np.random.default_rng(0)
    lengths = rng.integers(4, 500, 57).tolist()
    buckets = tok.bucket_ladder(512)

    def plan():
        planner = sched_mod.RaggedScheduler(buckets, 8, stats=OccupancyStats())
        return planner.schedule(_items(lengths))

    dispatches = plan()
    seen = [it.cell for d in dispatches for it in d.items]
    assert sorted(seen) == sorted(("cell", i) for i in range(len(lengths)))
    assert len(seen) == len(set(seen))  # exactly once
    for d in dispatches:
        assert d.kind in ("shared", "grouped")
        assert d.bucket in buckets
        # the shape dispatched: on the edge grid (or the ladder edge
        # itself), inside the ladder bucket the rows were queued under
        assert d.edge in sched_mod.prefix_edges(1, d.bucket)
        # every member's planned prefix fits its dispatch edge
        for it in d.items:
            assert min(it.prefix_len, max(buckets)) <= d.edge <= d.bucket

    again = plan()
    assert [(d.kind, d.bucket, d.edge, d.cells) for d in dispatches] == \
           [(d.kind, d.bucket, d.edge, d.cells) for d in again]


def _round_up(n, grid=sched_mod.PREFIX_EDGE_GRID):
    return -(-n // grid) * grid


# Prefix lengths -> what the plan must make of them (batch 4, ladder
# 64..512, no cross-cell groups). ``edges`` maps a ladder bucket to the
# prefix edge its dispatches run at.
EDGE_PLANS = {
    # the benchmark's own shape: 420-token rows into the 512 bucket
    "long-rows-tighten-512-to-448": (
        [420] * 8 + [421, 426, 430, 418], {512: 448}),
    # the longest row reaches the ladder edge: nothing to trim
    "row-on-the-ladder-edge": ([500] * 3 + [512], {512: 512}),
    "just-past-a-grid-line": ([449] * 4, {512: 512}),
    "on-a-grid-line": ([448] * 4, {512: 448}),
    # a 96 bucket has no grid line below it; 256 and 384 have one each
    "small-buckets": ([70] * 4 + [100] * 4 + [130] * 4 + [300] * 4,
                      {96: 96, 128: 128, 256: 192, 384: 320}),
    # one key, one edge: the longest row of ANY of its dispatches decides
    "one-edge-per-key": ([130] * 4 + [250] * 4 + [140] * 4, {256: 256}),
    # a promoted tail (decode cost 200 makes a lone row climb the whole
    # ladder) counts where it lands: the 30-token row rides the 256
    # bucket's first dispatch, the row it displaced ends alone in 512 —
    # and runs there at the edge ITS length asks for
    "promotion-moves-the-row": ([200] * 4 + [30], {256: 256, 512: 256}, 200),
    "promotion-keeps-the-edge": ([150] * 4 + [30], {256: 192, 512: 192},
                                 200),
    # over-long rows are truncated into the largest bucket, as before
    "over-long-rows-keep-the-bucket": ([600, 700, 520, 513], {512: 512}),
    "over-long-beside-short": ([390] * 4 + [900], {512: 512}),
}


@pytest.mark.parametrize("name", sorted(EDGE_PLANS))
def test_plan_tightens_prefix_edge_to_the_rows_it_carries(name):
    lengths, want, *decode_cost = EDGE_PLANS[name]
    buckets = tok.bucket_ladder(512)
    batch = 4

    def plan():
        stats = OccupancyStats()
        planner = sched_mod.RaggedScheduler(
            buckets, batch, group_cells=False, stats=stats,
            decode_cost=decode_cost[0] if decode_cost else None)
        return planner.schedule(_items(lengths)), stats

    dispatches, stats = plan()
    by_key = {}
    for d in dispatches:
        by_key.setdefault((d.kind, d.bucket), []).append(d)
    assert {b: ds[0].edge for (_, b), ds in by_key.items()} == want
    slot_tokens = trimmed = 0
    for (_, bucket), ds in by_key.items():
        longest = max(it.prefix_len for d in ds for it in d.items)
        for d in ds:
            # one edge per (kind, bucket) key, after promotion
            assert d.edge == ds[0].edge
            # round-up-to-64 of the key's longest prefix, never above the
            # ladder bucket ...
            assert d.edge == min(bucket, _round_up(longest))
            # ... never below any member that fits the ladder at all
            for it in d.items:
                assert d.edge >= min(it.prefix_len, max(buckets))
            slots = sched_mod._tail_batch(len(d.items), batch)
            slot_tokens += slots * d.edge
            trimmed += slots * (bucket - d.edge)
    # identical inputs plan identical edges
    again, _ = plan()
    assert [(d.bucket, d.edge, d.cells) for d in again] == \
           [(d.bucket, d.edge, d.cells) for d in dispatches]
    # the counters charge the shape DISPATCHED, not the ladder's
    real = sum(lengths)
    assert stats.trimmed_slots == trimmed
    assert sum(b.slot_tokens for b in stats.buckets.values()) == slot_tokens
    assert stats.padding_waste_pct == pytest.approx(
        100.0 * (slot_tokens - real) / slot_tokens)
    assert stats.edge_trim_pct == pytest.approx(
        100.0 * trimmed / (slot_tokens + trimmed))
    summ = stats.summary()
    assert summ["trimmed_slots"] == trimmed
    assert summ["edge_trim_pct"] == round(stats.edge_trim_pct, 2)
    assert set(stats.buckets) == {b for _, b in by_key}   # ladder names


def test_grouped_dispatch_edge_follows_the_group_prefix():
    # 4 cells sharing 150 leading tokens: one grouped dispatch whose
    # prefill rows are the GROUP prefixes (plen), so the edge rounds plen
    # up, not the members' full lengths.
    shared = [50 + (i % 40) for i in range(150)]
    items = []
    for i in range(4):
        ids = shared + [300 + i] * (4 + i)
        items.append(sched_mod.SweepItem(
            cell=("g", i), bin_ids=tuple(ids + [7] * 5),
            conf_ids=tuple(ids + [9] * 5), lcp=len(ids)))
    stats = OccupancyStats()
    planner = sched_mod.RaggedScheduler(tok.bucket_ladder(512), 8,
                                        stats=stats)
    (d,) = planner.schedule(items)
    assert d.kind == "grouped" and d.bucket == 256
    plen = d.groups[0].plen
    assert 150 <= plen <= 154
    assert d.edge == _round_up(plen) == 192
    assert stats.trimmed_slots == 1 * (256 - 192)   # one prefill row


@pytest.mark.parametrize("length,bucket,want", [
    (420, 512, (448, 512)), (449, 512, (512,)), (100, 128, (128,)),
    (70, 96, (96,)), (130, 256, (192, 256)), (10, 256, (64, 128, 192, 256)),
    (600, 512, (512,)),
])
def test_prefix_edges_lists_every_extent_a_row_may_run_at(length, bucket,
                                                         want):
    assert sched_mod.prefix_edges(length, bucket) == want


@pytest.mark.parametrize("grid,want", [
    (sched_mod.PREFIX_EDGE_GRID, {512: 448, 768: 576}),
    (tok.FLASH_BLOCK, {512: 512, 768: 640}),
])
def test_flash_prefill_engine_plans_on_the_kernel_block(grid, want):
    # A flash-prefill engine's sweep passes the kernel's 128 block as
    # the grid: its edges stay whole blocks (the ladder's own up to 512,
    # 640 inside the 768 bucket), so no dispatch drops to dense attention.
    planner = sched_mod.RaggedScheduler(
        tok.bucket_ladder(1024), 4, group_cells=False, edge_grid=grid,
        stats=OccupancyStats())
    dispatches = planner.schedule(_items([420] * 4 + [570] * 4))
    assert {d.bucket: d.edge for d in dispatches} == want
    assert sched_mod.prefix_edges(570, 768, grid) == tuple(
        range(want[768], 768, grid)) + (768,)


# ---------------------------------------------------------------------------
# Plan windows (ISSUE 35): a closed window's plan is its part of the
# whole grid's
# ---------------------------------------------------------------------------

def _doc_rows(prompt, doc_len, n_rows, sfx=(2, 2), tail=9):
    """Rows of one prompt: a ``doc_len``-token document of its own, a
    short tail a row, format suffixes of ``sfx`` tokens."""
    doc = [1000 * (prompt + 1) + j for j in range(doc_len)]
    rows = []
    for r in range(n_rows):
        ids = tuple(doc + [20 + r] * (tail + r % 3))
        rows.append(sched_mod.SweepItem(
            cell=(prompt, r), bin_ids=ids + (5,) * sfx[0],
            conf_ids=ids + (6,) * sfx[1], lcp=len(ids)))
    return rows


def _planner(cap, **kw):
    return sched_mod.RaggedScheduler(
        tok.bucket_ladder(256), 4, new_budget=8, decode_cost=12,
        group_cells=False, token_cap=cap, stats=OccupancyStats(), **kw)


def _plan_in_windows(planner, prompts):
    """What engine/sweep._fill_windows does with the scheduler: a window
    grows a prompt at a time, is planned once closed, and the first one
    to close with prompts left looks at one row of each of them."""
    floors = sched_mod.EdgeFloors()
    windows, items, closed = [], [], True
    for i, rows in enumerate(prompts):
        items += rows
        closed = closed and planner.closed(rows)
        more = i + 1 < len(prompts)
        if more and not closed:
            continue
        if more and not windows:
            planner.foresee([p[0] for p in prompts[i + 1:]], floors)
        windows.append(planner.schedule(items, floors))
        items = []
    return windows


def _shapes(dispatches):
    return [(d.kind, d.bucket, d.edge, d.sfx_bucket_a, d.sfx_bucket_b,
             d.refilled, d.cells) for d in dispatches]


# Rows a prompt (the original + whole groups of 4), and the format
# suffixes of each: doc16k-shaped traffic has groups on three of five
# prompts and two lone originals; which prompt comes first, and which has
# the longest format, differs by seed.
WINDOW_GRIDS = {
    "lone-original-first": ([1, 9, 5, 1, 5], [(2, 2)] * 5),
    "group-first": ([9, 1, 5, 5, 1], [(2, 2)] * 5),
    "longest-format-last": ([1, 9, 5, 1, 5],
                            [(2, 2), (3, 2), (2, 2), (2, 2), (11, 9)]),
    "longest-format-on-a-lone-original": (
        [5, 9, 1, 5, 1], [(2, 2), (2, 2), (12, 2), (2, 2), (2, 2)]),
    "two-prompts": ([5, 5], [(2, 2), (9, 2)]),
}


@pytest.mark.parametrize("name", sorted(WINDOW_GRIDS))
def test_long_rows_under_a_cap_plan_a_prompt_at_a_time(name):
    """160-token documents in the 256 bucket under a cap of 384: two rows
    of different prompts never fit one pass, so every prompt is a closed
    window, and the windows' dispatches, in order, are the whole grid's to
    the last field: cells, edges, suffix buckets."""
    counts, sfx = WINDOW_GRIDS[name]
    prompts = [_doc_rows(p, 160, n, sfx[p]) for p, n in enumerate(counts)]
    whole = _planner(384).schedule([it for rows in prompts for it in rows])
    planner = _planner(384)
    windows = _plan_in_windows(planner, prompts)
    assert len(windows) == len(prompts)
    assert _shapes([d for w in windows for d in w]) == _shapes(whole)
    assert {d.edge for d in whole} == {192}
    # ONE OccupancyStats a call: the windows add up to the whole grid's.
    one = _planner(384)
    one.schedule([it for rows in prompts for it in rows])
    assert planner.stats.summary() == one.stats.summary()


@pytest.mark.parametrize("case,cap,kw,lengths,want", [
    ("no-cap", 0, {}, [170, 170], False),
    ("long-rows", 384, {}, [170, 200], True),
    ("one-short-row", 384, {}, [170, 100], False),   # bucket 128: 2 fit
    ("cap-two-rows-fit", 512, {}, [170, 170], False),
    ("cells-grouped", 384, {"group_cells": True}, [170, 170], False),
])
def test_a_window_is_closed_only_where_the_cap_keeps_strangers_apart(
        case, cap, kw, lengths, want):
    planner = sched_mod.RaggedScheduler(
        tok.bucket_ladder(256), 4, token_cap=cap,
        **{"group_cells": False, **kw})
    assert planner.closed(_items(lengths)) is want


@pytest.mark.parametrize("counts,cap", [
    ([9, 5, 5], 0),            # no cap: trunk512-shaped
    ([5, 9, 5], 512),          # a cap two strangers fit under
])
def test_a_grid_that_never_closes_is_one_window(counts, cap):
    prompts = [_doc_rows(p, 160, n) for p, n in enumerate(counts)]
    planner = _planner(cap)
    windows = _plan_in_windows(planner, prompts)
    assert len(windows) == 1
    assert _shapes(windows[0]) == _shapes(
        _planner(cap).schedule([it for rows in prompts for it in rows]))


def test_short_rows_after_long_ones_end_the_windows():
    """Closed prompts are windows of their own up to the first prompt
    with a row a stranger could ride with; the rest is one window."""
    prompts = [_doc_rows(0, 160, 5), _doc_rows(1, 160, 1),
               _doc_rows(2, 60, 5), _doc_rows(3, 160, 5)]
    windows = _plan_in_windows(_planner(384), prompts)
    assert [sorted({c[0] for d in w for c in d.cells}) for w in windows] == [
        [0], [1], [2, 3]]
    cells = [c for w in windows for d in w for c in d.cells]
    assert sorted(cells) == sorted(it.cell for rows in prompts
                                   for it in rows)


def test_the_edges_of_a_call_only_grow():
    """A window plans at no less than the windows before it: a longer
    suffix or prefix met later raises the edges from there on, and never
    lowers what a key already ran at."""
    planner = _planner(384)
    floors = sched_mod.EdgeFloors()
    first = planner.schedule(_doc_rows(0, 160, 5, sfx=(9, 2)), floors)
    assert {(d.edge, d.sfx_bucket_a, d.sfx_bucket_b) for d in first} == {
        (192, 16, 8)}
    second = planner.schedule(_doc_rows(1, 160, 5, sfx=(2, 9), tail=40),
                              floors)
    assert {(d.edge, d.sfx_bucket_a, d.sfx_bucket_b) for d in second} == {
        (256, 16, 16)}
    third = planner.schedule(_doc_rows(2, 160, 5), floors)
    assert {(d.edge, d.sfx_bucket_a, d.sfx_bucket_b) for d in third} == {
        (256, 16, 16)}
    # Without floors a plan owes nothing to the one before it.
    assert {d.edge for d in planner.schedule(_doc_rows(2, 160, 5))} == {192}


def test_slot_refill_promotes_ragged_tail_once():
    # 9 short cells at batch 4: two full dispatches + a 1-cell tail. The
    # cost model promotes the tail into the 96 bucket (1 * 96 < 1-slot
    # padded dispatch at 64? no — vs _tail_batch(1,4)=1 slot * 64) only
    # when cheaper, so just pin totality + the refilled counter's books.
    lengths = [30] * 9 + [90] * 4
    stats = OccupancyStats()
    planner = sched_mod.RaggedScheduler(
        tok.bucket_ladder(256), 4, group_cells=False, stats=stats)
    dispatches = planner.schedule(_items(lengths))
    assert sum(len(d.items) for d in dispatches) == len(lengths)
    assert sum(b.cells for b in stats.buckets.values()) == len(lengths)
    assert sum(b.refilled for b in stats.buckets.values()) == \
           sum(d.refilled for d in dispatches)
    assert 0.0 < stats.occupancy_pct <= 100.0
    assert 0.0 <= stats.padding_waste_pct < 100.0


def test_prefix_groups_form_only_on_long_shared_prefixes():
    # 4 cells sharing 24 leading tokens (>= min_group_prefix, >= half of
    # each prefill) group; 4 cells with disjoint prompts never do.
    shared = [50 + i for i in range(24)]
    items = []
    for i in range(4):
        ids = shared + [200 + i] * (4 + i)
        items.append(sched_mod.SweepItem(
            cell=("g", i), bin_ids=tuple(ids + [7] * 5),
            conf_ids=tuple(ids + [9] * 5), lcp=len(ids)))
    solo = _items([40, 45, 50, 55])
    planner = sched_mod.RaggedScheduler(
        tok.bucket_ladder(256), 8, stats=OccupancyStats())
    dispatches = planner.schedule(items + solo)
    grouped = [d for d in dispatches if d.kind == "grouped"]
    assert len(grouped) == 1
    assert sorted(it.cell for it in grouped[0].items) == \
           sorted(("g", i) for i in range(4))
    assert grouped[0].groups[0].plen >= 24
    # the disjoint cells all ride shared dispatches
    rest = [it.cell for d in dispatches if d.kind == "shared"
            for it in d.items]
    assert sorted(rest) == sorted(("cell", i) for i in range(4))


# ---------------------------------------------------------------------------
# Engine-level parity on the fake backend
# ---------------------------------------------------------------------------

def _tiny_engine(rt, seed=2):
    import jax

    from lir_tpu.engine.runner import ScoringEngine
    from lir_tpu.models import decoder
    from lir_tpu.models.registry import ModelConfig

    cfg = ModelConfig(name="sched-smoke", vocab_size=FakeTokenizer.VOCAB,
                      hidden_size=64, n_layers=2, n_heads=4,
                      intermediate_size=128, max_seq_len=256)
    params = decoder.init_params(cfg, jax.random.PRNGKey(seed))
    return ScoringEngine(params, cfg, FakeTokenizer(), rt), params, cfg


def _varlen_grid(rng):
    """2 prompts x variable-length rephrasings spanning several buckets;
    prompt 0's rephrasings share their first 20 words so the ragged run
    also exercises the cross-cell prefix-group path."""
    from lir_tpu.data.prompts import LegalPrompt

    words = ("coverage policy flood water damage claim insurer premium "
             "exclusion endorsement peril deductible adjuster settle "
             "liability clause binding interpret statute meaning").split()

    def text(n):
        return " ".join(rng.choice(words) for _ in range(n)) + " ?"

    shared_head = " ".join(rng.choice(words) for _ in range(20))
    prompts = (
        LegalPrompt(main=shared_head + " " + text(8),
                    response_format="Answer Yes or No .",
                    target_tokens=("Yes", "No"),
                    confidence_format="Give a number from 0 to 100 ."),
        LegalPrompt(main=text(30),
                    response_format="Answer Yes or No .",
                    target_tokens=("Yes", "No"),
                    confidence_format="Give a number from 0 to 100 ."),
    )
    perturbations = (
        # same 20-word head, short tails -> a 4+ cell prefix group
        [shared_head + " " + text(4 + i) for i in range(4)],
        # disjoint, strongly varied lengths -> bucket ladder + refill
        [text(n) for n in (5, 90, 140, 12, 70, 25, 110)],
    )
    return prompts, perturbations


def _assert_rows_match(rows_a, rows_b):
    """Per-cell equality of two sweeps of one grid: token/text readouts
    bit for bit, float readouts to shape-fusion tolerance (a cell padded
    to another length fuses slightly differently; the last ulp of a
    logprob can move)."""
    def key(r):
        return (r.original_main, r.rephrased_main)

    by_key = {key(r): r for r in rows_b}
    assert set(map(key, rows_a)) == set(by_key)
    for r in rows_a:
        l = by_key[key(r)]
        assert r.model_response == l.model_response
        assert r.model_confidence_response == l.model_confidence_response
        assert r.confidence_value == l.confidence_value
        np.testing.assert_allclose(r.token_1_prob, l.token_1_prob,
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(r.token_2_prob, l.token_2_prob,
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(r.weighted_confidence,
                                   l.weighted_confidence,
                                   rtol=1e-5, atol=1e-7)
        lp_r, lp_l = (json.loads(r.log_probabilities),
                      json.loads(l.log_probabilities))
        assert list(lp_r) == list(lp_l)  # same top-20 ids, same order
        np.testing.assert_allclose(list(lp_r.values()),
                                   list(lp_l.values()), atol=2e-6)


def test_tight_edge_sweep_matches_ladder_edge_per_cell(tmp_path,
                                                       monkeypatch):
    """Tightening a bucket's prefix edge removes masked slots and nothing
    else: every cell's readout equals the sweep that pads to the ladder
    edges (the grid set so coarse that every edge IS its bucket), to the
    tolerance ragged-vs-legacy already uses."""
    from lir_tpu.engine.sweep import run_perturbation_sweep

    rng = np.random.default_rng(11)
    prompts, perturbations = _varlen_grid(rng)

    def run(sub):
        rt = RuntimeConfig(batch_size=4, max_seq_len=256)
        engine, _, _ = _tiny_engine(rt)
        rows = run_perturbation_sweep(
            engine, "sched-tiny", prompts, perturbations,
            tmp_path / sub / "results.xlsx", checkpoint_every=100)
        return rows, engine.occupancy

    rows_t, occ_t = run("tight")
    monkeypatch.setattr(sched_mod, "PREFIX_EDGE_GRID", 1 << 20)
    rows_l, occ_l = run("ladder")
    assert len(rows_t) == len(rows_l) == 13
    _assert_rows_match(rows_t, rows_l)
    # The two runs really dispatched different shapes.
    assert occ_l.trimmed_slots == 0 and occ_l.edge_trim_pct == 0.0
    assert occ_t.trimmed_slots > 0
    assert occ_t.padding_waste_pct < occ_l.padding_waste_pct
    assert set(occ_t.buckets) == set(occ_l.buckets)   # same queues


@pytest.mark.slow
def test_ragged_sweep_matches_legacy_per_cell(tmp_path):
    """The tentpole's safety property: bucket ladder + slot refill +
    prefix grouping change dispatch COMPOSITION only — every cell's D6
    readout equals the legacy todo-order path's: token/text readouts bit
    for bit, float readouts to shape-fusion tolerance (a cell padded to
    a different bucket length fuses slightly differently; the last ulp
    of a logprob can move)."""
    from lir_tpu.engine.sweep import run_perturbation_sweep

    rng = np.random.default_rng(11)
    prompts, perturbations = _varlen_grid(rng)

    def run(ragged, sub):
        rt = RuntimeConfig(batch_size=4, max_seq_len=256,
                           ragged_scheduler=ragged)
        engine, _, _ = _tiny_engine(rt)
        rows = run_perturbation_sweep(
            engine, "sched-tiny", prompts, perturbations,
            tmp_path / sub / "results.xlsx", checkpoint_every=100)
        return rows, engine

    rows_r, eng_r = run(True, "ragged")
    rows_l, _ = run(False, "legacy")
    assert len(rows_r) == len(rows_l) == 13
    _assert_rows_match(rows_r, rows_l)

    # The ragged run actually scheduled (counters populated and sane).
    stats = eng_r.occupancy
    assert stats is not None
    assert sum(b.cells for b in stats.buckets.values()) == 13
    assert 0.0 < stats.occupancy_pct <= 100.0
    assert 0.0 <= stats.padding_waste_pct < 100.0
    assert stats.grouped_cells >= 4  # the shared-head rephrasings grouped


@pytest.mark.parametrize("kv_int8", [False, True])
def test_grouped_handoff_keeps_the_answers(kv_int8):
    """decode_fused_grouped(reuse_cache=True), the way the sweep calls it:
    the handoff notes (ledger, recurrent counters) read the cache the
    program returned, whose K/V sides are (payload, scale) pairs under
    ``kv_cache_int8``; the second call decodes into the first's cache.
    Both answer as the call without the handoff does."""
    import dataclasses

    engine, _, cfg = _tiny_engine(
        RuntimeConfig(batch_size=4, max_seq_len=256))
    if kv_int8:
        from lir_tpu.engine.runner import ScoringEngine

        engine = ScoringEngine(
            engine.params, dataclasses.replace(cfg, kv_cache_int8=True),
            FakeTokenizer(), RuntimeConfig(batch_size=4, max_seq_len=256))
    head = "the quick brown fox jumps over the lazy dog with filler text"
    mains = [f"{head} word {i * 7} tail {i}" for i in range(3)]
    ftok = engine.tokenizer
    bin_ids = [ftok(m + " Respond with either Yes or No only").input_ids
               for m in mains]
    conf_ids = [ftok(m + " Give a confidence number from 0 to 100").input_ids
                for m in mains]
    items = sched_mod.build_items(bin_ids, conf_ids, list(range(3)))
    plen = len(ftok(head).input_ids)
    groups = [sched_mod.PrefixGroup(items=tuple(items), plen=plen)]
    sfx = tok.pick_bucket(
        [max(len(it.bin_ids), len(it.conf_ids)) - plen for it in items],
        sched_mod.SUFFIX_BUCKETS)
    t1 = np.full((3,), FakeTokenizer.YES, np.int32)
    t2 = np.full((3,), FakeTokenizer.NO, np.int32)

    def call(reuse):
        out, m = engine.decode_fused_grouped(
            groups, t1, t2, 3, 3, early_stop=False, bucket=16,
            sfx_bucket=sfx, reuse_cache=reuse, use_prefix_cache=False)
        assert m == 6
        return out

    plain, first, second = call(False), call(True), call(True)
    assert engine.recurrent_stats.dispatches == 0        # no mixer here
    for got in (first, second):
        np.testing.assert_array_equal(np.asarray(got.generated[:6]),
                                      np.asarray(plain.generated[:6]))
        np.testing.assert_array_equal(np.asarray(got.p_yes[:6]),
                                      np.asarray(plain.p_yes[:6]))


@pytest.mark.slow
def test_grouped_decode_matches_shared_pairwise():
    """decode_fused_grouped on one-cell groups ([bin, conf] members,
    group_idx = [0,0,1,1,...]) == decode_fused_shared on the same
    prompts — the pairwise special case the grouped path generalizes."""
    engine, _, _ = _tiny_engine(
        RuntimeConfig(batch_size=4, max_seq_len=256))
    mains = [f"the quick brown fox {i} jumps over the lazy dog "
             f"word {i * 7} extra filler text here" for i in range(4)]
    bins = [m + " Respond with either Yes or No only" for m in mains]
    confs = [m + " Give a confidence number from 0 to 100" for m in mains]
    t1 = np.full((4,), FakeTokenizer.YES, np.int32)
    t2 = np.full((4,), FakeTokenizer.NO, np.int32)
    NEW = 4

    ftok = engine.tokenizer
    bin_ids = [ftok(p).input_ids for p in bins]
    conf_ids = [ftok(p).input_ids for p in confs]
    items = sched_mod.build_items(bin_ids, conf_ids, list(range(4)))
    groups = [sched_mod.PrefixGroup(items=(it,), plen=it.lcp)
              for it in items]
    bucket = tok.pick_bucket([it.prefix_len for it in items],
                             engine.buckets)
    sfx = tok.pick_bucket(
        [max(len(it.bin_ids), len(it.conf_ids)) - it.lcp for it in items],
        sched_mod.SUFFIX_BUCKETS)

    out, m = engine.decode_fused_grouped(
        groups, t1, t2, NEW, NEW, early_stop=False,
        bucket=bucket, sfx_bucket=sfx)
    assert m == 8
    ref_a, ref_b = engine.decode_fused_shared(
        bins, confs, t1, t2, new_tokens=NEW, conf_tokens=NEW,
        early_stop=False)

    for start, ref in ((0, ref_a), (1, ref_b)):
        rows = slice(start, m, 2)
        np.testing.assert_array_equal(np.asarray(out.generated[rows]),
                                      np.asarray(ref.generated))
        np.testing.assert_allclose(np.asarray(out.p_yes[rows]),
                                   np.asarray(ref.p_yes),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(out.p_no[rows]),
                                   np.asarray(ref.p_no),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(out.topk_ids[rows]),
                                      np.asarray(ref.topk_ids))
        np.testing.assert_allclose(np.asarray(out.topk_logprobs[rows]),
                                   np.asarray(ref.topk_logprobs),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(out.weighted_confidence[1:m:2]),
        np.asarray(ref_b.weighted_confidence), rtol=1e-5, atol=1e-6)
