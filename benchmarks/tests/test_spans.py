"""The readers of what the program names (``harness/spans.py``) on a
hand-made trace, and the five counter metrics that read the program's
span totals and compile counters on a tiny CPU window."""

import json

import pytest

import run as bench_run
from harness import readers, spans, trace

MS = 1e6                                   # ns


def _planes(anchors=True):
    """Two runs of one dispatch program (10 ms and 8 ms), the second from
    the donated variant with one instruction named otherwise; a small
    program in between; the last device operation ends at 40 ms."""
    ops = [
        ("%while.1 = (s32[]) while(...)", 0 * MS, 9 * MS),         # container
        ("%fusion.7 = bf16[8,4]{1,0} fusion(...)", 0 * MS, 4 * MS),
        ("%cascade_attention.2 = f32[8]{0} custom-call(...)", 4 * MS, 1 * MS),
        ("%fusion.9 = bf16[8]{0} fusion(...)", 5 * MS, 2 * MS),
        ("%flash_decode_trunk.2 = (f32[2,8]{1,0}) custom-call(...)",
         7 * MS, 2 * MS),
        ("%copy.3 = bf16[4]{0} copy(...)", 9 * MS, 0.5 * MS),
        ("%add.1 = f32[] add(...)", 20 * MS, 1 * MS),               # jit_add
        ("%fusion.7 = bf16[8,4]{1,0} fusion(...)", 32 * MS, 4 * MS),
        ("%fusion.11 = bf16[8]{0} fusion(...)", 36 * MS, 3 * MS),
        ("%copy.3 = bf16[4]{0} copy(...)", 39 * MS, 1 * MS),
    ]
    modules = [("jit_greedy_decode_fused_shared_cascade(111)", 0, 10 * MS),
               ("jit_add(5)", 20 * MS, 1 * MS),
               ("jit_greedy_decode_fused_shared_cascade(222)", 32 * MS,
                8 * MS)]
    host = {"python3": [("sweep/call", 1 * MS, 44 * MS)]}
    if anchors:
        host["python3"] += [(spans.CLOCK_ANCHOR, 0.5 * MS, 0.002 * MS),
                            (spans.CLOCK_ANCHOR, 46 * MS, 0.002 * MS)]
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": host}


TABLES = [
    {"label": "cascade/fresh", "module":
     "jit_greedy_decode_fused_shared_cascade", "instructions": 5,
     "recompiled": False,
     "scopes": {"fusion.7": "lir.prefill", "cascade_attention.2":
                "lir.prefill", "fusion.9": "lir.extend",
                "flash_decode_trunk.2": "lir.decode"}},
    {"label": "cascade/donated", "module":
     "jit_greedy_decode_fused_shared_cascade", "instructions": 3,
     "recompiled": False,
     "scopes": {"fusion.7": "lir.prefill", "fusion.11": "lir.decode"}},
    {"label": "add", "module": "jit_add", "instructions": 1,
     "recompiled": False, "scopes": {"add.1": "lir.readout"}},
]

# The recorder's clock runs 100 s behind the profiler's.
BEHIND = 100.0


def _spans(anchors=True):
    def at(ms):
        return ms / 1e3 - BEHIND

    ev = [
        {"name": "engine/compile_load", "id": 1, "thread": "compile-plan_0",
         "t0": at(-9000), "t1": at(-4000), "args": {"label": "a"}},
        {"name": "engine/compile_load", "id": 2, "thread": "compile-plan_1",
         "t0": at(-8000), "t1": at(-3000), "args": {"label": "b"}},
        {"name": "sweep/call", "id": 10, "thread": "MainThread",
         "t0": at(1), "t1": at(45)},
        {"name": "sweep/plan", "id": 11, "parent": 10, "thread": "MainThread",
         "t0": at(1), "t1": at(3)},
        {"name": "sweep/dispatch", "id": 12, "parent": 10,
         "thread": "MainThread", "t0": at(3), "t1": at(4),
         "args": {"dispatch": 0}},
        {"name": "sweep/drain", "id": 20, "cause": 12,
         "thread": "sweep-writer", "t0": at(4), "t1": at(16),
         "args": {"dispatch": 0}},
        {"name": "stream/fold", "id": 21, "parent": 20,
         "thread": "sweep-writer", "t0": at(4), "t1": at(5)},
        {"name": "sweep/drain_wait", "id": 22, "parent": 20,
         "thread": "sweep-writer", "t0": at(5), "t1": at(11)},
        {"name": "sweep/drain", "id": 30, "cause": 12,
         "thread": "sweep-writer", "t0": at(30), "t1": at(44),
         "args": {"dispatch": 1}},
        {"name": "sweep/drain_wait", "id": 31, "parent": 30,
         "thread": "sweep-writer", "t0": at(30), "t1": at(41)},
    ]
    if anchors:
        ev += [{"name": spans.CLOCK_ANCHOR, "id": 40, "thread": "MainThread",
                "t0": at(0.5), "t1": at(0.502)},
               {"name": spans.CLOCK_ANCHOR, "id": 41, "thread": "MainThread",
                "t0": at(46), "t1": at(46.002)}]
    return ev


def _context(**kw):
    red = trace.reduce_planes(_planes(), 0.050, 1)
    ctx = {"trace": red, "planes": _planes(), "spans": _spans(),
           "scope_tables": TABLES}
    ctx.update(kw)
    return ctx


def test_phase_split_by_hand():
    split = spans.phase_seconds(_planes(), TABLES, "^jit_greedy_decode")
    assert split["runs"] == 2 and split["unmatched_runs"] == 0
    assert split["module_s"] == pytest.approx(0.018)
    # run 1 by the fresh table, run 2 by the donated one (fusion.11)
    assert split["scopes"] == {
        "lir.decode": pytest.approx(0.002 + 0.003),
        "lir.extend": pytest.approx(0.002),
        "lir.prefill": pytest.approx(0.004 + 0.001 + 0.004)}
    assert split["other_s"] == pytest.approx(0.0015)        # the copies
    assert split["ops_s"] == pytest.approx(0.0175)          # no container
    assert "lir.readout" not in split["scopes"]             # jit_add is out


def test_phase_readers_add_up_to_the_program():
    ctx = _context()
    phases = [spans.trace_phase_time(ctx, scopes) for scopes in (
        ["lir.prefill"], ["lir.extend"], ["lir.decode", "lir.readout"],
        ["other"])]
    assert phases == [pytest.approx(4.5), pytest.approx(1.0),
                      pytest.approx(2.5), pytest.approx(0.75)]
    whole = readers.trace_module_time(ctx, "^jit_greedy_decode")
    assert whole == pytest.approx(9.0)
    assert sum(phases) == pytest.approx(8.75)               # cover 97%


def test_no_scope_no_span_no_anchor_reads_nothing():
    assert spans.trace_phase_time(_context(scope_tables=[]),
                                  ["lir.prefill"]) is None
    unscoped = [dict(t, scopes={}) for t in TABLES]
    assert spans.trace_phase_time(_context(scope_tables=unscoped),
                                  ["other"]) is None
    assert spans.trace_phase_time(_context(), ["lir.prefill"],
                                  pattern="^jit_nothing") is None
    no_anchor = _context(planes=_planes(anchors=False))
    assert spans.anchor_offsets(no_anchor["planes"], _spans()) == []
    assert spans.trace_tail(no_anchor) is None
    assert spans.trace_tail(_context(spans=_spans(anchors=False))) is None
    assert spans.span_seconds(_context(), "sweep/nothing") is None
    assert spans.span_seconds(_context(spans=[]), "sweep/plan") is None


def test_anchors_lay_the_recorder_on_the_device_clock():
    offsets = spans.anchor_offsets(_planes(), _spans())
    assert offsets == [pytest.approx(BEHIND), pytest.approx(BEHIND)]
    # last device operation ends at 40 ms, the call at 45 ms
    assert spans.trace_tail(_context()) == pytest.approx(5.0)


def test_span_self_time_union_and_per():
    ctx = _context()
    own = spans.self_seconds(ctx["spans"])
    assert own[20] == pytest.approx(0.012 - 0.001 - 0.006)
    assert own[30] == pytest.approx(0.014 - 0.011)
    assert spans.span_seconds(ctx, "sweep/plan", scale=1e3) == (
        pytest.approx(2.0))
    assert spans.span_seconds(ctx, "sweep/drain", what="self",
                              per="sweep/drain", scale=1e3) == (
        pytest.approx((5.0 + 3.0) / 2))
    # two loads of 5 s overlapping by 4 s, both before the call
    assert spans.span_seconds(ctx, "engine/compile_load", what="union",
                              before="sweep/call") == pytest.approx(6.0)
    assert spans.span_seconds(ctx, "engine/compile_load") == (
        pytest.approx(10.0))


def test_every_host_line_of_the_recorded_trace_is_kept(tmp_path):
    """The trace recorded on the v5e has three host lines named
    ``python3``: ``trace.read_planes`` keeps one, ``spans.read_planes``
    all, and then the program's spans are there to name an idle gap.
    Nothing an accepted metric reads differs between the two."""
    import gzip
    from pathlib import Path

    data = Path(__file__).resolve().parent / "data"
    pb = tmp_path / "small.xplane.pb"
    pb.write_bytes(gzip.decompress((data / "small.xplane.pb.gz").read_bytes()))
    kept, every = trace.read_planes(pb), spans.read_planes(pb)

    def named(planes):
        found = {}
        for line in planes[trace.HOST_PLANE].values():
            for name, _, _ in line:
                if trace.SPAN.match(name) or name.startswith("stream/"):
                    found[name] = found.get(name, 0) + 1
        return found

    assert named(every) == {"sweep/dispatch": 7, "sweep/drain": 7,
                            "stream/fold": 7}
    assert sum(named(kept).values()) < 21
    assert len(every[trace.HOST_PLANE]) > len(kept[trace.HOST_PLANE])
    a, b = (trace.reduce_planes(p, 1.0, 1) for p in (kept, every))
    assert a["busy_s"] == b["busy_s"] and a["modules"] == b["modules"]
    assert a["ops"] == b["ops"]
    labels = {k for k, _ in b["breakdown"]["idle_gaps"]}
    assert labels & {"sweep/dispatch", "sweep/drain"}


def test_new_metric_files_name_readers_that_exist():
    bench = json.loads((bench_run.REPO / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        doc = json.loads((bench_run.HERE / "metrics" /
                          f"{m['name']}.json").read_text())
        assert doc["reader"] in readers.READERS, m["name"]


NEW = ("host_fill_ms.sweep", "drain_host_ms_per_dispatch.sweep",
       "tail_ms.sweep", "program_load_s.sweep",
       "programs_dispatched_pct.sweep")


def test_counter_metrics_on_a_tiny_window(tmp_path):
    """The window driver itself on the CPU: the five metrics this PR adds
    read the program's span totals and compile counters through the
    ``counter`` reader that was there."""
    import tiny
    from harness import sweep_window
    from lir_tpu.models import decoder

    decoder.FUSED_DECODE_INTERPRET_ON_CPU = True
    decoder.CASCADE_INTERPRET_ON_CPU = True
    cell, bench, files = tiny.files_for("mistral-7b", "sweep-trunk512")
    ctx = bench_run.Context(spec=files["spec"], ref=files["ref"],
                            mix=files["mix"], runtime=files["runtime"],
                            seed=5, seconds=2.0, out=tmp_path,
                            check_config=False)
    record = sweep_window.run(ctx)
    assert record["failed"] == 0
    wanted = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert len(wanted) == len(NEW)
    got = readers.read_all(wanted, {"counters": record["counters"],
                                    "window": record["window"]})
    assert set(got) == set(NEW), got
    value = {k: v["value"] for k, v in got.items()}
    window_ms = 1e3 * record["window"]["seconds"]
    assert 0.0 < value["host_fill_ms.sweep"] < window_ms
    assert 0.0 < value["drain_host_ms_per_dispatch.sweep"] < window_ms
    assert 0.0 <= value["tail_ms.sweep"] < window_ms
    assert 0.0 < value["program_load_s.sweep"] <= ctx.setup_s
    assert 0.0 < value["programs_dispatched_pct.sweep"] <= 100.0
    # a program without the counters (the parent of this PR): nothing
    bare = {"counters": {"before": {"sources": {}},
                         "after": {"sources": {}}}, "window": {}}
    assert readers.read_all(wanted, bare) == {}
