"""The trace reducer: by hand on made-up planes, and on a small trace
recorded on the v5e (``data/small.xplane.pb.gz``, made by
``record_trace.py``: the sweep cell at two layers of 512 wide, batch 8)."""

import gzip
import json
from pathlib import Path

import pytest

from harness import flops, peaks, readers, trace
from references import decoder as ref

DATA = Path(__file__).resolve().parent / "data"


def _planes():
    ms = 1e6
    ops = [
        ("%while.1 = (s32[]) while(...)", 0 * ms, 10 * ms),     # a loop ...
        ("%fusion.7 = bf16[8,4]{1,0} fusion(...)", 0 * ms, 4 * ms),
        ("%fusion.7 = bf16[8,4]{1,0} fusion(...)", 5 * ms, 4 * ms),
        ("%flash_decode_trunk.1 = (f32[2,8]{1,0}) custom-call(...)",
         9 * ms, 1 * ms),                                       # ... ends at 10
        ("%copy.3 = bf16[4]{0} copy(...)", 14 * ms, 2 * ms),    # gap 10..14
    ]
    modules = [("jit_greedy_decode_fused_shared_cascade(123)", 0, 10 * ms),
               ("jit_add(5)", 14 * ms, 2 * ms)]
    host = {"main/1": [("PjitFunction(jit(f))", 9 * ms, 4 * ms),
                       ("Wait for donation holds", 11 * ms, 1.5 * ms)],
            "python3": [("sweep/dispatch", 15 * ms, 1 * ms)]}
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": host}


def test_reducer_by_hand():
    red = trace.reduce_planes(_planes(), window_s=0.020, chips=1)
    assert red["busy_s"] == pytest.approx(0.012)          # union, not sum
    assert red["window_s"] == 0.020 and red["chips"] == 1
    assert red["ops"]["fusion.7 bf16[8,4]"] == (pytest.approx(0.008), 2)
    assert red["ops"]["flash_decode_trunk.1 (f32[2,8]"] == (
        pytest.approx(0.001), 1)
    assert not any(k.startswith("while") for k in red["ops"])
    assert red["modules"]["jit_greedy_decode_fused_shared_cascade"] == (
        pytest.approx(0.010), 1)
    assert red["breakdown"]["device_ops"][0][0] == "fusion.7 bf16[8,4]"
    # the 4 ms gap, named by the innermost host event open at its middle
    assert red["breakdown"]["idle_gaps"] == [
        ["Wait for donation holds", pytest.approx(0.004)]]


def test_program_spans_win_over_runtime_events():
    planes = _planes()
    planes["/host:CPU"]["python3"].append(("sweep/drain", 10e6, 5e6))
    red = trace.reduce_planes(planes, 0.020, 1)
    assert red["breakdown"]["idle_gaps"][0][0] == "sweep/drain"


def test_no_tpu_plane_is_an_error():
    with pytest.raises(RuntimeError):
        trace.reduce_planes({"/host:CPU": {}}, 1.0, 1)


def test_readers_on_the_hand_trace():
    spec, _ = ref.load("mistral-7b")
    red = trace.reduce_planes(_planes(), 0.020, 1)
    pk = peaks.peaks_for("TPU v5e")
    sizes = {"batch": 40, "extent": 455.0, "trunk": 64}
    ctx = {"trace": red, "spec": spec, "peaks": pk,
           "window": {"needed_flops": 197e12 * 0.010,
                      "kernel_calls": {"decode_attention_call": sizes}}}
    assert readers.trace_step_mfu(ctx) == pytest.approx(50.0)
    assert readers.trace_module_time(ctx, "^jit_greedy_decode") == (
        pytest.approx(10.0))
    assert readers.trace_module_time(ctx, "^jit_nothing") is None
    least, bound = flops.roofline_seconds(
        *flops.decode_attention_call(spec, **sizes), pk)
    assert bound == "memory"
    assert readers.trace_kernel_roofline(
        ctx, r"^flash_decode\w*trunk", "decode_attention_call") == (
        pytest.approx(100.0 * least / 0.001))
    assert readers.trace_kernel_roofline(
        ctx, "^cascade_attention", "cascade_prefill_call") is None


def test_reducer_on_the_recorded_trace(tmp_path):
    want = json.loads((DATA / "small.expected.json").read_text())
    pb = tmp_path / "small.xplane.pb"
    pb.write_bytes(gzip.decompress((DATA / "small.xplane.pb.gz").read_bytes()))
    red = trace.reduce_planes(trace.read_planes(pb), want["window_s"], 1)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0.0 < red["busy_s"] < red["window_s"]
    for name, (seconds, count) in want["modules"].items():
        assert red["modules"][name] == (pytest.approx(seconds), count)
    for pattern, (seconds, count) in want["kernels"].items():
        got = readers._matching(red["ops"], pattern)
        assert got == (pytest.approx(seconds), count), pattern
