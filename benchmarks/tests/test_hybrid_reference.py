"""``references/hybrid.py`` against the program's own forward at a tiny
width; its controls, which must read further off than the served
precision; and ``harness/ssm.py``'s operation and byte counts against
numbers worked by hand."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import builders, flops, peaks, ssm
from references import hybrid as ref

SEED = 2**31 + 78
SMALL = dict(ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_groups=2,
             ssm_chunk=16)


def _load():
    raw = json.loads((Path(ref.__file__).resolve().parents[1] / "configs"
                      / "falcon-h1-34b.json").read_text())
    return ref.spec_from_config("falcon-h1-34b", raw)


@pytest.fixture(scope="module")
def case():
    from lir_tpu.models import decoder as prog

    tiny = dataclasses.replace(_load(), vocab=512, d=64, layers=3, heads=4,
                               kv_heads=2, head_dim=16, ffn=128, **SMALL)
    cfg = dataclasses.replace(builders.program_config(tiny, check=False),
                              **SMALL)
    params = builders.build_params(tiny, ref, SEED)
    toks = np.random.default_rng(0).integers(3, 512, (2, 40))
    pos = np.tile(np.arange(40), (2, 1))
    want = np.asarray(ref.logits_at(tiny, SEED, toks, pos))
    return tiny, cfg, params, toks, pos, want, prog


def test_full_size_file_matches_the_programs_preset():
    builders.program_config(_load())             # raises on any difference


def test_reference_equals_program_in_float32(case):
    tiny, cfg, params, toks, pos, want, prog = case
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32)
                       if a.dtype == jnp.bfloat16 else a, params)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(prog.forward(p32, cfg, jnp.asarray(toks)))
    assert np.abs(got - want).max() < 2e-3
    assert 1.0 < want.std() < 3.0                # logits are not flat


def test_controls_read_further_off_than_the_served_precision(case):
    tiny, cfg, params, toks, pos, want, prog = case
    served = np.abs(np.asarray(prog.forward(params, cfg, jnp.asarray(toks)))
                    - want).max()
    off = {}
    for precision in ("int8", "fp8", "bf16_state"):
        low = np.asarray(ref.logits_at(tiny, SEED, toks, pos,
                                       precision=precision))
        off[precision] = np.abs(low - want).max()
    assert off["int8"] > 1.5 * served and off["fp8"] > 4.0 * served, off
    # The state control rounds one tensor and reads far nearer: reported
    # against the limits (PERF.md §4), it sets none.
    assert 0.0 < off["bf16_state"] < off["fp8"], off


def test_scan_and_step_counts_against_hand_worked_numbers():
    spec = _load()
    # 32 heads x 128 x 256 = 1,048,576 state elements a row and layer.
    f, b = ssm.ssm_step_call(spec, 40)
    assert f == 5 * 1_048_576 * 40 == 209_715_200
    # state 2 x 40 x 4 MiB; x, y 2 x 40 x 4096 x 2; B, C 2 x 40 x 512 x 2;
    # dt 40 x 32 x 4.
    assert b == 335_544_320 + 655_360 + 81_920 + 5_120 == 336_286_720
    v5e = peaks.peaks_for("TPU v5e")
    least, bound = flops.roofline_seconds(f, b, v5e)
    assert bound == "memory" and abs(least - 410.6e-6) < 0.1e-6
    # One window: 40 rows of 384 tokens.
    f, b = ssm.scan_window(spec, 40, 40 * 384)
    assert f == 5 * 1_048_576 * 15_360 == 80_530_636_800
    per_token = 2 * 4096 * 2 + 2 * 512 * 2 + 32 * 4          # 18,560
    assert b == per_token * 15_360 + 335_544_320 == 620_625_920
    # A group's four calls: remainder (above), trunk of 64 at one row,
    # two suffixes of 20 tokens a row; the mean of the four.
    fm, bm = ssm.ssd_scan_call(spec, 40, 448, trunk=64, suffix=20.0)
    trunk = (5 * 1_048_576 * 64, per_token * 64 + 2 * 4_194_304)
    sfx = (5 * 1_048_576 * 800, per_token * 800 + 335_544_320)
    assert fm == (f + trunk[0] + 2 * sfx[0]) / 4
    assert bm == (b + trunk[1] + 2 * sfx[1]) / 4
    # Without a trunk or suffixes it is the one window.
    assert ssm.ssd_scan_call(spec, 40, 384) == (f, b)


def test_reader_is_silent_without_a_mixer_or_a_matching_operation():
    spec = _load()
    ctx = {"spec": spec, "trace": {"ops": {"fusion.1 f32[2]": (1.0, 3)}},
           "window": {"kernel_calls": {"ssm_step_call": [
               {"batch": 40, "dispatches": 9}]}},
           "peaks": peaks.peaks_for("TPU v5e")}
    args = ssm.METRICS["ssm_step_roofline"]["args"]
    assert ssm.trace_ssm_roofline(ctx, **args) is None
    ctx["trace"]["ops"]["ssm_step (bf16[40,1,4096]"] = (0.5e-3 * 96, 96)
    got = ssm.trace_ssm_roofline(ctx, **args)
    assert abs(got - 100 * 410.6e-6 / 0.5e-3) < 0.1
    assert ssm.trace_ssm_roofline(dict(ctx, spec=object()), **args) is None


def test_reader_weights_each_dispatch_shape_by_its_calls():
    """One dispatch of the 5 originals beside 9 groups of 40: a call's
    least time is the mean over the 10 dispatches' calls, 36.5 rows'
    worth of state and not 40."""
    spec = _load()
    v5e = peaks.peaks_for("TPU v5e")
    shapes = [{"batch": 5, "dispatches": 1}, {"batch": 40, "dispatches": 9}]
    ctx = {"spec": spec, "peaks": v5e,
           "trace": {"ops": {"ssm_step.14 (bf16[40,1,4096]": (0.4, 864),
                             "ssm_step.14 (bf16[8,1,4096]": (0.02, 96)}},
           "window": {"kernel_calls": {"ssm_step_call": shapes}}}
    got = ssm.trace_ssm_roofline(ctx, **ssm.METRICS["ssm_step_roofline"
                                                     ]["args"])
    per_row = 8_407_168 / v5e.hbm_bytes_per_s        # 336,286,720 / 40
    assert abs(got - 100 * 960 * 36.5 * per_row / 0.42) < 1e-6
    # The scan: an originals' dispatch has three calls (no trunk), a
    # group's has four; 1 x 3 + 9 x 4 = 39 calls, each at its own size.
    scan = [dict(shapes[0], length=100.0, trunk=0, suffix=20.0),
            dict(shapes[1], length=448.0, trunk=64, suffix=20.0)]
    ctx["window"]["kernel_calls"]["ssd_scan_call"] = scan
    ctx["trace"]["ops"]["ssd_scan.42 (bf16[40,384,4096]"] = (0.25, 312)
    got = ssm.trace_ssm_roofline(ctx, **ssm.METRICS["ssd_scan_roofline"
                                                     ]["args"])
    least = lambda rows, tokens: flops.roofline_seconds(  # noqa: E731
        *ssm.scan_window(spec, rows, tokens), v5e)[0]
    want = (least(5, 500) + 2 * least(5, 100)
            + 9 * (least(40, 15_360) + least(1, 64) + 2 * least(40, 800)))
    assert abs(got - 100 * 312 * (want / 39) / 0.25) < 1e-6


def test_window_calls_size_the_originals_and_the_groups():
    from harness import traffic

    spec = _load()
    mix = json.loads((Path(ref.__file__).resolve().parents[1] / "traffic"
                      / "sweep-trunk512.json").read_text())
    prompts = traffic.load_prompts(mix)
    perts = traffic.sweep_groups(mix, prompts, SEED, 2, stream=0)
    calls = ssm.window_calls(spec, mix, prompts, perts)
    orig, group = calls["ssd_scan_call"]
    assert orig["batch"] == len(prompts) and orig["dispatches"] == 1
    assert orig["trunk"] == 0 and orig["length"] < 200
    assert group["batch"] == 40 and group["trunk"] == 64
    assert group["dispatches"] == sum(len(m) for m in perts) / 40
    assert 400 < group["length"] <= 512 and 8 <= group["suffix"] <= 32
    assert calls["ssm_step_call"] == [
        {"batch": s["batch"], "dispatches": s["dispatches"]}
        for s in (orig, group)]
    assert ssm.window_calls(spec, mix, [], []) == {}
