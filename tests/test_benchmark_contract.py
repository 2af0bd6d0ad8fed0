"""The benchmark's contract with its reference modules, in tier-1.

The cases of ``benchmarks/tests/test_contract.py`` (the weight tree, the
preset's fields one by one, a toy family of two kinds of layer, the FLOP
counts) are imported so that the driver's run counts them, and the
``sala`` family (references/sala.py, harness/sala.py: layers that differ in
kind, four kernels) is driven through the same doors: ``load_files``,
``program_config``, ``build_params``, ``tokens_flops`` over two rows of
``layer_costs``, ``trace_kernel_roofline`` with ``module: "sala"``.
"""

import dataclasses
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
for p in (REPO / "benchmarks", REPO / "benchmarks" / "tests"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from test_contract import *  # noqa: E402,F401,F403 (the cases, the fixture)
from test_contract import SEED  # noqa: E402

import run as bench_run  # noqa: E402
from harness import builders, flops, peaks, readers, sala, traffic  # noqa: E402
from references import sala as ref  # noqa: E402

CELL = {"name": "minicpm-sala.sweep-doc16k", "config": "minicpm-sala",
        "traffic": "sweep-doc16k"}


@pytest.fixture(scope="module")
def files():
    return bench_run.load_files(CELL)


def test_the_cell_is_found_by_its_names(files):
    bench, cell = bench_run.load_cell(CELL["name"])
    assert cell["chips"] == 1 and cell["config"] == "minicpm-sala"
    assert files["ref"] is ref and files["spec"].preset == "minicpm-sala"
    assert files["runtime"] == {"batch_size": 40, "max_seq_len": 16512,
                                "dispatch_tokens": 16384,
                                "sweep_group_min_cells": 0,
                                "donate_first": True}
    assert files["mix"]["head_words"] == 16000
    assert files["mix"]["rephrasing_words"] == 16128
    assert set(files["limits"]) == {"logprob_gap", "token_gap",
                                    "min_served_tokens"}
    reports = [m["name"] for m in bench["per_layer"]
               if CELL["name"] in m["workloads"]]
    assert len(reports) == 20 and {
        "lightning_scan_roofline", "lightning_step_roofline",
        "sparse_prefill_roofline", "sparse_decode_roofline",
        "sparse_blocks_kept_pct.sweep", "trunk_prefill_share_pct.sweep",
        "trunk_held_dispatch_pct.sweep", "fill_hidden_pct.sweep",
        "recurrent_state_share_pct.sweep", "state_forks_per_dispatch.sweep",
        "step_mfu_pct.sweep"} <= set(reports)
    for name in reports:
        assert (bench_run.HERE / "metrics" / f"{name}.json").exists(), name


def test_the_configuration_is_the_published_one(files):
    raw = json.loads((bench_run.HERE / "configs" / "minicpm-sala.json"
                      ).read_text())
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(r for r in map(json.loads, catalog.read_text()
                                  .splitlines())
                   if r["name"] == "MiniCPM-SALA")
        assert raw["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert raw[key] == value, key
    assert raw["reduced"] == [] and raw["left_out"].startswith("nothing")
    assert {"sparse_config", "lightning_decay", "mup_denominator",
            "max_position_embeddings", "use_output_norm"} <= set(
        raw["assumed"])
    spec = files["spec"]
    assert spec.kinds.count("sparse") == 8 and spec.layers == 32
    assert builders.program_config(spec, ref).n_layers == 32
    fields = ref.program_fields(spec)
    assert "pos_embedding" not in fields and fields["attn_rope"] is False
    assert len(fields) == 32


def test_the_served_tree_is_a_group_a_kind(files):
    from lir_tpu.models.quant import QuantTensor

    tiny = ref.tiny(files["spec"])
    params = builders.build_params(tiny, ref, SEED)
    groups = params["layers"]
    assert set(groups) == {"sparse", "lightning"}
    assert groups["sparse"]["wq"].q.shape == (2, 64, 64)
    assert groups["sparse"]["wk"].q.shape == (2, 64, 32)       # 2 kv heads
    assert groups["lightning"]["wk"].q.shape == (2, 64, 64)
    assert isinstance(groups["lightning"]["wg"], QuantTensor)
    assert "o_norm" in groups["lightning"] and "o_norm" not in groups["sparse"]
    assert groups["sparse"]["q_norm"].dtype == jnp.bfloat16
    again = builders.build_params(tiny, ref, SEED)
    assert (np.asarray(groups["lightning"]["wo"].q)
            == np.asarray(again["layers"]["lightning"]["wo"].q)).all()
    other = builders.build_params(tiny, ref, SEED + 1)
    assert (np.asarray(groups["sparse"]["wq"].q)
            != np.asarray(other["layers"]["sparse"]["wq"].q)).any()
    one = dataclasses.replace(tiny, kinds=("lightning", "sparse"))
    assert builders.build_params(one, ref, SEED)["layers"]["sparse"][
        "wq"].q.shape == (1, 64, 64)


@pytest.mark.parametrize("kind", ["sparse", "lightning"])
def test_the_reference_makes_a_layer_as_it_is_served(files, kind):
    """``layer_made`` (a payload a program, for the reference's passes) is
    leaf for leaf the layer the served tree holds."""
    import jax

    tiny = ref.tiny(files["spec"])
    served = jax.jit(ref.weights, static_argnums=(0,))(
        tiny, ref.seed_key(SEED))["layers"][kind]
    made = ref.layer_made(tiny, ref.seed_key(SEED), kind, 1)
    assert jax.tree.structure(made) == jax.tree.structure(served)
    for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(served)):
        assert a.dtype == b.dtype
        assert (np.asarray(a) == np.asarray(b[1])).all()


def test_the_reference_product_is_float32_of_the_exact_one():
    """Three bfloat16 terms of the activation against the int8 payload:
    the float64 product to float32's rounding, which one bfloat16 pass
    misses by a thousand times as much."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((8, 512)).astype(np.float32)
    w = {"q": jnp.asarray(rng.integers(-127, 128, (512, 64)), jnp.int8),
         "scale": jnp.full((64,), 0.01, jnp.float32)}
    exact = x.astype(np.float64) @ np.asarray(w["q"], np.float64) * 0.01
    got = np.asarray(ref._mm(jnp.asarray(x), w, "float32"), np.float64)
    one = np.asarray(jnp.matmul(
        jnp.asarray(x).astype(jnp.bfloat16), w["q"].astype(jnp.bfloat16),
        preferred_element_type=jnp.float32), np.float64) * 0.01
    assert np.abs(got - exact).max() < 2e-5 * np.abs(exact).max()
    assert np.abs(one - exact).max() > 1e-3 * np.abs(exact).max()


def test_flops_sum_over_the_two_kinds_of_layer(files):
    spec = files["spec"]
    (ls, ps, ws, ss), (ll, pl_, wl, sl) = spec.layer_costs
    assert (ls, ll, ws, wl, ss) == (8, 24, 32 * 256, 0, 0)
    assert ps == 4096 * (3 * 4096 + 2 * 256) + 3 * 4096 * 16384
    assert pl_ == 5 * 4096 * 4096 + 3 * 4096 * 16384
    assert sl == 5 * 32 * 128 * 128
    # Token 16000 alone (it attends 16001 keys), by hand.
    want = (8 * (2 * ps + 2 * 8192 * 16001) + 24 * (2 * pl_ + sl))
    assert flops.tokens_flops(spec, 16000, 16001) == want
    # A whole cell: the trunk is someone else's.
    cell = flops.scoring_cell_flops(spec, 16128, 16160, 16170, 4, 8,
                                    trunk=16000)
    assert cell == (flops.tokens_flops(spec, 16000, 16128)
                    + flops.tokens_flops(spec, 16128, 16163)
                    + flops.tokens_flops(spec, 16128, 16177)
                    + 2.0 * 4096 * 73448 * 12)


def test_kept_keys_are_counted_from_positions(files):
    spec = files["spec"]
    keys, kernels = sala.kept_keys(spec, [0, 100, 8191, 8192, 16000, 16127,
                                          16199], 16000)
    # Dense up to 8192 tokens of context; then first block + the blocks
    # reaching into the last 2048 positions + 64 others.
    assert list(keys[:3]) == [1, 101, 8192] and list(kernels[:3]) == [0] * 3
    assert keys[3] == 64 + 64 * 64 + (8192 + 1 - 96 * 64)
    assert kernels[3] == (8192 - 32) // 16 + 1
    # A window query behind the 16,000-token trunk: its own 128 keys too.
    assert keys[5] == 64 + 64 * 64 + (16000 - 220 * 64) + 128
    assert keys[6] == 64 + 64 * 64 + (16000 - 221 * 64) + 200
    program = __import__("lir_tpu.ops.sparse_attention",
                         fromlist=["kept_blocks"])
    for p in (8192, 12345, 15999):      # whole blocks: the program's count
        kept, _, _ = program.kept_blocks(
            [p], 16000, block=64, topk=64, init_blocks=1, window=2048,
            dense_len=8192)
        assert -(-int(sala.kept_keys(spec, [p], 16000)[0][0]) // 64) == kept


def test_the_four_kernels_are_sized_from_the_traffic(files):
    spec, mix = files["spec"], files["mix"]
    prompts = traffic.load_prompts(mix)
    assert len(prompts) == 5
    assert all(len(p.main.split()) > 16000 for p in prompts)
    perts = [[p.main] * 40 if i == 0 else [] for i, p in enumerate(prompts)]
    calls = sala.window_calls(spec, mix, prompts, perts, (4, 8))
    assert set(calls) == set(sala.CALLS)
    originals, groups = calls["sparse_decode_call"]
    assert originals["rows"] == 1 and originals["dispatches"] == 5
    assert groups["rows"] == 40 and groups["trunk"] == 16000
    assert groups["dispatches"] == 1
    sizes = {k: v for k, v in groups.items() if k != "dispatches"}
    scan = sala.CALLS["lightning_scan_call"](spec, **sizes)
    assert len(scan) == 4                   # windows, trunk, two suffixes
    state = 32 * 128 * 128
    assert scan[1] == (5.0 * state * 16000,
                       (4 * 4096 * 2 + 128) * 16000 + 2.0 * state * 4)
    step = sala.CALLS["lightning_step_call"](spec, **sizes)
    assert step == [(5.0 * state * 40, (4 * 4096 * 2 + 128) * 40
                     + 2.0 * 40 * state * 4)]
    assert len(sala.CALLS["sparse_decode_call"](spec, **sizes)) == 12
    assert len(sala.CALLS["sparse_prefill_call"](spec, **sizes)) == 4
    # A family without these layers sizes nothing.
    other = bench_run.load_files({"name": "x", "config": "mistral-7b",
                                  "traffic": "sweep-trunk512"}, limits={})
    assert sala.window_calls(other["spec"], mix, prompts, perts, (4, 8)) == {}


def test_a_roofline_reads_the_familys_own_module(files):
    spec, mix = files["spec"], files["mix"]
    prompts = traffic.load_prompts(mix)
    perts = [[p.main] * 40 if i == 0 else [] for i, p in enumerate(prompts)]
    pk = peaks.peaks_for("TPU v5e")
    ctx = {"spec": spec, "peaks": pk,
           "trace": {"ops": {"lightning_step.7 (bf16[40,1,4096]": (0.5, 288),
                             "ssm_step.2": (9.0, 9)}},
           "traffic": {"mix": mix, "prompts": prompts, "perts": perts,
                       "steps": (4, 8)}}
    metric = json.loads((bench_run.HERE / "metrics" /
                         "lightning_step_roofline.json").read_text())
    assert metric["args"]["module"] == "sala"
    got = readers.READERS[metric["reader"]](ctx, **metric["args"])
    state = 32 * 128 * 128

    def least(rows):
        return max(5.0 * state * rows / pk.bf16_flops,
                   ((4 * 4096 * 2 + 128) * rows + 8.0 * rows * state)
                   / pk.hbm_bytes_per_s)

    mean = (5 * least(1) + 1 * least(40)) / 6
    assert got == pytest.approx(100.0 * 288 * mean / 0.5)
    out = readers.read_all([{"name": "sparse_decode_roofline", "unit": "%"},
                            {"name": "lightning_step_roofline", "unit": "%"}],
                           ctx)
    assert set(out) == {"lightning_step_roofline"}     # no sparse op traced
