"""``references/mla_moe.py``, ``harness/mla_moe.py`` and the cell
``kimi-k2-6.sweep-doc16k`` (run by hand, with the other tests of this
directory): the family contract (``program_fields``, the served tree,
``layer_costs`` through ``flops.tokens_flops``), the two kernels' sizes
from the traffic against hand-worked numbers, a roofline read through the
family's own module, a document rows share passes once and changes nothing,
and a whole run of the cell at a tiny size answers ``correct``.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
import tiny
from harness import builders, flops, mla_moe as sizing, peaks, readers
from harness import traffic
from references import mla_moe as ref

SEED = 2**31 + 41
CELL = {"name": "kimi-k2-6.sweep-doc16k", "config": "kimi-k2-6",
        "traffic": "sweep-doc16k"}


@pytest.fixture(scope="module")
def files():
    return bench_run.load_files(CELL)


def test_the_cell_is_found_by_its_names(files):
    bench, cell = bench_run.load_cell(CELL["name"])
    assert cell["chips"] == 1 and cell["traffic"] == "sweep-doc16k"
    assert files["ref"] is ref and files["spec"].preset == "kimi-k2-6-l13"
    assert files["runtime"]["dispatch_tokens"] == 16384
    reports = {m["name"] for m in bench["per_layer"]
               if CELL["name"] in m.get("workloads", [])}
    assert {"latent_prefill_roofline", "latent_decode_roofline",
            "latent_keys_expanded_pct.sweep", "trunk_held_dispatch_pct.sweep",
            "expert_rows_padded_pct.sweep", "hbm_peak_pct.sweep"} <= reports
    # exaone_moe's module sizes a trunk a group, not a trunk held: left off.
    assert not {"expert_matmul_roofline", "cascade_prefill_roofline",
                "decode_kernel_roofline"} & reports
    for name in reports:
        assert (bench_run.HERE / "metrics" / f"{name}.json").exists(), name


def test_program_fields_name_every_published_width(files):
    spec = files["spec"]
    fields = ref.program_fields(spec)
    assert (fields["q_lora_rank"], fields["kv_lora_rank"],
            fields["qk_nope_dim"], fields["qk_rope_dim"],
            fields["v_head_dim"], fields["head_dim"]) == (
                1536, 512, 128, 64, 128, 192)
    assert fields["rope_scaling"] == (64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert fields["experts_held"] == (0, 12) and fields["num_experts"] == 384
    cfg = builders.program_config(spec, ref)
    for key, value in fields.items():
        assert getattr(cfg, key) == value, key


def test_the_served_tree_is_what_the_program_reads(files):
    from lir_tpu.models.quant import QuantTensor

    spec = ref.tiny(files["spec"])
    params = builders.build_params(spec, ref, SEED)
    assert set(params) == {"tok_embed", "final_ln", "lm_head",
                           "dense_layers", "layers"}
    layers = params["layers"]
    assert layers["wkv_b"].q.shape == (2, 16, 4 * (16 + 16))
    assert layers["wkv_a"].q.shape == (2, 64, 16 + 8)
    assert layers["q_a_norm"].shape == (2, 32)
    assert layers["experts"]["w_gate"].q.shape == (2, 4, 64, 32)
    assert isinstance(layers["shared"]["w_down"], QuantTensor)
    assert layers["router"]["w"].dtype == jnp.float32
    assert params["dense_layers"]["w_up"].q.shape == (1, 64, 128)


def test_flops_sum_over_the_two_rows(files):
    spec = files["spec"]
    (ld, pd, wd, _), (lr, pr, wr, _) = spec.layer_costs
    assert (ld, lr) == (1, 12) and wd == wr == 64 * (192 + 128)
    want = (2 * pd + 2 * wd * 401) + 12 * (2 * pr + 2 * wr * 401)
    assert flops.tokens_flops(spec, 400, 401) == want


def test_the_two_kernels_are_sized_from_the_traffic(files):
    spec, mix = files["spec"], files["mix"]
    prompts = traffic.load_prompts(mix)
    perts = [[p.main] * 40 if i == 0 else [] for i, p in enumerate(prompts)]
    calls = sizing.window_calls(spec, mix, prompts, perts, (4, 8))
    assert set(calls) == set(sizing.CALLS)
    trunk, originals, groups = calls["latent_prefill_call"]
    assert (trunk["rows"], trunk["shared"], trunk["dispatches"]) == (
        1, 16000, 5)
    assert originals["held"] and groups["rows"] == 40
    # The trunk over itself: 2 * 64 * 320 a causal pair, 16000 * 16001 / 2
    # pairs; its queries, outputs, keys and values each once.
    (f, b), = sizing.prefill_calls(spec, 1, 16000, 0, (), (0, 0))
    assert f == 2.0 * 64 * 320 * 16000 * 16001 / 2
    assert b == 2.0 * 64 * 320 * 16000 * 2
    # 40 rows of 128 behind the trunk, then suffixes of 10 and 20.
    window, sa, sb = sizing.prefill_calls(spec, 40, 16128, 16000, (10, 20),
                                          (4, 8), held=True)
    keys = 40 * (16128 * 16129 - 16000 * 16001) / 2
    assert window[0] == 2.0 * 64 * 320 * keys
    assert window[1] == 2.0 * 64 * 320 * (40 * 128 + 16000 + 40 * 128)
    assert sb[1] == 2.0 * 64 * 320 * (40 * 20 + 16000 + 40 * 148)
    # A decode step of 40 rows over the trunk: five blocks of 8 rows, the
    # latent read once each.
    d = sizing.decode_calls(spec, 40, 16128, 16000, (10, 20), (4, 8))
    assert len(d) == 12
    assert d[0][0] == 2.0 * 40 * 64 * 16000 * (576 + 512)
    assert d[0][1] == (2.0 * 576 * 16000 * 5 + 2.0 * 40 * 64 * 576
                       + 4.0 * 40 * 64 * 514)
    one = sizing.decode_calls(spec, 1, 16128, 16000, (10, 20), (1, 0))
    assert one[0][1] == (2.0 * 576 * 16000 + 2.0 * 64 * 576
                         + 4.0 * 64 * 514)
    # The trunk's own pass decodes nothing.
    assert [s["rows"] for s in calls["latent_decode_call"]] == [1, 40]
    other = bench_run.load_files({"name": "x", "config": "mistral-7b",
                                  "traffic": "sweep-trunk512"}, limits={})
    assert sizing.window_calls(other["spec"], mix, prompts, perts,
                               (4, 8)) == {}


def test_a_roofline_reads_the_familys_own_module(files):
    spec, mix = files["spec"], files["mix"]
    prompts = traffic.load_prompts(mix)
    perts = [[p.main] * 40 if i == 0 else [] for i, p in enumerate(prompts)]
    ctx = {"spec": spec, "peaks": peaks.peaks_for("TPU v5e"),
           "trace": {"ops": {"latent_prefill.3 (bf16[": (60.0, 13 * 8),
                             "latent_decode.4 (f32[": (5.0, 13 * 96)}},
           "traffic": {"mix": mix, "prompts": prompts, "perts": perts,
                       "steps": (4, 8)}}
    out = readers.read_all([{"name": "latent_prefill_roofline", "unit": "%"},
                            {"name": "latent_decode_roofline", "unit": "%"}],
                           ctx)
    assert set(out) == {"latent_prefill_roofline", "latent_decode_roofline"}
    for v in out.values():
        assert 0 < v["value"] < 100


def test_a_shared_document_passes_once_and_changes_nothing():
    raw = json.loads((bench_run.HERE / "configs" / "kimi-k2-6.json"
                      ).read_text())
    spec = ref.tiny(ref.spec_from_config("kimi-k2-6", raw))
    rng = np.random.default_rng(4)
    doc = rng.integers(3, spec.vocab, 1040)
    rows = np.stack([np.concatenate([doc, rng.integers(3, spec.vocab, 24)])
                     for _ in range(3)]).astype(np.int32)
    pos = np.tile(np.arange(1040, 1064)[None], (3, 1))
    shared = ref.logits_at(spec, SEED, rows, pos)
    alone = ref.unembed(spec, ref.top_made(spec, ref.seed_key(SEED)),
                        jnp.take_along_axis(
                            ref.forward_rows(spec, ref.seed_key(SEED),
                                             jnp.asarray(rows)),
                            jnp.asarray(pos)[:, :, None], axis=1))
    # float32 both ways, the document's keys passed once or each row's.
    np.testing.assert_allclose(shared, alone, atol=1e-4)


def test_a_tiny_run_of_the_cell_is_correct(monkeypatch):
    """The cell at a tiny size through the timed path, instruments cut to
    384 words (a trunk held, 4-row groups), kernels interpreted."""
    from lir_tpu.models import decoder

    monkeypatch.setattr(decoder, "CASCADE_INTERPRET_ON_CPU", True)
    monkeypatch.setattr(decoder, "LATENT_INTERPRET_ON_CPU", True)
    cell, bench, files = tiny.files_for("kimi-k2-6", "sweep-doc16k")
    files["mix"] = dict(files["mix"], head_words=384, rephrasing_words=392,
                        group_rows=4, max_groups_per_prompt=2,
                        window_groups=6, reference_rows=4)
    files["runtime"] = dict(files["runtime"], batch_size=4, max_seq_len=768,
                            dispatch_tokens=1000)
    files["limits"] = dict(files["limits"], logprob_gap=0.2, token_gap=0.2)
    monkeypatch.setattr(
        bench_run.sys.modules["harness.traffic"], "load_prompts",
        lambda mix, _real=bench_run.sys.modules[
            "harness.traffic"].load_prompts: [
            dataclasses.replace(p, main=" ".join(
                p.main.split()[:384] + p.main.split()[16000:]))
            for p in _real(mix)])
    got = bench_run.drive(cell, bench, files, SEED + 7, 2.0, False,
                          jax.devices()[:1], check_config=False)
    assert got["correct"], got["compared"]
    assert got["attempted"] > 0 and got["failed"] == 0
