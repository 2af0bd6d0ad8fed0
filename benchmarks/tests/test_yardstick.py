"""The yardstick's own arithmetic: peaks, FLOPs and bytes, traffic."""

import dataclasses
import shutil

import numpy as np
import pytest

from harness import flops, peaks, readers, tokenizer, traffic
from references import decoder as ref


def test_peaks_refuse_unknown_device():
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
    assert peaks.peaks_for("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9000")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


@pytest.mark.parametrize("name,per_layer,total_b", [
    # wq 4096*4096 + wk,wv 2*4096*1024 + wo 4096*4096 + 3*4096*14336
    ("mistral-7b", 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336,
     6.979),
    # wq 4544*4544 + wk,wv 2*4544*64 + wo 4544*4544 + 2*4544*18176
    ("falcon-7b", 2 * 4544 * 4544 + 2 * 4544 * 64 + 2 * 4544 * 18176, 6.626),
])
def test_layer_parameters_by_hand(name, per_layer, total_b):
    spec, _ = ref.load(name)
    assert spec.layer_matmul_params == per_layer
    assert round(32 * per_layer / 1e9, 3) == total_b


def test_token_flops_by_hand():
    spec, _ = ref.load("mistral-7b")
    # one token at position 0: 2 * params + 4 * 32 * 128 * 1 a layer
    one = 32 * (2 * spec.layer_matmul_params + 4 * 32 * 128)
    assert flops.tokens_flops(spec, 0, 1) == one
    # positions 2 and 3 attend 3 and 4 keys
    two = 32 * (2 * 2 * spec.layer_matmul_params + 4 * 32 * 128 * 7)
    assert flops.tokens_flops(spec, 2, 4) == two
    assert flops.logits_flops(spec, 3) == 3 * 2 * 4096 * 32000
    # a cell: 10 shared, binary 12, confidence 13, 4 and 8 new tokens
    cell = (flops.tokens_flops(spec, 0, 10) + flops.tokens_flops(spec, 10, 15)
            + flops.tokens_flops(spec, 10, 20) + flops.logits_flops(spec, 12))
    assert flops.scoring_cell_flops(spec, 10, 12, 13, 4, 8) == cell
    # the group's trunk is someone else's
    assert (flops.scoring_cell_flops(spec, 10, 12, 13, 4, 8, trunk=6)
            == cell - flops.tokens_flops(spec, 0, 6))


def test_kernel_shapes_by_hand():
    m, _ = ref.load("mistral-7b")
    f, b = flops.decode_attention_call(m, batch=40, extent=500)
    assert f == 4 * 40 * 32 * 128 * 500
    assert b == 2 * 40 * 8 * 128 * 500 * 2 + 2 * 40 * 32 * 128 * 2
    fa, _ = ref.load("falcon-7b")
    f, b = flops.decode_attention_call(fa, batch=40, extent=500)
    assert f == 4 * 40 * 71 * 64 * 500
    assert b == 2 * 40 * 1 * 64 * 500 * 2 + 2 * 40 * 71 * 64 * 2
    # 40 rows of 100 keys sharing their first 20: 20 + 40 * 80 key rows
    f, b = flops.decode_attention_call(fa, batch=40, extent=100, trunk=20)
    assert f == 4 * 40 * 71 * 64 * 100
    assert b == 2 * 64 * 2 * (20 + 40 * 80) + 2 * 40 * 71 * 64 * 2
    # cascade prefill, 4 rows of 10 queries, 3 shared: 6 + 4 * (55 - 6)
    f, b = flops.cascade_prefill_call(m, batch=4, length=10, trunk=3)
    assert f == 4 * 32 * 128 * (6 + 4 * 49)
    assert b == (2 * 8 * 128 * 2 + 2 * 32 * 128 * 2) * (3 + 4 * 7)
    pk = peaks.peaks_for("TPU v5e")
    t, bound = flops.roofline_seconds(197e12, 1.0, pk)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.roofline_seconds(1.0, 819e9, pk)
    assert (t, bound) == (1.0, "memory")


def test_tokenizer_restates_the_programs_rule():
    from lir_tpu.backends.fake import FakeTokenizer

    tok = FakeTokenizer(vocab=32000)
    text = "Yes the insurer No shall pay 42 percent"
    assert tokenizer.encode(text, 32000) == tok(text).input_ids
    ids = [1, 2, 77, 31999]
    assert tokenizer.served_ids(tok.decode(ids)) == ids


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_traffic_repeats_from_a_seed(seed):
    mix = traffic.load_mix("sweep-trunk512")
    prompts = traffic.load_prompts(mix)
    a = traffic.sweep_groups(mix, prompts, seed, 7, stream=2)
    b = traffic.sweep_groups(mix, prompts, seed, 7, stream=2)
    assert a == b
    assert a != traffic.sweep_groups(mix, prompts, seed + 1, 7, stream=2)
    assert a != traffic.sweep_groups(mix, prompts, seed, 7, stream=1)
    assert sum(len(p) for p in a) == 7 * mix["group_rows"]
    assert all(len(p) % mix["group_rows"] == 0 for p in a)
    assert max(len(p) for p in a) == (mix["max_groups_per_prompt"]
                                      * mix["group_rows"])
    for p, mains in zip(prompts, a):
        head = p.main.split()[:mix["head_words"]]
        assert all(m.split()[:len(head)] == head and
                   len(m.split()) == mix["rephrasing_words"] for m in mains)
        assert len(set(mains)) == len(mains)


SWEEP_MIXES = traffic.mix_names("sweep")


def _deal(mix, dealt):
    return [len(mains) // mix["group_rows"] for mains in dealt]


@pytest.mark.parametrize("name", SWEEP_MIXES)
@pytest.mark.parametrize("key", ["window_groups", "trace_groups"])
def test_two_seeds_are_dealt_the_same_window_in_other_words(name, key):
    """The mix fixes the window; the seed changes only the words: equal
    groups a prompt in the same grid order, the anchor full, the groups
    that are left dealt to the other prompts by index."""
    mix = traffic.load_mix(name)
    prompts = traffic.load_prompts(mix)
    a, b = (traffic.sweep_groups(mix, prompts, seed, mix[key], stream=2)
            for seed in (11, 2**31 + 5))
    assert _deal(mix, a) == _deal(mix, b)
    assert sum(_deal(mix, a)) == mix[key]
    cap = mix["max_groups_per_prompt"]
    anchor = _deal(mix, a).index(cap)
    others = [n for i, n in enumerate(_deal(mix, a)) if i != anchor]
    assert others == sorted(others, reverse=True)       # index order
    assert max(others) - min(others) <= 1 or max(others) == cap
    for p, rows_a, rows_b in zip(prompts, a, b):
        head = p.main.split()[:mix["head_words"]]
        for m in rows_a[:2] + rows_b[:2]:
            assert m.split()[:len(head)] == head
        assert not set(rows_a) & set(rows_b)            # other words


def test_the_window_sizes_are_the_ones_the_cells_were_measured_at():
    trunk = traffic.load_mix("sweep-trunk512")
    assert (trunk["window_groups"], trunk["trace_groups"]) == (15, 6)
    assert trunk["window_groups"] == 3 * 5              # the cap: 605 cells
    doc = traffic.load_mix("sweep-doc16k")
    assert (doc["window_groups"], doc["trace_groups"]) == (7, 7)


def test_the_long_document_window_holds_no_prompt_without_a_group():
    """A lone original was the cut's doing, not the users': the researcher
    sweeps rephrasings of every prompt."""
    mix = traffic.load_mix("sweep-doc16k")
    prompts = traffic.load_prompts(mix)
    for key in ("window_groups", "trace_groups"):
        dealt = traffic.sweep_groups(mix, prompts, 3, mix[key], stream=2)
        assert _deal(mix, dealt) == [1, 3, 1, 1, 1]
        assert sum(1 + len(mains) for mains in dealt) == 285


def test_the_warm_rate_sizes_no_window(monkeypatch):
    """``sweep_window.run`` at a tiny size with the timed warm pass made to
    look 0.7 and 1.4 times as fast: the same groups, cells and deal."""
    import jax

    import run as bench_run
    import tiny
    from harness import sweep_window
    from lir_tpu.models import decoder

    for hook in ("FUSED_DECODE_INTERPRET_ON_CPU", "CASCADE_INTERPRET_ON_CPU"):
        monkeypatch.setattr(decoder, hook, True)
    real = sweep_window._sweep
    windows = []
    for scale in (1.0, 0.7, 1.4):
        def faster(*args, _scale=scale):
            rows, seconds = real(*args)
            return rows, seconds / _scale

        monkeypatch.setattr(sweep_window, "_sweep", faster)
        cell, bench, files = tiny.files_for("mistral-7b", "sweep-trunk512")
        out = bench_run.HERE / ".out" / f"{cell['name']}.rate"
        if out.exists():
            shutil.rmtree(out)
        out.mkdir(parents=True)
        ctx = bench_run.Context(
            spec=files["spec"], ref=files["ref"], mix=files["mix"],
            runtime=files["runtime"], seed=9, seconds=2.0, out=out,
            check_config=False)
        window = sweep_window.run(ctx)["window"]
        windows.append(window)
        assert set(window["setup_stages"]) == {
            "imports_and_devices", "weights", "engine", "warm_pass_1",
            "plan_wait", "warm_pass_2", "window_dealt"}
        assert ctx.setup_s == pytest.approx(
            sum(window["setup_stages"].values()), abs=0.05)
    jax.clear_caches()
    for w in windows:
        assert (w["groups"], w["cells"], w["deal"]) == (
            4, 5 + 4 * 8, windows[0]["deal"])
    assert sum(windows[0]["deal"]) == 4
    assert windows[2]["warm_rate"] > 1.5 * windows[1]["warm_rate"]


def test_sweep_grid_shape_is_the_same_for_every_group_count():
    mix = traffic.load_mix("sweep-trunk512")
    prompts = traffic.load_prompts(mix)
    cap = mix["max_groups_per_prompt"]
    anchor = max(range(len(prompts)),
                 key=lambda i: len(prompts[i].response_format.split()))
    for seed in (1, 2, 3):
        for n in range(cap, cap * len(prompts) + 1):
            dealt = traffic.sweep_groups(mix, prompts, seed, n, stream=0)
            assert len(dealt[anchor]) == cap * mix["group_rows"]
            assert max(len(p) for p in dealt) == cap * mix["group_rows"]


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_serve_schedule_same_work_for_every_seed(seed):
    mix = traffic.load_mix("serve-steady")
    prompts = traffic.load_prompts(mix)
    a = traffic.serve_schedule(mix, prompts, seed, 30.0, stream=2)
    assert a == traffic.serve_schedule(mix, prompts, seed, 30.0, stream=2)
    other = traffic.serve_schedule(mix, prompts, seed + 1, 30.0, stream=2)
    assert len(a) == len(other) == round(mix["rate_per_s"] * 30.0)
    lengths = lambda s: sorted(len(x.main.split()) for x in s)  # noqa: E731
    assert lengths(a) == lengths(other)
    gaps = lambda s: np.percentile(  # noqa: E731
        np.diff([x.due_s for x in s]), [25, 50, 90, 99])
    np.testing.assert_allclose(gaps(a), gaps(other), rtol=0.05)
    assert all(0.0 <= x.due_s <= 30.0 for x in a)
    assert len({x.main for x in a}) == len(a)          # no exact repeats


def test_counter_reader_and_missing_values():
    ctx = {"counters": {"before": {"sources": {"c": {"fields": {"n": 2}}}},
                        "after": {"sources": {"c": {"fields": {"n": 12}}}}},
           "window": {"tokens": 200, "head": 4}}
    assert readers.counter(ctx, "delta:sources.c.fields.n", "window:tokens",
                           "window:head", 100.0) == 20.0
    assert readers.counter(ctx, "delta:sources.c.fields.missing") is None
    assert readers.counter(ctx, "after:sources.c.fields.n") == 12.0


def test_weights_are_the_same_layer_by_layer_and_whole():
    import jax

    from harness import builders

    spec, _ = ref.load("falcon-7b")
    tiny = dataclasses.replace(spec, vocab=512, d=71 * 2, layers=3,
                               head_dim=2, ffn=64)
    seed = 2**31 + 77
    params = builders.build_params(tiny, ref, seed)
    key = ref.seed_key(seed)
    for layer in range(tiny.layers):
        w = ref.layer_weights(tiny, key, layer)
        for name, leaf in w.items():
            got = params["layers"][name]
            if "q" in leaf:
                assert (np.asarray(got.q[layer]) == np.asarray(leaf["q"])).all()
                assert got.q.dtype == np.int8
            else:
                for k, v in leaf.items():
                    assert (np.asarray(got[k][layer]) == np.asarray(v)).all()
    assert jax.tree.leaves(params)[0].dtype is not None
