"""The yardstick's own arithmetic: peaks, FLOPs and bytes, traffic."""

import dataclasses

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
