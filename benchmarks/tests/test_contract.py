"""The contract between the harness and a reference module
(``references/<family>.py``; README.md has the table): the harness asks
``program_fields``, ``weights``, ``spec.layer_costs`` and ``tiny``, and a
module that sizes kernels for ``CALLS`` and ``window_calls``; it knows no
family's field by name. Held here for the two modules the benchmark has
and for a toy family of TWO kinds of layer that exists only in this file.
"""

import copy
import dataclasses
import functools
import json
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from harness import builders, flops, peaks, readers, traffic
from references import decoder, hybrid

CONFIGS = {"mistral-7b": decoder, "falcon-7b": decoder,
           "falcon-h1-34b": hybrid}
SEED = 2**31 + 30


def _spec(config):
    raw = json.loads((bench_run.HERE / "configs" / f"{config}.json"
                      ).read_text())
    return CONFIGS[config].spec_from_config(config, raw)


# ---------------------------------------------------------------------------
# (a) weights(spec, key) is the tree the old recipe made, bit for bit
# ---------------------------------------------------------------------------

def _old_build_params(spec, ref, seed):
    """``builders.build_params`` as it stood before PR 30: the layers a
    vmap of ``layer_weights``, the top leaves beside them, one jitted
    call, a ONE-level wrap."""
    from lir_tpu.models.quant import QuantTensor

    @functools.partial(jax.jit, static_argnums=(0,))
    def make(spec, key):
        layers = jax.vmap(lambda l: ref.layer_weights(spec, key, l))(
            jnp.arange(spec.layers))
        return layers, ref.top_weights(spec, key)

    def wrap(tree):
        return {name: (QuantTensor(q=leaf["q"], scale=leaf["scale"])
                       if isinstance(leaf, dict) and "q" in leaf else leaf)
                for name, leaf in tree.items()}

    layers, top = make(spec, ref.seed_key(seed))
    params = wrap(top)
    params["layers"] = wrap(layers)
    return params


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_weights_are_the_old_recipes_bit_for_bit(config):
    ref = CONFIGS[config]
    tiny = ref.tiny(_spec(config))
    new = builders.build_params(tiny, ref, SEED)
    old = _old_build_params(tiny, ref, SEED)
    assert jax.tree.structure(new) == jax.tree.structure(old)
    for (path, a), b in zip(jax.tree.leaves_with_path(new),
                            jax.tree.leaves(old)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), path
    assert new["layers"]["wq"].q.dtype == jnp.int8
    assert new["layers"]["wq"].q.shape[0] == tiny.layers


def test_tiny_shrinks_every_width_the_family_has():
    for config, ref in CONFIGS.items():
        spec = _spec(config)
        tiny = ref.tiny(spec)
        assert tiny.layers == 2 and tiny.vocab == 2048 and tiny.d <= 142
        assert tiny.preset == spec.preset
        assert tiny.weight_bytes < 2_000_000
    tiny = hybrid.tiny(_spec("falcon-h1-34b"))
    assert (tiny.ssm_heads, tiny.ssm_head_dim, tiny.ssm_state,
            tiny.ssm_groups, tiny.ssm_conv, tiny.ssm_chunk) == (
        4, 16, 16, 2, 4, 16)                 # registry's tiny-falcon-h1


# ---------------------------------------------------------------------------
# (b) program_config compares exactly the module's fields
# ---------------------------------------------------------------------------

OTHER_WORD = {"rotary": "alibi", "rmsnorm": "layernorm",
              "layernorm": "rmsnorm", "silu": "gelu", "gelu": "relu"}


def _other(value):
    if isinstance(value, bool):
        return not value
    if isinstance(value, str):
        return OTHER_WORD[value]
    if isinstance(value, tuple):
        return (_other(value[0]),) + value[1:]
    return value * 2 + 1


FIELD_CASES = [(config, field)
               for config in ("mistral-7b", "falcon-h1-34b")
               for field in CONFIGS[config].program_fields(_spec(config))]


def test_the_field_lists_are_the_ones_the_contract_names():
    plain = set(decoder.program_fields(_spec("mistral-7b")))
    assert len(plain) == 18 and {"pos_embedding", "rotary_pct",
                                 "kv_cache_int8"} <= plain
    mixed = set(hybrid.program_fields(_spec("falcon-h1-34b")))
    assert mixed - plain == set(hybrid.MIXER_SIZES + hybrid.MULTIPLIERS)
    assert len(mixed) == 18 + 6 + 9 and plain <= mixed


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_the_real_files_match_the_programs_presets(config):
    cfg = builders.program_config(_spec(config), CONFIGS[config])
    assert cfg.n_layers == _spec(config).layers


@pytest.mark.parametrize("config,field", FIELD_CASES)
def test_a_preset_off_in_one_field_is_refused(monkeypatch, config, field):
    from lir_tpu.models import registry

    spec, ref = _spec(config), CONFIGS[config]
    preset = registry.REGISTRY[spec.preset]()
    off = copy.copy(preset)      # not ``replace``: the program's own checks
    object.__setattr__(off, field, _other(getattr(preset, field)))
    monkeypatch.setitem(registry.REGISTRY, spec.preset, lambda: off)
    with pytest.raises(ValueError, match=rf"has {field}="):
        builders.program_config(spec, ref)
    # Unchecked (the tests' shrunk sizes), the preset takes the file's word
    # in exactly that field again.
    assert builders.program_config(spec, ref, check=False) == preset


# ---------------------------------------------------------------------------
# (c) a family of two kinds of layer, known to no file of the harness
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ToySpec:
    name: str
    preset: str
    vocab: int
    d: int
    keyed_layers: int        # kind A: attends keys (q, k of 16; v of 8)
    state_layers: int        # kind B: keeps no keys, a state of 7 FLOPs

    @property
    def layer_costs(self) -> tuple:
        return ((self.keyed_layers, 1000, 2 * (16 + 8), 0),
                (self.state_layers, 500, 0, 7))


def _toy_reference() -> types.ModuleType:
    mod = types.ModuleType("references.toy_two_kinds")

    def spec_from_config(name, raw):
        return ToySpec(name, raw["lir_tpu"]["preset"], raw["vocab_size"],
                       raw["hidden_size"], raw["keyed_layers"],
                       raw["state_layers"])

    def program_fields(spec):
        return {"vocab_size": spec.vocab, "hidden_size": spec.d,
                "n_layers": spec.keyed_layers + spec.state_layers}

    def int8(key, n, shape):
        return {"q": jax.random.randint(key, (n,) + shape, -127, 127,
                                        jnp.int8),
                "scale": jnp.full((n, shape[-1]), 0.01, jnp.float32)}

    def weights(spec, key):
        ka, kb, ke = jax.random.split(key, 3)
        return {
            "tok_embed": jax.random.normal(ke, (spec.vocab, spec.d)
                                           ).astype(jnp.bfloat16),
            "layers": {
                "a": {"wq": int8(ka, spec.keyed_layers, (spec.d, 16)),
                      "ln": {"scale": jnp.ones((spec.keyed_layers, spec.d),
                                               jnp.bfloat16)}},
                "b": {"mlp": {"experts": {"w_up": int8(
                    kb, spec.state_layers, (4, spec.d, 8))}},
                      "decay": jnp.zeros((spec.state_layers, 3),
                                         jnp.bfloat16)}}}

    mod.spec_from_config, mod.program_fields = spec_from_config, program_fields
    mod.weights, mod.seed_key = weights, decoder.seed_key
    mod.tiny = lambda spec: spec
    return mod


def _toy_shapes() -> types.ModuleType:
    """A module that sizes one kernel: a dispatch of ``rows`` rows makes
    two calls a layer, one bound by compute and one by memory."""
    mod = types.ModuleType("harness.toy_shapes")
    pk = peaks.peaks_for("TPU v5e")
    mod.CALLS = {"toy_call": lambda spec, rows: [
        (pk.bf16_flops * 1e-3 * rows, 1.0),
        (1.0, pk.hbm_bytes_per_s * 3e-3 * rows)]}
    mod.window_calls = lambda spec, mix, prompts, perts, steps: {
        "toy_call": [{"rows": 1, "dispatches": mix["ones"]},
                     {"rows": 4, "dispatches": 2}]}
    return mod


@pytest.fixture
def toy(monkeypatch, tmp_path):
    """The toy family as a later PR would bring it: a configuration file
    that names its reference module, found by that name alone."""
    monkeypatch.setitem(sys.modules, "references.toy_two_kinds",
                        _toy_reference())
    monkeypatch.setitem(sys.modules, "harness.toy_shapes", _toy_shapes())
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "toy.json").write_text(json.dumps({
        "vocab_size": 64, "hidden_size": 32, "keyed_layers": 2,
        "state_layers": 1, "lir_tpu": {
            "preset": "mistral-7b", "reference": "toy_two_kinds",
            "runtime": {"batch_size": 8}}}))
    monkeypatch.setattr(bench_run, "HERE", tmp_path)
    return bench_run.load_files({"name": "toy.sweep-trunk512",
                                 "config": "toy",
                                 "traffic": "sweep-trunk512"}, limits={})


def test_two_kinds_of_layer_pass_the_harness(toy):
    from lir_tpu.models.quant import QuantTensor

    ref, spec = toy["ref"], toy["spec"]
    assert ref.__name__ == "references.toy_two_kinds"
    assert toy["runtime"] == {"batch_size": 8}

    # The preset is held to the module's three fields and no other.
    with pytest.raises(ValueError, match="has vocab_size=32000"):
        builders.program_config(spec, ref)
    cfg = builders.program_config(spec, ref, check=False)
    assert (cfg.vocab_size, cfg.hidden_size, cfg.n_layers) == (64, 32, 3)
    assert cfg.n_heads == 32                     # the preset's own, kept

    # Nested groups, each stacked over its own layers; an int8 leaf two
    # levels down comes out in the program's container.
    params = builders.build_params(spec, ref, SEED)
    a, b = params["layers"]["a"], params["layers"]["b"]
    assert isinstance(a["wq"], QuantTensor) and a["wq"].q.shape == (2, 32, 16)
    up = b["mlp"]["experts"]["w_up"]
    assert isinstance(up, QuantTensor) and up.q.shape == (1, 4, 32, 8)
    assert up.q.dtype == jnp.int8 and up.scale.shape == (1, 8)
    assert set(a["ln"]) == {"scale"} and b["decay"].shape == (1, 3)
    again = builders.build_params(spec, ref, SEED)
    assert (np.asarray(up.q) == np.asarray(
        again["layers"]["b"]["mlp"]["experts"]["w_up"].q)).all()

    # Tokens 2 and 3 (they attend 3 + 4 = 7 keys): kind A twice
    # 2 * 1000 * 2 + 2 * 48 * 7 = 4672, kind B once 2 * 500 * 2 + 7 * 2.
    assert flops.tokens_flops(spec, 2, 4) == 2 * 4672 + 2014 == 11358
    assert flops.logits_flops(spec, 3) == 3 * 2 * 32 * 64

    # 5 dispatches of one row and 2 of four: 14 calls, least times
    # 5 * (1 + 3) + 2 * (4 + 12) = 52 ms; the trace holds 28 calls in 0.4 s.
    ctx = {"spec": spec, "peaks": peaks.peaks_for("TPU v5e"),
           "trace": {"ops": {"toy_kernel.3 (f32[4,8]": (0.3, 20),
                             "toy_kernel.4 (f32[1,8]": (0.1, 8),
                             "fusion.9 f32[4]": (1.0, 5)}},
           "traffic": {"mix": {"ones": 5}, "prompts": [], "perts": [],
                       "steps": (2, 10)}}
    got = readers.trace_kernel_roofline(ctx, "^toy_kernel", "toy_call",
                                        module="toy_shapes")
    assert got == pytest.approx(100.0 * 28 * (52e-3 / 14) / 0.4)
    assert readers.trace_kernel_roofline(ctx, "^toy_kernel", "other_call",
                                         module="toy_shapes") is None
    assert readers.trace_kernel_roofline(ctx, "^no_such", "toy_call",
                                         module="toy_shapes") is None


def test_a_metric_file_can_name_the_module(toy, monkeypatch, tmp_path):
    """``read_all`` finds the reader and the module by the metric's file."""
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "toy_roofline.json").write_text(json.dumps({
        "reader": "trace_kernel_roofline",
        "args": {"pattern": "^toy_kernel", "shape": "toy_call",
                 "module": "toy_shapes"}}))
    monkeypatch.setattr(readers, "ROOT", tmp_path)
    ctx = {"spec": toy["spec"], "peaks": peaks.peaks_for("TPU v5e"),
           "trace": {"ops": {"toy_kernel.3 (f32[4,8]": (0.4, 28)}},
           "traffic": {"mix": {"ones": 5}, "prompts": [], "perts": [],
                       "steps": (2, 10)}}
    out = readers.read_all([{"name": "toy_roofline", "unit": "%"}], ctx)
    assert out == {"toy_roofline": {
        "value": pytest.approx(100.0 * 28 * (52e-3 / 14) / 0.4),
        "unit": "%"}}


def test_no_harness_file_names_a_familys_field():
    named = ("rotary", "parallel_block", "ssm_", "n_kv_heads", "toy")
    for path in ("harness/builders.py", "harness/readers.py",
                 "harness/sweep_window.py", "run.py"):
        text = (bench_run.REPO / "benchmarks" / path).read_text()
        assert not [w for w in named if w in text], path


# ---------------------------------------------------------------------------
# (d) the FLOP count is the one it was, to the last digit
# ---------------------------------------------------------------------------

def _old_tokens_flops(spec, start, stop):
    n = max(stop - start, 0)
    keys = n * (start + stop + 1) / 2.0
    per_layer = (2.0 * spec.layer_matmul_params * n
                 + 4.0 * spec.heads * spec.head_dim * keys)
    return spec.layers * per_layer


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_flop_counts_did_not_move(config):
    spec = _spec(config)
    for start, stop in ((0, 1), (2, 4), (0, 448), (64, 448), (448, 471),
                        (10, 10), (12, 7)):
        assert flops.tokens_flops(spec, start, stop) == _old_tokens_flops(
            spec, start, stop), (start, stop)
    old = (_old_tokens_flops(spec, 64, 437) + _old_tokens_flops(spec, 437, 450)
           + _old_tokens_flops(spec, 437, 470)
           + 2.0 * spec.d * spec.vocab * 12)
    assert flops.scoring_cell_flops(spec, 437, 449, 461, 2, 10,
                                    trunk=64) == old


def test_kernel_sizes_are_the_ones_the_window_used_to_record():
    """``flops.window_calls`` against the arithmetic ``needed_flops`` held
    until PR 30, on real traffic: the same means, digit for digit."""
    import statistics

    from harness import tokenizer

    spec = _spec("mistral-7b")
    mix = traffic.load_mix("sweep-trunk512")
    prompts = traffic.load_prompts(mix)
    perts = traffic.sweep_groups(mix, prompts, SEED, 3, stream=2)
    sizes = {"shared": [], "bin": [], "conf": []}
    for p, mains in zip(prompts, perts):
        for k, main in enumerate([p.main] + list(mains)):
            b, c, shared = tokenizer.encode_pair(p, main, spec.vocab)
            if k:
                sizes["shared"].append(shared)
                sizes["bin"].append(len(b))
                sizes["conf"].append(len(c))
    mean = statistics.fmean
    extent = (2 * (mean(sizes["bin"]) + 3 / 2)
              + 10 * (mean(sizes["conf"]) + 11 / 2)) / 12
    ctx = {"spec": spec, "traffic": {"mix": mix, "prompts": prompts,
                                     "perts": perts, "steps": (2, 10)}}
    assert readers.window_calls(ctx, "flops") == {
        "decode_attention_call": [{"batch": 40, "extent": extent,
                                   "trunk": 64, "dispatches": 1}],
        "cascade_prefill_call": [{"batch": 40,
                                  "length": mean(sizes["shared"]),
                                  "trunk": 64, "dispatches": 1}]}


# ---------------------------------------------------------------------------
# (e) a sweep window is the mix's, reckoned at one trunk a prompt a call
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", traffic.mix_names("sweep"))
def test_a_sweep_mix_names_its_windows(name):
    """The driver takes the window's groups from the mix and from nothing
    the program does; no sweep mix keeps a length in seconds."""
    mix = traffic.load_mix(name)
    cap = mix["max_groups_per_prompt"]
    prompts = traffic.load_prompts(mix)
    for key in ("window_groups", "trace_groups"):
        assert isinstance(mix[key], int)
        assert cap <= mix[key] <= cap * len(prompts)
    assert "trace_seconds" not in mix
    text = (bench_run.REPO / "benchmarks/harness/sweep_window.py"
            ).read_text()
    assert "ctx.seconds" not in text and "trace_seconds" not in text


def test_needed_flops_count_one_trunk_a_prompt_by_hand():
    """Two prompts, three groups of two rows: prompt A two groups, prompt
    B one. Each prompt's original is counted whole; its other rows, the
    first of each group too, behind the four head words."""
    from harness import sweep_window

    spec = _spec("mistral-7b")
    fmt = dict(response_format="Answer Yes or No",
               target_tokens=("Yes", "No"),
               confidence_format="Answer from 0 to 100 now")
    a = traffic.Prompt(main="a b c d e f g", **fmt)
    b = traffic.Prompt(main="h i j k l m", **fmt)
    mix = {"head_words": 4, "group_rows": 2}
    perts = [["a b c d x y z w", "a b c d y z", "a b c d z x y",
              "a b c d w w w"], ["h i j k q r", "h i j k r s t"]]
    need, offered = sweep_window.needed_flops(spec, mix, [a, b], perts, 2, 3)

    def cell(words, trunk):
        # the formats share "Answer": shared = words + 1; 4 and 6 own words
        return flops.scoring_cell_flops(spec, words + 1, words + 4,
                                        words + 6, 2, 3, trunk)

    want = (cell(7, 0) + cell(8, 4) + cell(6, 4) + cell(7, 4) + cell(7, 4)
            + cell(6, 0) + cell(6, 4) + cell(7, 4))
    assert need == want
    assert offered == sum(2 * w + 10 - (w + 1) for w in
                          (7, 8, 6, 7, 7, 6, 6, 7))
    # at one trunk a GROUP (the count until PR 36) three more rows were whole
    old = want + 3 * flops.tokens_flops(spec, 0, 4)
    assert need < old


def test_the_long_document_kernels_are_sized_at_one_trunk_pass_a_prompt():
    from harness import sala
    from references import sala as sala_ref

    raw = json.loads((bench_run.HERE / "configs" / "minicpm-sala.json"
                      ).read_text())
    spec = sala_ref.spec_from_config("minicpm-sala", raw)
    mix = traffic.load_mix("sweep-doc16k")
    prompts = traffic.load_prompts(mix)
    # the cell's own deal at one row a group (the sizes are means)
    perts = [[p.main] * (40 * n) for p, n in zip(prompts, (1, 3, 1, 1, 1))]
    calls = sala.window_calls(spec, mix, prompts, perts, (4, 8))
    for name in ("lightning_scan_call", "sparse_prefill_call"):
        trunk, originals, groups = calls[name]
        assert trunk == {"rows": 1, "shared": 16000, "trunk": 0, "sfx": (),
                         "steps": (0, 0), "dispatches": 5}
        assert originals["rows"] == 1 and originals["dispatches"] == 5
        assert groups["rows"] == 40 and groups["dispatches"] == 7
        assert originals["held"] and groups["held"]
        assert originals["trunk"] == groups["trunk"] == 16000
    # The trunk's pass is one call over 16,000 tokens at one row; what
    # reads it makes the window's and the suffixes' calls and no other.
    state = 32 * 128 * 128
    sizes = lambda s: {k: v for k, v in s.items()  # noqa: E731
                       if k != "dispatches"}
    trunk, originals, groups = calls["lightning_scan_call"]
    assert sala.scan_calls(spec, **sizes(trunk)) == [
        (5.0 * state * 16000, (4 * 4096 * 2 + 128) * 16000 + 2.0 * state * 4)]
    assert len(sala.scan_calls(spec, **sizes(groups))) == 3
    assert len(sala.scan_calls(spec, **sizes(originals))) == 3
    one = sala.prefill_calls(spec, **sizes(trunk))
    assert one == [sala._attend(spec, np.arange(16000), 16000, 16000)]
    assert len(sala.prefill_calls(spec, **sizes(groups))) == 3
    # 16k tokens of scan a prompt, not a group and an original: 5 passes
    # where 12 were reckoned.
    scanned = sum(s["dispatches"] * sum(
        f for f, _ in sala.scan_calls(spec, **sizes(s)))
        for s in calls["lightning_scan_call"])
    own = 5 * (originals["shared"] - 16000) + 280 * (groups["shared"] - 16000)
    sfx = 5 * sum(originals["sfx"]) + 280 * sum(groups["sfx"])
    assert scanned == pytest.approx(
        5.0 * state * (5 * 16000 + own + sfx), rel=1e-12)
    # The decode kernels make no call in a trunk's pass.
    for name in ("lightning_step_call", "sparse_decode_call"):
        assert [s["rows"] for s in calls[name]] == [1, 40]
        assert not any("held" in s for s in calls[name])
    # A mix without a trunk has no such pass and holds nothing.
    plain = sala.window_calls(spec, dict(mix, head_words=0), prompts, perts,
                              (4, 8))
    assert [s["trunk"] for s in plain["lightning_scan_call"]] == [0, 0]
    assert not any(s["held"] for s in plain["lightning_scan_call"])
