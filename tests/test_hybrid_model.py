"""A Mamba-2 mixer beside attention in every block (Falcon-H1): the
program against the plain reference (benchmarks/references/hybrid.py) at
tiny sizes on the CPU, float32 served tree, seeded weights.

What is pinned: prefill + extend + decode_step through the cache equal the
reference's full forward; the chunked scan kernel equals the token
recurrence (lengths that are no chunk multiple, an initial state,
right-padded rows: each row's final state is the state at its own last
real token); the two format branches after a shared prefix equal two
independent passes (the fork of the recurrent state); the cascade program
equals the dense shared program with the trunk's state computed once; and
an absent mixer leaves the older models' programs as they were.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "benchmarks"))

from references import hybrid as ref  # noqa: E402

from lir_tpu.engine import generate  # noqa: E402
from lir_tpu.models import cache as cache_mod  # noqa: E402
from lir_tpu.models import decoder, registry  # noqa: E402
from lir_tpu.ops import ssd_scan  # noqa: E402

from dispatch_helpers import fused_shared, fused_shared_cascade

SEED = 2**31 + 26
VOCAB = 512


def _spec():
    import json

    raw = json.loads((REPO / "benchmarks/configs/falcon-h1-34b.json"
                      ).read_text())
    spec = ref.spec_from_config("falcon-h1-34b", raw)
    return dataclasses.replace(
        spec, vocab=VOCAB, d=64, layers=3, heads=4, kv_heads=2, head_dim=16,
        ffn=128, ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_groups=2,
        ssm_chunk=16)


def _cfg(spec):
    full = registry.REGISTRY[spec.preset]()
    return dataclasses.replace(
        full, vocab_size=spec.vocab, hidden_size=spec.d,
        n_layers=spec.layers, n_heads=spec.heads, n_kv_heads=spec.kv_heads,
        head_dim=spec.head_dim, intermediate_size=spec.ffn,
        ssm_heads=spec.ssm_heads, ssm_head_dim=spec.ssm_head_dim,
        ssm_state=spec.ssm_state, ssm_groups=spec.ssm_groups,
        ssm_chunk=spec.ssm_chunk)


def _params(spec):
    """The served tree in float32 (int8 matrices dequantised)."""
    key = ref.seed_key(SEED)

    def f32(tree):
        out = {}
        for name, leaf in tree.items():
            if isinstance(leaf, dict) and "q" in leaf:
                out[name] = (leaf["q"].astype(jnp.float32)
                             * leaf["scale"][..., None, :])
            elif isinstance(leaf, dict):
                out[name] = {k: v.astype(jnp.float32)
                             for k, v in leaf.items()}
            else:
                out[name] = leaf.astype(jnp.float32)
        return out

    layers = jax.vmap(lambda l: ref.layer_weights(spec, key, l))(
        jnp.arange(spec.layers))
    params = f32(ref.top_weights(spec, key))
    params["layers"] = f32(layers)
    return params


@pytest.fixture(scope="module")
def model():
    spec = _spec()
    return spec, _cfg(spec), _params(spec)


@pytest.fixture(params=[False, True], ids=["recurrence", "kernels"])
def kernels(request):
    """Both lowerings of the mixer: the token recurrence in XLA (what a
    CPU runs) and the Pallas kernels under the interpreter."""
    was = decoder.SSM_INTERPRET_ON_CPU
    decoder.SSM_INTERPRET_ON_CPU = request.param
    yield request.param
    decoder.SSM_INTERPRET_ON_CPU = was


def _rows(rng, n, lo, hi, width):
    lens = rng.integers(lo, hi + 1, n)
    lens[0] = hi
    toks = rng.integers(3, VOCAB, (n, width))
    mask = (np.arange(width)[None] < lens[:, None]).astype(np.int32)
    return toks * mask, mask, lens


def test_the_preset_carries_the_published_mixer_and_multipliers():
    """What builders.program_config's fixed list does not check."""
    import json

    raw = json.loads((REPO / "benchmarks/configs/falcon-h1-34b.json"
                      ).read_text())
    cut, full = (registry.REGISTRY[n]() for n in
                 (raw["lir_tpu"]["preset"], "falcon-h1-34b"))
    assert dataclasses.replace(full, n_layers=cut.n_layers) == cut
    assert full.n_layers == raw["published"]["num_hidden_layers"] == 72
    assert cut.n_layers == raw["num_hidden_layers"]
    assert raw["reduced"] == ["num_hidden_layers"]
    same = {"ssm_heads": "mamba_n_heads", "ssm_head_dim": "mamba_d_head",
            "ssm_state": "mamba_d_state", "ssm_groups": "mamba_n_groups",
            "ssm_conv": "mamba_d_conv", "ssm_chunk": "mamba_chunk_size",
            "ssm_inner": "mamba_d_ssm",
            "embedding_multiplier": "embedding_multiplier",
            "lm_head_multiplier": "lm_head_multiplier",
            "attention_in_multiplier": "attention_in_multiplier",
            "attention_out_multiplier": "attention_out_multiplier",
            "key_multiplier": "key_multiplier",
            "ssm_in_multiplier": "ssm_in_multiplier",
            "ssm_out_multiplier": "ssm_out_multiplier"}
    for ours, theirs in same.items():
        assert getattr(cut, ours) == raw[theirs], ours
    assert list(cut.mlp_multipliers) == raw["mlp_multipliers"]
    assert list(cut.ssm_multipliers) == raw["ssm_multipliers"]
    assert cut.ssm_in_width == 9248 and cut.ssm_conv_dim == 5120


def test_prefill_extend_decode_equal_the_references_full_forward(model,
                                                                 kernels):
    spec, cfg, params = model
    rng = np.random.default_rng(1)
    B, S, S2, T = 3, 21, 6, 40
    prefix, pm, plen = _rows(rng, B, 5, S, S)
    sfx, sm, slen = _rows(rng, B, 1, S2, S2)
    nxt = rng.integers(3, VOCAB, (B,))
    with jax.default_matmul_precision("highest"):
        _, cache, _ = decoder.prefill(params, cfg, jnp.asarray(prefix),
                                      jnp.asarray(pm), T)
        cm = np.concatenate([pm, sm, np.zeros((B, T - S - S2), np.int32)], 1)
        lg1, cache, pos = decoder.extend(params, cfg, cache,
                                         jnp.asarray(sfx), jnp.asarray(sm),
                                         jnp.asarray(cm), S)
        cm[:, S + S2] = 1
        lg2, cache = decoder.decode_step(params, cfg, cache,
                                         jnp.asarray(nxt, jnp.int32), pos,
                                         S + S2, jnp.asarray(cm))
    for r in range(B):
        seq = np.concatenate([prefix[r, :plen[r]], sfx[r, :slen[r]],
                              nxt[r:r + 1]])[None]
        n = seq.shape[1]
        want = np.asarray(ref.logits_at(spec, SEED, seq,
                                        np.array([[n - 2, n - 1]])))[0]
        assert 1.0 < want.std() < 3.0            # logits are not flat
        assert np.abs(np.asarray(lg1[r]) - want[0]).max() < 2e-3
        assert np.abs(np.asarray(lg2[r]) - want[1]).max() < 2e-3


def test_left_padded_prefill_and_greedy_steps_equal_the_reference(model,
                                                                 kernels):
    """The full-prompt path pads on the left: a masked slot before the
    first token reads like the empty history."""
    spec, cfg, params = model
    rng = np.random.default_rng(5)
    B, S, new = 3, 19, 3
    lens = np.array([19, 11, 4])
    toks = rng.integers(3, VOCAB, (B, S))
    mask = (np.arange(S)[None] >= S - lens[:, None]).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        gen, logits = generate.greedy_decode(
            params, cfg, jnp.asarray(toks * mask), jnp.asarray(mask),
            max_new_tokens=new)
    gen, logits = np.asarray(gen), np.asarray(logits)
    for r in range(B):
        seq = np.concatenate([toks[r, S - lens[r]:], gen[r, :new - 1]])[None]
        pos = np.arange(lens[r] - 1, lens[r] - 1 + new)[None]
        want = np.asarray(ref.logits_at(spec, SEED, seq, pos))[0]
        assert np.abs(logits[r] - want).max() < 2e-3


def _scan_inputs(B, T, H, P, G, N, seed):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 1.0)
    a = -jnp.exp(jax.random.uniform(k[2], (H,), minval=-1.0, maxval=1.5))
    b = jax.random.normal(k[3], (B, T, G, N))
    c = jax.random.normal(k[4], (B, T, G, N))
    s = jax.random.normal(k[5], (B, H, P, N))
    return x, dt, a, b, c, s


@pytest.mark.parametrize("T,chunk", [(37, 16), (9, 16), (64, 16), (50, 24),
                                     (16, 16)])
@pytest.mark.parametrize("initial", [False, True], ids=["empty", "carried"])
def test_chunked_scan_equals_the_token_recurrence(T, chunk, initial):
    B, H, P, G, N = 3, 4, 16, 2, 16
    x, dt, a, b, c, s = _scan_inputs(B, T, H, P, G, N, T)
    if not initial:
        s = jnp.zeros_like(s)
    # Right-padded rows of unequal length: dt is 0 at a masked slot.
    lens = np.array([T, max(T // 2, 1), 1])
    mask = np.arange(T)[None] < lens[:, None]
    dt = dt * mask[..., None]
    y0, s0 = ssd_scan.ssd_scan_tokens(x, dt, a, b, c, s)
    y1, s1 = ssd_scan.ssd_scan(x, dt, a, b, c, s, chunk=chunk,
                               interpret=True)
    assert np.abs(np.asarray(s0 - s1)).max() < 2e-5
    assert np.abs(np.asarray((y0 - y1) * mask[..., None, None])).max() < 1e-4
    # Each row's final state is the state at its own last real token.
    for r, n in enumerate(lens):
        _, sr = ssd_scan.ssd_scan_tokens(x[r:r + 1, :n], dt[r:r + 1, :n], a,
                                         b[r:r + 1, :n], c[r:r + 1, :n],
                                         s[r:r + 1])
        assert np.abs(np.asarray(sr[0] - s1[r])).max() < 2e-5


@pytest.mark.parametrize("B", [1, 3])
def test_single_token_kernel_equals_the_recurrence(B):
    H, P, G, N = 4, 16, 2, 16
    x, dt, a, b, c, s = _scan_inputs(B, 1, H, P, G, N, 7 + B)
    y0, s0 = ssd_scan.ssd_scan_tokens(x, dt, a, b, c, s)
    y0 = y0[:, 0]
    y1, s1 = ssd_scan.ssm_step(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], s,
                               interpret=True)
    assert np.abs(np.asarray(y0 - y1)).max() < 1e-5
    assert np.abs(np.asarray(s0 - s1)).max() < 1e-5


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("length", [None, 20], ids=["ssm_step", "ssd_scan"])
def test_stacked_state_moves_one_layer_and_no_other(layer, length):
    """Both scan kernels over the cache's stacked (L, B, H, P, N) state
    with a traced layer index (the layer loop's form, models/decoder.
    _mixer): layer l's outputs and new state equal, bit for bit, the call
    on that layer's own state, and every other layer of the buffer is
    what it was."""
    L, B, H, P, G, N = 3, 3, 4, 16, 2, 16
    T = length or 1
    x, dt, a, b, c, _ = _scan_inputs(B, T, H, P, G, N, 31 + layer)
    stack = jnp.asarray(np.random.default_rng(5).normal(
        size=(L, B, H, P, N)), jnp.float32)
    if length is None:
        def call(state, **kw):
            return ssd_scan.ssm_step(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0],
                                     state, interpret=True, **kw)
    else:
        def call(state, **kw):
            return ssd_scan.ssd_scan(x, dt, a, b, c, state, chunk=8,
                                     interpret=True, **kw)
    # Both under jit: what XLA fuses around the kernel (exp(dt * a)) is
    # then compiled the same way on both sides.
    y_own, s_own = jax.jit(call)(stack[layer])
    y, out = jax.jit(lambda s, l: call(s, layer=l))(stack, jnp.int32(layer))
    assert out.shape == stack.shape and out.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_own))
    np.testing.assert_array_equal(np.asarray(out[layer]), np.asarray(s_own))
    assert np.abs(np.asarray(out[layer] - stack[layer])).max() > 1e-3
    for other in set(range(L)) - {layer}:
        np.testing.assert_array_equal(np.asarray(out[other]),
                                      np.asarray(stack[other]))


def _shared_inputs(rng, B, S, S2, shared_head=0):
    prefix, pm, _ = _rows(rng, B, S - 9, S, S)
    if shared_head:
        prefix[:, :shared_head] = prefix[0, :shared_head]
    sa, sam, _ = _rows(rng, B, 2, S2, S2)
    sb, sbm, _ = _rows(rng, B, 2, S2, S2)
    ids = rng.integers(3, VOCAB, (2, B)).astype(np.int32)
    args = [jnp.asarray(v) for v in (prefix, pm, sa, sam, sb, sbm)]
    args += [jnp.asarray(ids[0]), jnp.asarray(ids[1]),
             jnp.arange(3, 13, dtype=jnp.int32),
             jnp.arange(10, dtype=jnp.float32)]
    return args


def test_two_branches_after_a_shared_prefix_equal_two_full_passes(model,
                                                                  kernels):
    """The fork: branch B starts from the state at the prefix's end, not
    from where branch A left it."""
    spec, cfg, params = model
    rng = np.random.default_rng(2)
    B, S, S2, new = 4, 24, 6, 3
    args = _shared_inputs(rng, B, S, S2)
    with jax.default_matmul_precision("highest"):
        out_a, out_b = fused_shared(
            params, cfg, *args, max_new_a=new, max_new_b=new, topk=5)
    prefix, pm, sa, sam, sb, sbm = (np.asarray(a) for a in args[:6])
    for out, sfx, sm in ((out_a, sa, sam), (out_b, sb, sbm)):
        gen = np.asarray(out.generated)
        for r in range(B):
            seq = np.concatenate([prefix[r, :pm[r].sum()],
                                  sfx[r, :sm[r].sum()], gen[r, :new - 1]])
            n0 = pm[r].sum() + sm[r].sum()
            pos = np.arange(n0 - 1, n0 - 1 + new)[None]
            want = np.asarray(ref.logits_at(spec, SEED, seq[None], pos))[0]
            # Greedy tokens are the reference's, and the first position's
            # top log-probabilities agree.
            assert (want.argmax(-1) == gen[r]).all()
            lp = want[0] - jax.scipy.special.logsumexp(want[0])
            top = np.sort(np.asarray(lp))[::-1][:5]
            assert np.abs(top - np.asarray(out.topk_logprobs[r])).max() < 2e-3


def test_cascade_program_equals_the_dense_shared_program(model, kernels):
    """The trunk's K/V and recurrent state computed once at batch 1 and
    handed to every row: the same answers as every row computing them."""
    spec, cfg, params = model
    was = decoder.CASCADE_INTERPRET_ON_CPU
    decoder.CASCADE_INTERPRET_ON_CPU = True
    try:
        rng = np.random.default_rng(3)
        B, S, S2, new, trunk = 4, 32, 6, 3, 16
        args = _shared_inputs(rng, B, S, S2, shared_head=trunk)
        with jax.default_matmul_precision("highest"):
            dense = fused_shared(
                params, cfg, *args, max_new_a=new, max_new_b=new, topk=5,
                return_cache=True)
            casc = fused_shared_cascade(
                params, cfg, *args, max_new_a=new, max_new_b=new,
                trunk_len=trunk, topk=5, return_cache=True)
    finally:
        decoder.CASCADE_INTERPRET_ON_CPU = was
    for d, c in zip(dense[:2], casc[:2]):
        assert (np.asarray(d.generated) == np.asarray(c.generated)).all()
        assert np.abs(np.asarray(d.topk_logprobs)
                      - np.asarray(c.topk_logprobs)).max() < 2e-3
    # The returned caches hold the same recurrent state (branch B's end).
    for d, c in zip(dense[2][2:], casc[2][2:]):
        assert d.shape == c.shape
        assert np.abs(np.asarray(d) - np.asarray(c)).max() < 2e-3


def test_the_ssm_state_is_float32_in_a_bfloat16_engine(model, kernels):
    """The configuration states a float32 SSM state, and no limit of the
    cell's ``correct`` can see a lower one (the bfloat16-state control
    reads below the program, PERF.md §4): so the dtype is pinned here,
    through every entry point that hands the cache on, in an engine whose
    weights, activations and K/V are bfloat16."""
    _, cfg, params = model
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    B, S, S2, T = 2, 16, 8, 40
    ones = lambda *shape: jnp.ones(shape, jnp.int32)  # noqa: E731

    def program():
        held = [decoder.init_cache(cfg, B, T, jnp.bfloat16)]
        _, cache, pos = decoder.prefill(params, cfg, ones(B, S), ones(B, S),
                                        T)
        held.append(cache)
        cm = jnp.concatenate([ones(B, S + S2),
                              jnp.zeros((B, T - S - S2), jnp.int32)], 1)
        _, cache, pos = decoder.extend(params, cfg, cache, ones(B, S2),
                                       ones(B, S2), cm, S)
        held.append(cache)
        _, cache = decoder.decode_step(params, cfg, cache, ones(B), pos,
                                       S + S2, cm)
        held.append(cache)
        shared = fused_shared(
            params, cfg, ones(B, S), ones(B, S), ones(B, S2), ones(B, S2),
            ones(B, S2), ones(B, S2), ones(B), ones(B),
            jnp.arange(3, 13, dtype=jnp.int32),
            jnp.arange(10, dtype=jnp.float32), max_new_a=2, max_new_b=2,
            return_cache=True)
        held.append(decoder.rewind(shared[2], held[-1]))
        held.append(cache_mod.gather_rows(shared[2], jnp.asarray([1, 0, 1])))
        return held

    for ck, cv, state, tail in jax.eval_shape(program):
        assert state.dtype == jnp.float32
        assert ck.dtype == cv.dtype == tail.dtype == jnp.bfloat16


def test_gather_rows_moves_both_kinds_of_state(model):
    _, cfg, _ = model
    cache = decoder.init_cache(cfg, 3, 8)
    cache = tuple(a + jnp.arange(3, dtype=a.dtype).reshape(
        [3 if i == ax else 1 for i in range(a.ndim)])
        for a, ax in zip(cache, (3, 3, 1, 1)))
    out = cache_mod.gather_rows(cache, jnp.asarray([2, 2, 0, 1]))
    assert out[0].shape[3] == out[2].shape[1] == 4
    assert float(out[0][0, 0, 0, 1, 0]) == float(out[2][0, 1, 0, 0, 0]) == 2.0


class _Engine:
    """As much of an engine as a refusal looks at."""

    def __init__(self, cfg):
        self.cfg = cfg


@pytest.mark.parametrize("what", ["verify", "paged", "piggyback", "int8",
                                  "migrate_out", "migrate_in", "tiers",
                                  "radix"])
def test_what_cannot_hold_recurrent_state_refuses(model, what):
    from lir_tpu.engine.runner import ScoringEngine
    from lir_tpu.serve import migrate

    _, cfg, params = model
    tok = jnp.ones((2, 4), jnp.int32)
    if what == "int8":
        with pytest.raises(ValueError, match="kv_cache_int8"):
            dataclasses.replace(cfg, kv_cache_int8=True)
        return
    with pytest.raises(NotImplementedError, match="recurrent state"):
        if what == "verify":
            decoder.verify_extend(params, cfg, decoder.init_cache(cfg, 2, 8),
                                  tok, jnp.ones((2, 8), jnp.int32), 0)
        elif what == "migrate_out":
            migrate.export_prefix(_Engine(cfg), 64, [1, 2, 3])
        elif what == "migrate_in":
            migrate.import_prefix(_Engine(cfg), None)
        elif what == "tiers":
            ScoringEngine.attach_tiers(_Engine(cfg), object())
        elif what == "radix":
            eng = _Engine(cfg)
            eng.prefix_cache, eng.encoder_decoder = None, False
            eng._prefill_fn = None
            eng.rt = dataclasses.make_dataclass(
                "Rt", [("prefix_cache_pages", int, 8)])()
            ScoringEngine.enable_prefix_cache(eng)
        elif what == "paged":
            generate._paged_prefix(
                params, cfg, generate.PagedFront(None, None, 0, tok, tok),
                tok, 8)
        else:
            generate.shared_piggyback_prefill(
                params, cfg, tok, tok, tok, tok, tok, tok, max_new_a=2,
                max_new_b=2)


@pytest.mark.parametrize("family", ["mistral", "falcon"])
def test_an_absent_mixer_leaves_the_older_programs_as_they_were(family):
    """The lowered text of a shared dispatch program holds nothing of the
    mixer or of a multiplier, and its cache is the (K, V) pair."""
    cfg = registry.tiny(family)
    assert not cfg.has_mixer
    params = decoder.init_params(cfg, jax.random.PRNGKey(0))
    args = _shared_inputs(np.random.default_rng(4), 2, 16, 4)
    lowered = fused_shared(
        params, cfg, *args, max_new_a=2, max_new_b=2, topk=5,
        return_cache=True, lower=True)
    text = lowered.as_text()
    assert "ssd_scan" not in text and "ssm_step" not in text
    out = jax.eval_shape(
        lambda p: fused_shared(
            p, cfg, *args, max_new_a=2, max_new_b=2, topk=5,
            return_cache=True), params)
    assert len(out[2]) == 2
    assert decoder.rewind(out[2], out[2]) is out[2]
    assert len(decoder.init_cache(cfg, 2, 8)) == 2


def test_a_sweep_shares_the_trunk_state_and_forks_twice_a_row(tmp_path):
    """Through run_perturbation_sweep -> scheduler -> compile plan -> the
    cascade program, like every other model: the trunk's state computed
    once per dispatch, each branch started from the prefix's state, no
    speculative or piggyback variant planned."""
    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.data.prompts import LegalPrompt
    from lir_tpu.engine import compile_plan
    from lir_tpu.engine.runner import ScoringEngine
    from lir_tpu.engine.sweep import run_perturbation_sweep
    from lir_tpu.observe import registry as metrics_mod

    cfg = dataclasses.replace(
        registry.tiny("falcon-h1"), vocab_size=FakeTokenizer.VOCAB,
        hidden_size=32, n_layers=2, n_heads=2, n_kv_heads=1,
        intermediate_size=64, max_seq_len=256)
    params = decoder.init_params(cfg, jax.random.PRNGKey(2))
    hooks = ("CASCADE_INTERPRET_ON_CPU", "FUSED_DECODE_INTERPRET_ON_CPU",
             "SSM_INTERPRET_ON_CPU")
    was = [getattr(decoder, h) for h in hooks]
    for h in hooks:
        setattr(decoder, h, True)
    try:
        compile_plan.exec_cache_clear()
        engine = ScoringEngine(params, cfg, FakeTokenizer(),
                               RuntimeConfig(batch_size=4, max_seq_len=256,
                                             sweep_group_min_cells=0))
        assert engine.cascade_supported()
        assert not engine.spec_supported()
        assert not engine.piggyback_supported()
        rng = np.random.default_rng(7)
        words = ("coverage policy flood water damage claim insurer "
                 "premium exclusion endorsement").split()
        head = " ".join(rng.choice(words) for _ in range(40))

        def text():
            return head + " " + " ".join(rng.choice(words)
                                         for _ in range(12)) + " ?"

        lp = (LegalPrompt(main=text(), response_format="Answer Yes or No .",
                          target_tokens=("Yes", "No"),
                          confidence_format="Give a number from 0 to 100 ."),)
        rows = run_perturbation_sweep(engine, "hybrid", lp,
                                      ([text() for _ in range(8)],),
                                      tmp_path / "rows.csv")
        engine.exec_registry.wait()
    finally:
        for h, v in zip(hooks, was):
            setattr(decoder, h, v)
    assert len(rows) == 9
    assert all(r.token_1_prob is not None for r in rows)
    rec, casc = engine.recurrent_stats, engine.cascade_stats
    assert casc.cascade_dispatches >= 2
    assert rec.dispatches >= casc.cascade_dispatches
    assert rec.trunk_states_shared == casc.trunk_rows_deduped > 0
    assert rec.forks == 2 * 9                       # two a row, no more
    assert rec.state_bytes > 0 and rec.kv_bytes > 0
    assert rec.scan_calls and rec.step_calls
    assert not [s for s in engine.compile_stats.shapes
                if "spec" in s or s.startswith("piggy")]
    snap = metrics_mod.engine_registry(engine).snapshot(device_memory=False)
    got = snap["sources"]["recurrent"]
    assert got["fields"]["forks"] == rec.forks
    assert 0.0 < got["summary"]["state_share"] < 1.0


@pytest.mark.parametrize("program", ["shared", "cascade", "grouped"])
def test_the_engines_counts_are_the_calls_its_program_makes(program,
                                                            monkeypatch):
    """``recurrent``'s forks, scan_calls and step_calls are what the host
    says of the program it dispatched. Here the dispatched program is
    traced once more with every scan window, single-token update and
    rewind counted (times the trip count of each loop around it: the
    ``lax.scan`` over layers, ``generate._stepped`` over decode steps):
    the host's word has to be the program's."""
    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.engine import scheduler, tokens
    from lir_tpu.engine.runner import ScoringEngine

    cfg = dataclasses.replace(
        registry.tiny("falcon-h1"), vocab_size=FakeTokenizer.VOCAB,
        hidden_size=32, n_layers=2, n_heads=2, n_kv_heads=1,
        intermediate_size=64, max_seq_len=256)
    params = decoder.init_params(cfg, jax.random.PRNGKey(2))
    monkeypatch.setattr(decoder, "CASCADE_INTERPRET_ON_CPU",
                        program == "cascade")
    engine = ScoringEngine(params, cfg, FakeTokenizer(),
                           RuntimeConfig(batch_size=4, max_seq_len=256))
    dispatched = []

    def spy(*a, _fn=generate.greedy_decode_dispatch, **kw):
        dispatched.append((_fn, a, dict(kw, scratch_cache=None)))
        return _fn(*a, **kw)
    monkeypatch.setattr(generate, "greedy_decode_dispatch", spy)

    head = "coverage policy flood water damage claim insurer premium " * 5
    mains = [(head if program != "shared" else "") + f"row {i} " * (3 + i)
             + "tail" for i in range(3)]
    bins = [m + " Answer Yes or No ." for m in mains]
    confs = [m + " Give a number from 0 to 100 ." for m in mains]
    t1 = np.full((3,), FakeTokenizer.YES, np.int32)
    t2 = np.full((3,), FakeTokenizer.NO, np.int32)
    if program == "grouped":
        ftok = engine.tokenizer
        items = scheduler.build_items([ftok(p).input_ids for p in bins],
                                      [ftok(p).input_ids for p in confs],
                                      list(range(3)))
        plen = len(ftok(head).input_ids)
        sfx = tokens.pick_bucket(
            [max(len(it.bin_ids), len(it.conf_ids)) - plen for it in items],
            scheduler.SUFFIX_BUCKETS)
        engine.decode_fused_grouped(
            [scheduler.PrefixGroup(items=tuple(items), plen=plen)], t1, t2,
            2, 3, early_stop=False, bucket=48, sfx_bucket=sfx,
            reuse_cache=True, use_prefix_cache=False)
        rows = 6                                   # [bin, conf] a cell
    else:
        engine.decode_fused_shared(bins, confs, t1, t2, new_tokens=2,
                                   conf_tokens=3, early_stop=False,
                                   reuse_cache=True, use_prefix_cache=False)
        rows = 3
    (fn, args, kwargs), = dispatched
    assert (args[2].front, args[2].layout) == {
        "shared": ("prefill", "pair"), "cascade": ("cascade", "pair"),
        "grouped": ("prefill", "grouped")}[program], args[2]
    rec = engine.recurrent_stats
    assert rec.dispatches == 1

    made = {"scan": 0, "step": 0, "rewind": 0, "gather": 0}

    def counted(key, fn, when=lambda *a: True):
        def call(*a, **kw):
            made[key if when(*a) else "step"] += 1
            return fn(*a, **kw)
        return call

    def times(n, body):
        """``body`` with its calls counted once per iteration of a loop of
        ``n``, however often the loop traces it; call ``.settle()`` after
        the loop."""
        once = {}

        def counted_body(*a):
            before = dict(made)
            out = body(*a)
            for k in made:
                once[k], made[k] = made[k] - before[k], before[k]
            return out

        def settle():
            for k, v in once.items():
                made[k] += n * v

        counted_body.settle = settle
        return counted_body

    real_scan, real_stepped = jax.lax.scan, generate._stepped

    def scan(f, init, xs=None, length=None, **kw):
        n = (length if length is not None
             else jax.tree.leaves(xs)[0].shape[0])
        body = times(n, f)
        out = real_scan(body, init, xs, length, **kw)
        body.settle()
        return out

    def stepped(n, state0, emit, advance):
        """The decode loop is a while loop that ends when every row is
        done; with the stops off, as here, it makes its ``n`` steps."""
        body = times(n, advance)
        out = real_stepped(n, state0, emit, body)
        body.settle()
        return out

    monkeypatch.setattr(jax.lax, "scan", scan)
    monkeypatch.setattr(generate, "_stepped", stepped)
    monkeypatch.setattr(ssd_scan, "ssd_scan_tokens", counted(
        "scan", lambda x, dt, a, b, c, state: (jnp.zeros_like(x), state),
        lambda x, *_: x.shape[1] > 1))
    monkeypatch.setattr(decoder, "rewind", counted("rewind", decoder.rewind))
    monkeypatch.setattr(cache_mod, "gather_rows",
                        counted("gather", cache_mod.gather_rows))
    jax.clear_caches()
    jax.eval_shape(lambda: fn.__wrapped__(*args, **kwargs))
    assert rec.scan_calls == made["scan"]
    assert rec.step_calls == made["step"]
    # Branches started from the state held at the prefix's end, per real
    # row: the first from the snapshot itself, one more per rewind; the
    # grouped program's one row gather hands every member its copy.
    branches = made["gather"] if program == "grouped" else 1 + made["rewind"]
    assert rec.forks == rows * branches
    assert made["gather"] == (program == "grouped")
