"""Routed experts and window layers (K-EXAONE): the program against the
plain reference (benchmarks/references/exaone_moe.py) at tiny sizes on the
CPU, seeded weights.

What is pinned: the preset is the configuration file's model; a dispatch
through the ordinary path (a trunk longer than the window, two format
suffixes, decode steps) equals the reference's full forward; the experts'
terms over shares that cover every expert, the shared expert once, are the
uncut layer; a router that sends every token to the held experts drops
none; the gate normalizes the plain scores over all chosen experts and the
choice reads score + bias; a query sees key ``p - W + 1`` and not key
``p - W`` on every route that takes a window; the grouped matmul kernel is
``lax.ragged_dot``'s product; the routing and window counters are what the
dispatched program did; and the family is driven through the benchmark's
doors (``load_files``, ``program_config``, ``build_params``,
``tokens_flops``, ``trace_kernel_roofline``).
"""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "benchmarks"))

import run as bench_run  # noqa: E402
from harness import builders, exaone_moe as sizing  # noqa: E402
from harness import flops, peaks, readers, traffic  # noqa: E402
from references import exaone_moe as ref  # noqa: E402

from lir_tpu.engine import generate  # noqa: E402
from lir_tpu.models import decoder, moe, quant, registry  # noqa: E402
from lir_tpu.ops import expert_matmul as gmm  # noqa: E402
from lir_tpu.utils.profiling import ExpertStats  # noqa: E402

SEED = 2**31 + 39
CELL = {"name": "k-exaone-236b.sweep-trunk512", "config": "k-exaone-236b",
        "traffic": "sweep-trunk512"}


def _published():
    raw = json.loads((REPO / "benchmarks/configs/k-exaone-236b.json"
                      ).read_text())
    return ref.spec_from_config("k-exaone-236b", raw), raw


def _model(spec=None, f32=True):
    spec = spec or ref.tiny(_published()[0])
    cfg = builders.program_config(spec, ref, check=False)
    params = builders.build_params(spec, ref, SEED)
    if f32:
        params = jax.tree.map(lambda a: a.astype(jnp.float32)
                              if a.dtype == jnp.bfloat16 else a, params)
    return spec, cfg, params


@pytest.fixture(params=[False, True], ids=["xla", "kernels"])
def kernels(request, monkeypatch):
    monkeypatch.setattr(decoder, "FUSED_DECODE_INTERPRET_ON_CPU",
                        request.param)
    monkeypatch.setattr(decoder, "MOE_INTERPRET_ON_CPU", request.param)
    return request.param


# ---------------------------------------------------------------------------
# The preset
# ---------------------------------------------------------------------------

def test_the_preset_is_the_configuration_files_model():
    spec, raw = _published()
    cfg = registry.REGISTRY["k-exaone-236b-l12"]()
    assert builders.program_config(spec, ref) == cfg
    assert (cfg.hidden_size, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size) == (6144, 64, 8, 128,
                                                       18432, 19200)
    assert cfg.layer_windowed == (True, True, True, False) * 3
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_top_k,
            cfg.expert_size, cfg.expert_layers) == (128, (0, 16), 8, 2048, 11)
    whole = registry.REGISTRY["k-exaone-236b"]()
    assert whole.n_layers == 48 and whole.experts_held == (0, 128)
    assert raw["published"]["num_hidden_layers"] == 48
    # Parameters a token multiplies here (PERF.md section 4).
    dense, win, glob = spec.layer_costs
    assert dense == (1, 113_246_208 + 339_738_624, 16384, 0)
    assert win[0] == 8 and glob[0] == 3
    assert win[1] == 113_246_208 + 786_432 + 2 * 37_748_736


@pytest.mark.parametrize("what", ["verify", "spec", "piggyback"])
def test_what_cannot_carry_the_routing_counts_refuses(what):
    _, cfg, params = _model()
    if what == "verify":
        with pytest.raises(NotImplementedError, match="routing counts"):
            decoder.verify_extend(params, cfg, None,
                                  jnp.zeros((1, 2), jnp.int32),
                                  jnp.ones((1, 8), jnp.int32), 4)
        return
    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.engine.runner import ScoringEngine

    engine = ScoringEngine(params, cfg, FakeTokenizer(vocab=2048),
                           RuntimeConfig(batch_size=8, max_seq_len=128,
                                         spec_decode=True, spec_k=4))
    assert not (engine.spec_supported() if what == "spec"
                else engine.piggyback_supported())


# ---------------------------------------------------------------------------
# (a) A dispatch through the ordinary path against the full forward
# ---------------------------------------------------------------------------

B, S, TRUNK, S2, STEPS = 8, 40, 16, 8, 3
LENS = [40, 37, 33, 40, 29, 36, 40, 31]


def _dispatch(cfg, params, front, seed=7):
    """One pair dispatch of the sweep's shape (``front``: the trunk at one
    row + cascade, or every row's prefix whole), no early stop. Returns
    (outs, the prefix tokens, the two suffixes)."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(3, 2048, (B, S)).astype(np.int32)
    prefix[:, :TRUNK] = prefix[0, :TRUNK]
    pm = np.zeros((B, S), np.int32)
    for r, n in enumerate(LENS):
        pm[r, :n] = 1
    sfx = [rng.integers(3, 2048, (B, S2)).astype(np.int32) for _ in range(2)]
    ones = jnp.ones((B, S2), jnp.int32)
    args = generate.DispatchArgs(
        prefix=jnp.asarray(prefix), prefix_mask=jnp.asarray(pm),
        sfx=tuple(jnp.asarray(s) for s in sfx), sfx_mask=(ones, ones),
        yes_ids=jnp.full((B,), 5, jnp.int32),
        no_ids=jnp.full((B,), 6, jnp.int32),
        digit_ids=jnp.arange(10, 20, dtype=jnp.int32),
        digit_vals=jnp.arange(10, dtype=jnp.float32))
    program = generate.Program(front=front, max_new=(STEPS, STEPS), topk=5,
                               trunk=TRUNK if front == "cascade" else 0)
    with jax.default_matmul_precision("highest"):
        outs, _, _ = generate.greedy_decode_dispatch(params, cfg, program,
                                                     args)
    return outs, prefix, sfx


@pytest.mark.parametrize("front", ["cascade", "prefill"])
def test_a_dispatch_equals_the_references_full_forward(front, kernels):
    """Every probability the dispatch reads (the yes id at each decode
    step of both branches) and every token it decodes equal the
    reference's full forward of each row's tokens alone, float32 served
    tree: the trunk (16 tokens) is twice the window (8), so the window
    layers of the rows' windows and steps see no trunk key."""
    spec, cfg, params = _model()
    outs, prefix, sfx = _dispatch(cfg, params, front)
    rows, at = [], []
    for out, s in zip(outs, sfx):
        gen = np.asarray(out.generated)
        for r, n in enumerate(LENS):
            rows.append(np.concatenate([prefix[r, :n], s[r], gen[r]]))
            at.append(np.arange(n + S2 - 1, n + S2 - 1 + STEPS))
    width = max(len(r) for r in rows)
    tokens = np.stack([np.pad(r, (0, width - len(r))) for r in rows])
    want = np.asarray(ref.logits_at(spec, SEED, tokens, np.stack(at)))
    p_yes = np.concatenate([np.asarray(o.p_yes) for o in outs])
    gen = np.concatenate([np.asarray(o.generated) for o in outs])
    probs = np.exp(want - np.log(np.exp(want).sum(-1, keepdims=True)))
    np.testing.assert_allclose(p_yes, probs[..., 5], atol=2e-5, rtol=1e-3)
    assert (gen == want.argmax(-1)).all()
    assert outs[-1].expert_counts.counts.shape == (7, 9)
    assert outs[-1].expert_counts.keys.shape == (3,)


# ---------------------------------------------------------------------------
# (b)-(d) The expert layer
# ---------------------------------------------------------------------------

def _expert_tree(spec, layer, experts):
    tree = builders.wrap_quantized(ref._served_layer(ref.layer_weights(
        spec, ref.seed_key(SEED), layer, jnp.asarray(experts))))
    return tree


def _hidden(spec, n=24, seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(2.0 * rng.standard_normal((2, n, spec.d)), jnp.float32)


@pytest.mark.parametrize("count", [4, 8, 16])
def test_shares_covering_every_expert_add_up_to_the_uncut_layer(count):
    """Chips holding ``count`` experts each, side by side: their outputs,
    each its held experts' terms plus the shared expert, summed with the
    shared expert counted once, are the layer with every expert."""
    spec, cfg, _ = _model()
    h = _hidden(spec)
    total = 0.0
    for first in range(0, spec.experts, count):
        share = dataclasses.replace(cfg, experts_held=(first, count))
        lp = _expert_tree(spec, 2, range(first, first + count))
        with jax.default_matmul_precision("highest"):
            out, counts = moe.expert_layer(h, lp, share)
            total = total + out
        assert int(counts[-1]) == h.shape[0] * h.shape[1]
    with jax.default_matmul_precision("highest"):
        shared = moe.shared(h, lp)
        want = ref.uncut_experts(spec, ref.seed_key(SEED), 2, h)
    got = total - (spec.experts // count - 1) * shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=1e-4)


def test_a_pass_with_no_divisor_near_the_chunk_is_padded_not_split_fine(
        monkeypatch):
    """A pass of a prime count of tokens, above the chunk: cut into the
    fewest chunks (padded with masked tokens, which route nowhere), it is
    the pass in one piece, counts included."""
    spec, cfg, _ = _model()
    lp = _expert_tree(spec, 2, range(cfg.experts_held[0],
                                     sum(cfg.experts_held)))
    h = _hidden(spec, n=37)[:1]                    # 37 tokens, prime
    mask = jnp.ones((1, 37), jnp.int32).at[0, 5].set(0)
    with jax.default_matmul_precision("highest"):
        whole, c_whole = moe.expert_layer(h, lp, cfg, mask=mask)
        monkeypatch.setattr(moe, "CHUNK_TOKENS", 16)   # 3 chunks of 13
        cut, c_cut = moe.expert_layer(h, lp, cfg, mask=mask)
    np.testing.assert_allclose(np.asarray(cut), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)
    held = cfg.experts_held[1]
    np.testing.assert_array_equal(np.asarray(c_cut)[:held],
                                  np.asarray(c_whole)[:held])
    assert int(c_cut[-1]) == int(c_whole[-1]) == 36


@pytest.mark.parametrize("held", ["one", "all"])
def test_a_router_that_sends_every_token_to_the_held_experts_drops_none(
        held, monkeypatch):
    """The selection bias of one held expert (or of every held one: the
    worst case, top_k rows a token) so high every token chooses it: the
    grouped matmul (kernel, interpreted) computes every pick, and the
    layer is the reference's."""
    monkeypatch.setattr(decoder, "MOE_INTERPRET_ON_CPU", True)
    spec, cfg, _ = _model(f32=False)
    first, count = cfg.experts_held
    hot = [first + 1] if held == "one" else list(range(first, first + count))
    experts = jnp.arange(first, first + count)
    lp = _expert_tree(spec, 3, experts)
    lp["router"]["bias"] = lp["router"]["bias"].at[jnp.asarray(hot)].add(50.)
    w = ref.layer_weights(spec, ref.seed_key(SEED), 3, experts)
    w["router"] = lp["router"]
    h = _hidden(spec, n=40)
    T = h.shape[0] * h.shape[1]
    with jax.default_matmul_precision("highest"):
        got, counts = moe.expert_layer(h, lp, cfg)
        want = ref._mlp(h, w["shared"], "float32") + ref.routed_terms(
            spec, h, w, experts)
    counts = np.asarray(counts)
    picks = counts[:count]
    for e in hot:
        assert picks[e - first] == T              # every token, none dropped
    if held == "all":                             # top_k rows a token
        assert picks.sum() == T * cfg.expert_top_k
    assert (counts[count:2 * count] >= picks).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=1e-4)


def test_the_gate_normalizes_scores_over_all_chosen_and_the_choice_reads_the_bias():
    spec, cfg, _ = _model()
    lp = _expert_tree(spec, 1, range(4, 8))
    router = dict(lp["router"])
    router["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(9),
                                             (spec.experts,))
    x = _hidden(spec).reshape(-1, spec.d)
    with jax.default_matmul_precision("highest"):
        idx, gates = moe.route(x, router, cfg)
        s = jax.nn.sigmoid(x @ router["w"])
    s, idx, gates = np.asarray(s), np.asarray(idx), np.asarray(gates)
    by_sum = np.argsort(-(s + np.asarray(router["bias"])), axis=-1)[:, :4]
    assert (np.sort(idx, -1) == np.sort(by_sum, -1)).all()
    by_score = np.argsort(-s, axis=-1)[:, :4]
    assert (np.sort(by_score, -1) != np.sort(idx, -1)).any()
    chosen = np.take_along_axis(s, idx, -1)
    np.testing.assert_allclose(gates, 2.5 * chosen / chosen.sum(-1,
                                                               keepdims=True),
                               rtol=1e-5)
    with_bias = chosen + np.asarray(router["bias"])[idx]
    assert np.abs(gates - 2.5 * with_bias / with_bias.sum(-1, keepdims=True)
                  ).max() > 1e-2
    # The reference chooses and gates the same way.
    r_idx, r_gates = ref.route(spec, jnp.asarray(x), router)
    assert (np.asarray(r_idx) == idx).all()
    np.testing.assert_allclose(np.asarray(r_gates), gates, rtol=1e-5)


def _planted():
    from importlib import util

    path = REPO / "benchmarks" / "tests" / "fault_readings.py"
    mod = util.spec_from_file_location("fault_readings", path)
    module = util.module_from_spec(mod)
    mod.loader.exec_module(module)
    return module.planted


@pytest.mark.parametrize("fault", ["zero_routed", "gate_from_s_plus_b",
                                   "held_off_by_one"])
def test_a_planted_expert_fault_moves_the_served_logits_far(fault):
    """Each fault benchmarks/tests/fault_readings.py plants on the chip,
    here on the bfloat16 served tree: it moves the program's log-
    probabilities off the reference's by far more than the sound program
    is off them, with the seeded router's choices at the convention's
    scale."""
    spec, _, params = _model(f32=False)
    rng = np.random.default_rng(17)
    tokens = jnp.asarray(rng.integers(3, 2048, (2, 48)), jnp.int32)
    pos = np.tile(np.arange(48), (2, 1))
    want = jax.nn.log_softmax(ref.logits_at(spec, SEED, tokens, pos), -1)

    def gap():
        cfg = builders.program_config(spec, ref, check=False)
        got = decoder.forward(params, cfg, tokens)
        return float(jnp.abs(jax.nn.log_softmax(got, -1) - want).max())

    sound = gap()
    with _planted()(fault):
        faulty = gap()
    assert sound < 0.1 and faulty > 10 * sound, (sound, faulty)


def test_the_grouped_matmul_kernel_is_ragged_dots_product():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    E, D, F, tm = 4, 256, 128, 16
    wg, wu = (quant.quantize(jax.random.normal(k[i], (E, D, F)))
              for i in (0, 1))
    wd = quant.quantize(jax.random.normal(k[2], (E, F, D)))
    tiles = jnp.array([2, 0, 1, 3], jnp.int32)
    M = 8 * tm
    x = (jax.random.normal(k[3], (M, D))
         * (jnp.arange(M) < 6 * tm)[:, None]).astype(jnp.bfloat16)
    te = jnp.repeat(jnp.arange(E, dtype=jnp.int32), tiles,
                    total_repeat_length=M // tm)
    h = gmm.gate_up(x, wg, wu, te, jnp.sum(tiles), tm, interpret=True)
    want = gmm.gate_up_xla(x, wg, wu, tiles, tm)
    assert (np.asarray(h[:6 * tm]) == np.asarray(want[:6 * tm])).all()
    y = gmm.down(want, wd, te, jnp.sum(tiles), tm, interpret=True)
    ywant = gmm.down_xla(want, wd, tiles, tm)
    np.testing.assert_allclose(np.asarray(y[:6 * tm], np.float32),
                               np.asarray(ywant[:6 * tm], np.float32),
                               rtol=1e-2, atol=0.25)


# ---------------------------------------------------------------------------
# (e) The window, on every route that takes one
# ---------------------------------------------------------------------------

W = 8


def _route_out(route, k, v, q, qpos, kmask):
    """One query row a batch row at ``qpos`` over keys (B, T, K, hd)."""
    from importlib import import_module

    Bq, T, K, hd = k.shape
    kpos = decoder.mask_positions(kmask)
    if route == "dense":
        cfg = registry.tiny("exaone-moe")
        bias = decoder._causal_bias(jnp.ones((Bq, 1), jnp.int32),
                                    qpos[:, None], cfg, kpos, kmask,
                                    window=W)
        return decoder._attention_cached(
            q[:, None], k.transpose(2, 1, 0, 3), v.transpose(2, 1, 0, 3),
            bias, cfg)[:, 0]
    if route == "decode_kernel":
        fd = import_module("lir_tpu.ops.flash_decode")
        out, _ = fd.flash_decode(q, k.transpose(2, 1, 0, 3),
                                 v.transpose(2, 1, 0, 3), qpos, kmask, kpos,
                                 window=jnp.int32(W), block_k=16,
                                 interpret=True)
        return out.reshape(Bq, -1)
    cp = import_module("lir_tpu.ops.cascade_prefill")
    # The cascade: keys [0, 32) the trunk (one row), the rest each row's
    # own window, the query the window's last slot: key p - W is the
    # trunk's last, key p - W + 1 the window's first.
    t = 32
    R = T - t
    sfx_mask = jnp.ones((Bq, R), jnp.int32)
    qp = t + jnp.broadcast_to(jnp.arange(R), (Bq, R))
    qs = jnp.zeros((Bq, R) + q.shape[1:], q.dtype).at[:, -1].set(q)
    out = cp.cascade_attention(qs, k[:, t:], v[:, t:], k[0, :t].transpose(
        1, 0, 2), v[0, :t].transpose(1, 0, 2), sfx_mask, qp,
        window=jnp.int32(W), interpret=True)
    return out[:, -1].reshape(Bq, -1)


@pytest.mark.parametrize("route", ["dense", "decode_kernel", "cascade"])
def test_a_query_sees_key_p_minus_w_plus_1_and_not_key_p_minus_w(route):
    Bq, T, K, H, hd = 8, 40, 2, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    k = jax.random.normal(ks[0], (Bq, T, K, hd))
    k = k.at[:, :32].set(k[0, :32])
    v = jax.random.normal(ks[1], (Bq, T, K, hd))
    v = v.at[:, :32].set(v[0, :32])
    q = jax.random.normal(ks[2], (Bq, H, hd))
    kmask = jnp.ones((Bq, T), jnp.int32)
    p = T - 1
    qpos = jnp.full((Bq,), p, jnp.int32)
    base = _route_out(route, k, v, q, qpos, kmask)
    for slot, moves in ((p - W, False), (p - W + 1, True)):
        k2 = k.at[:, slot].add(3.0)
        v2 = v.at[:, slot].add(3.0)
        got = _route_out(route, k2, v2, q, qpos, kmask)
        changed = float(jnp.abs(got - base).max())
        assert (changed > 1e-3) == moves, (slot, changed)


@pytest.mark.parametrize("trunk", [0, 32])
def test_the_windowed_decode_kernel_counts_the_blocks_it_fetches(trunk):
    """The kernel's (read, needed, calls): a K block is brought in where
    the program before it named another (skipped programs name the block
    held, the trunk's splits block 0 for every batch block), and the keys
    needed are those within each query's window."""
    from importlib import import_module

    fd = import_module("lir_tpu.ops.flash_decode")

    Bq, T, K, H, hd = 32, 128, 2, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    k = jax.random.normal(ks[0], (K, T, Bq, hd))
    v = jax.random.normal(ks[1], (K, T, Bq, hd))
    q = jax.random.normal(ks[2], (Bq, H, hd))
    lens = np.asarray(40 + 2 * np.arange(Bq))     # the rows' real slots
    kmask = jnp.asarray(np.arange(T)[None] < lens[:, None], jnp.int32)
    kpos = decoder.mask_positions(kmask)
    qpos = jnp.asarray(lens - 1, jnp.int32)
    _, keys = fd.flash_decode_trunk(q, k, v, qpos, kmask, kpos,
                                    trunk_len=trunk, window=jnp.int32(W),
                                    block_k=16, interpret=True)
    bb = fd.batch_block(Bq)
    split = fd.decode_split(T, Bq, H // K, 16)
    nt = trunk // split
    kp = np.where(np.asarray(kmask) > 0, np.asarray(kpos), 1 << 30)
    qp = np.asarray(qpos)[:, None]
    attended = (kp <= qp) & (kp > qp - W)              # (B, T)
    names, held = [], None
    for j in range(T // split):
        for i in range(Bq // bb):
            if attended[i * bb:(i + 1) * bb, j * split:(j + 1) * split].any():
                held = (j, 0 if j < nt else i)
            names.append(held)
    first = next(n for n in names if n is not None)
    names = [first if n is None else n for n in names]
    fetched = 1 + sum(a != b for a, b in zip(names, names[1:]))
    assert np.asarray(keys).tolist() == [fetched * split * bb,
                                         int(attended.sum()), 1]
    assert fetched < (T // split) * (Bq // bb)


# ---------------------------------------------------------------------------
# (f) The counters are what the dispatched program did
# ---------------------------------------------------------------------------

def _reference_picks(spec, seqs):
    """{expert layer: held picks} the reference's routers make over the
    (sequence, positions) pairs given: the hidden state entering each
    layer's expert MLP of every listed position."""
    key = ref.seed_key(SEED)
    width = max(len(s) for s, _ in seqs)
    tokens = np.stack([np.pad(s, (0, width - len(s))) for s, _ in seqs])
    first, count = spec.held
    picks = {}
    with jax.default_matmul_precision("highest"):
        x = ref.embed(spec, key, jnp.asarray(tokens))
        for layer in range(spec.layers):
            window = spec.windows[layer]
            if layer < spec.dense_layers:
                x = ref.dense_block(spec, key, layer, x)
                continue
            w = ref.layer_weights(spec, key, layer,
                                  jnp.arange(first, first + count))
            mid = x + ref._rms(ref._attention(spec, x, w, window, "float32"),
                               w["ln1"]["scale"], spec.eps)
            idx, _ = ref.route(spec, mid, w["router"])
            held = np.asarray((idx >= first) & (idx < first + count)).sum(-1)
            picks[layer] = sum(int(held[i, pos].sum())
                               for i, (_, pos) in enumerate(seqs))
            x = ref.expert_block(spec, key, layer, x)
    return picks


def test_the_counters_are_what_the_dispatched_program_did(monkeypatch):
    """Through the engine (a cascade dispatch of the sweep's shape): the
    routing counts the program returns, folded as the sweep's drain folds
    them, are the reference's routers' over every token the program ran
    (the trunk once, each row's remainder, both suffixes and the tokens
    each branch's decode steps fed), and the window counters are the
    windowed decode kernel's over every decode step: the keys the window
    bias allows its queries, and at least those read."""
    monkeypatch.setattr(decoder, "CASCADE_INTERPRET_ON_CPU", True)
    monkeypatch.setattr(decoder, "FUSED_DECODE_INTERPRET_ON_CPU", True)
    spec, cfg, params = _model()
    engine = builders.build_engine(params, cfg, {"batch_size": B,
                                                 "max_seq_len": 256})
    rng = np.random.default_rng(11)
    head = list(rng.integers(20, 2048, 32))
    rows_a, rows_b = [], []
    for r in range(B):
        body = head + list(rng.integers(20, 2048, 40 - r))
        rows_a.append(body + list(rng.integers(20, 2048, 5)))
        rows_b.append(body + list(rng.integers(20, 2048, 7)))
    with jax.default_matmul_precision("highest"):
        fused, cfused = engine.decode_fused_shared(
            None, None, np.full(B, 5), np.full(B, 6), 3, 4,
            pretokenized_a=rows_a, pretokenized_b=rows_b, bucket=128,
            reuse_cache=True, n_real=B)
    trunk = engine.cascade_stats.summary()
    assert trunk["cascade_dispatches"] == 1
    stats = ExpertStats()
    stats.fold(np.asarray(cfused.expert_counts.counts), cfg.expert_top_k)
    t = 32                              # the head, on the trunk quantum
    seqs = []
    for r in range(B):
        n = 72 - r
        gen_a = np.asarray(fused.generated)[r, :3]
        gen_b = np.asarray(cfused.generated)[r, :4]
        seq_a = np.asarray(rows_a[r] + list(gen_a))
        seq_b = np.asarray(rows_b[r] + list(gen_b))
        seqs.append((seq_a, np.arange(0 if r == 0 else t, n + 5 + 3)))
        seqs.append((seq_b, np.arange(n, n + 7 + 4)))
    picks = _reference_picks(spec, seqs)
    assert stats.picks_held == sum(picks.values())
    queries = sum(len(pos) for _, pos in seqs)
    assert stats.picks == queries * cfg.expert_top_k * cfg.expert_layers
    assert stats.rows_computed >= stats.picks_held
    # Window counters: every layer's decode steps run the windowed kernel
    # (a global layer's window reaches every key), a query a row a step;
    # a window layer's query needs W keys here, a global one's all.
    engine.window_stats.fold(np.asarray(cfused.expert_counts.keys))
    ws = engine.window_stats.summary()
    steps = 3 + 4
    assert ws["calls"] == steps * cfg.n_layers
    win = sum(cfg.layer_windowed)
    assert ws["key_slots_needed"] > W * B * steps * win
    assert ws["key_slots_needed"] < (W * win + 128 * (cfg.n_layers - win)
                                     ) * B * steps
    assert ws["key_slots_read"] >= ws["key_slots_needed"]


def test_the_new_counters_reach_the_metrics_snapshot():
    from lir_tpu.observe import registry as obs

    _, cfg, params = _model()
    engine = builders.build_engine(params, cfg, {"batch_size": B,
                                                 "max_seq_len": 128})
    engine.expert_stats.fold(np.array([[3, 1, 16, 16, 10]] * 7), 4)
    snap = obs.engine_registry(engine).snapshot(device_memory=False)
    moe_fields = snap["sources"]["moe"]["fields"]
    assert moe_fields["picks_held"] == 28 and moe_fields["picks"] == 280
    assert moe_fields["rows_padding"] == 7 * (32 - 4)
    engine.window_stats.fold(np.array([300, 120, 3]))
    snap = obs.engine_registry(engine).snapshot(device_memory=False)
    assert snap["sources"]["window"]["fields"] == {
        "key_slots_read": 300, "key_slots_needed": 120, "calls": 3}


# ---------------------------------------------------------------------------
# (g) The family through the benchmark's doors
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def files():
    return bench_run.load_files(CELL)


def test_the_cell_is_found_by_its_names(files):
    bench, cell = bench_run.load_cell(CELL["name"])
    assert cell["chips"] == 1 and cell["traffic"] == "sweep-trunk512"
    assert files["ref"] is ref and files["spec"].preset == "k-exaone-236b-l12"
    assert files["runtime"] == {"batch_size": 40, "max_seq_len": 512}
    reports = {m["name"] for m in bench["per_layer"]
               if CELL["name"] in m.get("workloads", [])}
    assert {"expert_matmul_roofline", "windowed_decode_roofline",
            "windowed_cascade_roofline",
            "expert_rows_padded_pct.sweep", "window_keys_read_pct.sweep",
            "step_mfu_pct.sweep", "hbm_peak_pct.sweep"} <= reports
    assert not {"decode_kernel_roofline", "cascade_prefill_roofline"} & reports
    for name in reports:
        assert (bench_run.HERE / "metrics" / f"{name}.json").exists(), name
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL["name"] in e2e["prompts_per_s"]["workloads"]


def test_the_configuration_is_the_catalogs_row_cut_as_listed():
    spec, raw = _published()
    assert raw["source"] == ("https://huggingface.co/LGAI-EXAONE/"
                             "K-EXAONE-236B-A23B/blob/main/config.json")
    assert (raw["num_experts_per_tok"], raw["moe_intermediate_size"],
            raw["sliding_window"], raw["routed_scaling_factor"]) == (
                8, 2048, 128, 2.5)
    assert raw["layer_types"] == ["sliding_attention"] * 3 + [
        "full_attention"] + (["sliding_attention"] * 3 + [
            "full_attention"]) * 2
    assert set(raw["reduced"]) == {"num_hidden_layers", "num_experts",
                                   "vocab_size", "layer_types",
                                   "mlp_layer_types", "sliding_windows"}
    assert {"norm_placement", "qk_norm", "global_nope", "selection_bias",
            "held_experts"} <= set(raw["assumed"])
    assert raw["left_out"].startswith("multi-token prediction")
    assert spec.experts == 128 and spec.held == (0, 16) and spec.layers == 12


def test_the_served_tree_is_what_the_program_reads(files):
    from lir_tpu.models.quant import QuantTensor

    spec = ref.tiny(files["spec"])
    params = builders.build_params(spec, ref, SEED)
    assert set(params) == {"tok_embed", "final_ln", "lm_head",
                           "dense_layers", "layers"}
    dense, layers = params["dense_layers"], params["layers"]
    assert dense["w_up"].q.shape == (1, 64, 128)
    assert layers["experts"]["w_gate"].q.shape == (7, 4, 64, 32)
    assert isinstance(layers["shared"]["w_down"], QuantTensor)
    assert layers["router"]["w"].dtype == jnp.float32
    assert layers["router"]["w"].shape == (7, 64, 16)
    assert layers["q_norm"].shape == (7, 16)
    other = builders.build_params(spec, ref, SEED + 1)
    assert (np.asarray(layers["experts"]["w_up"].q)
            != np.asarray(other["layers"]["experts"]["w_up"].q)).any()


def test_flops_sum_over_the_three_rows(files):
    spec = files["spec"]
    (ld, pd, wd, _), (lw, pw, ww, _), (lg, pg, wg, _) = spec.layer_costs
    assert (ld, lw, lg) == (1, 8, 3) and pw == pg and wd == ww == wg
    want = (2 * pd + 2 * wd * 401) + 11 * (2 * pw + 2 * ww * 401)
    assert flops.tokens_flops(spec, 400, 401) == want


def test_the_two_kernels_are_sized_from_the_traffic(files):
    spec, mix = files["spec"], files["mix"]
    prompts = traffic.load_prompts(mix)
    perts = [[p.main] * 40 if i == 0 else [] for i, p in enumerate(prompts)]
    calls = sizing.window_calls(spec, mix, prompts, perts, (4, 8))
    assert set(calls) == set(sizing.CALLS)
    originals, groups = calls["expert_matmul_call"]
    assert originals["batch"] == 5 and groups["batch"] == 40
    sizes = {k: v for k, v in groups.items() if k != "dispatches"}
    ex = sizing.CALLS["expert_matmul_call"](spec, **sizes)
    # trunk pass, prefix chunks, two suffixes, 12 decode steps; 2 calls each
    assert len(ex) % 2 == 0 and len(ex) >= 2 * (1 + 1 + 2 + 12)
    decode = sizing.expert_matmul_calls(spec, 40)
    reached = 16 * (1 - (15 / 16) ** 40)
    assert decode[0][0] == 2.0 * 40 * 6144 * 4096
    assert decode[0][1] == pytest.approx(reached * 2 * 6144 * 2048
                                         + 40 * 6144 * 2 + 40 * 2048 * 2)
    d = calls["windowed_decode_call"][1]
    win = sizing.CALLS["windowed_decode_call"](
        spec, **{k: v for k, v in d.items() if k != "dispatches"})
    assert len(win) == 12
    # At the cell's ~450-key extents a window layer reads 128 keys a row
    # and no trunk key; a global layer every key, the trunk's once.
    long = sizing.windowed_decode_call(spec, 40, 450.0, 64)
    kv = 2.0 * 8 * 128 * 2
    assert long[0][1] == kv * 40 * 128 + 2.0 * 40 * 64 * 128 * 2
    assert long[3][1] == kv * (64 + 40 * (450 - 64)) + 2.0 * 40 * 64 * 128 * 2
    # The cascade runs only behind a shared trunk: the groups' dispatches.
    (c,) = calls["windowed_cascade_call"]
    assert (c["batch"], c["trunk"]) == (40, 64)
    casc = sizing.windowed_cascade_call(spec, 40, 384.0, 64)
    assert len(casc) == 12
    tri = lambda n: n * (n + 1) / 2.0  # noqa: E731
    keys_win = tri(64) + 40 * (tri(128) + 128 * (384 - 128) - tri(64))
    keys_glob = tri(64) + 40 * (tri(384) - tri(64))
    assert casc[0][0] == 4.0 * 64 * 128 * keys_win
    assert casc[3][0] == 4.0 * 64 * 128 * keys_glob
    qo = 2.0 * 64 * 128 * 2 * (64 + 40 * 320)
    assert casc[0][1] == kv * (64 + 40 * 320) + qo
    assert casc[3][1] == casc[0][1]      # the window reaches the whole trunk
    # A pass of a prime count of tokens: the fewest chunks of at most
    # CHUNK_TOKENS, each its share (the program pads them equal).
    assert sizing._chunks(4099) == 2 and sizing._chunks(8192) == 2
    prime = sizing.expert_matmul_call(spec, 1, 4099.0)
    assert len(prime) == 4
    other = bench_run.load_files({"name": "x", "config": "mistral-7b",
                                  "traffic": "sweep-trunk512"}, limits={})
    assert sizing.window_calls(other["spec"], mix, prompts, perts,
                               (4, 8)) == {}


def test_a_roofline_reads_the_familys_own_module(files):
    spec, mix = files["spec"], files["mix"]
    prompts = traffic.load_prompts(mix)
    perts = [[p.main] * 40 if i == 0 else [] for i, p in enumerate(prompts)]
    pk = peaks.peaks_for("TPU v5e")
    ctx = {"spec": spec, "peaks": pk,
           "trace": {"ops": {"expert_matmul_gate_up.3 (bf16[": (0.9, 500),
                             "expert_matmul_down.4 (bf16[": (0.6, 500),
                             "flash_decode_trunk.2": (9.0, 9),
                             "cascade_attention.5": (0.4, 36)}},
           "traffic": {"mix": mix, "prompts": prompts, "perts": perts,
                       "steps": (4, 8)}}
    metric = json.loads((bench_run.HERE / "metrics" /
                         "expert_matmul_roofline.json").read_text())
    assert metric["args"]["module"] == "exaone_moe"
    got = readers.READERS[metric["reader"]](ctx, **metric["args"])
    assert 0 < got < 100
    out = readers.read_all([{"name": "windowed_decode_roofline", "unit": "%"},
                            {"name": "expert_matmul_roofline", "unit": "%"},
                            {"name": "windowed_cascade_roofline", "unit": "%"}],
                           ctx)
    # No windowed decode op traced; the cascade is read through this module.
    assert set(out) == {"expert_matmul_roofline", "windowed_cascade_roofline"}
    assert 0 < out["windowed_cascade_roofline"]["value"] < 100
