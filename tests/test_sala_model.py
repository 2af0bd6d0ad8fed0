"""Layers that differ in kind (MiniCPM-SALA): lightning linear-attention
layers among softmax layers that select blocks. The program against the
plain reference (benchmarks/references/sala.py) at tiny sizes on the CPU,
float32 served tree, seeded weights.

What is pinned: the full forward equals the reference's with a layer of
each kind in both orders and the selection live; prefill + cascade extend
+ format extend + decode steps through the cache equal the reference's
full forward of each row alone (a shared trunk, rows on each side of
``dense_len``); the scan and step kernels at this family's shapes (a group
a head) equal the token recurrence; the selection's invariants and its
equality with the reference's; the block-masked attention kernel equals
the same mathematics in XLA; the cache holds K/V for the softmax layers
only and a float32 state for the lightning layers only; through the
engine a sweep shares the trunk, and the host's counters are what the
program it dispatched does.
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

from harness import builders  # noqa: E402
from references import sala as ref  # noqa: E402

from lir_tpu.models import decoder, mixed, registry  # noqa: E402
from lir_tpu.ops import sparse_attention as sparse  # noqa: E402
from lir_tpu.ops import ssd_scan  # noqa: E402

SEED = 2**31 + 31
ORDERS = {"sparse-first": ("sparse", "lightning", "lightning", "sparse"),
          "lightning-first": ("lightning", "sparse")}


def _published():
    raw = json.loads((REPO / "benchmarks/configs/minicpm-sala.json"
                      ).read_text())
    return ref.spec_from_config("minicpm-sala", raw)


def _tiny(order="sparse-first", **sizes):
    """A selection that is live on rows of ~96 tokens: 12 blocks of 8, the
    last 32 positions local, the best 2 of the others kept past 32."""
    sizes = {"window": 32, "dense_len": 32, **sizes}
    return dataclasses.replace(ref.tiny(_published(), ORDERS[order]), **sizes)


def _model(spec):
    cfg = builders.program_config(spec, ref, check=False)
    params = builders.build_params(spec, ref, SEED)
    params = jax.tree.map(lambda a: a.astype(jnp.float32)
                          if a.dtype == jnp.bfloat16 else a, params)
    return cfg, params


@pytest.fixture(params=[False, True], ids=["xla", "kernels"])
def kernels(request, monkeypatch):
    monkeypatch.setattr(decoder, "SSM_INTERPRET_ON_CPU", request.param)
    monkeypatch.setattr(decoder, "SPARSE_INTERPRET_ON_CPU", request.param)
    return request.param


def _reference(spec, tokens, positions):
    return np.asarray(ref.logits_at(spec, SEED, np.asarray(tokens),
                                    np.asarray(positions)))


# ---------------------------------------------------------------------------
# The preset and the cache
# ---------------------------------------------------------------------------

def test_the_preset_is_the_published_model():
    cfg = registry.REGISTRY["minicpm-sala"]()
    spec = _published()
    assert builders.program_config(spec, ref) == cfg
    assert cfg.layer_kinds == spec.kinds and len(cfg.layer_kinds) == 32
    assert [i for i, k in enumerate(cfg.layer_kinds) if k == "sparse"] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    assert [(k[0], n) for k, _, n in cfg.layer_runs] == [
        ("s", 1), ("l", 8), ("s", 1), ("l", 6), ("s", 2), ("l", 4), ("s", 1),
        ("l", 6), ("s", 3)]
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.hidden_size,
            cfg.intermediate_size, cfg.vocab_size) == (32, 2, 128, 4096,
                                                       16384, 73448)
    assert (cfg.lightning_heads, cfg.lightning_head_dim) == (32, 128)
    assert cfg.carries_state and not cfg.has_mixer
    params = sum(layers * per for layers, per, _, _ in spec.layer_costs)
    assert round(params / 1e9, 2) == 8.88           # + 0.60B embedding, head


@pytest.mark.parametrize("kinds, rounds", [
    (None, ([[0, 0], [1, 8], [2, 14], [4, 18], [5, 0]],
            [[1, 8], [1, 6], [2, 4], [1, 6], [3, 0]])),
    (("lightning", "sparse"), ([[0, 0], [0, 0]], [[0, 1], [1, 0]])),
    (("sparse", "sparse", "lightning"), ([[0, 0]], [[2, 1]])),
], ids=["published", "lightning-first", "one-round"])
def test_a_pass_holds_one_layer_body_a_kind(kinds, rounds):
    """The order's runs fold into rounds of (sparse run, lightning run), so
    a traced pass holds ONE loop a kind under one scan however many runs
    the order has (nine scans a pass cost the published model's dispatch
    programs four times the compile)."""
    if kinds is None:
        cfg = registry.REGISTRY["minicpm-sala"]()
    else:
        cfg, _ = _model(_tiny(kinds=kinds))
    first, count = mixed.layer_rounds(cfg)
    assert first.tolist() == rounds[0] and count.tolist() == rounds[1]
    order = [(kind, f + i) for fs, cs in zip(first, count)
             for kind, f, c in zip(mixed.KIND_ORDER, fs, cs)
             for i in range(c)]
    seen = {}
    assert order == [(k, seen.setdefault(k, []).append(0) or len(seen[k]) - 1)
                     for k in cfg.layer_kinds]
    if kinds is None:
        return
    cfg, params = _model(_tiny(kinds=kinds))
    tokens = jnp.ones((2, 24), jnp.int32)
    text = str(jax.make_jaxpr(lambda p: decoder.forward(
        p, cfg, tokens, jnp.ones_like(tokens)))(params))
    assert text.count(" while[") == 2          # one loop a kind


def test_the_cache_holds_each_kind_of_state_for_its_own_layers_only():
    cfg, _ = _model(_tiny())
    cache = decoder.init_cache(cfg, 3, 64, jnp.bfloat16)
    tail_k, tail_v, state, pooled, main_k, main_v = cache
    assert main_k.shape == main_v.shape == (2, 3, 2, 64, 16)   # 2 sparse
    assert state.shape == (2, 3, 4, 16, 16)                    # 2 lightning
    assert state.dtype == jnp.float32 and main_k.dtype == jnp.bfloat16
    assert pooled.shape == (2, 3, 2, 31, 16)
    kv, rec = mixed.cache_kinds(cache)
    assert len(kv) == 4 and rec == (state,)
    _, served, _ = decoder.prefill(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                     if a.dtype == jnp.float32 and a.ndim > 1 else a,
                     _model(_tiny())[1]), cfg,
        jnp.ones((2, 40), jnp.int32), jnp.ones((2, 40), jnp.int32), 48)
    assert served[2].dtype == jnp.float32 and served[4].dtype == jnp.bfloat16
    assert served[0].shape[3] == 8 and served[4].shape[3] == 40


@pytest.mark.parametrize("what", ["verify", "spec", "piggyback", "int8",
                                  "seq_parallel", "grouped"])
def test_what_cannot_hold_this_state_refuses(what):
    cfg, params = _model(_tiny())
    if what == "verify":
        with pytest.raises(NotImplementedError, match="recurrent state"):
            decoder.verify_extend(params, cfg, None, jnp.zeros((1, 2),
                                                               jnp.int32),
                                  jnp.ones((1, 8), jnp.int32), 4)
    elif what == "int8":
        with pytest.raises(ValueError, match="one mixer a layer"):
            dataclasses.replace(cfg, kv_cache_int8=True)
    elif what == "seq_parallel":
        with pytest.raises(NotImplementedError, match="differ in kind"):
            decoder.forward(params, cfg, jnp.zeros((1, 8), jnp.int32),
                            attn_impl=lambda *a: None)
    elif what == "grouped":
        from lir_tpu.engine import generate

        i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
        with pytest.raises(NotImplementedError, match="grouped batch"):
            generate.greedy_decode_dispatch(
                params, cfg, generate.Program(layout="grouped",
                                              max_new=(2,)),
                generate.DispatchArgs(
                    prefix=i32(1, 16), prefix_mask=i32(1, 16) + 1,
                    sfx=(i32(2, 8),), sfx_mask=(i32(2, 8) + 1,),
                    yes_ids=i32(2), no_ids=i32(2), digit_ids=i32(3),
                    digit_vals=jnp.zeros((3,)), group_idx=i32(2)))
    else:
        from lir_tpu.backends.fake import FakeTokenizer
        from lir_tpu.config import RuntimeConfig
        from lir_tpu.engine.runner import ScoringEngine

        engine = ScoringEngine(params, cfg, FakeTokenizer(vocab=2048),
                               RuntimeConfig(batch_size=4, max_seq_len=128,
                                             spec_decode=True, spec_k=4))
        assert not (engine.spec_supported() if what == "spec"
                    else engine.piggyback_supported())


# ---------------------------------------------------------------------------
# Program against reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", sorted(ORDERS))
def test_full_forward_equals_the_reference(order, kernels):
    spec = _tiny(order)
    cfg, params = _model(spec)
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, spec.vocab, (3, 96)).astype(np.int32)
    positions = np.tile(np.arange(5, 96, 6)[None], (3, 1)).astype(np.int32)
    want = _reference(spec, tokens, positions)
    got = np.take_along_axis(
        np.asarray(decoder.forward(params, cfg, jnp.asarray(tokens))),
        positions[:, :, None], axis=1)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    # The selection is live: a query past dense_len drops blocks.
    kept, offered, dense = sparse.kept_blocks(
        np.arange(96), 96, block=spec.block, topk=spec.topk,
        init_blocks=spec.init_blocks, window=spec.window,
        dense_len=spec.dense_len)
    assert dense == 32 and kept < offered


_extend = jax.jit(decoder.extend, static_argnums=(1, 6))
_step = jax.jit(decoder.decode_step, static_argnums=(1,))


def _branch(cfg, params, cache, pm, sfx, steps, at, total):
    """One format branch on ``cache``: extend by ``sfx`` then ``steps``
    greedy tokens; returns every logit row read and the tokens fed."""
    B, S2 = sfx.shape
    ones = jnp.ones((B, S2), jnp.int32)
    cm = jnp.concatenate([pm, ones, jnp.zeros((B, total - at - S2),
                                              jnp.int32)], axis=1)
    logits, cache, pos = _extend(params, cfg, cache, sfx, ones, cm, at)
    rows, fed = [logits], []
    for j in range(steps):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        fed.append(tok)
        cm = cm.at[:, at + S2 + j].set(1)
        logits, cache = _step(params, cfg, cache, tok, pos + j,
                              jnp.int32(at + S2 + j), cm)
        rows.append(logits)
    return np.stack([np.asarray(r) for r in rows], 1), np.stack(
        [np.asarray(t) for t in fed], 1) if fed else np.zeros((B, 0), int)


@pytest.mark.parametrize("front", ["cascade", "prefill"])
def test_through_the_cache_equals_the_references_full_forward(front,
                                                              kernels):
    """Trunk at one row + the rows' windows behind it (or each row's whole
    prefix, lengths on both sides of ``dense_len``), then two format
    branches from the prefix's state, each an extend and greedy steps:
    every logit read equals the reference's full forward of that row's
    tokens alone."""
    spec = _tiny()
    cfg, params = _model(spec)
    rng = np.random.default_rng(5)
    B, S, S2, steps = 3, 80, 8, 3
    total = S + S2 + steps + 1
    prefix = rng.integers(3, spec.vocab, (B, S)).astype(np.int32)
    if front == "cascade":
        prefix[:, :64] = prefix[0, :64]
        lens = [80, 75, 66]
    else:
        lens = [80, 20, 41]                   # 20: every query dense
    pm = np.zeros((B, S), np.int32)
    for r, n in enumerate(lens):
        pm[r, :n] = 1
    pm = jnp.asarray(pm)
    if front == "cascade":
        _, trunk, _ = decoder.prefill(params, cfg, jnp.asarray(prefix[:1, :64]),
                                      jnp.ones((1, 64), jnp.int32), 64)
        start = decoder.cascade_extend(params, cfg, trunk,
                                       jnp.asarray(prefix[:, 64:]),
                                       pm[:, 64:], 64, total)
        assert start[4].shape[1] == 1 and start[0].shape[1] == B
    else:
        _, start, _ = decoder.prefill(params, cfg, jnp.asarray(prefix), pm,
                                      total)
    cache = start
    served, rows, at = [], [], []
    for b in range(2):
        if b:
            cache = decoder.rewind(cache, start)
        sfx = rng.integers(3, spec.vocab, (B, S2)).astype(np.int32)
        got, fed = _branch(cfg, params, cache, pm, jnp.asarray(sfx), steps,
                           S, total)
        served.append(got)
        for r, n in enumerate(lens):        # right padding is inert
            row = np.concatenate([prefix[r, :n], sfx[r], fed[r]])
            rows.append(np.pad(row, (0, total - len(row))))
            at.append(np.arange(n + S2 - 1, n + S2 + steps))
    want = _reference(spec, np.stack(rows), np.stack(at))
    np.testing.assert_allclose(np.concatenate(served), want, atol=3e-4,
                               rtol=0)


# ---------------------------------------------------------------------------
# The scan kernels at this family's shapes: a group a head
# ---------------------------------------------------------------------------

def _lightning_inputs(B, T, H, P, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x, b, c = (jax.random.normal(k, (B, T, H, P)) for k in ks[:3])
    dt = (jax.random.uniform(ks[3], (B, T, 1)) > 0.2).astype(jnp.float32)
    return (x, jnp.broadcast_to(dt, (B, T, H)), -mixed.lightning_slopes(H),
            b, c, jax.random.normal(ks[4], (B, H, P, P)))


@pytest.mark.parametrize("H", [4, 16])
@pytest.mark.parametrize("T,chunk", [(37, 16), (9, 16), (64, 16), (50, 24)])
def test_lightning_scan_equals_the_token_recurrence(T, chunk, H):
    x, dt, a, b, c, s0 = _lightning_inputs(2, T, H, 16, T + H)
    want_y, want_s = ssd_scan.ssd_scan_tokens(x, dt, a, b, c, s0)
    y, s = ssd_scan.ssd_scan(x, dt, a, b, c, s0, chunk=chunk, interpret=True,
                             name="lightning_scan")
    np.testing.assert_allclose(y, want_y, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(s, want_s, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("H", [4, 16])
@pytest.mark.parametrize("layer", [None, 1])
def test_lightning_step_equals_the_recurrence(H, layer):
    x, dt, a, b, c, s0 = _lightning_inputs(3, 1, H, 16, 40 + H)
    want_y, want_s = ssd_scan.ssd_scan_tokens(x, dt, a, b, c, s0)
    state = s0 if layer is None else jnp.stack([s0 * 0 + 7, s0, s0 * 0 - 3])
    y, s = ssd_scan.ssm_step(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], state,
                             interpret=True, layer=layer,
                             name="lightning_step")
    np.testing.assert_allclose(y, want_y[:, 0], atol=1e-5, rtol=1e-5)
    if layer is None:
        np.testing.assert_allclose(s, want_s, atol=1e-5, rtol=1e-5)
    else:
        np.testing.assert_allclose(s[1], want_s, atol=1e-5, rtol=1e-5)
        assert float(s[0].min()) == 7 and float(s[2].max()) == -3


def test_the_decay_is_lightning_attentions_slopes():
    s = np.asarray(mixed.lightning_slopes(32))
    np.testing.assert_allclose(s[[0, 31]], [2 ** -0.25, 2 ** -8], rtol=1e-6)
    np.testing.assert_allclose(s, np.asarray(ref.slopes(32)), rtol=1e-6)


# ---------------------------------------------------------------------------
# The selection
# ---------------------------------------------------------------------------

SIZES = dict(block=8, kernel=4, stride=2, topk=2, init_blocks=1, window=16,
             dense_len=32)


def _selection(seed, Bm=2, K=2, G=2, T=96, hd=16, positions=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    keys = jax.random.normal(ks[0], (Bm, K, T, hd))
    qpos = (jnp.tile(jnp.arange(T)[None], (Bm, 1)) if positions is None
            else jnp.asarray(positions))
    q = jax.random.normal(ks[1], (Bm, K, G, qpos.shape[1], hd))
    pooled = sparse.pool_keys(keys, SIZES["kernel"], SIZES["stride"])
    keep, bound = sparse.select_blocks(
        q, pooled, qpos, jnp.full((Bm,), T, jnp.int32), n_blocks=T // 8,
        **SIZES)
    return q, keys, np.asarray(qpos), np.asarray(keep), np.asarray(bound)


def test_the_selection_keeps_what_it_must_and_nothing_ahead():
    _, _, qpos, keep, bound = _selection(1)
    Bm, K, N, NB = keep.shape
    b = np.arange(NB)
    for p in range(N):
        row = keep[:, :, p]                                   # (Bm, K, NB)
        causal = b * 8 <= p
        assert not row[..., ~causal].any(), p                 # never ahead
        if p + 1 <= 32:
            assert (row == causal).all(), p                   # dense, exactly
            continue
        assert row[..., 0].all()                              # first block
        local = causal & ((b + 1) * 8 >= p - 16 + 2)
        assert row[..., local].all(), p                       # local window
        others = causal & ~local & (b >= 1)
        assert (row[..., others].sum(-1) == min(2, others.sum())).all(), p
    assert (bound == qpos).all()


@pytest.mark.parametrize("seed", [2, 3])
def test_the_selection_is_the_references(seed):
    q, keys, qpos, keep, _ = _selection(seed, Bm=1)
    spec = dataclasses.replace(_tiny(), window=16)
    want = ref._kept(spec, jnp.moveaxis(q[0], 2, 0),        # (t, K, G, hd)
                     jnp.moveaxis(sparse.pool_keys(keys[0], 4, 2), 1, 0),
                     jnp.asarray(qpos[0]), 96)
    assert (np.moveaxis(np.asarray(want), 0, 1) == keep[0]).all()


def test_the_hosts_count_is_what_the_selection_keeps():
    pos = np.asarray([[3, 31, 32, 40, 77, 95, 100, 130]])
    _, _, _, keep, _ = _selection(4, Bm=1, positions=pos)
    kept, offered, dense = sparse.kept_blocks(pos[0], 96, **{
        k: v for k, v in SIZES.items() if k not in ("kernel", "stride")})
    assert kept == keep[0, 0].sum() == keep[0, 1].sum()
    assert dense == 2 and offered == sum(min(p // 8 + 1, 12) for p in pos[0])


# ---------------------------------------------------------------------------
# The block-masked attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", ["trunk_window", "rows", "decode", "ragged"])
def test_the_attention_kernel_equals_the_same_mathematics_in_xla(case):
    Bm, N, T = {"trunk_window": (1, 72, 128), "rows": (3, 40, 128),
                "decode": (1, 5, 256), "ragged": (2, 19, 100)}[case]
    K, G, hd = 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 5)
    q = jax.random.normal(ks[0], (Bm, K, G, N, hd))
    k, v = (jax.random.normal(kk, (Bm, K, T, hd)) for kk in ks[1:3])
    nb = -(-T // 8)
    keep = jax.random.uniform(ks[3], (Bm, K, N, nb)) > 0.4
    bound = jax.random.randint(ks[4], (Bm, N), -1, T + 20)
    bound = jnp.minimum(bound, T - 1).at[0, 0].set(-1)        # sees nothing
    want = sparse.attend_main_xla(q, k, v, keep, bound, block=8)
    got = sparse.attend_main(q, k, v, keep, bound, block=8, interpret=True)
    stacked = sparse.attend_main(
        q, jnp.stack([k * 0, k]), jnp.stack([v * 0, v]), keep, bound,
        block=8, layer=jnp.int32(1), interpret=True, name="sparse_decode")
    for a, b, c in zip(got, want, stacked):
        dead = ~np.isfinite(np.asarray(want[1]))
        np.testing.assert_allclose(np.where(dead[..., None] if a.ndim == 5
                                            else dead, 0, a),
                                   np.where(dead[..., None] if b.ndim == 5
                                            else dead, 0, b),
                                   atol=2e-5, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    assert np.asarray(got[2])[0, :, :, 0].max() == 0          # l of the blind


# ---------------------------------------------------------------------------
# Through the engine
# ---------------------------------------------------------------------------

def test_a_sweep_shares_the_trunk_and_counts_what_it_dispatched(
        tmp_path, monkeypatch):
    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.data.prompts import LegalPrompt
    from lir_tpu.engine import compile_plan
    from lir_tpu.engine.runner import ScoringEngine
    from lir_tpu.engine.sweep import run_perturbation_sweep
    from lir_tpu.observe import registry as metrics_mod

    spec = dataclasses.replace(_tiny("lightning-first"), window=96,
                               vocab=FakeTokenizer.VOCAB)
    cfg, params = _model(spec)
    monkeypatch.setattr(decoder, "CASCADE_INTERPRET_ON_CPU", True)
    passes = []
    real = mixed._run_layers
    monkeypatch.setattr(mixed, "_run_layers", lambda p, c, x, win, cache: (
        passes.append((x.shape[0], x.shape[1], win["fill"])),
        real(p, c, x, win, cache))[1])
    compile_plan.exec_cache_clear()
    engine = ScoringEngine(params, cfg, FakeTokenizer(),
                           RuntimeConfig(batch_size=4, max_seq_len=256,
                                         sweep_group_min_cells=0,
                                         dispatch_tokens=256))
    assert engine.cascade_supported() and not engine.spec_supported()
    rng = np.random.default_rng(7)
    words = ("coverage policy flood water damage claim insurer premium "
             "exclusion endorsement").split()
    head = " ".join(rng.choice(words) for _ in range(128))

    def text():
        return head + " " + " ".join(rng.choice(words)
                                     for _ in range(12)) + " ?"

    lp = (LegalPrompt(main=text(), response_format="Answer Yes or No .",
                      target_tokens=("Yes", "No"),
                      confidence_format="Give a number from 0 to 100 ."),)
    rows = run_perturbation_sweep(engine, "sala", lp,
                                  ([text() for _ in range(7)],),
                                  tmp_path / "rows.csv")
    engine.exec_registry.wait()
    assert len(rows) == 8 and all(r.token_1_prob is not None for r in rows)
    casc, rec, sp = (engine.cascade_stats, engine.recurrent_stats,
                     engine.sparse_stats)
    # 8 rows of 141 tokens in the 256 bucket under a cap of 256 beside a
    # 128-token trunk: dispatches of 2. The trunk is more than half of
    # what a dispatch would prefill (128 >= 2 x 64), so it is a value:
    # ONE trunk program for the call, every dispatch takes its cache.
    n = casc.cascade_dispatches
    assert n == 4 and casc.dense_fallbacks == 0
    assert casc.trunk_programs == 1 and casc.trunk_held_dispatches == n
    assert casc.trunk_tokens_prefilled == 128
    assert casc.tokens_prefilled == 128 + 8 * (13 + 5 + 8)
    # The first dispatch had the trunk run for it (rows - 1), the others
    # found it held (rows).
    assert rec.trunk_states_shared == casc.trunk_rows_deduped == 8 - 1
    assert rec.forks == 2 * 8
    # The programs as traced: the trunk program is a fill at one row and
    # nothing else; a dispatch program the windows, two extends (scans:
    # one a lightning layer each) and two decode loops whose body is
    # traced once and runs its budget of steps, and NO fill.
    fills = [p for p in passes if p[2]]
    assert fills and all(p[:2] == (1, 128) for p in fills)
    windows = [p for p in passes if p[1] == 64 and not p[2]]
    extends = [p for p in passes if 1 < p[1] < 64]
    steps = [p for p in passes if p[1] == 1]
    programs = len(steps) // 2
    assert len(extends) == len(steps) == 2 * programs
    # ... and the front alone once more where a donated variant is lowered
    # over the cache's avals (generate.dispatch_cache_avals).
    assert programs <= len(windows) <= 2 * programs
    assert len(windows) + len(extends) + len(steps) + len(fills) == len(passes)
    lightning = cfg.kind_layers("lightning")
    budget = engine.rt.sweep_decode_tokens + engine.rt.sweep_confidence_tokens
    assert rec.scan_calls == (n * 3 + 1) * lightning
    assert rec.step_calls == n * budget * lightning
    assert rec.state_bytes > 0 and rec.kv_bytes > 0
    assert sp.queries == (casc.tokens_prefilled + 8 * budget) * cfg.kind_layers(
        "sparse")
    assert 0 < sp.blocks_kept < sp.blocks_offered
    assert sp.dense_queries == spec.dense_len and sp.pooled_key_bytes > 0
    snap = metrics_mod.engine_registry(engine).snapshot(device_memory=False)
    assert snap["sources"]["sparse"]["fields"]["blocks_kept"] == sp.blocks_kept
    assert 0 < snap["sources"]["sparse"]["summary"]["kept_share"] < 1
    assert not [s for s in engine.compile_stats.shapes
                if "spec" in s or s.startswith("piggy") or "grouped" in s]


def test_long_rows_ride_together_only_where_they_share_a_trunk():
    from lir_tpu.engine import scheduler as sched

    def item(ids):
        ids = tuple(ids)
        return sched.SweepItem(cell=None, bin_ids=ids + (1, 5),
                               conf_ids=ids + (2, 6), lcp=len(ids))

    doc_a, doc_b = list(range(10, 210)), list(range(300, 500))
    rows = ([item(doc_a + [7] * 40)] + [item(doc_a + [8 + i] * 56)
                                        for i in range(9)]
            + [item(doc_b + [7] * 40)] + [item(doc_b + [8 + i] * 56)
                                          for i in range(4)])
    plan = sched.RaggedScheduler((64, 128, 256, 384), 4, token_cap=384,
                                 group_cells=False).schedule(rows)
    sizes = [len(d.items) for d in plan]
    assert sorted(sizes, reverse=True) == [4, 4, 4, 2, 1]
    for d in plan:                             # no dispatch mixes documents
        assert len({it.bin_ids[0] for it in d.items}) == 1
    free = sched.RaggedScheduler((64, 128, 256, 384), 4, group_cells=False
                                 ).schedule(rows)
    assert sorted(len(d.items) for d in free) == [3, 4, 4, 4]
