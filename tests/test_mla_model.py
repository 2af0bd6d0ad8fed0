"""Latent attention with YaRN rotation before the expert layer (Kimi-K2):
the program against the plain reference (benchmarks/references/mla_moe.py)
at tiny sizes on the CPU, seeded weights, float32.

What is pinned: the preset is the configuration file's model; a prefill,
then a format extend, then decode steps through the latent cache equal the
reference's full forward (expanded passes, absorbed decode); the absorbed
attention is the expanded attention; YaRN's frequencies and softmax scale
are the published formula worked by hand; a 40-row dispatch behind ONE
latent trunk equals each row's own forward; the experts' terms over shares
that cover every expert, the shared expert once, are the uncut layer; the
two kernels equal their XLA twins over many tiles; the latent counters are
what the passes did; and whatever holds K/V by row refuses the model with
one plain error.

Every comparison is float32 against float32 through different orders of
summation (tiles, the merge of two legs, W_k folded into the query): the
tolerances allow that and nothing more, about 1e-4 of logits of size ~8.
"""

import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "benchmarks"))

from harness import builders  # noqa: E402
from references import mla_moe as ref  # noqa: E402

from lir_tpu.models import decoder, mla, moe, registry  # noqa: E402
from lir_tpu.ops import latent_attention as la  # noqa: E402

SEED = 2**31 + 41
# float32 against float32, summed in other orders: logits are ~8 wide.
ATOL = 2e-4


def _published():
    raw = json.loads((REPO / "benchmarks/configs/kimi-k2-6.json"
                      ).read_text())
    return ref.spec_from_config("kimi-k2-6", raw), raw


def _model(spec=None):
    spec = spec or ref.tiny(_published()[0])
    cfg = builders.program_config(spec, ref, check=False)
    params = builders.build_params(spec, ref, SEED)
    params = jax.tree.map(lambda a: a.astype(jnp.float32)
                          if a.dtype == jnp.bfloat16 else a, params)
    return spec, cfg, params


@pytest.fixture(params=[False, True], ids=["xla", "kernels"])
def kernels(request, monkeypatch):
    monkeypatch.setattr(decoder, "LATENT_INTERPRET_ON_CPU", request.param)
    monkeypatch.setattr(decoder, "MOE_INTERPRET_ON_CPU", request.param)
    return request.param


def _tokens(rows, length, seed=0, vocab=2048):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (rows, length)).astype(np.int32)


# ---------------------------------------------------------------------------
# The preset
# ---------------------------------------------------------------------------

def test_the_preset_is_the_configuration_files_model():
    spec, raw = _published()
    cfg = registry.REGISTRY["kimi-k2-6-l13"]()
    assert builders.program_config(spec, ref) == cfg
    assert (cfg.hidden_size, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim) == (
                7168, 64, 1536, 512, 128, 64, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_top_k,
            cfg.expert_size, cfg.expert_layers, cfg.vocab_size) == (
                384, (0, 12), 8, 2048, 12, 20480)
    assert cfg.latent and cfg.trunk_apart
    whole = registry.REGISTRY["kimi-k2-6"]()
    assert whole.n_layers == 61 and whole.experts_held == (0, 384)
    assert raw["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 384,
                                "vocab_size": 163840}
    # Parameters (PERF.md section 4): attention 101.12 M, an expert 44.04 M.
    assert spec.attention_params == 101_122_048
    assert spec.expert_params == 44_040_192
    dense, routed = spec.layer_costs
    assert dense == (1, 101_122_048 + 396_361_728, 64 * 320, 0)
    assert routed[0] == 12
    assert routed[1] == (101_122_048 + 7168 * 384 + 44_040_192
                         + 8 * 12 * 44_040_192 // 384)
    # 8.91 B parameters here; bytes: int8 matrices, a float32 router, a
    # bfloat16 embedding.
    routed_layer = (spec.attention_params + 7168 * 384
                    + 13 * spec.expert_params)
    params = dense[1] + 12 * routed_layer + 2 * 20480 * 7168
    assert round(params / 1e9, 2) == 8.91
    assert spec.weight_bytes == params + 12 * 3 * 7168 * 384 + 20480 * 7168


# ---------------------------------------------------------------------------
# Through the latent cache against the full forward
# ---------------------------------------------------------------------------

def test_prefill_extend_and_decode_equal_the_references_full_forward(
        kernels):
    spec, cfg, params = _model()
    B, S, S2, N = 3, 24, 8, 4
    toks = _tokens(B, S + S2 + N)
    positions = np.tile(np.arange(S + S2 + N)[None], (B, 1))
    want = np.asarray(ref.logits_at(spec, SEED, toks, positions))
    T = S + S2 + N + 4
    logits, cache, _ = decoder.prefill(params, cfg, jnp.asarray(toks[:, :S]),
                                       jnp.ones((B, S), jnp.int32), T)
    np.testing.assert_allclose(logits, want[:, S - 1], atol=ATOL)
    assert cache[1].shape == (3, B, S, 24) and cache[0].shape == (
        3, B, T - S, 24)
    mask = jnp.concatenate([jnp.ones((B, S + S2), jnp.int32),
                            jnp.zeros((B, T - S - S2), jnp.int32)], 1)
    logits, cache, nxt = decoder.extend(
        params, cfg, cache, jnp.asarray(toks[:, S:S + S2]),
        jnp.ones((B, S2), jnp.int32), mask, S)
    np.testing.assert_allclose(logits, want[:, S + S2 - 1], atol=ATOL)
    assert list(np.asarray(nxt)) == [S + S2] * B
    for j in range(N):
        slot = S + S2 + j
        mask = mask.at[:, slot].set(1)
        logits, cache = decoder.decode_step(
            params, cfg, cache, jnp.asarray(toks[:, slot]),
            jnp.full((B,), slot), slot, mask)
        np.testing.assert_allclose(logits, want[:, slot], atol=ATOL)


def test_the_absorbed_attention_is_the_expanded_attention():
    _, cfg, params = _model()
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    B, T = 2, 37
    rng = np.random.default_rng(3)
    latent = jnp.asarray(rng.normal(size=(B, T, 24)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, cfg.n_heads, 24)), jnp.float32)
    s = mla.softmax_scale(cfg)
    k, v = mla._expand(latent, lp, cfg)                  # (B, H, T, .)
    p = jax.nn.softmax(jnp.einsum("bhd,bhtd->bht", q, k) * s, axis=-1)
    expanded = jnp.einsum("bht,bhtd->bhd", p, v).reshape(B, -1)
    qa = mla._absorb(q, lp, cfg)
    main = la.decode_main_xla(qa, latent[:, :20], jnp.full((B,), 20),
                              value_width=cfg.kv_lora_rank, sm_scale=s)
    own = la.decode_own(qa, latent[:, 20:T - 1], jnp.ones((B, T - 21)),
                        jnp.broadcast_to(jnp.arange(20, T - 1), (B, T - 21)),
                        jnp.full((B,), T), value_width=cfg.kv_lora_rank,
                        sm_scale=s)
    new = la.decode_self(qa, latent[:, T - 1], value_width=cfg.kv_lora_rank,
                         sm_scale=s)
    absorbed = mla._unabsorb(la.merge(main, own, new), lp, cfg, jnp.float32)
    np.testing.assert_allclose(absorbed, expanded, atol=1e-5)


def test_yarn_and_the_softmax_scale_are_the_published_formula():
    cfg = registry.kimi_k2_6()
    d, theta = 64, 50000.0

    def corr(beta):              # DeepSeek-V3 yarn_find_correction_dim
        return d * math.log(4096 / (beta * 2 * math.pi)) / (
            2 * math.log(theta))

    assert (round(corr(32), 2), round(corr(1), 2)) == (8.91, 19.16)
    low, high = 8, 20
    want = []
    for i in range(d // 2):
        extra = theta ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extra / 64 * ramp + extra * (1 - ramp))
    got = decoder.yarn_inv_freq(d, theta, cfg.rope_scaling)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(ref.inv_freq(_published()[0]), want,
                               rtol=1e-12)
    assert got[7] == pytest.approx(theta ** (-14 / 64))        # untouched
    assert got[20] == pytest.approx(theta ** (-40 / 64) / 64)  # stretched
    scale = 192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2
    assert (0.1 * math.log(64) + 1) ** 2 == pytest.approx(2.0047, abs=1e-4)
    assert mla.softmax_scale(cfg) == pytest.approx(scale, rel=1e-12)
    assert ref.softmax_scale(_published()[0]) == pytest.approx(scale)
    # sin and cos keep their size: mscale / mscale_all_dim = 1.
    sin, cos = decoder._rope_sincos(jnp.arange(5)[None], d, theta,
                                    cfg.rope_scaling)
    np.testing.assert_allclose(sin ** 2 + cos ** 2, 1.0, atol=1e-6)


def test_a_forty_row_dispatch_behind_one_latent_trunk_is_each_rows_own(
        kernels):
    """The held path's shape: the trunk prefilled ONCE at one row, 40 rows'
    remainders over it, a format suffix and a decode step, each row against
    its own forward, which knows nothing of the others."""
    spec, cfg, params = _model()
    B, TR, S, S2 = 40, 32, 44, 4
    toks = _tokens(B, S + S2 + 1, seed=5)
    toks[:, :TR] = toks[0, :TR]
    lens = [S - (r % 5) for r in range(B)]     # ragged remainders
    rem = np.zeros((B, S - TR), np.int32)
    rem_mask = np.zeros((B, S - TR), np.int32)
    rows = []
    for r, n in enumerate(lens):
        rem[r, :n - TR] = toks[r, TR:n]
        rem_mask[r, :n - TR] = 1
        rows.append(np.concatenate([toks[r, :n], toks[r, S:S + S2 + 1]]))
    T = S + S2 + 4
    _, trunk, _ = decoder.prefill(params, cfg, jnp.asarray(toks[:1, :TR]),
                                  jnp.ones((1, TR), jnp.int32), TR)
    trunk = tuple(trunk[:2]) + (moe.empty_counts(cfg),)
    cache = decoder.cascade_extend(params, cfg, trunk, jnp.asarray(rem),
                                   jnp.asarray(rem_mask), TR, T)
    assert cache[1].shape[1] == 1                      # the trunk, once
    mask = np.zeros((B, T), np.int32)
    mask[:, :TR] = 1
    mask[:, TR:S] = rem_mask
    mask[:, S:S + S2] = 1
    logits, cache, nxt = decoder.extend(
        params, cfg, cache, jnp.asarray(toks[:, S:S + S2]),
        jnp.ones((B, S2), jnp.int32), jnp.asarray(mask), S)
    mask[:, S + S2] = 1
    step, cache = decoder.decode_step(
        params, cfg, cache, jnp.asarray(toks[:, S + S2]), nxt, S + S2,
        jnp.asarray(mask))
    width = max(len(r) for r in rows)
    packed = np.zeros((B, width), np.int32)
    for r, seq in enumerate(rows):
        packed[r, :len(seq)] = seq
    at = np.asarray([[n + S2 - 1, n + S2] for n in lens])
    want = np.asarray(ref.logits_at(spec, SEED, packed, at))
    np.testing.assert_allclose(logits, want[:, 0], atol=ATOL)
    np.testing.assert_allclose(step, want[:, 1], atol=ATOL)
    # Each row's own key positions once, the trunk's once (the window
    # pass); the suffix pass expands the trunk and every row's slots again.
    real = sum(n - TR for n in lens)
    expanded, needed, absorbed, queries = np.asarray(cache[2].latent)
    L = cfg.n_layers
    assert needed == L * (TR + real + B * S2)
    assert expanded == L * ((TR + B * (S - TR)) + (TR + B * (S - TR + S2)))
    assert absorbed == L * B and queries == L * (real + B * S2)


@pytest.mark.parametrize("count", [32])
def test_shares_covering_every_expert_add_up_to_the_uncut_layer(count):
    """Four chips' shares of 8 of 32 experts: each share's layer less the
    shared expert, summed, and the shared expert once, is the whole
    deployment's layer (the reference with every expert held)."""
    spec = dataclasses.replace(ref.tiny(_published()[0]), experts=count,
                               held=(0, count), top_k=4)
    key = ref.seed_key(SEED)
    layer = 1
    u = jnp.asarray(np.random.default_rng(2).normal(size=(12, 64)),
                    jnp.float32)
    lk = jax.random.fold_in(key, layer)
    with jax.default_matmul_precision("highest"):
        uncut = ref._routed_terms(spec, key, lk, u, "float32")
        shared = ref._mlp(u, ref._mlp_weights(
            jax.random.fold_in(lk, 30), spec.d, spec.expert_ffn), "float32")
    total = shared
    for first in range(0, count, 8):
        share = dataclasses.replace(spec, held=(first, 8))
        cfg = builders.program_config(share, ref, check=False)
        w = ref.layer_weights(share, key, layer,
                              jnp.arange(first, first + 8))
        lp = builders.wrap_quantized(
            {k: w[k] for k in ("router", "shared", "experts")})
        lp = jax.tree.map(lambda a: a.astype(jnp.float32)
                          if a.dtype == jnp.bfloat16 else a, lp)
        out, _ = moe.expert_layer(u[None], lp, cfg)
        total = total + (out[0] - shared)
    np.testing.assert_allclose(total, uncut, atol=1e-4)


# ---------------------------------------------------------------------------
# The kernels against their XLA twins
# ---------------------------------------------------------------------------

def _prefill_case(rows_shared: bool):
    rng = np.random.default_rng(11)
    H, dq, dv = 2, 24, 16
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    if rows_shared:
        # Three rows of 300 queries over one trunk of 1100 and their own
        # 300 keys each: two query tiles, three trunk tiles, own tiles by
        # row.
        B, S, Tm = 3, 300, 1100
        q = f(1, H, B * S, dq)
        q_pos = jnp.asarray(np.tile(np.arange(Tm, Tm + S), B))[None]
        q_row = jnp.repeat(jnp.arange(B), S)[None]
        own_pos = q_pos.at[0, 7].set(la.NO_KEY)        # one slot unwritten
        own = (f(1, H, B * S, dq), f(1, H, B * S, dv), own_pos, q_row,
               q_row)
        return (q, f(1, H, Tm, dq), f(1, H, Tm, dv), jnp.asarray([Tm]),
                q_pos, own)
    # Per-row main parts (a prefill): two rows of 700, 650 real.
    B, S = 2, 700
    q_pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    return (f(B, H, S, dq), f(B, H, S, dq), f(B, H, S, dv),
            jnp.asarray([S, 650]), q_pos, None)


@pytest.mark.parametrize("rows_shared", [True, False],
                         ids=["shared-trunk", "per-row"])
def test_the_prefill_kernel_is_its_xla_twin_over_many_tiles(rows_shared):
    q, mk, mv, mlen, q_pos, own = _prefill_case(rows_shared)
    s = 0.3
    want = la.prefill_xla(q, mk, mv, mlen, q_pos, own, sm_scale=s)
    got = la.prefill(q, mk, mv, mlen, q_pos, own, sm_scale=s,
                     own_span=2 * 300 if rows_shared else 0,
                     interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("rows,main_rows,length", [(16, 1, 1040),
                                                   (3, 3, 500), (1, 1, 96)])
def test_the_decode_kernel_is_its_xla_twin_over_many_tiles(rows, main_rows,
                                                           length):
    rng = np.random.default_rng(13)
    L, H, dl, r = 2, 4, 24, 16
    latent = jnp.asarray(rng.normal(size=(L, main_rows, length, dl)),
                         jnp.float32)
    q = jnp.asarray(rng.normal(size=(rows, H, dl)), jnp.float32)
    mlen = jnp.asarray([length - 9 * i for i in range(main_rows)])
    got = la.decode_main(q, latent, mlen, value_width=r, sm_scale=0.25,
                         layer=jnp.int32(1), interpret=True)
    want = la.decode_main_xla(q, latent[1], mlen, value_width=r,
                              sm_scale=0.25)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# What cannot hold a latent cache refuses, in one plain error
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("what", ["radix", "paged", "verify", "piggyback",
                                  "serve"])
def test_what_holds_kv_by_row_refuses_latent_attention(what):
    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.engine import generate
    from lir_tpu.engine.runner import ScoringEngine

    _, cfg, params = _model()
    ones = jnp.ones((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="ROADMAP M2"):
        if what == "radix":
            ScoringEngine(params, cfg, FakeTokenizer(vocab=2048),
                          RuntimeConfig(batch_size=8, max_seq_len=128,
                                        prefix_cache=True))
        elif what == "paged":
            generate._paged_prefix(params, cfg, None, ones, 16)
        elif what == "verify":
            decoder.verify_extend(params, cfg, None, ones[:, :2], ones, 4)
        elif what == "piggyback":
            generate._piggyback_extend(params, cfg, ones, ones, ones, ones,
                                       ones, ones, 2, 2)
        else:
            from lir_tpu.serve.server import ScoringServer

            engine = ScoringEngine(params, cfg, FakeTokenizer(vocab=2048),
                                   RuntimeConfig(batch_size=8,
                                                 max_seq_len=128))
            ScoringServer(engine, "kimi")


# ---------------------------------------------------------------------------
# The sweep through the ordinary engine, and what it counts
# ---------------------------------------------------------------------------

def test_a_sweep_holds_the_latent_trunk_and_counts_what_it_expanded(
        monkeypatch, tmp_path):
    """``run_perturbation_sweep`` over the doc16k mix cut to 448-word
    instruments (kernels interpreted, 4-row groups; a trunk is held where
    it is at least what the rows prefill behind it, as in the cell): every
    dispatch behind a shared trunk takes the held latent trunk, and the latent counters
    come back with the answers: each pass expands what it reads, so the
    expanded keys exceed the needed ones, every decode query absorbed."""
    import run as bench_run
    from harness import sweep_window
    from tests import tiny

    from lir_tpu.observe import registry as obs

    monkeypatch.setattr(decoder, "CASCADE_INTERPRET_ON_CPU", True)
    monkeypatch.setattr(decoder, "LATENT_INTERPRET_ON_CPU", True)
    cell, bench, files = tiny.files_for("kimi-k2-6", "sweep-doc16k")
    mix = dict(files["mix"], head_words=448, rephrasing_words=456,
               group_rows=4, max_groups_per_prompt=2, window_groups=6)
    real = sweep_window.traffic.load_prompts
    monkeypatch.setattr(sweep_window.traffic, "load_prompts", lambda m: [
        dataclasses.replace(p, main=" ".join(p.main.split()[:448]
                                             + p.main.split()[16000:]))
        for p in real(m)])
    runtime = dict(files["runtime"], batch_size=4, max_seq_len=768,
                   dispatch_tokens=1000)
    ctx = bench_run.Context(spec=files["spec"], ref=files["ref"], mix=mix,
                            runtime=runtime, seed=SEED, seconds=1.0,
                            out=tmp_path, check_config=False)
    record = sweep_window.run(ctx)
    assert record["failed"] == 0 and record["attempted"] == 29

    def delta(source, field):
        return (obs_field(record["counters"]["after"], source, field)
                - obs_field(record["counters"]["before"], source, field))

    def obs_field(snap, source, field):
        return snap["sources"][source]["fields"][field]

    assert delta("cascade", "cascade_dispatches") > 0
    assert delta("cascade", "trunk_held_dispatches") == delta(
        "cascade", "cascade_dispatches")
    expanded, needed = (delta("latent", k) for k in ("keys_expanded",
                                                     "keys_needed"))
    assert needed > 0 and expanded > 2 * needed
    assert delta("latent", "queries_absorbed") > 0
    assert delta("latent", "dispatches") == delta("moe", "dispatches") > 0
    snap = record["counters"]["after"]["sources"]["latent"]
    assert snap["type"] == "LatentStats"
    assert set(snap["fields"]) == set(obs.STATS_SCHEMA["LatentStats"])
