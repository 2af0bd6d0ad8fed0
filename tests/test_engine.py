"""Engine tests: readout rule, greedy decode, batched scorer, sharded forward.

The readout rule under test is C13 (compare_base_vs_instruct.py:185-305):
scan first 10 generated positions, first top-2 yes/no hit wins, fallback to
position 0. Sharding tests exercise the same Mesh/pjit paths as a v5e-8 via
8 virtual CPU devices (SURVEY.md §4).
"""

import numpy as np
import pytest
import torch

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from lir_tpu.backends.fake import FakeTokenizer
from lir_tpu.config import MeshConfig, RuntimeConfig
from lir_tpu.engine import generate, score, tokens as tok
from lir_tpu.engine.runner import ScoringEngine
from lir_tpu.models import decoder
from lir_tpu.models.loader import config_from_hf, convert_decoder
from lir_tpu.models.registry import tiny
from lir_tpu.parallel import sharding


def _tiny_llama_params(vocab=1000, seed=0):
    import transformers as tf
    torch.manual_seed(seed)
    hf = tf.LlamaForCausalLM(tf.LlamaConfig(
        vocab_size=vocab, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, intermediate_size=128,
        max_position_embeddings=256, tie_word_embeddings=False)).eval()
    cfg, fam = config_from_hf(hf.config)
    return convert_decoder(hf.state_dict(), cfg, fam), cfg, hf


# ---------------------------------------------------------------------------
# Readout rule (pure function, synthetic logits)
# ---------------------------------------------------------------------------

def test_readout_first_top2_match_wins():
    B, T, V = 2, 12, 50
    yes_id, no_id = 7, 9
    logits = np.full((B, T, V), -10.0, np.float32)
    logits[:, :, 3] = 5.0          # dominant distractor everywhere
    logits[:, :, 4] = 4.0          # second-place distractor
    # Row 0: yes enters top-2 at position 3 (beats the 4.0 distractor).
    logits[0, 3, yes_id] = 4.5
    logits[0, 3, no_id] = 1.0
    # Row 1: no match anywhere -> fallback position 0.
    res = score.readout_from_step_logits(
        jnp.asarray(logits), jnp.zeros((B, T), jnp.int32),
        jnp.int32(yes_id), jnp.int32(no_id))
    assert int(res.position_found[0]) == 3 and bool(res.yes_no_found[0])
    assert int(res.position_found[1]) == 0 and not bool(res.yes_no_found[1])
    # Probabilities read at the matched position.
    probs = jax.nn.softmax(jnp.asarray(logits[0, 3]))
    np.testing.assert_allclose(float(res.yes_prob[0]), float(probs[yes_id]),
                               rtol=1e-6)
    # Both readouts present and consistent (SURVEY §1 drift fixed).
    rp = float(res.relative_prob[0])
    orr = float(res.odds_ratio[0])
    assert 0.0 <= rp <= 1.0
    np.testing.assert_allclose(orr / (1 + orr), rp, rtol=1e-4)


def test_weighted_confidence():
    B, V = 1, 40
    ids = jnp.asarray([5, 6], jnp.int32)
    vals = jnp.asarray([0.0, 100.0], jnp.float32)
    logits = np.full((B, 1, V), -10.0, np.float32)
    logits[0, 0, 5] = 2.0   # p(0)
    logits[0, 0, 6] = 2.0   # p(100) equal -> E[v] = 50
    out = score.weighted_confidence(jnp.asarray(logits), ids, vals)
    np.testing.assert_allclose(float(out[0]), 50.0, atol=1e-4)


# ---------------------------------------------------------------------------
# Greedy decode vs repeated full forward
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_greedy_decode_matches_full_forward():
    params, cfg, hf = _tiny_llama_params()
    rng = np.random.default_rng(0)
    S, NEW = 7, 5
    toks = rng.integers(3, 1000, size=(2, S)).astype(np.int32)
    gen, step_logits = generate.greedy_decode(
        params, cfg, jnp.asarray(toks), jnp.ones((2, S), jnp.int32),
        max_new_tokens=NEW)
    gen = np.asarray(gen)

    with torch.no_grad():
        out = hf.generate(torch.tensor(toks.astype(np.int64)),
                          max_new_tokens=NEW, do_sample=False,
                          output_scores=True, return_dict_in_generate=True,
                          pad_token_id=0)
    ref_gen = out.sequences[:, S:].numpy()
    np.testing.assert_array_equal(gen, ref_gen)
    for t in range(NEW):
        np.testing.assert_allclose(np.asarray(step_logits[:, t, :]),
                                   out.scores[t].numpy(), atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# End-to-end batched scorer with the fake tokenizer
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_scoring_engine_end_to_end():
    tokenizer = FakeTokenizer()
    params, cfg, _ = _tiny_llama_params(vocab=FakeTokenizer.VOCAB)
    eng = ScoringEngine(params, cfg, tokenizer,
                        RuntimeConfig(batch_size=4, max_new_tokens=12,
                                      max_seq_len=64))
    prompts = [f"Is a tomato number {i} a fruit ? Answer Yes or No" for i in range(6)]
    rows = eng.score_prompts(prompts)
    assert len(rows) == 6
    for r in rows:
        assert 0.0 <= r.yes_prob <= 1.0 and 0.0 <= r.no_prob <= 1.0
        assert np.isnan(r.relative_prob) or 0.0 <= r.relative_prob <= 1.0
        assert 0 <= r.position_found < 10
        assert isinstance(r.completion, str)
    # Deterministic: same prompts -> identical numbers.
    rows2 = eng.score_prompts(prompts)
    np.testing.assert_allclose([r.yes_prob for r in rows],
                               [r.yes_prob for r in rows2], rtol=0, atol=0)


def test_fake_tokenizer_yes_no_ids():
    t = FakeTokenizer()
    # Decoder rule: leading-space variant first; fake tokenizer strips spaces
    # so both resolve to the reserved ids.
    assert tok.yes_no_ids(t) == (FakeTokenizer.YES, FakeTokenizer.NO)


# ---------------------------------------------------------------------------
# Sharded forward on the 8-virtual-device mesh
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_forward_matches_single_device():
    params, cfg, _ = _tiny_llama_params()
    mesh = sharding.build_mesh(MeshConfig(data=2, model=4))
    sharded = sharding.shard_params(params, cfg, mesh)

    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(3, 1000, size=(4, 10)).astype(np.int32))
    toks_sharded = jax.device_put(toks, sharding.batch_sharding(mesh))

    ref = decoder.forward(params, cfg, toks)
    out = jax.jit(lambda p, t: decoder.forward(p, cfg, t))(sharded, toks_sharded)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_sharded_greedy_decode():
    params, cfg, _ = _tiny_llama_params()
    mesh = sharding.build_mesh(MeshConfig(data=2, model=4))
    sharded = sharding.shard_params(params, cfg, mesh)
    rng = np.random.default_rng(2)
    toks = rng.integers(3, 1000, size=(4, 6)).astype(np.int32)
    mask = np.ones_like(toks)

    ref_gen, ref_logits = generate.greedy_decode(
        params, cfg, jnp.asarray(toks), jnp.asarray(mask), max_new_tokens=4)
    bs = sharding.batch_sharding(mesh)
    gen, logits = generate.greedy_decode(
        sharded, cfg, jax.device_put(jnp.asarray(toks), bs),
        jax.device_put(jnp.asarray(mask), bs), max_new_tokens=4)
    np.testing.assert_array_equal(np.asarray(gen), np.asarray(ref_gen))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.slow
def test_fused_decode_matches_capture_path():
    """The fused in-scan readout must equal the full-logit-capture path
    bit-for-bit on every field the sweeps consume."""
    from lir_tpu.engine import generate as gen_mod
    from lir_tpu.engine import score as score_mod
    from lir_tpu.engine import tokens as tok_mod

    params, cfg, _ = _tiny_llama_params(vocab=FakeTokenizer.VOCAB)
    tokenizer = FakeTokenizer()
    prompts = ["Is a cat an animal Yes or No",
               "Is a rock an animal Yes or No",
               "some other prompt entirely"]
    toks, mask = tok_mod.left_pad_batch(tokenizer, prompts, 16)
    toks_j, mask_j = jnp.asarray(toks), jnp.asarray(mask)

    B = len(prompts)
    yes_ids = np.full((B,), FakeTokenizer.YES, np.int32)
    no_ids = np.full((B,), FakeTokenizer.NO, np.int32)
    digit_ids, digit_vals = tok_mod.integer_token_table(tokenizer)

    gen, step_logits = gen_mod.greedy_decode(params, cfg, toks_j, mask_j,
                                             max_new_tokens=8)
    ref = score_mod.readout_from_step_logits(
        step_logits, gen, jnp.asarray(yes_ids), jnp.asarray(no_ids),
        scan_positions=8)
    ref_topk_vals, ref_topk_ids = score_mod.topk_logprobs(step_logits, k=10)
    ref_wconf = score_mod.weighted_confidence(
        step_logits, jnp.asarray(digit_ids), jnp.asarray(digit_vals))

    fused = gen_mod.greedy_decode_fused(
        params, cfg, toks_j, mask_j, jnp.asarray(yes_ids),
        jnp.asarray(no_ids), jnp.asarray(digit_ids), jnp.asarray(digit_vals),
        max_new_tokens=8, topk=10)
    out = score_mod.readout_from_fused(
        fused, jnp.asarray(yes_ids), jnp.asarray(no_ids), scan_positions=8)

    np.testing.assert_array_equal(np.asarray(out.generated), np.asarray(ref.generated))
    np.testing.assert_array_equal(np.asarray(out.position_found),
                                  np.asarray(ref.position_found))
    np.testing.assert_array_equal(np.asarray(out.yes_no_found),
                                  np.asarray(ref.yes_no_found))
    np.testing.assert_allclose(np.asarray(out.yes_prob),
                               np.asarray(ref.yes_prob), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out.no_prob),
                               np.asarray(ref.no_prob), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(fused.topk_ids),
                                  np.asarray(ref_topk_ids))
    np.testing.assert_allclose(np.asarray(fused.topk_logprobs),
                               np.asarray(ref_topk_vals), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(fused.weighted_confidence),
                               np.asarray(ref_wconf), rtol=1e-5)


# ---------------------------------------------------------------------------
# Shared-prefix fused decode (one prefill serves both sweep formats)
# ---------------------------------------------------------------------------

import dataclasses as _dc

from lir_tpu.models.registry import ModelConfig as _MC

from dispatch_helpers import fused_shared


@pytest.mark.parametrize("family,int8kv", [
    ("llama", False),   # rotary + RMSNorm + gated MLP
    ("llama", True),    # + int8 KV cache (extend quantizes suffix k/v)
    ("bloom", False),   # ALiBi + embedding LayerNorm
    ("gpt2", False),    # learned positions + tied embeddings
])
@pytest.mark.slow
def test_shared_prefix_decode_matches_full_prompts(family, int8kv):
    """greedy_decode_fused_shared == two greedy_decode_fused calls on the
    concatenated prompts, for every position-dependent readout. Rows have
    DIFFERENT prefix and suffix lengths, so per-row position bookkeeping
    (left-padded prefix + right-padded suffix) is exercised."""
    from lir_tpu.models.registry import tiny as tiny_cfg

    cfg = tiny_cfg(family)
    if int8kv:
        cfg = _dc.replace(cfg, kv_cache_int8=True)
    params = decoder.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    V = cfg.vocab_size
    prefix_lens = [10, 17, 5, 23]
    sa_lens = [3, 5, 2, 4]
    sb_lens = [6, 2, 7, 3]
    prefix_ids = [rng.integers(3, V, n).tolist() for n in prefix_lens]
    sa_ids = [rng.integers(3, V, n).tolist() for n in sa_lens]
    sb_ids = [rng.integers(3, V, n).tolist() for n in sb_lens]
    yes_ids = rng.integers(3, V, 4).astype(np.int32)
    no_ids = rng.integers(3, V, 4).astype(np.int32)
    digit_ids = np.asarray([5, 6, 7], np.int32)
    digit_vals = np.asarray([10.0, 50.0, 90.0], np.float32)
    NEW_A, NEW_B = 4, 6

    def ref(full_ids, n_new, d_ids, d_vals):
        toks, mask = tok.left_pad_ids(full_ids, 32, 0)
        return generate.greedy_decode_fused(
            params, cfg, jnp.asarray(toks), jnp.asarray(mask),
            jnp.asarray(yes_ids), jnp.asarray(no_ids),
            jnp.asarray(d_ids), jnp.asarray(d_vals), max_new_tokens=n_new)

    ref_a = ref([p + s for p, s in zip(prefix_ids, sa_ids)], NEW_A,
                np.zeros((0,), np.int32), np.zeros((0,), np.float32))
    ref_b = ref([p + s for p, s in zip(prefix_ids, sb_ids)], NEW_B,
                digit_ids, digit_vals)

    pre, pre_mask = tok.left_pad_ids(prefix_ids, 32, 0)
    sa, sa_mask = tok.right_pad_ids(sa_ids, 8, 0)
    sb, sb_mask = tok.right_pad_ids(sb_ids, 8, 0)
    out_a, out_b = fused_shared(
        params, cfg, jnp.asarray(pre), jnp.asarray(pre_mask),
        jnp.asarray(sa), jnp.asarray(sa_mask), jnp.asarray(sb),
        jnp.asarray(sb_mask), jnp.asarray(yes_ids), jnp.asarray(no_ids),
        jnp.asarray(digit_ids), jnp.asarray(digit_vals),
        max_new_a=NEW_A, max_new_b=NEW_B)

    # int8 KV: the reference path's FIRST position comes from the dense
    # (unquantized) prefill, while the shared path reads it through the
    # quantized cache — a real ~0.5% numeric difference, same one every
    # decode step already carries. fp32 paths agree to float tolerance.
    tol = dict(rtol=2e-2, atol=2e-2) if int8kv else dict(rtol=1e-4, atol=1e-5)
    for out, refd in ((out_a, ref_a), (out_b, ref_b)):
        if not int8kv:
            np.testing.assert_array_equal(np.asarray(out.generated),
                                          np.asarray(refd.generated))
            np.testing.assert_array_equal(np.asarray(out.top2_ids),
                                          np.asarray(refd.top2_ids))
        np.testing.assert_allclose(np.asarray(out.p_yes),
                                   np.asarray(refd.p_yes), **tol)
        np.testing.assert_allclose(np.asarray(out.p_no),
                                   np.asarray(refd.p_no), **tol)
    if not int8kv:
        np.testing.assert_array_equal(np.asarray(out_a.topk_ids),
                                      np.asarray(ref_a.topk_ids))
        np.testing.assert_allclose(np.asarray(out_a.topk_logprobs),
                                   np.asarray(ref_a.topk_logprobs),
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_b.weighted_confidence),
                               np.asarray(ref_b.weighted_confidence), **tol)


@pytest.mark.slow
def test_engine_decode_fused_shared_matches_decode_fused():
    """Runner-level: tokenize/LCP-split/pad host prep reproduces the plain
    decode_fused readouts on real prompt strings (FakeTokenizer)."""
    cfg = _MC(name="shared-smoke", vocab_size=FakeTokenizer.VOCAB,
              hidden_size=64, n_layers=2, n_heads=4, intermediate_size=128,
              max_seq_len=256)
    params = decoder.init_params(cfg, jax.random.PRNGKey(2))
    engine = ScoringEngine(params, cfg, FakeTokenizer(),
                           RuntimeConfig(batch_size=4, max_seq_len=256))
    mains = [f"the quick brown fox {i} jumps over the lazy dog "
             f"word {i * 7} more filler text here" for i in range(4)]
    bins = [m + " Respond with either Yes or No only" for m in mains]
    confs = [m + " Give a confidence number from 0 to 100" for m in mains]
    t1 = np.full((4,), FakeTokenizer.YES, np.int32)
    t2 = np.full((4,), FakeTokenizer.NO, np.int32)

    fused_a = engine.decode_fused(bins, t1, t2, max_new_tokens=4)
    fused_b = engine.decode_fused(confs, t1, t2, with_digits=True,
                                  max_new_tokens=6)
    out_a, out_b = engine.decode_fused_shared(bins, confs, t1, t2,
                                              new_tokens=4, conf_tokens=6)
    np.testing.assert_array_equal(np.asarray(out_a.generated),
                                  np.asarray(fused_a.generated))
    np.testing.assert_allclose(np.asarray(out_a.p_yes),
                               np.asarray(fused_a.p_yes),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out_a.topk_ids),
                                  np.asarray(fused_a.topk_ids))
    np.testing.assert_array_equal(np.asarray(out_b.generated),
                                  np.asarray(fused_b.generated))
    np.testing.assert_allclose(np.asarray(out_b.weighted_confidence),
                               np.asarray(fused_b.weighted_confidence),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.slow
def test_fused_decode_digit_early_stop_mechanics():
    """Early-stopped fused decode vs the plain run: each row's tokens match
    the full decode until its stop point (EOS, or a standalone digit run
    followed by a non-gluing token), then the row emits EOS fill;
    position-0 readouts are bitwise identical. Replayed host-side from the
    full run's tokens with the same class machine."""
    cfg = _MC(name="earlystop-smoke", vocab_size=256, hidden_size=32,
              n_layers=2, n_heads=4, intermediate_size=64, max_seq_len=128)
    params = decoder.init_params(cfg, jax.random.PRNGKey(7))
    rng = np.random.default_rng(11)
    toks = rng.integers(3, 256, size=(4, 8)).astype(np.int32)
    mask = np.ones_like(toks)
    t1 = np.full((4,), 10, np.int32)
    t2 = np.full((4,), 11, np.int32)
    eos = 5
    # Synthetic vocab surface classes: ids 0-2 mod 4 cycle through
    # "▁85"-like (PURE|PREFIX|ENDS_WORD), ","-like (0), "st"-like
    # (STARTS_WORD|ENDS_WORD); eos id is TRANSPARENT.
    cls = np.zeros((256,), np.int32)
    cls[np.arange(256) % 4 == 0] = tok.STOP_PURE | tok.STOP_PREFIX | tok.STOP_ENDS_WORD
    cls[np.arange(256) % 4 == 2] = tok.STOP_STARTS_WORD | tok.STOP_ENDS_WORD
    cls[eos] = tok.STOP_TRANSPARENT
    T = 20
    kw = dict(max_new_tokens=T)
    full = generate.greedy_decode_fused(
        params, cfg, jnp.asarray(toks), jnp.asarray(mask),
        jnp.asarray(t1), jnp.asarray(t2), jnp.zeros((0,), jnp.int32),
        jnp.zeros((0,), jnp.float32), **kw)
    early = generate.greedy_decode_fused(
        params, cfg, jnp.asarray(toks), jnp.asarray(mask),
        jnp.asarray(t1), jnp.asarray(t2), jnp.zeros((0,), jnp.int32),
        jnp.zeros((0,), jnp.float32), stop_mask=jnp.asarray(cls),
        eos_id=jnp.int32(eos), **kw)
    g_full = np.asarray(full.generated)
    g_early = np.asarray(early.generated)
    stopped = 0
    for j in range(4):
        expect, done, run, prev_ew = [], False, False, False
        for t in range(T):
            emit = eos if done else int(g_full[j, t])
            expect.append(emit)
            c = int(cls[emit])
            pure, prefix = bool(c & 1), bool(c & 2)
            glue, ends_w, transp = bool(c & 4), bool(c & 8), bool(c & 16)
            done = done or emit == eos or (run and not glue and not transp)
            if not transp:
                run = (pure and (prefix or not prev_ew)) or (
                    run and pure and not prefix)
                prev_ew = ends_w
        stopped += done
        np.testing.assert_array_equal(g_early[j], expect)
    assert stopped == 4, "seeded run should stop every row inside the budget"
    # Position-0 readouts are computed before any step runs — identical.
    np.testing.assert_array_equal(np.asarray(early.topk_ids),
                                  np.asarray(full.topk_ids))
    np.testing.assert_allclose(np.asarray(early.p_yes[:, 0]),
                               np.asarray(full.p_yes[:, 0]), rtol=1e-6)


def test_digit_stop_classes_surface_semantics():
    """The early-stop class table must read DECODED surfaces, not raw
    strings: byte tokens map to their byte ('<0x0A>' is a newline, '<0x30>'
    is the digit 0), REGISTERED specials are transparent (metadata, not
    surface form: an unregistered <div> that decodes to literal text must
    classify by its surface — ADVICE r4), space-prefixed digits are
    standalone-integer openers, and letter-glued pieces ('st', 'a1b') glue
    — so '1st' never reads as a parseable integer."""
    class Stub:
        all_special_ids = [4, 5]

        def convert_ids_to_tokens(self, ids):
            table = ["▁Yes", "▁85", "<0x0A>", "<0x30>", "</s>",
                     "<|reserved_special_token_0|>", "a1b", "100",
                     "st", ",", "Ġ42", "Ġ", "<div>"]
            return [table[i] for i in ids]

        def __len__(self):
            return 13

    cls = tok.digit_stop_classes(Stub(), 13)
    P, X, W, E, T = (tok.STOP_PURE, tok.STOP_PREFIX, tok.STOP_STARTS_WORD,
                     tok.STOP_ENDS_WORD, tok.STOP_TRANSPARENT)
    assert cls[0] == X | E                 # ▁Yes: fresh word, not digits
    assert cls[1] == P | X | E             # ▁85: standalone integer opener
    assert cls[2] == X                     # newline byte = space prefix only
    assert cls[3] == P | W | E             # '0' byte: digit, glues
    assert cls[4] == T                     # </s>
    assert cls[5] == T                     # reserved special
    assert cls[6] == W | E                 # a1b: glues, not pure
    assert cls[7] == P | W | E             # bare 100: pure but gluing
    assert cls[8] == W | E                 # st: the '1st' glue piece
    assert cls[9] == 0                     # ',' terminator
    assert cls[10] == P | X | E            # Ġ42 (byte-BPE space prefix)
    # 'Ġ' alone is a letter CODEPOINT but decodes to a bare space: prefix
    # only, NOT word-ending ('\n' + '85' must still open a digit run).
    assert cls[11] == X
    # Unregistered <div> is literal text (code-trained vocabs), NOT
    # transparent: both bracket chars are non-word → plain terminator.
    assert cls[12] == 0

    class RawStub:
        """No special-id metadata; transparency must come from the
        decode-to-empty check instead."""

        def convert_ids_to_tokens(self, ids):
            table = ["</s>", "<div>"]
            return [table[i] for i in ids]

        def convert_tokens_to_string(self, toks):
            return "".join("" if t == "</s>" else t for t in toks)

        def __len__(self):
            return 2

    cls2 = tok.digit_stop_classes(RawStub(), 2)
    assert cls2[0] == T
    assert cls2[1] == 0


@pytest.mark.slow
def test_engine_early_stop_disabled_without_token_strings():
    """FakeTokenizer renders ids as '<123>' and exposes no per-token
    strings: the engine must resolve digit_stop_mask to None and score
    identically with early_stop on/off (the bench stays budget-honest)."""
    cfg = _MC(name="nostop-smoke", vocab_size=FakeTokenizer.VOCAB,
              hidden_size=64, n_layers=2, n_heads=4, intermediate_size=128,
              max_seq_len=256)
    params = decoder.init_params(cfg, jax.random.PRNGKey(8))
    engine = ScoringEngine(params, cfg, FakeTokenizer(),
                           RuntimeConfig(batch_size=2, max_seq_len=256))
    assert engine.digit_stop_mask is None
    prompts = ["is a levee failure a flood", "is rust damage covered"]
    t1 = np.full((2,), FakeTokenizer.YES, np.int32)
    t2 = np.full((2,), FakeTokenizer.NO, np.int32)
    on = engine.decode_fused(prompts, t1, t2, with_digits=True,
                             max_new_tokens=6, early_stop=True)
    off = engine.decode_fused(prompts, t1, t2, with_digits=True,
                              max_new_tokens=6, early_stop=False)
    np.testing.assert_array_equal(np.asarray(on.generated),
                                  np.asarray(off.generated))


def test_shared_prefix_len_caps_for_nonempty_suffix():
    a = [1, 2, 3, 4]
    assert tok.shared_prefix_len(a, a) == 3          # strict-prefix guard
    assert tok.shared_prefix_len(a, [1, 2, 9]) == 2
    assert tok.shared_prefix_len([7], [8]) == 0
    assert tok.shared_prefix_len(a, [1, 2, 3, 4, 5]) == 3


@pytest.mark.slow
def test_decode_fused_shared_falls_back_on_long_suffix():
    """Prompt pairs that diverge early (suffix > largest suffix bucket) must
    take the plain two-prefill path, not silently truncate the instruction
    the readout depends on."""
    cfg = _MC(name="fallback-smoke", vocab_size=FakeTokenizer.VOCAB,
              hidden_size=64, n_layers=2, n_heads=4, intermediate_size=128,
              max_seq_len=1024)
    params = decoder.init_params(cfg, jax.random.PRNGKey(4))
    engine = ScoringEngine(params, cfg, FakeTokenizer(),
                           RuntimeConfig(batch_size=2, max_seq_len=1024))
    # Shared prefix of 2 words; suffixes of ~300 words each (> 256 bucket).
    long_a = "start shared " + " ".join(f"alpha{i}" for i in range(300))
    long_b = "start shared " + " ".join(f"beta{i}" for i in range(300))
    t1 = np.full((2,), FakeTokenizer.YES, np.int32)
    t2 = np.full((2,), FakeTokenizer.NO, np.int32)
    out_a, out_b = engine.decode_fused_shared(
        [long_a] * 2, [long_b] * 2, t1, t2, new_tokens=2, conf_tokens=2)
    ref_a = engine.decode_fused([long_a] * 2, t1, t2, max_new_tokens=2)
    np.testing.assert_array_equal(np.asarray(out_a.generated),
                                  np.asarray(ref_a.generated))
    np.testing.assert_allclose(np.asarray(out_a.p_yes),
                               np.asarray(ref_a.p_yes), rtol=1e-6)


@pytest.mark.slow
def test_decode_fused_shared_falls_back_on_overlong_prefix(caplog):
    """When the common token prefix exceeds the largest prefix bucket, the
    shared path must NOT keep more context than the plain path (which
    left-truncates the whole prompt): it falls back to two full prefills so
    over-long semantics stay pinned across paths (ADVICE r3 #2)."""
    cfg = _MC(name="overlong-smoke", vocab_size=FakeTokenizer.VOCAB,
              hidden_size=64, n_layers=2, n_heads=4, intermediate_size=128,
              max_seq_len=1024)
    params = decoder.init_params(cfg, jax.random.PRNGKey(5))
    # rt.max_seq_len=128 -> prefix buckets [64, 128].
    engine = ScoringEngine(params, cfg, FakeTokenizer(),
                           RuntimeConfig(batch_size=2, max_seq_len=128))
    shared = " ".join(f"common{i}" for i in range(200))   # lcp >> 128
    bins = [shared + " answer yes or no"] * 2
    confs = [shared + " give a number"] * 2
    t1 = np.full((2,), FakeTokenizer.YES, np.int32)
    t2 = np.full((2,), FakeTokenizer.NO, np.int32)
    with caplog.at_level("INFO", logger="lir_tpu"):
        out_a, out_b = engine.decode_fused_shared(
            bins, confs, t1, t2, new_tokens=2, conf_tokens=2)
    assert any("shared-prefix fallback" in r.message
               and "exceeds the largest bucket" in r.message
               for r in caplog.records)
    ref_a = engine.decode_fused(bins, t1, t2, max_new_tokens=2)
    ref_b = engine.decode_fused(confs, t1, t2, with_digits=True,
                                max_new_tokens=2)
    np.testing.assert_array_equal(np.asarray(out_a.generated),
                                  np.asarray(ref_a.generated))
    np.testing.assert_allclose(np.asarray(out_a.p_yes),
                               np.asarray(ref_a.p_yes), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(out_b.generated),
                                  np.asarray(ref_b.generated))


@pytest.mark.slow
def test_decode_fused_shared_falls_back_on_learned_pos_overflow(caplog):
    """Learned-position models: prefix bucket + suffix bucket + new tokens
    can overrun the position table even when each bucket individually fits
    (the constructor only trims for the plain path) — the shared path must
    detect this and take the trimmed plain path (ADVICE r3 #1)."""
    cfg = _MC(name="learnedpos-smoke", vocab_size=FakeTokenizer.VOCAB,
              hidden_size=64, n_layers=2, n_heads=4, intermediate_size=128,
              max_seq_len=160, pos_embedding="learned")
    params = decoder.init_params(cfg, jax.random.PRNGKey(6))
    engine = ScoringEngine(params, cfg, FakeTokenizer(),
                           RuntimeConfig(batch_size=2, max_seq_len=256,
                                         max_new_tokens=4))
    # Constructor trim: buckets <= 160-4 -> [64, 128]. Total prompt ~120
    # tokens fits the 128 bucket (so the over-long-total branch stays
    # quiet), but prefix bucket 128 + suffix bucket 32 + 2 new tokens =
    # 162 > the 160-row position table -> must fall back.
    shared = " ".join(f"body{i}" for i in range(100))
    bins = [shared + " " + " ".join(f"ba{i}" for i in range(18))] * 2
    confs = [shared + " " + " ".join(f"bc{i}" for i in range(18))] * 2
    t1 = np.full((2,), FakeTokenizer.YES, np.int32)
    t2 = np.full((2,), FakeTokenizer.NO, np.int32)
    with caplog.at_level("INFO", logger="lir_tpu"):
        out_a, _ = engine.decode_fused_shared(
            bins, confs, t1, t2, new_tokens=2, conf_tokens=2)
    assert any("shared-prefix fallback" in r.message
               and "learned-position" in r.message for r in caplog.records)
    ref_a = engine.decode_fused(bins, t1, t2, max_new_tokens=2)
    np.testing.assert_array_equal(np.asarray(out_a.generated),
                                  np.asarray(ref_a.generated))


@pytest.mark.slow
def test_data_parallel_mesh_8x1_replicated_params():
    """Pure data-parallel serving (mesh 8x1): params replicate, the batch
    shards on `data`, and scores equal the single-device run — the int8-7B
    v5e-8 deployment mode (DEPLOY.md §2; perturb_prompts.py:294-330)."""
    params, cfg, _ = _tiny_llama_params()
    mesh = sharding.build_mesh(MeshConfig(data=8, model=1))
    sharded = sharding.shard_params(params, cfg, mesh)
    rng = np.random.default_rng(3)
    toks = rng.integers(3, 1000, size=(8, 6)).astype(np.int32)
    mask = np.ones_like(toks)

    ref_gen, ref_logits = generate.greedy_decode(
        params, cfg, jnp.asarray(toks), jnp.asarray(mask), max_new_tokens=4)
    bs = sharding.batch_sharding(mesh)
    gen, logits = generate.greedy_decode(
        sharded, cfg, jax.device_put(jnp.asarray(toks), bs),
        jax.device_put(jnp.asarray(mask), bs), max_new_tokens=4)
    np.testing.assert_array_equal(np.asarray(gen), np.asarray(ref_gen))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               atol=1e-4, rtol=1e-4)
    # Params really are replicated: with model=1 every device holds the
    # FULL weight (the named model axis has size 1 -> no actual split).
    wq = sharded["layers"]["wq"]
    assert wq.sharding.shard_shape(wq.shape) == wq.shape


@pytest.mark.slow
def test_sample_decode_typed_prng_key_batch():
    """Per-row PRNG streams must work with BOTH key flavors: legacy
    uint32 (B, 2) arrays and modern typed keys (shape (B,)). The typed
    batch previously misrouted into the single-key path and crashed."""
    cfg = _MC(name="key-smoke", vocab_size=64, hidden_size=32, n_layers=2,
              n_heads=4, intermediate_size=64, max_seq_len=64)
    params = decoder.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(3, 64, (3, 5)), jnp.int32)
    mask = jnp.ones_like(toks)

    legacy = jnp.stack([jax.random.PRNGKey(i) for i in range(3)])
    assert generate.is_per_row_keys(legacy)
    g1 = generate.sample_decode(params, cfg, toks, mask, legacy,
                                max_new_tokens=4)
    typed = jax.vmap(jax.random.key)(jnp.arange(3, dtype=jnp.uint32))
    assert generate.is_per_row_keys(typed)
    g2 = generate.sample_decode(params, cfg, toks, mask, typed,
                                max_new_tokens=4)
    assert g1.shape == g2.shape == (3, 4)
    # Scalar keys of both flavors route to the single-stream path.
    assert not generate.is_per_row_keys(jax.random.PRNGKey(0))
    assert not generate.is_per_row_keys(jax.random.key(0))
    g3 = generate.sample_decode(params, cfg, toks, mask, jax.random.key(7),
                                max_new_tokens=4)
    assert g3.shape == (3, 4)


@pytest.mark.slow
def test_shared_prefix_scorer_on_dp_mesh():
    """The sweep's shared-prefix scorer on a pure data-parallel (8x1)
    engine — the recommended int8-7B serving mode — equals the
    single-device run."""
    params, cfg, _ = _tiny_llama_params()
    mesh = sharding.build_mesh(MeshConfig(data=8, model=1))
    sharded = sharding.shard_params(params, cfg, mesh)
    tok_f = FakeTokenizer()
    rt = RuntimeConfig(batch_size=8, max_seq_len=64)
    plain = ScoringEngine(params, cfg, tok_f, rt)
    dp = ScoringEngine(sharded, cfg, tok_f, rt)
    mains = [f"levee failure case number {i} in the policy ?"
             for i in range(8)]
    bins = [m + " Answer Yes or No ." for m in mains]
    confs = [m + " Give a number 0 to 100 ." for m in mains]
    t1 = np.full((8,), FakeTokenizer.YES, np.int32)
    t2 = np.full((8,), FakeTokenizer.NO, np.int32)
    pa, pb = plain.decode_fused_shared(bins, confs, t1, t2,
                                       new_tokens=3, conf_tokens=4)
    da, db = dp.decode_fused_shared(bins, confs, t1, t2,
                                    new_tokens=3, conf_tokens=4)
    np.testing.assert_array_equal(np.asarray(da.generated),
                                  np.asarray(pa.generated))
    np.testing.assert_allclose(np.asarray(da.p_yes), np.asarray(pa.p_yes),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(db.weighted_confidence),
                               np.asarray(pb.weighted_confidence), atol=1e-3)
