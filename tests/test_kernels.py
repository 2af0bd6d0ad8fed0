"""PR-7 fused kernel layer: Pallas flash-decode (interpret mode on CPU —
the same kernel runs compiled on the chip), int8 matmul fusion, and
chunked prefill/decode piggybacking.

Three parity contracts pinned here:
- flash_decode == the dense decode-attention path: exact argmax through
  the greedy loop, logits within float tolerance, for masked/padded rows,
  GQA, ALiBi, and every bucket-ladder cache extent;
- quant.matmul's fused s8 x s8 dot == the dequantized reference for both
  static and dynamic QuantTensors, and quant.shared_quant is bit-identical
  to per-matrix activation quantization;
- a piggybacked dispatch chain == the sequential dispatches per row
  (int readouts exact, float readouts to tolerance), including through
  the sweep's chain orchestration on the fake backend.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lir_tpu.engine import generate
from lir_tpu.models import decoder, quant
from lir_tpu.models.registry import ModelConfig
from lir_tpu.ops import flash_decode, pick_split

from dispatch_helpers import fused_shared


def _tiny_cfg(**kw) -> ModelConfig:
    base = dict(name="kernels-tiny", vocab_size=128, hidden_size=32,
                n_layers=2, n_heads=4, n_kv_heads=2, intermediate_size=64,
                max_seq_len=512)
    base.update(kw)
    return ModelConfig(**base)


def _dense_decode_reference(q, k, v, q_pos, mask, key_pos, slopes=None):
    """The decode path's dense attention (decoder._attention_cached +
    _causal_bias semantics), spelled out independently."""
    B, H, hd = q.shape
    K = k.shape[0]
    G = H // K
    qg = q.reshape(B, 1, K, G, hd)
    scores = jnp.einsum("bskgd,ktbd->bkgst", qg, k).astype(jnp.float32)
    T = k.shape[1]
    scores = scores.reshape(B, H, 1, T) / math.sqrt(hd)
    allowed = (key_pos[:, None, :] <= q_pos[:, None, None]) & (mask[:, None, :] > 0)
    bias = jnp.where(allowed, 0.0, jnp.float32(-1e9))[:, None, :, :]
    if slopes is not None:
        bias = bias + (slopes[None, :, None, None]
                       * key_pos.astype(jnp.float32)[:, None, None, :])
    probs = jax.nn.softmax(scores + bias, axis=-1).astype(q.dtype)
    pg = probs.reshape(B, K, G, 1, T)
    out = jnp.einsum("bkgst,ktbd->bskgd", pg, v)
    return out.reshape(B, H, hd)


class TestFlashDecodeKernel:
    def _case(self, T, seed=0, B=3, H=4, K=2, hd=16):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(K, T, B, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(K, T, B, hd)), jnp.float32)
        mask = np.zeros((B, T), np.int32)
        mask[0, : max(T // 4, 1)] = 1        # short row
        mask[1, T // 8: T - T // 8] = 1      # interior hole pattern
        mask[2, :] = 1                       # full row
        key_pos = np.maximum(np.cumsum(mask, -1) - 1, 0)
        q_pos = np.asarray([mask[r].sum() - 1 for r in range(B)], np.int32)
        return (q, k, v, jnp.asarray(q_pos), jnp.asarray(mask),
                jnp.asarray(key_pos))

    @pytest.mark.parametrize("T", [8, 76, 128, 152, 280])
    def test_matches_dense_per_bucket_extent(self, T):
        """Every cache extent the bucket ladder plans (bucket + suffix +
        decode budget — including the non-power-of-two ones) lowers with
        an exact split and matches the dense path."""
        q, k, v, q_pos, mask, key_pos = self._case(T)
        exp = _dense_decode_reference(q, k, v, q_pos, mask, key_pos)
        got = flash_decode(q, k, v, q_pos, mask, key_pos, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5)

    def test_pick_split_is_exact_division(self):
        for T in (8, 76, 108, 128, 152, 280, 1024):
            s = pick_split(T)
            assert T % s == 0 and 1 <= s <= min(T, 128)
        assert pick_split(128) == 128
        assert pick_split(280) == 56        # largest 8-aligned divisor
        assert pick_split(76) == 76         # no 8-aligned divisor: 1 split

    def test_masked_rows_and_causality(self):
        """A key slot is visible iff masked valid AND its position <= the
        query's — tightening q_pos must change the output."""
        q, k, v, q_pos, mask, key_pos = self._case(128, seed=3)
        full = flash_decode(q, k, v, q_pos, mask, key_pos, interpret=True)
        clipped = flash_decode(q, k, v, q_pos - 5, mask, key_pos,
                               interpret=True)
        exp = _dense_decode_reference(q, k, v, q_pos - 5, mask, key_pos)
        np.testing.assert_allclose(np.asarray(clipped), np.asarray(exp),
                                   atol=2e-5)
        assert float(jnp.abs(full - clipped).max()) > 1e-4

    def test_alibi_slopes(self):
        q, k, v, q_pos, mask, key_pos = self._case(64, seed=4, H=4, K=4)
        slopes = jnp.asarray(decoder.alibi_slopes(4))
        exp = _dense_decode_reference(q, k, v, q_pos, mask, key_pos,
                                      slopes=slopes)
        got = flash_decode(q, k, v, q_pos, mask, key_pos,
                           alibi_slopes=slopes, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5)

    def test_mqa_grouping(self):
        q, k, v, q_pos, mask, key_pos = self._case(64, seed=5, H=4, K=1)
        exp = _dense_decode_reference(q, k, v, q_pos, mask, key_pos)
        got = flash_decode(q, k, v, q_pos, mask, key_pos, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5)


class TestFlashDecodeSublaneBlocks:
    """Batches that fill whole sublane groups take the TPU block shape:
    8 cache rows per K/V block, queries contracted against the whole
    block and kept on the block diagonal (ops/flash_decode._decode_kernel).
    The small-batch cases above run one row per block, so the shape the
    chip compiles is pinned against the dense path here."""

    def _case(self, B, H, K, T=128, hd=16, S=None, seed=0, shared=0):
        rng = np.random.default_rng(seed)
        qshape = (B, H, hd) if S is None else (B, S, H, hd)
        q = jnp.asarray(rng.normal(size=qshape), jnp.float32)
        k = rng.normal(size=(K, T, B, hd)).astype(np.float32)
        v = rng.normal(size=(K, T, B, hd)).astype(np.float32)
        k[:, :shared] = k[:, :shared, :1]
        v[:, :shared] = v[:, :shared, :1]
        mask = np.zeros((B, T), np.int32)
        for r in range(B):
            lo = 0 if shared else (r % 3) * 5
            mask[r, lo: T - 3 * (r % 5)] = 1
        key_pos = np.maximum(np.cumsum(mask, -1) - 1, 0)
        last = mask.sum(-1).astype(np.int32) - 1
        q_pos = (last if S is None else
                 last[:, None] - np.arange(S - 1, -1, -1, np.int32)[None])
        return (q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(q_pos),
                jnp.asarray(mask), jnp.asarray(key_pos))

    @pytest.mark.parametrize("B,H,K", [(8, 4, 2), (16, 4, 1), (8, 4, 4),
                                       (24, 6, 2)])
    @pytest.mark.parametrize("alibi", [False, True])
    def test_single_query_matches_dense(self, B, H, K, alibi):
        from lir_tpu.ops.flash_decode import batch_block

        assert batch_block(B) == 8
        q, k, v, q_pos, mask, key_pos = self._case(B, H, K, seed=B + H)
        slopes = jnp.asarray(decoder.alibi_slopes(H)) if alibi else None
        exp = _dense_decode_reference(q, k, v, q_pos, mask, key_pos,
                                      slopes=slopes)
        got = flash_decode(q, k, v, q_pos, mask, key_pos,
                           alibi_slopes=slopes, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5)

    @pytest.mark.parametrize("B,H,K", [(8, 4, 2), (16, 4, 1)])
    @pytest.mark.parametrize("trunk", [0, 64, 100])
    def test_trunk_and_window_match_dense(self, B, H, K, trunk):
        """The trunk index map (every batch block reads block 0 inside
        the trunk) and the verify-window grid axis, against the dense
        path query by query."""
        from lir_tpu.ops.flash_decode import (flash_decode_mq_trunk,
                                              flash_decode_trunk)

        q, k, v, q_pos, mask, key_pos = self._case(
            B, H, K, S=3, seed=trunk + B, shared=trunk)
        got = flash_decode_mq_trunk(q, k, v, q_pos, mask, key_pos,
                                    trunk_len=trunk, interpret=True)
        for s in range(3):
            exp = _dense_decode_reference(q[:, s], k, v, q_pos[:, s], mask,
                                          key_pos)
            np.testing.assert_allclose(np.asarray(got[:, s]),
                                       np.asarray(exp), atol=2e-5)
            one = flash_decode_trunk(q[:, s], k, v, q_pos[:, s], mask,
                                     key_pos, trunk_len=trunk,
                                     interpret=True)
            np.testing.assert_allclose(np.asarray(one), np.asarray(exp),
                                       atol=2e-5)

    def test_wide_group_narrows_the_split(self):
        """falcon's 71-wide MQA group takes a narrower key split so the
        score tile stays inside VMEM; the ladder ignores the window."""
        from lir_tpu.ops.flash_decode import decode_split

        assert decode_split(512, 40, 4) == 128
        assert decode_split(512, 40, 1) == 128
        assert decode_split(512, 40, 71) == 32
        assert decode_split(76, 3, 2) == pick_split(76)


# need -> (extent, split) by query-group width G: what a dispatch that
# needs that many slots (prefix edge + suffix edge + decode budget)
# allocates, and how the decode kernel cuts it. 552 is the ladder's 512
# edge + 40 (23 splits of 24 if left alone), 488 / 424 / 296 / 168 the
# 448 / 384 / 256 / 128 edges + 40.
EXTENT_TABLE = {
    488: {1: (504, 72), 4: (504, 72), 71: (504, 56)},
    552: {1: (560, 112), 4: (560, 112), 71: (560, 56)},
    424: {1: (432, 72), 4: (432, 72), 71: (432, 48)},
    296: {1: (312, 104), 4: (312, 104), 71: (320, 40)},
    168: {1: (176, 88), 4: (176, 88), 71: (168, 56)},
    512: {1: (512, 128), 4: (512, 128), 71: (512, 32)},
    40: {1: (40, 40), 4: (40, 40), 71: (40, 40)},    # one split: as is
    13: {1: (16, 16), 4: (16, 16), 71: (16, 16)},    # onto the 8 grid
}


@pytest.mark.parametrize("batch", [8, 40])
@pytest.mark.parametrize("groups", [1, 4, 71])
@pytest.mark.parametrize("need", sorted(EXTENT_TABLE))
def test_decode_extent_table(need, groups, batch):
    """ops/flash_decode.decode_extent: the smallest multiple of 8 >= need,
    at most 32 above it, whose split is sublane-aligned and at least half
    the widest the (batch, group) shape may take."""
    from lir_tpu.ops.flash_decode import (EXTENT_GROWTH, decode_extent,
                                          decode_split)

    extent = decode_extent(need, batch, groups)
    split = decode_split(extent, batch, groups)
    assert (extent, split) == EXTENT_TABLE[need][groups]
    assert extent % 8 == 0 and need <= extent <= need + EXTENT_GROWTH == \
        need + 32
    cap = 128 if groups < 71 else 57        # 262144 // (8 * 8 * 71) = 57
    assert split % 8 == 0 and extent % split == 0
    assert 2 * split >= min(cap, extent)
    # no smaller multiple of 8 would have done
    for smaller in range(-(-need // 8) * 8, extent, 8):
        s = decode_split(smaller, batch, groups)
        assert s % 8 or 2 * s < min(cap, smaller)


def test_decode_extent_is_monotone_and_bounded():
    """A longer need never gets a shorter cache (the speculative program
    views its sequential extent inside the speculative one), and where no
    candidate splits well the extent is ``need`` on the 8 grid."""
    from lir_tpu.ops.flash_decode import decode_extent, decode_split

    for groups in (1, 4, 71):
        prev = 0
        for need in range(1, 2100):
            extent = decode_extent(need, 40, groups)
            assert prev <= extent and need <= extent <= need + 32
            assert extent % 8 == 0
            prev = extent
    # 729..735: 736, 744, 752, 760 all split narrow (32, 24, 16, 40)
    assert decode_extent(730, 40, 4) == 736
    assert decode_split(736, 40, 4) == 32


@pytest.fixture()
def fused_decode_interpret():
    """Arm the tier-1 interpret hook; jit caches key on cfg, so tests
    rename their cfg per mode instead of clearing global caches."""
    old = decoder.FUSED_DECODE_INTERPRET_ON_CPU
    decoder.FUSED_DECODE_INTERPRET_ON_CPU = True
    yield
    decoder.FUSED_DECODE_INTERPRET_ON_CPU = old


class TestFusedDecodeRouting:
    def test_greedy_decode_argmax_identical(self, fused_decode_interpret):
        """The full greedy loop through decode_step: fused flash-decode
        argmax-identical to the dense path, logits to tolerance."""
        cfg = _tiny_cfg()
        params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(3, 128, (3, 12)), jnp.int32)
        mask = np.ones((3, 12), np.int32)
        mask[0, :5] = 0                      # left-padded row
        mask = jnp.asarray(mask)
        dense_cfg = dataclasses.replace(cfg, fused_decode=False)
        gen_d, lg_d = generate.greedy_decode(params, dense_cfg, toks, mask,
                                             max_new_tokens=6)
        gen_f, lg_f = generate.greedy_decode(params, cfg, toks, mask,
                                             max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(gen_d), np.asarray(gen_f))
        np.testing.assert_allclose(np.asarray(lg_d), np.asarray(lg_f),
                                   atol=2e-5)

    def test_alibi_model_argmax_identical(self, fused_decode_interpret):
        cfg = _tiny_cfg(name="kernels-alibi", pos_embedding="alibi",
                        norm="layernorm", gated_mlp=False, n_kv_heads=4)
        params = decoder.init_params(cfg, jax.random.PRNGKey(1),
                                     dtype=jnp.float32)
        rng = np.random.default_rng(1)
        toks = jnp.asarray(rng.integers(3, 128, (2, 10)), jnp.int32)
        mask = jnp.ones((2, 10), jnp.int32)
        dense_cfg = dataclasses.replace(cfg, fused_decode=False)
        gen_d, _ = generate.greedy_decode(params, dense_cfg, toks, mask,
                                          max_new_tokens=5)
        gen_f, _ = generate.greedy_decode(params, cfg, toks, mask,
                                          max_new_tokens=5)
        np.testing.assert_array_equal(np.asarray(gen_d), np.asarray(gen_f))

    def test_no_fused_decode_flag_restores_dense(self):
        """RuntimeConfig.fused_decode=False reaches the model config (the
        --no-fused-decode path) and the dense route stays dense on CPU
        without the hook."""
        from lir_tpu.backends.fake import FakeTokenizer
        from lir_tpu.config import RuntimeConfig
        from lir_tpu.engine.runner import ScoringEngine

        cfg = _tiny_cfg(vocab_size=FakeTokenizer.VOCAB)
        params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
        eng = ScoringEngine(params, cfg, FakeTokenizer(),
                            RuntimeConfig(batch_size=2, fused_decode=False))
        assert eng.cfg.fused_decode is False
        eng2 = ScoringEngine(params, cfg, FakeTokenizer(),
                             RuntimeConfig(batch_size=2))
        assert eng2.cfg.fused_decode is True
        # CPU without the interpret hook: routing stays dense either way.
        assert not decoder._fused_decode_ok(
            eng2.cfg, 1, 1, (jnp.zeros((1,)), None, None))


class TestInt8MatmulFusion:
    def test_static_fused_matches_dequant_reference(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(5, 64)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
        qt = quant.quantize(w)
        np.testing.assert_allclose(
            np.asarray(quant.matmul(x, qt)), np.asarray(x @ qt.dequant()),
            rtol=1e-5, atol=1e-5)

    def test_dynamic_fused_matches_dequant_reference(self):
        """The s8 x s8 -> s32 dot with output-side scales equals the
        matmul of BOTH dequantized operands (integer accumulation is
        exact; only the scale multiplies round)."""
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(5, 64)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
        qt = dataclasses.replace(quant.quantize(w), dynamic=True)
        xq, xs = quant.dynamic_quant(x)
        ref = ((np.asarray(xq, np.float32) * np.asarray(xs)[:, None])
               @ np.asarray(qt.dequant()))
        np.testing.assert_allclose(np.asarray(quant.matmul(x, qt)), ref,
                                   rtol=1e-5, atol=1e-5)

    def test_shared_quant_bitwise_equals_per_matrix(self):
        """One shared activation quantization (the wq/wk/wv and
        w_up/w_gate call sites) is BIT-identical to quantizing per
        matrix — same amax/127 rule on the same tensor."""
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(3, 7, 32)), jnp.float32)
        w1 = dataclasses.replace(
            quant.quantize(jnp.asarray(rng.normal(size=(32, 16)),
                                       jnp.float32)), dynamic=True)
        w2 = dataclasses.replace(
            quant.quantize(jnp.asarray(rng.normal(size=(32, 24)),
                                       jnp.float32)), dynamic=True)
        xq = quant.shared_quant(x, w1, w2)
        assert isinstance(xq, quant.QuantActivation)
        np.testing.assert_array_equal(np.asarray(quant.matmul(xq, w1)),
                                      np.asarray(quant.matmul(x, w1)))
        np.testing.assert_array_equal(np.asarray(quant.matmul(xq, w2)),
                                      np.asarray(quant.matmul(x, w2)))

    def test_shared_quant_passthrough_for_static_or_dense(self):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(3, 32)), jnp.float32)
        w_static = quant.quantize(jnp.asarray(rng.normal(size=(32, 16)),
                                              jnp.float32))
        w_dyn = dataclasses.replace(w_static, dynamic=True)
        assert quant.shared_quant(x, w_static, w_dyn) is x
        assert quant.shared_quant(x, w_dyn, x) is x   # dense member

    @pytest.mark.parametrize("dynamic", [False, True])
    def test_quantized_forward_tracks_dense(self, dynamic):
        """End-to-end through the decoder's shared-quant call sites: the
        fused int8 forward tracks the dense model's readout."""
        cfg = _tiny_cfg(name=f"kernels-q{dynamic}")
        params = decoder.init_params(cfg, jax.random.PRNGKey(2),
                                     dtype=jnp.float32)
        qparams = quant.quantize_decoder_params(params, dynamic=dynamic)
        rng = np.random.default_rng(4)
        toks = jnp.asarray(rng.integers(3, 128, (2, 10)), jnp.int32)
        dense = jax.nn.softmax(
            decoder.forward(params, cfg, toks)[:, -1], axis=-1)
        fused = jax.nn.softmax(
            decoder.forward(qparams, cfg, toks)[:, -1], axis=-1)
        assert np.isfinite(np.asarray(fused)).all()
        assert float(jnp.abs(dense - fused).max()) < 0.06


def _assert_fused_out_close(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if np.issubdtype(x.dtype, np.floating):
            np.testing.assert_allclose(x, y, atol=1e-5)
        else:
            np.testing.assert_array_equal(x, y)


class TestPiggyback:
    def _dispatch(self, seed, B=3, S=16, SA=4, SB=8, V=128):
        rng = np.random.default_rng(seed)
        prefix = jnp.asarray(rng.integers(3, V, (B, S)), jnp.int32)
        pm = np.ones((B, S), np.int32)
        pm[0, S - 4:] = 0
        sa = jnp.asarray(rng.integers(3, V, (B, SA)), jnp.int32)
        sam = np.ones((B, SA), np.int32)
        sam[1, 2:] = 0
        sb = jnp.asarray(rng.integers(3, V, (B, SB)), jnp.int32)
        sbm = np.ones((B, SB), np.int32)
        sbm[2, 5:] = 0
        return (prefix, jnp.asarray(pm), sa, jnp.asarray(sam), sb,
                jnp.asarray(sbm))

    def test_chain_equals_sequential_dispatches(self):
        """prefill -> step -> step -> drain reproduces three sequential
        shared dispatches per row (int readouts exact)."""
        cfg = _tiny_cfg(name="kernels-piggy")
        params = decoder.init_params(cfg, jax.random.PRNGKey(3),
                                     dtype=jnp.float32)
        yes = jnp.asarray([5, 6, 7], jnp.int32)
        no = jnp.asarray([9, 10, 11], jnp.int32)
        d_ids = jnp.arange(10, 30, dtype=jnp.int32)
        d_vals = jnp.arange(0.0, 20.0, dtype=jnp.float32)
        na, nb = 3, 5
        ds = [self._dispatch(s) for s in (1, 2, 3)]
        seq = [fused_shared(
            params, cfg, *d, yes, no, d_ids, d_vals, max_new_a=na,
            max_new_b=nb) for d in ds]

        carry = generate.shared_piggyback_prefill(params, cfg, *ds[0],
                                                  max_new_a=na, max_new_b=nb)
        outs = []
        for d in ds[1:]:
            oa, ob, carry = generate.shared_piggyback_step(
                params, cfg, carry, *d, yes, no, d_ids, d_vals,
                max_new_a=na, max_new_b=nb)
            outs.append((oa, ob))
        S, SA, SB = 16, 4, 8
        outs.append(generate.shared_piggyback_drain(
            params, cfg, carry, yes, no, d_ids, d_vals, slot0_a=S + SA,
            slot0_b=S + SA + na + SB, max_new_a=na, max_new_b=nb))
        for s, p in zip(seq, outs):
            _assert_fused_out_close(s, p)

    def test_sweep_chains_and_matches_plain(self, tmp_path):
        """The ragged sweep forms piggyback chains (kernel_stats counters
        move) and its rows equal the piggyback-off sweep's."""
        import torch
        import transformers as tf

        from lir_tpu.backends.fake import FakeTokenizer
        from lir_tpu.config import RuntimeConfig
        from lir_tpu.data.prompts import LegalPrompt
        from lir_tpu.engine.runner import ScoringEngine
        from lir_tpu.engine.sweep import run_perturbation_sweep
        from lir_tpu.models.loader import config_from_hf, convert_decoder

        torch.manual_seed(0)
        hf = tf.LlamaForCausalLM(tf.LlamaConfig(
            vocab_size=FakeTokenizer.VOCAB, hidden_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, intermediate_size=128,
            max_position_embeddings=512,
            tie_word_embeddings=False)).eval()
        cfg, fam = config_from_hf(hf.config)
        params = convert_decoder(hf.state_dict(), cfg, fam)
        prompts = (LegalPrompt(
            main="Does a vehicle include a bicycle ?",
            response_format="Answer Covered or Not .",
            target_tokens=("Covered", "Not"),
            confidence_format="Give a number from 0 to 100 ."),)
        perturbations = ([
            f"Would a bicycle number {i} count as a vehicle maybe ?"
            for i in range(11)],)

        def run(piggy, sub):
            rt = RuntimeConfig(batch_size=4, max_new_tokens=8,
                               max_seq_len=256, piggyback_prefill=piggy,
                               sweep_group_min_cells=0)
            eng = ScoringEngine(params, cfg, FakeTokenizer(), rt)
            rows = run_perturbation_sweep(
                eng, "tiny", prompts, perturbations,
                tmp_path / f"r{sub}.xlsx", checkpoint_every=100)
            return rows, eng

        rows_on, eng_on = run(True, "on")
        rows_off, eng_off = run(False, "off")
        assert eng_on.kernel_stats.counters.get("chains_opened", 0) >= 1
        assert eng_on.kernel_stats.counters.get("piggybacked_steps", 0) >= 1
        assert eng_on.kernel_stats.counters.get("chains_drained", 0) >= 1
        assert not eng_off.kernel_stats.counters
        key = lambda r: r.rephrased_main  # noqa: E731
        for a, b in zip(sorted(rows_on, key=key),
                        sorted(rows_off, key=key)):
            assert a.model_response == b.model_response
            assert a.model_confidence_response == b.model_confidence_response
            assert a.confidence_value == b.confidence_value
            assert abs(a.token_1_prob - b.token_1_prob) < 1e-5
            assert abs(a.token_2_prob - b.token_2_prob) < 1e-5
            assert abs(a.weighted_confidence - b.weighted_confidence) < 1e-4

    def test_piggyback_respects_fault_wrapping(self):
        """A fault-wrapped engine (instance-shadowed dispatch methods)
        must not chain — the chain would bypass the injected sites."""
        from lir_tpu.backends.fake import FakeTokenizer
        from lir_tpu.config import RuntimeConfig
        from lir_tpu.engine.runner import ScoringEngine

        cfg = _tiny_cfg(vocab_size=FakeTokenizer.VOCAB)
        params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
        eng = ScoringEngine(params, cfg, FakeTokenizer(),
                            RuntimeConfig(batch_size=2))
        assert eng.piggyback_supported()
        eng.decode_fused_shared = lambda *a, **k: None   # wrap_engine style
        assert not eng.piggyback_supported()
        eng2 = ScoringEngine(params, cfg, FakeTokenizer(),
                             RuntimeConfig(batch_size=2,
                                           piggyback_prefill=False))
        assert not eng2.piggyback_supported()


class TestCostModelAndWatchdogSeed:
    def test_decode_floor_constants(self):
        from lir_tpu.engine import scheduler as sched

        # Fused pricing keeps the historical 1:1 decode-token price
        # (plans byte-identical); the unfused fallback prices higher.
        assert sched.decode_token_cost(True) == sched.DECODE_TOKEN_COST_FUSED
        assert (sched.bucket_cost(4, 64, 4, 12)
                == 4 * 64 + sched.decode_floor(4, 4, 12))
        unfused = sched.bucket_cost(4, 64, 4, 12, fused_decode=False)
        assert unfused > sched.bucket_cost(4, 64, 4, 12)
        assert sched.decode_floor(4, 4, 12, fused_decode=False) == (
            4 * 12 * sched.DECODE_TOKEN_COST_UNFUSED)

    def test_watchdog_seed_reads_scheduler_constants(self):
        from lir_tpu.engine import scheduler as sched
        from lir_tpu.guard.watchdog import DispatchWatchdog

        wd = DispatchWatchdog(multiple=1.0, floor_s=0.0)
        assert wd.seed_headroom == sched.watchdog_seed_headroom()
        wd.observe(cost=10, elapsed=1.0)
        # First sample is inflated by the headroom: a dense-path dispatch
        # at UNFUSED/FUSED x the fused timing stays inside the deadline.
        assert wd.deadline_for(10) == pytest.approx(
            1.0 * sched.watchdog_seed_headroom())
        wd2 = DispatchWatchdog(multiple=1.0, floor_s=0.0, seed_headroom=1.0)
        wd2.observe(cost=10, elapsed=1.0)
        assert wd2.deadline_for(10) == pytest.approx(1.0)


class TestOpsSurface:
    def test_ops_is_the_single_kernel_entry_point(self):
        import lir_tpu.ops as ops

        for name in ("flash_attention", "flash_decode", "pick_split",
                     "reference_attention", "ring_attention",
                     "ulysses_attention", "DEFAULT_BLOCK_Q",
                     "DEFAULT_BLOCK_K"):
            assert hasattr(ops, name), name
        # The re-export IS the parallel implementation, not a copy.
        from lir_tpu.parallel.ring_attention import (reference_attention,
                                                     ring_attention)
        assert ops.ring_attention is ring_attention
        assert ops.reference_attention is reference_attention
