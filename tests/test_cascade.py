"""Shared-prefix cascade prefill (ops/cascade_prefill + the cascade
dispatch path): the Hydragen-style prefix/suffix split behind the 36%
MFU plateau fix.

Parity contracts pinned here:
- ops/lse.merge_partials is BITWISE the inline log-sum-exp combine it
  was lifted out of flash_decode's kernels (the refactor changed no op);
- cascade_attention == dense softmax over trunk + window keys at every
  ladder trunk extent (including non-power-of-two trunks), under GQA /
  MQA, ALiBi, masked (pad) remainder rows, and fully-masked rows that
  defer entirely to the prefix leg — Pallas interpreter on CPU, the
  same kernel that runs compiled on the chip;
- the in-kernel int8 QK^T prefix leg == the dequantized reference built
  from models/quant.dynamic_quant's own rule;
- the cold cascade shared dispatch is argmax-identical (ints exact,
  floats to tolerance — the PR-7 bar) to the dense shared path, and the
  paged-warm trunk resume is BITWISE the unpaged cold cascade;
- scheduler pricing: bucket_cost's cascade discount and the watchdog's
  cascade seed spread, with defaults byte-identical to the old model;
- CascadeStats mirrors STATS_SCHEMA (the metrics-drift contract).
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
from lir_tpu.ops.cascade_prefill import (DEFAULT_BLOCK_N, cascade_attention,
                                         pick_block_n)
from lir_tpu.ops.lse import merge_partials

from dispatch_helpers import (assert_paged_equals_cold, fused_shared,
                              fused_shared_cascade)


def _tiny_cfg(**kw) -> ModelConfig:
    base = dict(name="cascade-tiny", vocab_size=128, hidden_size=32,
                n_layers=2, n_heads=4, n_kv_heads=2, intermediate_size=64,
                max_seq_len=512)
    base.update(kw)
    return ModelConfig(**base)


# ---------------------------------------------------------------------------
# Satellite 1: the lifted log-sum-exp partial merge
# ---------------------------------------------------------------------------

def _inline_merge_reference(o_p, m_p, l_p, axis):
    """The EXACT op sequence flash_decode._decode_kernel carried inline
    before the helper was lifted — kept verbatim here so any drift in
    merge_partials (a reorder, a different epsilon, a dtype change)
    breaks this test bitwise."""
    m = m_p.max(axis=axis)
    w = jnp.where(jnp.isfinite(m_p),
                  jnp.exp(m_p - jnp.expand_dims(m, axis)), 0.0)
    l = (w * l_p).sum(axis=axis)
    o = (w[..., None] * o_p).sum(axis=axis)
    return o / jnp.maximum(l, 1e-30)[..., None]


class TestMergePartials:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_bitwise_equals_pre_refactor_inline(self, seed):
        """Flash-decode-shaped partials: (B, H, splits, ...) with axis=2,
        including all-masked splits (m = -inf, l = 0)."""
        rng = np.random.default_rng(seed)
        B, H, S, hd = 3, 4, 5, 16
        o_p = jnp.asarray(rng.normal(size=(B, H, S, hd)), jnp.float32)
        m_p = np.asarray(rng.normal(size=(B, H, S)), np.float32)
        l_p = np.abs(rng.normal(size=(B, H, S))).astype(np.float32) + 0.1
        m_p[0, :, 2] = -np.inf        # an empty split
        l_p[0, :, 2] = 0.0
        m_p[1, 0, :] = -np.inf        # a fully-empty query row
        l_p[1, 0, :] = 0.0
        got = merge_partials(o_p, jnp.asarray(m_p), jnp.asarray(l_p), axis=2)
        exp = _inline_merge_reference(o_p, jnp.asarray(m_p),
                                      jnp.asarray(l_p), axis=2)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))

    def test_cascade_shaped_axis(self):
        """The cascade merge stacks two legs on axis=2 of a 5D/4D pair —
        same helper, same bitwise contract."""
        rng = np.random.default_rng(2)
        B, K, R, G, hd = 2, 2, 3, 2, 8
        o_p = jnp.asarray(rng.normal(size=(B, K, 2, R, G, hd)), jnp.float32)
        m_p = jnp.asarray(rng.normal(size=(B, K, 2, R, G)), jnp.float32)
        l_p = jnp.asarray(np.abs(rng.normal(size=(B, K, 2, R, G))) + 0.1,
                          jnp.float32)
        got = merge_partials(o_p, m_p, l_p, axis=2)
        exp = _inline_merge_reference(o_p, m_p, l_p, axis=2)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))

    def test_flash_decode_output_unchanged(self):
        """The refactored flash_decode still matches the dense decode
        reference (the kernel's merge now routes through the helper —
        the same contract tests/test_kernels.py pins per extent)."""
        from lir_tpu.ops import flash_decode

        rng = np.random.default_rng(3)
        B, H, K, hd, T = 3, 4, 2, 16, 128
        G = H // K
        q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(K, T, B, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(K, T, B, hd)), jnp.float32)
        mask = np.zeros((B, T), np.int32)
        mask[0, :40], mask[1, 10:90], mask[2, :] = 1, 1, 1
        key_pos = np.maximum(np.cumsum(mask, -1) - 1, 0)
        q_pos = np.asarray([mask[r].sum() - 1 for r in range(B)], np.int32)
        qg = q.reshape(B, 1, K, G, hd)
        scores = (jnp.einsum("bskgd,ktbd->bkgst", qg, k)
                  .reshape(B, H, 1, T).astype(jnp.float32)
                  / math.sqrt(hd))
        allowed = ((key_pos[:, None, :] <= q_pos[:, None, None])
                   & (mask[:, None, :] > 0))
        bias = jnp.where(jnp.asarray(allowed), 0.0,
                         jnp.float32(-1e9))[:, None, :, :]
        probs = jax.nn.softmax(scores + bias, axis=-1)
        exp = jnp.einsum("bkgst,ktbd->bskgd",
                         probs.reshape(B, K, G, 1, T), v).reshape(B, H, hd)
        got = flash_decode(q, k, v, jnp.asarray(q_pos), jnp.asarray(mask),
                           jnp.asarray(key_pos), interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# The cascade kernel vs a dense full-softmax reference
# ---------------------------------------------------------------------------

def _dense_mask(q_pos, sfx_mask, Tt, window=None):
    """(B, R, Tt + R) keys each query attends: trunk slot t is position t
    and always valid, window keys carry the row's mask, and the causal
    key-pos <= query-pos rule (keys ARE the queries' slots); under a
    ``window`` only keys within it of the query's position."""
    q_pos, sfx_mask = np.asarray(q_pos), np.asarray(sfx_mask)
    B = q_pos.shape[0]
    key_pos = np.concatenate([np.broadcast_to(np.arange(Tt), (B, Tt)),
                              q_pos], axis=1)
    key_ok = np.concatenate([np.ones((B, Tt), bool), sfx_mask > 0], axis=1)
    allowed = key_ok[:, None, :] & (key_pos[:, None, :] <= q_pos[:, :, None])
    if window is not None:
        allowed &= key_pos[:, None, :] > q_pos[:, :, None] - window
    return allowed


def _dense_cascade_reference(q, sfx_k, sfx_v, trunk_k, trunk_v, sfx_mask,
                             q_pos, slopes=None, window=None):
    """Plain softmax over trunk ++ window keys per row under
    :func:`_dense_mask`; ALiBi biases by key position
    (decoder._causal_bias convention)."""
    B, R, H, hd = q.shape
    K, Tt = trunk_k.shape[0], trunk_k.shape[1]
    G = H // K
    tk = jnp.broadcast_to(trunk_k[None], (B, K, Tt, hd))
    k_all = jnp.concatenate([tk, sfx_k.transpose(0, 2, 1, 3)], axis=2)
    v_all = jnp.concatenate(
        [jnp.broadcast_to(trunk_v[None], (B, K, Tt, hd)),
         sfx_v.transpose(0, 2, 1, 3)], axis=2)
    key_pos = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(Tt, dtype=jnp.float32)[None], (B, Tt)),
         q_pos.astype(jnp.float32)], axis=1)                   # (B, Tt+R)
    qg = (q.reshape(B, R, K, G, hd).astype(jnp.float32)
          / math.sqrt(hd))
    s = jnp.einsum("brkgd,bktd->bkrgt", qg, k_all.astype(jnp.float32),
                   precision="highest")
    if slopes is not None:
        sl = jnp.asarray(slopes, jnp.float32).reshape(K, G)
        s = s + (sl[None, :, None, :, None]
                 * key_pos[:, None, None, None, :])
    allowed = jnp.asarray(_dense_mask(q_pos, sfx_mask, Tt, window))
    s = jnp.where(allowed[:, None, :, None, :], s, -jnp.inf)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bkrgt,bktd->bkrgd", p, v_all.astype(jnp.float32),
                   precision="highest")
    return o.transpose(0, 2, 1, 3, 4).reshape(B, R, H, hd)


class TestCascadeKernel:
    def _case(self, Tt, R=8, seed=0, B=2, H=4, K=2, hd=16):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(B, R, H, hd)), jnp.float32)
        sk = jnp.asarray(rng.normal(size=(B, R, K, hd)), jnp.float32)
        sv = jnp.asarray(rng.normal(size=(B, R, K, hd)), jnp.float32)
        tk = jnp.asarray(rng.normal(size=(K, Tt, hd)), jnp.float32)
        tv = jnp.asarray(rng.normal(size=(K, Tt, hd)), jnp.float32)
        mask = np.ones((B, R), np.int32)
        mask[0, R // 2:] = 0           # right-padded remainder row
        if B > 2:
            mask[2, :] = 0             # whole prefix IS the trunk
        q_pos = Tt + np.maximum(np.cumsum(mask, -1) - 1, 0)
        return q, sk, sv, tk, tv, jnp.asarray(mask), jnp.asarray(q_pos)

    @pytest.mark.parametrize("Tt", [16, 48, 64, 100, 128])
    def test_matches_dense_per_trunk_extent(self, Tt):
        """Every ladder trunk extent, including the non-power-of-two
        ones (100 is not 8-aligned on the key axis — the whole-trunk
        block must still lower in interpret mode)."""
        case = self._case(Tt)
        exp = _dense_cascade_reference(*case)
        got = cascade_attention(*case, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5)

    def test_fully_masked_row_defers_to_prefix_leg(self):
        """A row whose whole prefix is the trunk has an all-masked
        remainder window: the suffix leg contributes m=-inf/l=0 and the
        merged output is pure trunk attention (finite everywhere)."""
        case = self._case(32, B=3, seed=1)
        got = cascade_attention(*case, interpret=True)
        exp = _dense_cascade_reference(*case)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5)

    def test_mqa_grouping(self):
        case = self._case(64, seed=2, H=4, K=1)
        exp = _dense_cascade_reference(*case)
        got = cascade_attention(*case, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5)

    def test_alibi_slopes(self):
        q, sk, sv, tk, tv, mask, q_pos = self._case(48, seed=3, H=4, K=4)
        slopes = decoder.alibi_slopes(4)
        exp = _dense_cascade_reference(q, sk, sv, tk, tv, mask, q_pos,
                                       slopes=slopes)
        got = cascade_attention(q, sk, sv, tk, tv, mask, q_pos,
                                alibi_slopes=slopes, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5)

    def test_int8_qk_matches_dequant_reference(self):
        """The in-kernel int8 prefix leg == a reference whose trunk
        scores are computed from models/quant.dynamic_quant's OWN
        dequantized q/k (s8 x s8 accumulation is exact below 2^24, so
        only the score scales round)."""
        q, sk, sv, tk, tv, mask, q_pos = self._case(64, seed=4)
        B, R, H, hd = q.shape
        K, Tt = tk.shape[0], tk.shape[1]
        G = H // K
        qf = (q.reshape(B, R, K, G, hd).transpose(2, 0, 1, 3, 4)
              .reshape(K, B * R * G, hd))
        deq_q, deq_k = [], []
        for h in range(K):
            qq, qs = quant.dynamic_quant(qf[h])
            kq, ks = quant.dynamic_quant(tk[h])
            deq_q.append(qq.astype(jnp.float32) * qs[:, None])
            deq_k.append(kq.astype(jnp.float32) * ks[:, None])
        dq = (jnp.stack(deq_q).reshape(K, B, R, G, hd)
              .transpose(1, 2, 0, 3, 4).reshape(B, R, H, hd))
        dk = jnp.stack(deq_k)
        exp = _dense_cascade_reference(dq, sk, sv, dk, tv, mask, q_pos)
        # ... except the suffix leg must use the UNquantized q — rebuild
        # the reference by merging the int8 trunk leg with the fp32
        # suffix leg via the same exact-split identity.
        from lir_tpu.ops.cascade_prefill import (_prefix_partials,
                                                 _suffix_partials)
        o_t, m_t, l_t = _prefix_partials(dq, dk, tv, None, False,
                                         DEFAULT_BLOCK_N, True)
        o_s, m_s, l_s = _suffix_partials(q, sk, sv, mask, q_pos, None)
        exp = merge_partials(jnp.stack([o_t, o_s], axis=2),
                             jnp.stack([m_t, m_s], axis=2),
                             jnp.stack([l_t, l_s], axis=2), axis=2)
        exp = (exp.transpose(0, 2, 1, 3, 4).reshape(B, R, H, hd))
        got = cascade_attention(q, sk, sv, tk, tv, mask, q_pos,
                                int8_qk=True, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=1e-5, atol=1e-5)

    def test_pick_block_n(self):
        assert pick_block_n(1000) == DEFAULT_BLOCK_N
        assert pick_block_n(128) == 128
        assert pick_block_n(60) == 64       # sublane-rounded small N
        assert pick_block_n(3) == 8

    def test_block_tail_padding(self):
        """N not a block multiple: pad rows compute garbage partials
        that are sliced off — output equals the dense reference."""
        case = self._case(32, R=5, B=3, seed=5)   # N = 3*5*2 = 30
        exp = _dense_cascade_reference(*case)
        got = cascade_attention(*case, block_n=16, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# The fused kernel's key loop: only the chunks a row block may attend
# ---------------------------------------------------------------------------

# (query heads, kv heads) of the sweep cells' head layouts: mistral-7b
# GQA 32/8, falcon-7b MQA 71/1, falcon-h1-34b 20/4, k-exaone-236b 64/8.
CELL_LAYOUTS = {"mistral": (32, 8), "falcon": (71, 1), "falcon-h1": (20, 4),
                "k-exaone": (64, 8)}


def _ragged_case(H, K, Tt, lengths, R=384, seed=0, hd=16,
                 dtype=jnp.float32):
    """One row per entry of ``lengths``, right-padded to that many real
    remainder tokens (0: the whole prefix is the trunk), positions as
    the program derives them (trunk_len + decoder.mask_positions)."""
    rng = np.random.default_rng(seed)
    B = len(lengths)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    mask = (np.arange(R)[None] < np.asarray(lengths)[:, None]).astype(
        np.int32)
    q_pos = Tt + np.maximum(np.cumsum(mask, -1) - 1, 0)
    return (draw(B, R, H, hd), draw(B, R, K, hd), draw(B, R, K, hd),
            draw(K, Tt, hd), draw(K, Tt, hd), jnp.asarray(mask),
            jnp.asarray(q_pos))


class TestFusedKeySkip:
    @pytest.mark.parametrize("layout", list(CELL_LAYOUTS))
    def test_matches_dense_at_the_cells_layouts(self, layout):
        """Every sweep cell's head layout at the cells' window of 384
        behind a 64-token trunk: a full row and a ragged one, so row
        blocks past a row's end (pad queries) run too."""
        H, K = CELL_LAYOUTS[layout]
        case = _ragged_case(H, K, 64, [384, 301], seed=len(layout))
        got = cascade_attention(*case, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(_dense_cascade_reference(*case)),
                                   atol=2e-5)

    @pytest.mark.parametrize("window", [128, 40])
    def test_window_blocks_that_miss_the_trunk(self, window):
        """k-exaone's window layers: blocks whose window reaches the trunk
        and blocks whose window does not, ragged and fully-masked rows
        (a pad query's window starts below its slot's)."""
        H, K = CELL_LAYOUTS["k-exaone"]
        case = _ragged_case(H, K, 64, [384, 150, 0], seed=window)
        got = cascade_attention(*case, window=jnp.int32(window),
                                interpret=True)
        exp = _dense_cascade_reference(*case, window=window)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-5)

    def test_a_global_layer_through_the_window_operand(self):
        """A global layer of a windowed model rides the same launch with
        the window at decoder.NO_WINDOW: every causal key, the trunk in
        every block."""
        H, K = CELL_LAYOUTS["k-exaone"]
        case = _ragged_case(H, K, 64, [384, 222], seed=5)
        got = cascade_attention(*case, window=jnp.int32(decoder.NO_WINDOW),
                                interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(_dense_cascade_reference(*case)),
                                   atol=2e-5)

    @pytest.mark.parametrize("Tt", [48, 100])
    def test_non_power_of_two_trunks_and_alibi(self, Tt):
        """ALiBi slopes on trunk and own keys, trunks off the lane grid,
        falcon-h1's group of 5."""
        H, K = CELL_LAYOUTS["falcon-h1"]
        q, sk, sv, tk, tv, mask, q_pos = _ragged_case(H, K, Tt, [384, 77, 0],
                                                      seed=Tt)
        slopes = decoder.alibi_slopes(H)
        got = cascade_attention(q, sk, sv, tk, tv, mask, q_pos,
                                alibi_slopes=slopes, interpret=True)
        exp = _dense_cascade_reference(q, sk, sv, tk, tv, mask, q_pos,
                                       slopes=slopes)
        # Biases of ~200 at these positions leave float32 scores ~1e-5
        # apart in any order of summation.
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=1e-4, atol=1e-4)

    def test_writes_the_query_dtype(self):
        """The result is written once, in q's dtype: bfloat16 in,
        bfloat16 out, the two-leg path's float32 result rounded."""
        H, K = CELL_LAYOUTS["falcon"]
        case = _ragged_case(H, K, 64, [384, 129], dtype=jnp.bfloat16)
        got = cascade_attention(*case, interpret=True)
        two_leg = cascade_attention(*case, fused_suffix=False, interpret=True)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(two_leg, np.float32),
                                   atol=1e-2)

    @pytest.mark.parametrize("window", [None, 128])
    def test_key_pairs_are_the_kernels_own(self, monkeypatch, window):
        """``key_pairs`` counts the keys of the chunks the kernel's bound
        table names (read off the ``pallas_call`` operand the launch is
        handed) and the pairs the dense mask admits."""
        from lir_tpu.ops import cascade_prefill as cp

        H, K = CELL_LAYOUTS["k-exaone"]
        Tt, lengths = 64, [384, 150, 0]
        case = _ragged_case(H, K, Tt, lengths, hd=8)
        tables = []
        real = cp.pl.pallas_call

        def spy(kernel, **kw):
            run = real(kernel, **kw)

            def call(*args):
                tables.append(np.asarray(args[0]))
                return run(*args)
            return call

        monkeypatch.setattr(cp.pl, "pallas_call", spy)
        win = None if window is None else jnp.int32(window)
        cp._cascade_fused(*case, None, True, win)
        R = case[0].shape[1]
        r, chunk = cp.fused_edges(R, H // K, Tt)
        lo, hi, trunk = tables[0].reshape(len(lengths), R // r, 3).T
        keys = (np.minimum((hi + 1) * chunk, R) - lo * chunk
                + trunk * Tt)
        computed, attended = cp.key_pairs(case[6], case[5], Tt, H // K,
                                          [window, window])
        assert computed == 2 * r * int(keys.sum())
        assert attended == 2 * int(_dense_mask(case[6], case[5], Tt,
                                               window).sum())
        full = 2 * len(lengths) * R * (Tt + R)     # every key, two layers
        assert attended < computed < full


# ---------------------------------------------------------------------------
# The cascade shared dispatch vs the dense shared path (generate level)
# ---------------------------------------------------------------------------

def _assert_fused_out_close(a, b, atol=1e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if np.issubdtype(x.dtype, np.floating):
            np.testing.assert_allclose(x, y, atol=atol)
        else:
            np.testing.assert_array_equal(x, y)


def _shared_trunk_dispatch(seed, B=3, S=48, trunk=32, SA=4, SB=8, V=128):
    """Shared-trunk inputs: every row's prefix leads with the same
    ``trunk`` tokens (right-padded canonical layout), tails differ."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(3, V, (B, S)).astype(np.int32)
    prefix[:, :trunk] = prefix[0, :trunk]
    pm = np.ones((B, S), np.int32)
    pm[0, S - 6:] = 0                      # a short row (still > trunk)
    sa = jnp.asarray(rng.integers(3, V, (B, SA)), jnp.int32)
    sam = np.ones((B, SA), np.int32)
    sam[min(1, B - 1), 2:] = 0
    sb = jnp.asarray(rng.integers(3, V, (B, SB)), jnp.int32)
    sbm = np.ones((B, SB), np.int32)
    sbm[B - 1, 5:] = 0
    return (jnp.asarray(prefix), jnp.asarray(pm), sa, jnp.asarray(sam),
            sb, jnp.asarray(sbm))


class TestCascadeSharedDecode:
    def _readout(self, B=3):
        yes = jnp.asarray([5, 6, 7][:B], jnp.int32)
        no = jnp.asarray([9, 10, 11][:B], jnp.int32)
        d_ids = jnp.arange(10, 30, dtype=jnp.int32)
        d_vals = jnp.arange(0.0, 20.0, dtype=jnp.float32)
        return yes, no, d_ids, d_vals

    def test_cold_cascade_argmax_identical_to_dense(self):
        """The PR-7 parity bar: ints (generated tokens, top-2/top-k ids)
        exact, interior floats to tolerance, vs the dense shared path."""
        cfg = _tiny_cfg()
        params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
        d = _shared_trunk_dispatch(1)
        ro = self._readout()
        na, nb = 3, 5
        dense = fused_shared(
            params, cfg, *d, *ro, max_new_a=na, max_new_b=nb)
        casc = fused_shared_cascade(
            params, cfg, *d, *ro, max_new_a=na, max_new_b=nb,
            trunk_len=32)
        _assert_fused_out_close(dense, casc, atol=5e-5)

    def test_nonquantum_trunk_and_tiny_rows(self):
        """A non-power-of-two trunk extent through the full dispatch."""
        cfg = _tiny_cfg(name="cascade-tiny-48")
        params = decoder.init_params(cfg, jax.random.PRNGKey(1),
                                     dtype=jnp.float32)
        d = _shared_trunk_dispatch(2, B=2, S=64, trunk=48)
        ro = self._readout(B=2)
        dense = fused_shared(
            params, cfg, *d, *ro, max_new_a=2, max_new_b=3)
        casc = fused_shared_cascade(
            params, cfg, *d, *ro, max_new_a=2, max_new_b=3, trunk_len=48)
        _assert_fused_out_close(dense, casc, atol=5e-5)

    def test_early_stop_parity(self):
        """Armed stop masks ride the cascade tail exactly as the dense
        branch code (the tail IS the dense path's own branch code)."""
        cfg = _tiny_cfg(name="cascade-tiny-stop")
        params = decoder.init_params(cfg, jax.random.PRNGKey(2),
                                     dtype=jnp.float32)
        d = _shared_trunk_dispatch(3)
        yes, no, d_ids, d_vals = self._readout()
        stop = jnp.zeros((128,), jnp.int32).at[jnp.arange(10, 30)].set(1)
        eos = jnp.int32(2)
        kw = dict(max_new_a=3, max_new_b=5, stop_mask_b=stop,
                  stop_mask_a=jnp.zeros((128,), jnp.int32), eos_id=eos)
        dense = fused_shared(
            params, cfg, *d, yes, no, d_ids, d_vals, **kw)
        casc = fused_shared_cascade(
            params, cfg, *d, yes, no, d_ids, d_vals, trunk_len=32, **kw)
        _assert_fused_out_close(dense, casc, atol=5e-5)

    def test_int8_qk_argmax_identical(self):
        """int8 QK^T on the trunk leg: argmax fields exact vs the fp32
        cascade, interior floats tolerance-bound (the PR-7 int8 bar)."""
        cfg = _tiny_cfg(name="cascade-tiny-i8")
        params = decoder.init_params(cfg, jax.random.PRNGKey(3),
                                     dtype=jnp.float32)
        d = _shared_trunk_dispatch(4)
        ro = self._readout()
        f32 = fused_shared_cascade(
            params, cfg, *d, *ro, max_new_a=3, max_new_b=5, trunk_len=32)
        i8 = fused_shared_cascade(
            params, cfg, *d, *ro, max_new_a=3, max_new_b=5, trunk_len=32,
            int8_qk=True)
        for x, y in zip(jax.tree.leaves(f32[0]) + jax.tree.leaves(f32[1]),
                        jax.tree.leaves(i8[0]) + jax.tree.leaves(i8[1])):
            x, y = np.asarray(x), np.asarray(y)
            if np.issubdtype(x.dtype, np.floating):
                np.testing.assert_allclose(x, y, atol=0.05)
            else:
                np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# Engine routing: eligibility, dense fallback, paged-warm bitwise
# ---------------------------------------------------------------------------

@pytest.fixture()
def cascade_interpret():
    """Arm the tier-1 interpret hook (mirrors fused_decode_interpret)."""
    old = decoder.CASCADE_INTERPRET_ON_CPU
    decoder.CASCADE_INTERPRET_ON_CPU = True
    yield
    decoder.CASCADE_INTERPRET_ON_CPU = old


def _fake_engine(rt=None, cfg_kw=None, **eng_kw):
    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.engine.runner import ScoringEngine

    cfg = _tiny_cfg(vocab_size=FakeTokenizer.VOCAB, **(cfg_kw or {}))
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    rt = rt or RuntimeConfig(batch_size=4)
    return ScoringEngine(params, cfg, FakeTokenizer(), rt, **eng_kw)


def _trunk_rows(B=4, trunk=32, tail=8, seed=0):
    rng = np.random.default_rng(seed)
    head = [int(x) for x in rng.integers(3, 200, trunk)]
    rows = [head + [int(x) for x in rng.integers(3, 200, tail - (r % 3))]
            for r in range(B)]
    return rows


class TestEngineRouting:
    def test_gates(self, cascade_interpret):
        from lir_tpu.config import RuntimeConfig

        eng = _fake_engine()
        assert eng.cascade_supported()
        off = _fake_engine(rt=RuntimeConfig(batch_size=4,
                                            cascade_prefill=False))
        assert not off.cascade_supported()
        assert off.shared_trunk(_trunk_rows(), 4, 64)[0] == 0

    def test_gate_needs_interpret_hook_on_cpu(self):
        eng = _fake_engine()
        assert not eng.cascade_supported()     # hook not armed, CPU

    def test_trunk_derivation(self, cascade_interpret):
        eng = _fake_engine()
        rows = _trunk_rows(trunk=39)           # LCP 39 -> snaps to 32
        assert eng.shared_trunk(rows, 4, 64)[0] == 32
        assert eng.shared_trunk(rows, 1, 64)[0] == 0      # min_rows
        short = _trunk_rows(trunk=20)          # below min_trunk
        assert eng.shared_trunk(short, 4, 64)[0] == 0
        # trunk must stay strictly inside the bucket
        ident = [list(range(3, 67))] * 4
        t = eng.shared_trunk(ident, 4, 64)[0]
        assert 0 < t < 64 and t % 16 == 0

    def test_dispatch_matches_dense_and_counts(self, cascade_interpret):
        from lir_tpu.config import RuntimeConfig

        rows = _trunk_rows()
        conf = [r + [7, 8] for r in rows]
        bins = [r + [5, 6] for r in rows]
        t1 = np.asarray([5] * 4, np.int32)
        t2 = np.asarray([9] * 4, np.int32)

        def dispatch(eng):
            return eng.decode_fused_shared(
                [""] * 4, [""] * 4, t1, t2, new_tokens=3, conf_tokens=4,
                pretokenized_a=bins, pretokenized_b=conf, bucket=64,
                sfx_buckets_ab=(8, 8), reuse_cache=True, n_real=4)

        on = _fake_engine()
        f_on = dispatch(on)
        assert on.cascade_stats.cascade_dispatches == 1
        assert on.cascade_stats.trunk_rows_deduped == 3
        assert on.cascade_stats.prefix_flops_saved > 0
        off = _fake_engine(rt=RuntimeConfig(batch_size=4,
                                            cascade_prefill=False))
        f_off = dispatch(off)
        assert off.cascade_stats.cascade_dispatches == 0
        for a, b in zip(f_on, f_off):
            _assert_fused_out_close(a, b, atol=5e-5)

    def test_dispatch_counts_the_kernels_key_pairs(self, cascade_interpret):
        """A cascade dispatch folds the fused kernel's (query, key) pairs
        into CascadeStats from its own remainder mask: the pairs the dense
        mask admits, and the keys of the chunks ``key_pairs`` reckons from
        the kernel's bound function (held to the kernel's own table in
        TestFusedKeySkip), over every layer; no device work."""
        from lir_tpu.ops.cascade_prefill import key_pairs

        rows = _trunk_rows()
        t = np.asarray([5] * 4, np.int32)
        eng = _fake_engine()
        eng.decode_fused_shared(
            [""] * 4, [""] * 4, t, t, new_tokens=3, conf_tokens=4,
            pretokenized_a=[r + [5, 6] for r in rows],
            pretokenized_b=[r + [7, 8] for r in rows], bucket=64,
            sfx_buckets_ab=(8, 8), reuse_cache=True, n_real=4)
        trunk, R = 32, 64 - 32
        mask = (np.arange(R)[None] < np.asarray(
            [len(r) - trunk for r in rows])[:, None]).astype(np.int32)
        q_pos = trunk + np.maximum(np.cumsum(mask, -1) - 1, 0)
        cfg = eng.cfg
        stats = eng.cascade_stats
        assert (stats.key_pairs_computed, stats.key_pairs_attended) == (
            key_pairs(q_pos, mask, trunk, cfg.n_heads // cfg.n_kv_heads,
                      [None] * cfg.n_layers))
        assert stats.key_pairs_attended == cfg.n_layers * int(
            _dense_mask(q_pos, mask, trunk).sum())
        assert stats.key_pairs_computed >= stats.key_pairs_attended

    def test_ineligible_dispatch_counts_dense_fallback(self,
                                                       cascade_interpret):
        eng = _fake_engine()
        rows = [[int(x) for x in np.random.default_rng(r).integers(
            3, 200, 40)] for r in range(4)]    # no shared trunk
        t = np.asarray([5] * 4, np.int32)
        eng.decode_fused_shared(
            [""] * 4, [""] * 4, t, t, new_tokens=2, conf_tokens=2,
            pretokenized_a=[r + [5] for r in rows],
            pretokenized_b=[r + [7] for r in rows], bucket=64,
            sfx_buckets_ab=(8, 8), reuse_cache=True, n_real=4)
        assert eng.cascade_stats.cascade_dispatches == 0
        assert eng.cascade_stats.dense_fallbacks == 1

    def test_paged_warm_trunk_bitwise_equals_cold(self, cascade_interpret):
        """Dispatch twice with the same shared trunk on a prefix-cached
        engine: the second gathers the trunk from the radix page pool
        and its tokens and decisions are the cold dispatch's, its
        floats within the CPU's few ulps between a window extension
        and a prefill (dispatch_helpers.assert_paged_equals_cold)."""
        from lir_tpu.config import RuntimeConfig

        eng = _fake_engine(rt=RuntimeConfig(batch_size=4,
                                            prefix_cache=True))
        assert eng.prefix_cache is not None
        rows = _trunk_rows(trunk=64, seed=7)
        bins = [r + [5, 6] for r in rows]
        conf = [r + [7, 8] for r in rows]
        t1 = np.asarray([5] * 4, np.int32)
        t2 = np.asarray([9] * 4, np.int32)

        def dispatch():
            return eng.decode_fused_shared(
                [""] * 4, [""] * 4, t1, t2, new_tokens=3, conf_tokens=4,
                pretokenized_a=bins, pretokenized_b=conf, bucket=128,
                sfx_buckets_ab=(8, 8), reuse_cache=True, n_real=4)

        cold = dispatch()
        assert eng.cascade_stats.cascade_dispatches == 1
        warm = dispatch()
        assert eng.cascade_stats.cascade_dispatches == 2
        assert eng.prefix_stats.hits >= 1
        for a, b in zip(warm, cold):
            assert_paged_equals_cold(a, b)


# ---------------------------------------------------------------------------
# Satellite 2: scheduler pricing + watchdog seed spread
# ---------------------------------------------------------------------------

class TestSchedulerCascade:
    def test_bucket_cost_defaults_byte_identical(self):
        from lir_tpu.engine import scheduler as sched

        base = sched.bucket_cost(4, 64, 4, 12)
        assert base == 4 * 64 + sched.decode_floor(4, 4, 12)
        assert sched.bucket_cost(4, 64, 4, 12, cascade=False,
                                 trunk_tokens=48) == base
        assert sched.bucket_cost(4, 64, 4, 12, trunk_tokens=48) == base

    def test_bucket_cost_cascade_discount(self):
        from lir_tpu.engine import scheduler as sched

        base = sched.bucket_cost(4, 64, 4, 12)
        disc = sched.bucket_cost(4, 64, 4, 12, cascade=True,
                                 trunk_tokens=32)
        # slots - 1 = 3 trunk prefills deduped
        assert disc == base - 3 * 32
        # the discount composes with cached tokens and clamps at zero
        floor = sched.decode_floor(4, 4, 12)
        assert sched.bucket_cost(4, 64, 4, 12, cached_tokens=4 * 64,
                                 cascade=True, trunk_tokens=64) == floor

    def test_watchdog_seed_cascade_spread(self):
        from lir_tpu.engine import scheduler as sched

        base = sched.watchdog_seed_headroom()
        assert sched.watchdog_seed_headroom(cascade=False) == base
        assert sched.watchdog_seed_headroom(cascade=True) == (
            base * sched.CASCADE_PREFILL_SPREAD)
        # composes with the speculative spread
        spec = sched.watchdog_seed_headroom(spec_decode=True)
        assert sched.watchdog_seed_headroom(
            spec_decode=True, cascade=True) == (
            spec * sched.CASCADE_PREFILL_SPREAD)
        assert sched.CASCADE_PREFILL_SPREAD > 1.0


# ---------------------------------------------------------------------------
# Satellite 3 tail: stats schema mirror + flops analytic
# ---------------------------------------------------------------------------

class TestCascadeStats:
    def test_schema_mirror(self):
        from lir_tpu.observe import registry as reg_mod
        from lir_tpu.utils.profiling import CascadeStats

        declared = set(reg_mod.STATS_SCHEMA["CascadeStats"])
        public = {f.name for f in dataclasses.fields(CascadeStats)
                  if not f.name.startswith("_")}
        assert declared == public

    def test_summary_and_registry(self, cascade_interpret):
        from lir_tpu.observe.registry import engine_registry
        from lir_tpu.utils.profiling import CascadeStats

        s = CascadeStats()
        s.count("cascade_dispatches", 3)
        s.count("dense_fallbacks")
        out = s.summary()
        assert out["cascade_frac"] == 0.75
        eng = _fake_engine()
        reg = engine_registry(eng)
        assert "cascade" in reg.snapshot()["sources"]

    def test_flops_saved_analytic(self):
        from lir_tpu.utils.profiling import cascade_prefill_flops_saved

        cfg = _tiny_cfg(name="cascade-flops")
        assert cascade_prefill_flops_saved(cfg, 1, 64) == 0.0
        assert cascade_prefill_flops_saved(cfg, 4, 0) == 0.0
        saved = cascade_prefill_flops_saved(cfg, 4, 64)
        assert saved > 0
        # 3 deduped rows, linear in (rows - 1)
        assert cascade_prefill_flops_saved(cfg, 7, 64) == 2 * saved
