"""Trunk-aware cascade DECODE + fully-fused cascade prefill (PR 17).

Parity contracts pinned here:
- ops/lse.merge_partials algebraic properties: all-masked partial sets
  are NaN-free (the all-zero-row convention), the merge is associative
  (pairwise == 3-way to float tolerance), and dtypes pass through;
- ops/flash_decode.flash_decode_trunk (and the _mq sibling) matches the
  flat split-K kernel at every trunk extent — the trunk-split dedup is
  a pure HBM-traffic lever, never an arithmetic change — including the
  nt == 0 passthrough, GQA/MQA grouping, and ALiBi (bitwise on the
  chip; exact-to-1-ulp under the CPU interpreter, see
  _assert_ulp_close);
- the fully-fused cascade prefill kernel (trunk and own keys under one
  softmax inside the Pallas kernel, no HBM round-trip for
  partials) is the two-leg path to float32 rounding at every trunk
  extent of the cascade matrix;
- generate-level: greedy_decode_fused_shared(decode_trunk=N) and the
  speculative sibling are BITWISE their decode_trunk=0 selves;
- engine routing: cascade_decode_supported gates, shared_trunk LCP
  reuse, CascadeStats decode counters (dispatches + analytic deduped
  trunk bytes), and the --no-cascade-decode static-config mirror;
- scheduler: decode_floor's decode_trunk_frac discount with defaults
  byte-identical to the old model.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lir_tpu.engine import generate
from lir_tpu.models import decoder
from lir_tpu.models.registry import ModelConfig
from lir_tpu.ops.cascade_prefill import cascade_attention
from lir_tpu.ops.flash_decode import (flash_decode, flash_decode_mq,
                                      flash_decode_mq_trunk,
                                      flash_decode_trunk, pick_split)
from lir_tpu.ops.lse import merge_partials

from dispatch_helpers import (fused_shared, fused_shared_cascade,
                              fused_shared_spec)


def _tiny_cfg(**kw) -> ModelConfig:
    base = dict(name="cascdec-tiny", vocab_size=128, hidden_size=32,
                n_layers=2, n_heads=4, n_kv_heads=2, intermediate_size=64,
                max_seq_len=512)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture()
def fused_decode_interpret():
    old = decoder.FUSED_DECODE_INTERPRET_ON_CPU
    decoder.FUSED_DECODE_INTERPRET_ON_CPU = True
    yield
    decoder.FUSED_DECODE_INTERPRET_ON_CPU = old


# ---------------------------------------------------------------------------
# Satellite: merge_partials property tests
# ---------------------------------------------------------------------------

class TestMergePartialsProperties:
    def _partials(self, seed, S, shape=(2, 3), hd=8, dtype=np.float32):
        rng = np.random.default_rng(seed)
        o = rng.normal(size=shape + (S, hd)).astype(dtype)
        m = rng.normal(size=shape + (S,)).astype(dtype)
        l = (np.abs(rng.normal(size=shape + (S,))) + 0.1).astype(dtype)
        return jnp.asarray(o), jnp.asarray(m), jnp.asarray(l)

    def test_all_masked_partials_nan_free(self):
        """EVERY partition empty (m = -inf, l = 0): the 1e-30 floor
        engages and the convention is an all-zero row — never NaN/inf,
        for any partition count including one."""
        for S in (1, 2, 5):
            o = jnp.zeros((2, 3, S, 8), jnp.float32)
            m = jnp.full((2, 3, S), -np.inf, jnp.float32)
            l = jnp.zeros((2, 3, S), jnp.float32)
            got = np.asarray(merge_partials(o, m, l, axis=2))
            assert np.isfinite(got).all(), S
            np.testing.assert_array_equal(got, np.zeros_like(got))

    def test_mixed_masked_rows_nan_free(self):
        """Some rows fully masked, others partially: finite everywhere,
        and the live rows ignore their empty partitions exactly."""
        o, m, l = self._partials(0, S=4)
        m = np.array(m)
        l = np.array(l)
        m[0, 0, :], l[0, 0, :] = -np.inf, 0.0        # dead row
        m[1, 2, 1], l[1, 2, 1] = -np.inf, 0.0        # one empty split
        full = merge_partials(o, jnp.asarray(m), jnp.asarray(l), axis=2)
        assert np.isfinite(np.asarray(full)).all()
        live = merge_partials(o[1, 2, [0, 2, 3]][None, None],
                              jnp.asarray(m[1, 2, [0, 2, 3]])[None, None],
                              jnp.asarray(l[1, 2, [0, 2, 3]])[None, None],
                              axis=2)
        np.testing.assert_allclose(np.asarray(full)[1, 2],
                                   np.asarray(live)[0, 0], rtol=1e-6)

    def test_pairwise_merge_associative_vs_three_way(self):
        """Merging partials {1,2} into a single combined partial (the
        running-max recombination every flash kernel uses), then merging
        with {3}, equals the flat 3-way merge: the reduction is
        associative, which is WHY the trunk/suffix split can recombine
        in any grouping without drift."""
        o, m, l = self._partials(1, S=3)
        three = merge_partials(o, m, l, axis=2)
        # Fold partials 0 and 1 into one combined partial triple.
        m2, l2, o2 = m[..., :2], l[..., :2], o[..., :2, :]
        m12 = m2.max(axis=-1)
        w = jnp.where(jnp.isfinite(m2), jnp.exp(m2 - m12[..., None]), 0.0)
        l12 = (w * l2).sum(axis=-1)
        o12 = (w[..., None] * o2).sum(axis=-2)
        pair = merge_partials(
            jnp.stack([o12, o[..., 2, :]], axis=-2),
            jnp.stack([m12, m[..., 2]], axis=-1),
            jnp.stack([l12, l[..., 2]], axis=-1), axis=2)
        np.testing.assert_allclose(np.asarray(pair), np.asarray(three),
                                   rtol=2e-6, atol=1e-7)

    def test_associativity_with_empty_partition(self):
        """Associativity holds when one of the folded partials is empty
        (m = -inf carries weight exactly 0 through the fold)."""
        o, m, l = self._partials(2, S=3)
        m = np.asarray(m).copy()
        l = np.asarray(l).copy()
        m[..., 1] = -np.inf
        l[..., 1] = 0.0
        m, l = jnp.asarray(m), jnp.asarray(l)
        three = merge_partials(o, m, l, axis=2)
        m2, l2, o2 = m[..., :2], l[..., :2], o[..., :2, :]
        m12 = m2.max(axis=-1)
        w = jnp.where(jnp.isfinite(m2), jnp.exp(m2 - m12[..., None]), 0.0)
        l12 = (w * l2).sum(axis=-1)
        o12 = (w[..., None] * o2).sum(axis=-2)
        pair = merge_partials(
            jnp.stack([o12, o[..., 2, :]], axis=-2),
            jnp.stack([m12, m[..., 2]], axis=-1),
            jnp.stack([l12, l[..., 2]], axis=-1), axis=2)
        np.testing.assert_allclose(np.asarray(pair), np.asarray(three),
                                   rtol=2e-6, atol=1e-7)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtype_preservation(self, dtype):
        """The merge emits the partials' own dtype — the kernels hand it
        float32 accumulators and must get float32 back (a silent
        down-cast here would corrupt every split path)."""
        o, m, l = self._partials(3, S=4)
        o, m, l = o.astype(dtype), m.astype(dtype), l.astype(dtype)
        got = merge_partials(o, m, l, axis=2)
        assert got.dtype == dtype
        assert got.shape == o.shape[:2] + (o.shape[-1],)


# ---------------------------------------------------------------------------
# Tentpole (a): trunk-aware flash-decode splits vs the flat kernel
# ---------------------------------------------------------------------------

def _assert_ulp_close(got, flat):
    """Identical arithmetic per partial — bitwise on the chip where the
    Pallas lowering fixes the tiling. Under the CPU interpreter XLA
    re-vectorizes the trunk leg's batched shapes (B*S*G rows in one
    GEMM vs the flat kernel's per-row grid), and its SIMD-vs-scalar
    ``exp`` tails can differ by 1 ulp on some inputs — so the CPU pin
    is exact-to-1-ulp, not exact-to-the-bit."""
    got, flat = np.asarray(got), np.asarray(flat)
    np.testing.assert_allclose(got, flat, rtol=3e-6, atol=3e-8)

def _decode_case(seed, B=3, H=4, K=2, hd=16, T=256, S=None, shared=None):
    """A decode-step cache state with realistic ragged masks; queries
    (B, H, hd) or (B, S, H, hd) when S is given (the verify window).
    The leading ``shared`` cache slots hold row 0's K/V in EVERY row —
    the shared-trunk precondition the trunk kernels dedup against (a
    cascade/shared dispatch broadcast or prefilled the trunk into every
    row, so those slots are bitwise-identical across the batch)."""
    rng = np.random.default_rng(seed)
    qshape = (B, H, hd) if S is None else (B, S, H, hd)
    q = jnp.asarray(rng.normal(size=qshape), jnp.float32)
    k = rng.normal(size=(K, T, B, hd)).astype(np.float32)
    v = rng.normal(size=(K, T, B, hd)).astype(np.float32)
    shared = T if shared is None else shared
    k[:, :shared] = k[:, :shared, :1]
    v[:, :shared] = v[:, :shared, :1]
    k, v = jnp.asarray(k), jnp.asarray(v)
    mask = np.zeros((B, T), np.int32)
    fill = [T - 16, T - 40, T][:B] + [T] * max(0, B - 3)
    for r in range(B):
        mask[r, :fill[r]] = 1
    key_pos = np.maximum(np.cumsum(mask, -1) - 1, 0)
    if S is None:
        q_pos = np.asarray([mask[r].sum() - 1 for r in range(B)], np.int32)
    else:
        last = np.asarray([mask[r].sum() - 1 for r in range(B)], np.int32)
        q_pos = last[:, None] - np.arange(S - 1, -1, -1, np.int32)[None]
    return (q, k, v, jnp.asarray(q_pos), jnp.asarray(mask),
            jnp.asarray(key_pos))


class TestTrunkDecodeBitwise:
    @pytest.mark.parametrize("trunk", [0, 64, 100, 128, 200, 255])
    def test_single_query_bitwise_flat(self, trunk):
        """flash_decode_trunk == flash_decode at every trunk extent:
        whole splits inside the trunk batch into the shared GEMM,
        partial trailing splits stay per-row, and the merge is the same
        reduction over the same partial values (see _assert_ulp_close
        for the CPU-interpreter bar)."""
        case = _decode_case(0, T=256, shared=trunk)
        flat = flash_decode(*case, interpret=True)
        got = flash_decode_trunk(*case, trunk_len=trunk, interpret=True)
        _assert_ulp_close(got, flat)

    def test_multi_trunk_splits(self):
        """A trunk spanning several whole splits (T=384 -> split 128,
        trunk 256 -> nt=2) still matches bitwise."""
        case = _decode_case(1, T=384, shared=256)
        assert pick_split(384) == 128
        flat = flash_decode(*case, interpret=True)
        got = flash_decode_trunk(*case, trunk_len=256, interpret=True)
        _assert_ulp_close(got, flat)

    def test_trunk_caps_at_cache_edge(self):
        """trunk_len >= T clamps to T-1: at least the final split always
        stays per-row (the rows' own tails differ)."""
        case = _decode_case(2, T=256)
        flat = flash_decode(*case, interpret=True)
        got = flash_decode_trunk(*case, trunk_len=10_000, interpret=True)
        _assert_ulp_close(got, flat)

    def test_mqa_and_alibi_bitwise(self):
        q, k, v, q_pos, mask, key_pos = _decode_case(3, H=4, K=1, T=256,
                                                     shared=128)
        slopes = decoder.alibi_slopes(4)
        flat = flash_decode(q, k, v, q_pos, mask, key_pos,
                            alibi_slopes=slopes, interpret=True)
        got = flash_decode_trunk(q, k, v, q_pos, mask, key_pos,
                                 alibi_slopes=slopes, trunk_len=128,
                                 interpret=True)
        _assert_ulp_close(got, flat)

    @pytest.mark.parametrize("trunk", [0, 128, 200])
    def test_multi_query_bitwise_flat(self, trunk):
        """The _mq sibling (speculative verify windows): same parity
        contract, every query in the window."""
        case = _decode_case(4, T=256, S=3, shared=trunk)
        flat = flash_decode_mq(*case, interpret=True)
        got = flash_decode_mq_trunk(*case, trunk_len=trunk, interpret=True)
        _assert_ulp_close(got, flat)

    def test_multi_query_alibi_bitwise(self):
        q, k, v, q_pos, mask, key_pos = _decode_case(5, T=256, S=4,
                                                     shared=128)
        slopes = decoder.alibi_slopes(4)
        flat = flash_decode_mq(q, k, v, q_pos, mask, key_pos,
                               alibi_slopes=slopes, interpret=True)
        got = flash_decode_mq_trunk(q, k, v, q_pos, mask, key_pos,
                                    alibi_slopes=slopes, trunk_len=128,
                                    interpret=True)
        _assert_ulp_close(got, flat)


# The four entry points reading layer l of a stacked (L, K, T, B, hd)
# operand (the decode step's layer loop, models/decoder._block) against
# the same call on that layer's own (K, T, B, hd) sides: one kernel, one
# grid, only the K/V index map's leading coordinate differs, so the
# outputs are equal to the bit under the interpreter too.
STACKED_CASES = [
    ("gqa", flash_decode, dict(H=4, K=2), None, {}, False),
    ("mqa", flash_decode, dict(H=4, K=1), None, {}, False),
    ("trunk", flash_decode_trunk, dict(H=4, K=2, shared=128), None,
     {"trunk_len": 128}, False),
    ("alibi", flash_decode, dict(H=4, K=4), None, {}, True),
    ("mqa-trunk-alibi", flash_decode_trunk, dict(H=4, K=1, shared=200),
     None, {"trunk_len": 200}, True),
    ("mq", flash_decode_mq, dict(H=4, K=2), 3, {}, False),
    ("mq-trunk", flash_decode_mq_trunk, dict(H=4, K=2, shared=128), 3,
     {"trunk_len": 128}, False),
]


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("name,fn,shape,window,static,alibi", STACKED_CASES,
                         ids=[c[0] for c in STACKED_CASES])
def test_stacked_operand_equals_the_layers_own_call(name, fn, shape, window,
                                                    static, alibi, layer):
    L = 3
    cases = [_decode_case(10 + i, T=256, S=window, **shape)
             for i in range(L)]
    q, _, _, q_pos, mask, key_pos = cases[layer]
    k = jnp.stack([c[1] for c in cases])
    v = jnp.stack([c[2] for c in cases])
    kw = dict(static, interpret=True)
    if alibi:
        kw["alibi_slopes"] = decoder.alibi_slopes(shape["H"])
    own = jax.jit(lambda kl, vl: fn(q, kl, vl, q_pos, mask, key_pos,
                                    **kw))(k[layer], v[layer])
    # The layer index traced, as the layer loop hands it over.
    got = jax.jit(lambda l: fn(q, k, v, q_pos, mask, key_pos, layer=l,
                               **kw))(jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(own))
    other = fn(q, k[1], v[1], q_pos, mask, key_pos, **kw)
    assert np.abs(np.asarray(other) - np.asarray(own)).max() > 1e-3


# ---------------------------------------------------------------------------
# Tentpole (b): fully-fused cascade prefill vs the two-leg path
# ---------------------------------------------------------------------------

def _prefill_case(Tt, R=8, seed=0, B=2, H=4, K=2, hd=16):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, R, H, hd)), jnp.float32)
    sk = jnp.asarray(rng.normal(size=(B, R, K, hd)), jnp.float32)
    sv = jnp.asarray(rng.normal(size=(B, R, K, hd)), jnp.float32)
    tk = jnp.asarray(rng.normal(size=(K, Tt, hd)), jnp.float32)
    tv = jnp.asarray(rng.normal(size=(K, Tt, hd)), jnp.float32)
    mask = np.ones((B, R), np.int32)
    mask[0, R // 2:] = 0
    if B > 2:
        mask[2, :] = 0
    q_pos = Tt + np.maximum(np.cumsum(mask, -1) - 1, 0)
    return q, sk, sv, tk, tv, jnp.asarray(mask), jnp.asarray(q_pos)


class TestFusedSuffixBitwise:
    @pytest.mark.parametrize("Tt", [16, 32, 48, 64, 100, 128])
    @pytest.mark.parametrize("R,B,K", [(8, 2, 2), (5, 3, 1), (8, 3, 4)])
    def test_fused_equals_two_leg(self, Tt, R, B, K):
        """The single-kernel cascade (trunk and own keys under one
        softmax in the Pallas kernel, no HBM round-trip for partials) is
        the two-leg path to float32 rounding at every trunk extent of the
        cascade matrix, under GQA / MQA, masked remainder rows, and
        fully-masked rows: the same scores, the softmax summed in another
        order."""
        case = _prefill_case(Tt, R=R, B=B, K=K, seed=Tt + R)
        two_leg = cascade_attention(*case, fused_suffix=False,
                                    interpret=True)
        fused = cascade_attention(*case, fused_suffix=True, interpret=True)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(two_leg),
                                   rtol=1e-6, atol=1e-6)

    def test_fused_alibi_bitwise(self):
        q, sk, sv, tk, tv, mask, q_pos = _prefill_case(48, seed=9, K=4)
        slopes = decoder.alibi_slopes(4)
        two_leg = cascade_attention(q, sk, sv, tk, tv, mask, q_pos,
                                    alibi_slopes=slopes,
                                    fused_suffix=False, interpret=True)
        fused = cascade_attention(q, sk, sv, tk, tv, mask, q_pos,
                                  alibi_slopes=slopes, fused_suffix=True,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(two_leg),
                                   rtol=1e-6, atol=1e-6)

    def test_int8_qk_routes_two_leg(self):
        """int8 QK^T keeps the two-leg lowering (the int8 prefix kernel
        has no fused sibling): fused_suffix=True with int8_qk is the
        int8 two-leg path verbatim."""
        case = _prefill_case(64, seed=10)
        a = cascade_attention(*case, int8_qk=True, fused_suffix=True,
                              interpret=True)
        b = cascade_attention(*case, int8_qk=True, fused_suffix=False,
                              interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Generate-level: decode_trunk threading is invisible to outputs
# ---------------------------------------------------------------------------

def _assert_trees_bitwise(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _trunk_shared_args(seed, B=3, S=128, trunk=96, SA=4, SB=8, V=128):
    """Shared-args tuple whose rows lead with a ``trunk``-token LCP, in
    a bucket big enough that the decode cache (S + sfx + new) spans
    multiple key splits — so the trunk leg actually engages."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(3, V, (B, S)).astype(np.int32)
    prefix[:, :trunk] = prefix[0, :trunk]
    pm = np.ones((B, S), np.int32)
    pm[0, S - 6:] = 0
    sa = jnp.asarray(rng.integers(3, V, (B, SA)), jnp.int32)
    sam = np.ones((B, SA), np.int32)
    sam[1, 2:] = 0
    sb = jnp.asarray(rng.integers(3, V, (B, SB)), jnp.int32)
    sbm = np.ones((B, SB), np.int32)
    sbm[B - 1, 5:] = 0
    yes = jnp.asarray([5, 6, 7][:B], jnp.int32)
    no = jnp.asarray([9, 10, 11][:B], jnp.int32)
    d_ids = jnp.arange(10, 30, dtype=jnp.int32)
    d_vals = jnp.arange(0.0, 20.0, dtype=jnp.float32)
    return (jnp.asarray(prefix), jnp.asarray(pm), sa, jnp.asarray(sam),
            sb, jnp.asarray(sbm), yes, no, d_ids, d_vals)


class TestGenerateDecodeTrunk:
    def test_sequential_bitwise(self, fused_decode_interpret):
        """greedy_decode_fused_shared with decode_trunk engaged is
        BITWISE its flat self — every payload leaf."""
        cfg = _tiny_cfg()
        params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                     dtype=jnp.float32)
        args = _trunk_shared_args(0)
        flat = fused_shared(
            params, cfg, *args, max_new_a=3, max_new_b=5)
        trunked = fused_shared(
            params, cfg, *args, max_new_a=3, max_new_b=5, decode_trunk=96)
        _assert_trees_bitwise(flat, trunked)

    def test_cascade_dispatch_bitwise(self, fused_decode_interpret):
        """The cascade prefill dispatch threads its own trunk into the
        decode tail (decode_trunk=trunk_len) — still bitwise vs the
        dense+flat shared path at the argmax bar's float fields too,
        when the model's cascade_decode static flag is OFF (trunk
        zeroed in the decoder gate)."""
        cfg = _tiny_cfg(name="cascdec-gate-off", cascade_decode=False)
        params = decoder.init_params(cfg, jax.random.PRNGKey(1),
                                     dtype=jnp.float32)
        old = decoder.CASCADE_INTERPRET_ON_CPU
        decoder.CASCADE_INTERPRET_ON_CPU = True
        try:
            args = _trunk_shared_args(1)
            on = fused_shared_cascade(
                params, cfg, *args, max_new_a=2, max_new_b=3, trunk_len=96)
            cfg_on = dataclasses.replace(cfg, name="cascdec-gate-on",
                                         cascade_decode=True)
            on2 = fused_shared_cascade(
                params, cfg_on, *args, max_new_a=2, max_new_b=3,
                trunk_len=96)
        finally:
            decoder.CASCADE_INTERPRET_ON_CPU = old
        _assert_trees_bitwise(on, on2)

    def test_spec_bitwise(self, fused_decode_interpret):
        """The speculative verify window rides flash_decode_mq_trunk:
        spec decode with decode_trunk engaged is bitwise flat spec."""
        cfg = _tiny_cfg(name="cascdec-spec")
        params = decoder.init_params(cfg, jax.random.PRNGKey(2),
                                     dtype=jnp.float32)
        args = _trunk_shared_args(2, SA=4, SB=8)
        B, Ta, Tb, k = 3, 3, 4, 2
        width = 128 + 8 + max(Ta, Tb)
        ctx = np.zeros((B, width), np.int32)
        lens = np.full((B,), 100, np.int32)
        ctx[:, :100] = np.asarray(args[0])[:, :100]
        si = (jnp.asarray(ctx), jnp.asarray(lens),
              jnp.zeros((B, Ta), jnp.int32), jnp.zeros((B,), jnp.int32),
              jnp.asarray(ctx), jnp.asarray(lens),
              jnp.zeros((B, Tb), jnp.int32), jnp.zeros((B,), jnp.int32))
        flat = fused_shared_spec(
            params, cfg, *args, *si, max_new_a=Ta, max_new_b=Tb, spec_k=k)
        trunked = fused_shared_spec(
            params, cfg, *args, *si, max_new_a=Ta, max_new_b=Tb, spec_k=k,
            decode_trunk=96)
        _assert_trees_bitwise(flat, trunked)


# ---------------------------------------------------------------------------
# Engine routing, counters, config mirror
# ---------------------------------------------------------------------------

def _fake_engine(rt=None, cfg_kw=None):
    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.engine.runner import ScoringEngine

    cfg = _tiny_cfg(vocab_size=FakeTokenizer.VOCAB, **(cfg_kw or {}))
    params = decoder.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    rt = rt or RuntimeConfig(batch_size=4)
    return ScoringEngine(params, cfg, FakeTokenizer(), rt)


def _trunk_rows(B=4, trunk=96, tail=8, seed=0):
    rng = np.random.default_rng(seed)
    head = [int(x) for x in rng.integers(3, 200, trunk)]
    return [head + [int(x) for x in rng.integers(3, 200, tail - (r % 3))]
            for r in range(B)]


class TestEngineDecodeTrunk:
    def test_gates(self, fused_decode_interpret):
        from lir_tpu.config import RuntimeConfig

        eng = _fake_engine()
        assert eng.cascade_decode_supported()
        assert eng.shared_trunk(_trunk_rows(), 4, 128)[1] == 96
        off = _fake_engine(rt=RuntimeConfig(batch_size=4,
                                            cascade_decode=False))
        assert not off.cascade_decode_supported()
        assert off.shared_trunk(_trunk_rows(), 4, 128)[1] == 0
        # the static model flag mirrors the runtime opt-out, so stale
        # executables can never serve the other mode
        assert off.cfg.cascade_decode is False
        assert eng.cfg.cascade_decode is True

    def test_gate_needs_fused_decode_kernels(self):
        eng = _fake_engine()          # hook not armed, CPU backend
        assert not eng.cascade_decode_supported()
        assert eng.shared_trunk(_trunk_rows(), 4, 128)[1] == 0

    def test_fused_suffix_flag_mirrors(self):
        from lir_tpu.config import RuntimeConfig

        eng = _fake_engine(rt=RuntimeConfig(batch_size=4,
                                            cascade_fused_suffix=False))
        assert eng.cfg.cascade_fused_suffix is False

    def test_trunk_reuses_lcp_discipline(self, fused_decode_interpret):
        """the decode trunk is the SAME quantized-LCP ladder the cascade
        prefill keys on: quantum snap, min_rows, bucket clamp."""
        eng = _fake_engine()
        rows = _trunk_rows(trunk=39)
        assert eng.shared_trunk(rows, 4, 64)[1] == 32    # snap to 32
        assert eng.shared_trunk(rows, 1, 64)[1] == 0     # min_rows
        ident = [list(range(3, 131))] * 4
        t = eng.shared_trunk(ident, 4, 128)[1]
        assert 0 < t < 128                                # bucket clamp

    def test_dispatch_counters_and_parity(self, fused_decode_interpret):
        """A shared dispatch over a 96-token trunk in a 128 bucket: ON
        counts a cascade-decode dispatch with nonzero analytic deduped
        trunk bytes; OFF counts nothing; payloads match at the PR-7
        argmax bar (the executables differ, the arithmetic does not)."""
        from lir_tpu.config import RuntimeConfig

        rows = _trunk_rows()
        bins = [r + [5, 6] for r in rows]
        conf = [r + [7, 8] for r in rows]
        t1 = np.asarray([5] * 4, np.int32)
        t2 = np.asarray([9] * 4, np.int32)

        def dispatch(eng):
            return eng.decode_fused_shared(
                [""] * 4, [""] * 4, t1, t2, new_tokens=3, conf_tokens=4,
                pretokenized_a=bins, pretokenized_b=conf, bucket=128,
                sfx_buckets_ab=(8, 8), reuse_cache=True, n_real=4)

        on = _fake_engine()
        f_on = dispatch(on)
        assert on.cascade_stats.cascade_decode_dispatches == 1
        assert on.cascade_stats.trunk_bytes_deduped > 0
        off = _fake_engine(rt=RuntimeConfig(batch_size=4,
                                            cascade_decode=False))
        f_off = dispatch(off)
        assert off.cascade_stats.cascade_decode_dispatches == 0
        assert off.cascade_stats.trunk_bytes_deduped == 0
        for a, b in zip(f_on, f_off):
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                x, y = np.asarray(x), np.asarray(y)
                if np.issubdtype(x.dtype, np.floating):
                    np.testing.assert_allclose(x, y, atol=5e-5)
                else:
                    np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# compile_plan keying
# ---------------------------------------------------------------------------

class TestCompilePlanDecodeTrunk:
    def test_spec_label_and_keying(self):
        import dispatch_helpers as cp

        flat = cp.shared_spec(128, 4, 8, 8, 3, 4, False, False)
        trunked = cp.shared_spec(128, 4, 8, 8, 3, 4, False, False,
                                 decode_trunk=96)
        assert flat.decode_trunk == 0
        assert trunked.decode_trunk == 96
        assert flat != trunked
        assert "/dtrunk96" in trunked.label
        assert "dtrunk" not in flat.label
        paged = cp.shared_paged_spec(128, 4, 64, 8, 8, 3, 4, False, False,
                                     decode_trunk=96)
        assert paged.decode_trunk == 96 and "/dtrunk96" in paged.label


# ---------------------------------------------------------------------------
# Pricing + the analytic dedup counter
# ---------------------------------------------------------------------------

class TestSchedulerDecodeTrunk:
    def test_decode_floor_defaults_byte_identical(self):
        from lir_tpu.engine import scheduler as sched

        base = sched.decode_floor(4, 4, 12)
        assert sched.decode_floor(4, 4, 12, decode_trunk_frac=0.0) == base
        assert sched.bucket_cost(4, 64, 4, 12,
                                 decode_trunk_frac=0.0) == (
            sched.bucket_cost(4, 64, 4, 12))

    def test_decode_floor_trunk_discount(self):
        from lir_tpu.engine import scheduler as sched

        base = sched.decode_floor(4, 4, 12)
        half = sched.decode_floor(4, 4, 12, decode_trunk_frac=0.5)
        full = sched.decode_floor(4, 4, 12, decode_trunk_frac=1.0)
        assert base > half > full > 0
        # deduped-row fraction: (slots-1)/slots; KV share caps the lever
        assert full == pytest.approx(
            base * (1 - sched.CASCADE_DECODE_KV_SHARE * 3 / 4))
        # one slot has nothing to dedup
        single = sched.decode_floor(1, 4, 12)
        assert sched.decode_floor(1, 4, 12, decode_trunk_frac=1.0) == single
        # frac clamps at 1
        assert sched.decode_floor(4, 4, 12, decode_trunk_frac=3.0) == full

    def test_bucket_cost_passthrough(self):
        from lir_tpu.engine import scheduler as sched

        base = sched.bucket_cost(4, 64, 4, 12)
        disc = sched.bucket_cost(4, 64, 4, 12, decode_trunk_frac=0.75)
        assert disc < base
        assert base - disc == pytest.approx(
            sched.decode_floor(4, 4, 12)
            - sched.decode_floor(4, 4, 12, decode_trunk_frac=0.75))


class TestBytesSavedAnalytic:
    def test_guards_and_ladder_mirror(self):
        from lir_tpu.utils.profiling import cascade_decode_bytes_saved

        cfg = _tiny_cfg(name="cascdec-bytes")
        assert cascade_decode_bytes_saved(cfg, 1, 96, 256, 3) == 0.0
        assert cascade_decode_bytes_saved(cfg, 4, 0, 256, 3) == 0.0
        assert cascade_decode_bytes_saved(cfg, 4, 96, 256, 0) == 0.0
        # trunk shorter than one split: kernel falls back flat, counter
        # reports zero (it mirrors the ladder, not an idealized bound)
        assert cascade_decode_bytes_saved(cfg, 4, 64, 256, 3) == 0.0
        # T=256 -> split 128, trunk 200 -> nt=1: per row-step bytes are
        # 2 (K+V) * n_kv * 128 * hd * 4B * n_layers
        hd = cfg.hidden_size // cfg.n_heads
        per = 2 * cfg.n_kv_heads * 128 * hd * 4 * cfg.n_layers
        got = cascade_decode_bytes_saved(cfg, 4, 200, 256, 3)
        assert got == per * 3 * 3
        # linear in deduped rows and steps
        assert cascade_decode_bytes_saved(cfg, 7, 200, 256, 3) == 2 * got
        assert cascade_decode_bytes_saved(cfg, 4, 200, 256, 6) == 2 * got
