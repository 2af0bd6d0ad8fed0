"""Sequence-parallelism parity: ring attention and Ulysses all-to-all must
match single-device softmax attention exactly, on a virtual 8-device mesh
(the same Mesh/shard_map code paths as a real slice — SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lir_tpu.config import MeshConfig
from lir_tpu.parallel import (
    reference_attention,
    ring_attention,
    seq_sharded,
    ulysses_attention,
)
from lir_tpu.parallel.sharding import build_mesh

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 (virtual) devices"
)


@pytest.fixture(scope="module")
def seq_mesh():
    return build_mesh(MeshConfig(data=1, model=1, seq=8))


def _qkv(B=2, S=64, H=8, hd=16, seed=0):
    rng = np.random.default_rng(seed)
    shape = (B, S, H, hd)
    return tuple(
        jnp.asarray(rng.normal(size=shape), jnp.float32) for _ in range(3)
    )


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, seq_mesh, causal):
        q, k, v = _qkv()
        expected = reference_attention(q, k, v, causal=causal)
        qs = jax.device_put(q, seq_sharded(seq_mesh))
        ks = jax.device_put(k, seq_sharded(seq_mesh))
        vs = jax.device_put(v, seq_sharded(seq_mesh))
        out = ring_attention(qs, ks, vs, seq_mesh, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), atol=2e-5
        )

    def test_output_stays_seq_sharded(self, seq_mesh):
        q, k, v = _qkv()
        qs = jax.device_put(q, seq_sharded(seq_mesh))
        out = ring_attention(qs, qs, qs, seq_mesh)

        # jax versions differ on whether trailing None axes are kept in a
        # result spec; compare specs normalized to the same rank.
        def _norm(spec):
            axes = list(spec)
            while axes and axes[-1] is None:
                axes.pop()
            return tuple(axes)

        assert _norm(out.sharding.spec) == _norm(seq_sharded(seq_mesh).spec)

    def test_jit_compatible(self, seq_mesh):
        q, k, v = _qkv(S=32)
        fn = jax.jit(lambda a, b, c: ring_attention(a, b, c, seq_mesh))
        out = fn(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(reference_attention(q, k, v)),
            atol=2e-5,
        )

    def test_single_block_fully_masked_rows(self, seq_mesh):
        # Causal masking with S == shards: first device's rows attend only
        # to themselves; no NaNs from the -inf accumulator path.
        q, k, v = _qkv(S=8)
        out = ring_attention(
            jax.device_put(q, seq_sharded(seq_mesh)),
            jax.device_put(k, seq_sharded(seq_mesh)),
            jax.device_put(v, seq_sharded(seq_mesh)),
            seq_mesh,
        )
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(reference_attention(q, k, v)),
            atol=2e-5,
        )


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, seq_mesh, causal):
        q, k, v = _qkv()
        expected = reference_attention(q, k, v, causal=causal)
        out = ulysses_attention(
            jax.device_put(q, seq_sharded(seq_mesh)),
            jax.device_put(k, seq_sharded(seq_mesh)),
            jax.device_put(v, seq_sharded(seq_mesh)),
            seq_mesh, causal=causal,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), atol=2e-5
        )

    def test_head_divisibility_enforced(self, seq_mesh):
        q, k, v = _qkv(H=6)
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q, k, v, seq_mesh)


def test_ring_matches_ulysses(seq_mesh):
    q, k, v = _qkv(seed=3)
    a = ring_attention(q, k, v, seq_mesh)
    b = ulysses_attention(q, k, v, seq_mesh)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


class TestMultihost:
    """Single-process degradations of the multi-host helpers (a real
    multi-process run needs multiple hosts; the sharding math is
    process-count-parameterized so it is testable here)."""

    def test_gather_identity_single_process(self):
        from lir_tpu.parallel import gather_rows

        rows = np.arange(12, dtype=np.float32).reshape(4, 3)
        np.testing.assert_array_equal(gather_rows(rows), rows)

    def test_host_shard_partition(self):
        from lir_tpu.parallel import host_shard

        items = list(range(10))
        shards = [host_shard(items, i, 3) for i in range(3)]
        assert shards[0] == [0, 3, 6, 9]
        assert shards[1] == [1, 4, 7]
        assert shards[2] == [2, 5, 8]
        # Partition: disjoint and complete.
        merged = sorted(x for s in shards for x in s)
        assert merged == items

    def test_barrier_noop_single_process(self):
        from lir_tpu.parallel import barrier

        barrier("test-point")  # must not raise


def test_ring_attention_gqa_repeat(seq_mesh):
    """K/V with fewer heads than q are repeated internally (GQA)."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(2, 64, 8, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
    k_full = jnp.repeat(k, 4, axis=2)
    v_full = jnp.repeat(v, 4, axis=2)
    expected = reference_attention(q, k_full, v_full, causal=True)
    out = ring_attention(q, k, v, seq_mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# Sequence-parallel MODEL forward (parallel/seq_forward): the full decoder
# with attention routed through the ring / Ulysses kernels must match the
# dense single-mesh forward exactly — including left-pad masks and ALiBi.
# ---------------------------------------------------------------------------

from lir_tpu.models import decoder
from lir_tpu.models.registry import ModelConfig
from lir_tpu.parallel import (
    forward_seq_parallel,
    prefill_seq_parallel,
    seq_batch_sharding,
)


def _llama_tiny(**kw):
    base = dict(name="seqfwd-llama", vocab_size=128, hidden_size=32,
                n_layers=2, n_heads=8, intermediate_size=64, max_seq_len=128)
    base.update(kw)
    return ModelConfig(**base)


def _tokens(cfg, B=2, S=32, seed=7, left_pad=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(3, cfg.vocab_size, (B, S))
    mask = np.ones((B, S), np.int32)
    if left_pad:
        for b in range(B):
            n = (b * left_pad) % S
            toks[b, :n] = 0
            mask[b, :n] = 0
    return jnp.asarray(toks, jnp.int32), jnp.asarray(mask)


class TestSeqParallelForward:
    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_matches_dense_forward(self, seq_mesh, impl):
        cfg = _llama_tiny()
        params = decoder.init_params(cfg, jax.random.PRNGKey(0))
        toks, mask = _tokens(cfg)
        expected = decoder.forward(params, cfg, toks, mask)
        sb = seq_batch_sharding(seq_mesh)
        out = forward_seq_parallel(
            params, cfg, jax.device_put(toks, sb), jax.device_put(mask, sb),
            mesh=seq_mesh, impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   atol=3e-4, rtol=1e-4)

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_left_padded_parity(self, seq_mesh, impl):
        """Ragged left-padded batches: mask-aware positions must propagate
        into the sharded kernels exactly like _causal_bias."""
        cfg = _llama_tiny()
        params = decoder.init_params(cfg, jax.random.PRNGKey(1))
        toks, mask = _tokens(cfg, B=4, left_pad=5)
        expected = decoder.forward(params, cfg, toks, mask)
        out = forward_seq_parallel(params, cfg, toks, mask,
                                   mesh=seq_mesh, impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   atol=3e-4, rtol=1e-4)

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_alibi_family(self, seq_mesh, impl):
        """bloom's ALiBi bias is applied inside the seq-parallel kernels."""
        cfg = _llama_tiny(name="seqfwd-bloom", pos_embedding="alibi",
                          norm="layernorm", embedding_norm=True,
                          gated_mlp=False, activation="gelu",
                          qkv_bias=True, attn_out_bias=True, mlp_bias=True)
        params = decoder.init_params(cfg, jax.random.PRNGKey(2))
        toks, mask = _tokens(cfg, left_pad=3)
        expected = decoder.forward(params, cfg, toks, mask)
        out = forward_seq_parallel(params, cfg, toks, mask,
                                   mesh=seq_mesh, impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   atol=3e-4, rtol=1e-4)

    def test_gqa_family(self, seq_mesh):
        cfg = _llama_tiny(name="seqfwd-gqa", n_kv_heads=2)
        params = decoder.init_params(cfg, jax.random.PRNGKey(3))
        toks, mask = _tokens(cfg)
        expected = decoder.forward(params, cfg, toks, mask)
        out = forward_seq_parallel(params, cfg, toks, mask, mesh=seq_mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   atol=3e-4, rtol=1e-4)

    def test_needs_mesh(self):
        cfg = _llama_tiny()
        params = decoder.init_params(cfg, jax.random.PRNGKey(0))
        toks, mask = _tokens(cfg)
        with pytest.raises(ValueError, match="mesh"):
            forward_seq_parallel(params, cfg, toks, mask)


class TestSeqParallelPrefill:
    def test_matches_dense_prefill_and_decodes(self, seq_mesh):
        """Seq-sharded prefill fills the SAME cache as dense prefill, and an
        ordinary dense decode step continues from it identically — the
        long-prompt recipe (shard the O(S^2) phase, decode cheap)."""
        cfg = _llama_tiny()
        params = decoder.init_params(cfg, jax.random.PRNGKey(4))
        toks, mask = _tokens(cfg, B=2, S=32, left_pad=4)
        max_len = 40

        el, (eck, ecv), epos = decoder.prefill(params, cfg, toks, mask, max_len)
        ol, (ock, ocv), opos = prefill_seq_parallel(
            params, cfg, toks, mask, max_len, mesh=seq_mesh)

        np.testing.assert_allclose(np.asarray(ol), np.asarray(el),
                                   atol=3e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(ock), np.asarray(eck),
                                   atol=3e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(ocv), np.asarray(ecv),
                                   atol=3e-4, rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(opos), np.asarray(epos))

        # One dense decode step from each cache must agree.
        B, S = toks.shape
        tok_next = jnp.argmax(el, axis=-1).astype(jnp.int32)
        full_mask = jnp.concatenate(
            [mask, jnp.zeros((B, max_len - S), mask.dtype)], axis=1)
        full_mask = full_mask.at[:, S].set(1)
        args = (tok_next, epos, jnp.int32(S), full_mask)
        dl, _ = decoder.decode_step(params, cfg, (eck, ecv), *args)
        sl, _ = decoder.decode_step(params, cfg, (ock, ocv), *args)
        np.testing.assert_allclose(np.asarray(sl), np.asarray(dl),
                                   atol=3e-4, rtol=1e-4)


def test_multihost_initialize_single_process_degrade():
    """multihost.initialize(): no cluster -> False, never raises (pod
    bring-up is opt-in; single-host jobs proceed unchanged); required=True
    escalates the same condition to a hard error (the CLI's --multihost).

    Runs in a subprocess with cluster env vars scrubbed: jax's cluster
    auto-detection must see a clean environment (a TPU host exports
    TPU_WORKER_HOSTNAMES), and a successful bring-up would
    leave a distributed service running for the rest of the session."""
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import os
        for v in ('SLURM_JOB_ID', 'OMPI_COMM_WORLD_SIZE',
                  'COORDINATOR_ADDRESS', 'TPU_WORKER_HOSTNAMES',
                  'CLOUD_TPU_TASK_ID', 'TPU_SKIP_MDS_QUERY'):
            os.environ.pop(v, None)
        os.environ['JAX_PLATFORMS'] = 'cpu'
        import jax
        jax.config.update('jax_platforms', 'cpu')
        from lir_tpu.parallel import multihost
        assert multihost.initialize() is False
        assert not multihost.is_multiprocess()
        try:
            multihost.initialize(required=True)
        except RuntimeError as e:
            assert 'multihost' in str(e)
        else:
            raise AssertionError('required=True did not escalate')
        print('DEGRADE-OK')
    """)
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=300,
                          cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "DEGRADE-OK" in proc.stdout


def test_engine_seq_parallel_prefill_matches_plain(seq_mesh):
    """ScoringEngine(seq_mesh=...): the engine's production scoring path
    (fused decode) prefills seq-sharded and must score identically to the
    plain engine — the long-context path wired end to end (CLI --mesh
    1x1x8 -> factory -> engine -> generate -> decoder prefill)."""
    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.engine.runner import ScoringEngine
    from lir_tpu.models import decoder
    from lir_tpu.models.registry import ModelConfig

    cfg = ModelConfig(name="eng-sp", vocab_size=FakeTokenizer.VOCAB,
                      hidden_size=32, n_layers=2, n_heads=8,
                      intermediate_size=64, max_seq_len=128)
    params = decoder.init_params(cfg, jax.random.PRNGKey(0))
    rt = RuntimeConfig(batch_size=4, max_new_tokens=5, max_seq_len=128)
    prompts = ["Is a tomato a vegetable ?",
               "Is a whale considered a fish in law ?"]

    plain = ScoringEngine(params, cfg, FakeTokenizer(), rt)
    sp = ScoringEngine(params, cfg, FakeTokenizer(), rt, seq_mesh=seq_mesh)
    assert sp._prefill_fn is not None

    r_plain = plain.score_prompts(prompts)
    r_sp = sp.score_prompts(prompts)
    for a, b in zip(r_plain, r_sp):
        np.testing.assert_allclose(b.relative_prob, a.relative_prob,
                                   atol=1e-4)
        assert b.completion == a.completion


def test_multihost_initialize_already_up_is_success(monkeypatch):
    """A launcher that already brought jax.distributed up must not turn
    --multihost into a hard error: initialize(required=True) probes
    process_count() and returns True (ADVICE r2 #2)."""
    import jax

    from lir_tpu.parallel import multihost

    def _raise(*a, **k):
        raise RuntimeError("jax.distributed.initialize was already called")

    monkeypatch.setattr(jax.distributed, "initialize", _raise)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    assert multihost.initialize(required=True) is True
    assert multihost.initialize() is True


def test_engine_shared_prefix_on_seq_mesh(seq_mesh):
    """The SWEEP's shared-prefix scorer composes with the seq-parallel
    prefill: the shared prefix prefills seq-sharded (ring attention), the
    suffix extensions and fused scans run dense, and the readouts equal
    the plain engine's."""
    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.engine.runner import ScoringEngine
    from lir_tpu.models import decoder
    from lir_tpu.models.registry import ModelConfig

    cfg = ModelConfig(name="eng-sp-shared", vocab_size=FakeTokenizer.VOCAB,
                      hidden_size=32, n_layers=2, n_heads=8,
                      intermediate_size=64, max_seq_len=128)
    params = decoder.init_params(cfg, jax.random.PRNGKey(1))
    rt = RuntimeConfig(batch_size=2, max_new_tokens=5, max_seq_len=128)
    mains = ["Is a levee failure considered a flood event under the policy ?",
             "Would a burst dam count as a flood for coverage purposes ?"]
    bins = [m + " Answer Yes or No ." for m in mains]
    confs = [m + " Give a number 0 to 100 ." for m in mains]
    t1 = np.full((2,), FakeTokenizer.YES, np.int32)
    t2 = np.full((2,), FakeTokenizer.NO, np.int32)

    plain = ScoringEngine(params, cfg, FakeTokenizer(), rt)
    sp = ScoringEngine(params, cfg, FakeTokenizer(), rt, seq_mesh=seq_mesh)
    pa, pb = plain.decode_fused_shared(bins, confs, t1, t2,
                                       new_tokens=3, conf_tokens=4)
    sa, sb = sp.decode_fused_shared(bins, confs, t1, t2,
                                    new_tokens=3, conf_tokens=4)
    np.testing.assert_array_equal(np.asarray(sa.generated),
                                  np.asarray(pa.generated))
    np.testing.assert_allclose(np.asarray(sa.p_yes), np.asarray(pa.p_yes),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(sb.weighted_confidence),
                               np.asarray(pb.weighted_confidence), atol=1e-3)
