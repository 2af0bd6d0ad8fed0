"""Factory round-trip: HF checkpoint directory -> ScoringEngine, logits
matching the torch reference model."""

import numpy as np
import pytest
import torch

from lir_tpu.config import RuntimeConfig
from lir_tpu.models.factory import engine_factory, is_encoder_decoder, load_engine


@pytest.fixture(scope="module")
def tiny_checkpoint(tmp_path_factory):
    import transformers as tf

    torch.manual_seed(0)
    model = tf.LlamaForCausalLM(tf.LlamaConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, intermediate_size=128,
        max_position_embeddings=256, tie_word_embeddings=False)).eval()
    path = tmp_path_factory.mktemp("ckpt") / "org__tiny-llama"
    path.mkdir()
    model.save_pretrained(path, safe_serialization=True)
    # No tokenizer files on purpose (zero-egress env): tokenizer-dependent
    # tests monkeypatch AutoTokenizer with the fake backend tokenizer.
    return path, model


def test_encdec_routing_rule():
    assert is_encoder_decoder("google/flan-t5-base")
    assert is_encoder_decoder("bigscience/T0_3B")
    assert is_encoder_decoder("allenai/tk-instruct-3b-def")
    assert not is_encoder_decoder("meta-llama/Llama-2-7b-hf")
    assert not is_encoder_decoder("tiiuae/falcon-7b")


@pytest.mark.slow
def test_state_dict_lazy_loading(tiny_checkpoint):
    from lir_tpu.models.factory import load_state_dict

    path, model = tiny_checkpoint
    state = load_state_dict(path)
    ref = model.state_dict()
    assert set(state.keys()) == set(ref.keys())
    key = "model.embed_tokens.weight"
    np.testing.assert_allclose(
        np.asarray(state[key]), ref[key].numpy(), atol=0
    )


@pytest.mark.slow
def test_load_engine_forward_parity(tiny_checkpoint, monkeypatch):
    """Engine built from the on-disk checkpoint produces the same logits as
    the torch model (the stage-3 validation gate, SURVEY.md §7 build order)."""
    import jax.numpy as jnp
    import transformers as tf

    path, torch_model = tiny_checkpoint

    # Bypass AutoTokenizer (no tokenizer files in the synthetic checkpoint).
    from lir_tpu.backends.fake import FakeTokenizer

    monkeypatch.setattr(
        tf.AutoTokenizer, "from_pretrained",
        classmethod(lambda cls, *a, **k: FakeTokenizer()),
    )
    engine = load_engine(path, RuntimeConfig(batch_size=4, max_new_tokens=4))
    assert not engine.encoder_decoder

    ids = np.array([[5, 9, 12, 40, 7]], dtype=np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.from_numpy(ids)).logits.numpy()
    from lir_tpu.models import decoder

    ours = np.asarray(
        decoder.forward(engine.params, engine.cfg, jnp.asarray(ids, jnp.int32))
    )
    np.testing.assert_allclose(ours, ref_logits, atol=2e-3)


def test_replica_devices_gives_each_replica_its_own():
    """``serve --replicas N``: replica i sits on device slice i (one
    factory used to land every replica on device 0)."""
    import jax

    from lir_tpu.parallel.sharding import replica_devices

    devs = jax.devices()
    assert len(devs) >= 8                      # conftest's virtual mesh
    assert [replica_devices(i, 1) for i in range(4)] == [
        [devs[0]], [devs[1]], [devs[2]], [devs[3]]]
    assert replica_devices(1, 4) == devs[4:8]
    assert replica_devices(2, 4) == devs[0:4]          # wraps
    assert replica_devices(5, 1, devs[:1]) == [devs[0]]  # one-device host
    with pytest.raises(ValueError, match="needs 4 devices"):
        replica_devices(0, 4, devs[:2])


def test_engine_factory_pins_replica_to_its_device(tiny_checkpoint,
                                                   monkeypatch):
    import jax
    import transformers as tf

    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.parallel.sharding import replica_devices

    monkeypatch.setattr(
        tf.AutoTokenizer, "from_pretrained",
        classmethod(lambda cls, *a, **k: FakeTokenizer()),
    )
    path, _ = tiny_checkpoint
    factory = engine_factory(path.parent, RuntimeConfig(batch_size=2))
    homes = []
    for i in range(3):
        engine = factory("org/tiny-llama", devices=replica_devices(i, 1))
        homes.append({d.id for leaf in jax.tree.leaves(engine.params)
                      for d in leaf.devices()})
    assert homes == [{jax.devices()[i].id} for i in range(3)]
    # ... and a dispatch follows its params there.
    out = engine.score_prompts(["a b c", "d e"])
    assert len(out) == 2


@pytest.mark.slow
def test_engine_factory_resolution(tiny_checkpoint, monkeypatch):
    import transformers as tf

    from lir_tpu.backends.fake import FakeTokenizer

    monkeypatch.setattr(
        tf.AutoTokenizer, "from_pretrained",
        classmethod(lambda cls, *a, **k: FakeTokenizer()),
    )
    path, _ = tiny_checkpoint
    factory = engine_factory(path.parent)
    engine = factory("org/tiny-llama")  # resolves org__tiny-llama
    assert engine.cfg.n_layers == 2
    with pytest.raises(FileNotFoundError, match="no local checkpoint"):
        factory("org/absent-model")


@pytest.mark.slow
def test_params_cache_roundtrip(tiny_checkpoint, tmp_path, monkeypatch):
    """Convert-once semantics: second load restores from the orbax cache
    without touching the safetensors state dict."""
    import transformers as tf

    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.models import cache as cache_mod
    from lir_tpu.models import factory as factory_mod

    monkeypatch.setattr(
        tf.AutoTokenizer, "from_pretrained",
        classmethod(lambda cls, *a, **k: FakeTokenizer()),
    )
    path, _ = tiny_checkpoint
    cache_root = tmp_path / "param_cache"

    e1 = load_engine(path, cache_root=cache_root)
    assert cache_mod.has_cached(cache_root, path.name)

    # Break the state-dict path: a cache hit must never call it.
    monkeypatch.setattr(
        factory_mod, "load_state_dict",
        lambda _p: (_ for _ in ()).throw(AssertionError("cache missed")),
    )
    e2 = load_engine(path, cache_root=cache_root)
    assert e2.cfg == e1.cfg
    np.testing.assert_allclose(
        np.asarray(e2.params["tok_embed"]), np.asarray(e1.params["tok_embed"])
    )


@pytest.fixture(scope="module")
def tiny_t5_checkpoint(tmp_path_factory):
    import transformers as tf

    torch.manual_seed(2)
    # vocab >= FakeTokenizer.VOCAB: the fake tokenizer hashes words into
    # ids up to 999; a smaller embedding would clamp them to garbage rows
    # and score NaN.
    model = tf.T5ForConditionalGeneration(tf.T5Config(
        vocab_size=1024, d_model=64, d_kv=16, d_ff=128, num_layers=2,
        num_heads=4, feed_forward_proj="gated-gelu",
        tie_word_embeddings=False, decoder_start_token_id=0)).eval()
    path = tmp_path_factory.mktemp("ckpt_t5") / "org__tiny-t5"
    path.mkdir()
    model.save_pretrained(path, safe_serialization=True)
    return path, model


@pytest.mark.slow
def test_load_engine_t5_mesh_shards_params(tiny_t5_checkpoint, monkeypatch):
    """--mesh is honored for encoder-decoder checkpoints: params shard with
    the enc-dec specs instead of being silently ignored (VERDICT r2 missing
    #4); --kv-cache-int8 warns that it has no effect on the seq2seq path
    (ADVICE r2 #4); a seq>1 mesh raises."""
    import logging

    import transformers as tf

    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import MeshConfig

    path, _ = tiny_t5_checkpoint
    monkeypatch.setattr(
        tf.AutoTokenizer, "from_pretrained",
        classmethod(lambda cls, *a, **k: FakeTokenizer()),
    )
    with pytest.raises(ValueError, match="seq=2 > 1 is not supported"):
        load_engine(path, RuntimeConfig(batch_size=2),
                    mesh_cfg=MeshConfig(data=2, model=2, seq=2))

    import lir_tpu.models.factory as factory_mod
    with pytest.MonkeyPatch.context() as mp:
        records = []
        mp.setattr(factory_mod.log, "warning",
                   lambda msg, *a: records.append(msg % a if a else msg))
        engine = load_engine(path, RuntimeConfig(batch_size=2),
                             mesh_cfg=MeshConfig(data=2, model=4),
                             kv_cache_int8=True)
        assert any("kv-cache-int8" in r and "no effect" in r for r in records)
    assert engine.encoder_decoder
    wq = engine.params["encoder"]["wq"]
    assert wq.sharding.shard_shape(wq.shape)[-1] == wq.shape[-1] // 4
    # Sharded engine still scores (full seq2seq decode on the mesh).
    rows = engine.score_prompts(["Is a tomato a vegetable ?"] * 2)
    assert all(np.isfinite(r.yes_prob) for r in rows)
