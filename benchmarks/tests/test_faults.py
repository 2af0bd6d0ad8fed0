"""A whole run after the look for a chip, on the CPU at a tiny size, with
the timed path sound and then broken underneath: ``correct`` has to come
out true, then false once for each fault a served cell can have."""

import pytest

import tiny


def test_sound_run_is_correct():
    out = tiny.drive("mistral-7b", "sweep-trunk512")
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "compared"


def test_parallel_block_run_is_correct():
    out = tiny.drive("falcon-7b", "sweep-trunk512")
    assert out["correct"] is True, out["compared"]


def _broken_logits(monkeypatch):
    """An answer altered where it is produced: every logit moved one
    token along."""
    import jax.numpy as jnp

    from lir_tpu.models import decoder

    real = decoder._unembed
    monkeypatch.setattr(decoder, "_unembed",
                        lambda p, c, x: jnp.roll(real(p, c, x), 1, axis=-1))


def _broken_attention(monkeypatch):
    """A kernel that computes the wrong thing: cached attention halved."""
    from lir_tpu.models import decoder

    for name in ("_attention_cached", "_attention_cached_flash",
                 "_attention_cached_flash_mq", "_attention_cascade"):
        real = getattr(decoder, name)
        monkeypatch.setattr(
            decoder, name,
            (lambda f: lambda *a, **k: 0.5 * f(*a, **k))(real))


def _dropped_answer(monkeypatch):
    """An answer that never comes."""
    from lir_tpu.engine import sweep

    real = sweep.run_perturbation_sweep
    monkeypatch.setattr(sweep, "run_perturbation_sweep",
                        lambda *a, **k: real(*a, **k)[:-1])


@pytest.mark.parametrize("fault", [_broken_logits, _broken_attention,
                                   _dropped_answer])
def test_fault_is_not_correct(monkeypatch, fault):
    import jax

    from lir_tpu.engine import compile_plan

    fault(monkeypatch)
    jax.clear_caches()
    compile_plan.exec_cache_clear()
    try:
        out = tiny.drive("mistral-7b", "sweep-trunk512", seed=6)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
        compile_plan.exec_cache_clear()
    assert out["correct"] is False, out["compared"]
