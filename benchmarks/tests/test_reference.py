"""The plain reference against the program's own forward, at a tiny width,
for both block types; and the controls, which must read far off."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import builders
from references import decoder as ref

SEED = 2**31 + 77


def _tiny(name):
    spec, _ = ref.load(name)
    if spec.parallel:
        return dataclasses.replace(spec, vocab=512, d=71 * 4, layers=3,
                                   head_dim=4, ffn=128)
    return dataclasses.replace(spec, vocab=512, d=64, layers=3, heads=4,
                               kv_heads=2, head_dim=16, ffn=128)


@pytest.fixture(scope="module", params=["mistral-7b", "falcon-7b"])
def case(request):
    from lir_tpu.models import decoder as prog

    tiny = _tiny(request.param)
    cfg = builders.program_config(tiny, check=False)
    params = builders.build_params(tiny, ref, SEED)
    toks = np.random.default_rng(0).integers(3, 512, (2, 24))
    pos = np.tile(np.arange(24), (2, 1))
    want = np.asarray(ref.logits_at(tiny, SEED, toks, pos))
    return tiny, cfg, params, toks, pos, want, prog


def test_full_size_files_match_the_programs_presets():
    for name in ("mistral-7b", "falcon-7b"):
        spec, _ = ref.load(name)
        builders.program_config(spec)            # raises on any difference


def test_reference_equals_program_in_float32(case):
    tiny, cfg, params, toks, pos, want, prog = case
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32)
                       if a.dtype == jnp.bfloat16 else a, params)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(prog.forward(p32, cfg, jnp.asarray(toks)))
    assert np.abs(got - want).max() < 1e-4
    assert 1.5 < want.std() < 2.5               # logits are not flat


def test_controls_read_further_off_than_the_served_precision(case):
    tiny, cfg, params, toks, pos, want, prog = case
    served = np.abs(np.asarray(prog.forward(params, cfg, jnp.asarray(toks)))
                    - want).max()
    for precision, factor in (("int8", 1.5), ("fp8", 4.0)):
        low = np.asarray(ref.logits_at(tiny, SEED, toks, pos,
                                       precision=precision))
        assert np.abs(low - want).max() > factor * served, precision
