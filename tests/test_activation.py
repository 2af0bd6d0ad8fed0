"""``decoder._act``: the exact GELU at every bfloat16 input, against
float64; the other activations bitwise ``jax.nn``'s, so the programs of
the presets that use them are pinned unchanged."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy import special

from lir_tpu.models.decoder import _act

# XLA flushes subnormals to zero (CPU and TPU alike): below the smallest
# normal the grid a result can land on is {0, 2**-126}, whatever the form.
FLUSH = 2.0 ** -126


def _every_finite_bfloat16() -> jax.Array:
    x = jnp.arange(1 << 16, dtype=jnp.uint16).view(jnp.bfloat16)
    return x[jnp.isfinite(x.astype(jnp.float32))]


def _f64(x: jax.Array) -> np.ndarray:
    return np.asarray(x.astype(jnp.float32)).astype(np.float64)


def _ulp_bf16(v: np.ndarray) -> np.ndarray:
    """Spacing of the bfloat16 grid (8 significant bits) at ``v``."""
    a = np.abs(v)
    exponent = np.floor(np.log2(np.where(a > 0, a, FLUSH)))
    return np.maximum(2.0 ** (exponent - 7), FLUSH)


def _exact_gelu(x: np.ndarray) -> np.ndarray:
    return 0.5 * x * special.erfc(-x / np.sqrt(2.0))     # float64


@pytest.fixture(scope="module")
def gelu_bf16():
    x = _every_finite_bfloat16()
    assert x.shape == (65_280,)
    return _f64(x), _f64(jax.jit(lambda v: _act(v, "gelu"))(x))


def test_gelu_is_exact_at_every_bfloat16_input(gelu_bf16):
    """Absolute error, not ulp distance (meaningless near zero): one
    bfloat16 ulp of the exact value, or 2**-23 * |x| where that is larger
    — below x ~ -5.5 the float32 ``1 + erf`` is a few float32 ulps of 1
    where the exact value is smaller still, harmless at activation scale."""
    x, got = gelu_bf16
    assert np.isfinite(got).all()
    exact = _exact_gelu(x)
    bound = np.maximum(_ulp_bf16(exact), 2.0 ** -23 * np.abs(x))
    err = np.abs(got - exact)
    worst = np.argmax(err / bound)
    assert (err <= bound).all(), (x[worst], got[worst], exact[worst])
    assert err[np.abs(x) < 8].max() <= 2.0 ** -7     # half an ulp at 2..4


def test_gelu_at_zero_and_its_odd_part(gelu_bf16):
    x, got = gelu_bf16
    assert (got[x == 0] == 0).all() and (x == 0).sum() == 2
    # gelu(x) - gelu(-x) == x: the finite inputs are symmetric, so the
    # value at -x is the value at the mirrored index.
    order = np.argsort(x, kind="stable")
    xs, gs = x[order], got[order]
    assert np.array_equal(xs, -xs[::-1])
    near = np.abs(xs) <= 8
    odd = gs - gs[::-1]
    assert (np.abs(odd - xs)[near] <= 2 * _ulp_bf16(xs)[near]).all()


def test_gelu_in_float32_keeps_float32_accuracy():
    """A float32 model (the parity tests, the benchmark's reference
    comparison) gets a float32 answer: no bfloat16 step inside."""
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.normal(0, 2, 50_000), np.linspace(-12, 12, 4801),
                        [0.0, -0.0, 1e-30, -1e-30, 50.0, -50.0]]
                       ).astype(np.float32)
    got = jax.jit(lambda v: _act(v, "gelu"))(jnp.asarray(x))
    assert got.dtype == jnp.float32
    x64 = x.astype(np.float64)
    err = np.abs(np.asarray(got).astype(np.float64) - _exact_gelu(x64))
    assert (err <= 2.0 ** -22 * np.abs(x64) + FLUSH).all()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("kind,fn", [
    ("silu", jax.nn.silu),
    ("gelu_new", lambda v: jax.nn.gelu(v, approximate=True)),
    ("relu", jax.nn.relu)], ids=["silu", "gelu_new", "relu"])
def test_other_activations_are_jax_nn_bitwise(kind, fn, dtype):
    x = _every_finite_bfloat16().astype(dtype)
    got = jax.jit(lambda v: _act(v, kind))(x)
    want = jax.jit(fn)(x)
    assert got.dtype == want.dtype == dtype
    width = jnp.uint16 if dtype == jnp.bfloat16 else jnp.uint32
    assert jnp.array_equal(got.view(width), want.view(width))
