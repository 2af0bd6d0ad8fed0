"""The layer loop with a cache carries the stacked cache and writes into
it where it lies (models/decoder._scan_blocks). What the change may not
move is the arithmetic: ``extend`` and ``decode_step`` on the tiny presets
give, bit for bit, the logits of the commit before it (PR 26's tree, the
per-layer ``xs`` / ``ys`` form), pinned here as constants, on the dense
routes and with the Pallas kernels interpreted.

To read the pins off a tree: ``python tests/test_stacked_cache.py`` from
its root prints the table.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lir_tpu.models import decoder, registry

B, S, S2, T, TRUNK = 8, 12, 4, 24, 4
ROWS, COLS = (0, 5), (3, 17, 101)

# (family, int8 K/V, kernels interpreted)
CASES = [("mistral", False, False), ("mistral", False, True),
         ("mistral", True, False), ("falcon", False, True),
         ("bloom", False, False), ("bloom", False, True),
         ("gptneox", False, False),
         ("falcon-h1", False, False), ("falcon-h1", False, True)]


def _drive(family, int8, kernels):
    """Prefill a right-padded prefix, extend a right-padded suffix over
    it, decode two tokens (the shared sweep path's order). Returns the
    logits of the extension and of the second step at ROWS x COLS, and
    the final cache."""
    cfg = registry.tiny(family)
    if int8:
        cfg = dataclasses.replace(cfg, kv_cache_int8=True)
    kp, kt, ks = jax.random.split(jax.random.PRNGKey(27), 3)
    params = decoder.init_params(cfg, kp, jnp.float32)
    lens = jnp.asarray([12, 9, 12, 7, 11, 12, 5, 10], jnp.int32)
    prefix = jax.random.randint(kt, (B, S), 1, cfg.vocab_size, jnp.int32)
    pmask = (jnp.arange(S)[None, :] < lens[:, None]).astype(jnp.int32)
    # The first TRUNK tokens are one row's, as a shared-trunk dispatch has
    # them (the trunk-aware kernels read them from the first batch block).
    prefix = prefix.at[:, :TRUNK].set(prefix[0, :TRUNK])
    sfx = jax.random.randint(ks, (B, S2), 1, cfg.vocab_size, jnp.int32)
    slens = jnp.asarray([4, 3, 4, 2, 4, 1, 4, 3], jnp.int32)
    smask = (jnp.arange(S2)[None, :] < slens[:, None]).astype(jnp.int32)

    was = (decoder.FUSED_DECODE_INTERPRET_ON_CPU,
           decoder.SSM_INTERPRET_ON_CPU)
    decoder.FUSED_DECODE_INTERPRET_ON_CPU = kernels
    decoder.SSM_INTERPRET_ON_CPU = kernels
    try:
        @jax.jit
        def run(params, prefix, pmask, sfx, smask):
            _, cache, _ = decoder.prefill(params, cfg, prefix, pmask, T)
            cm = jnp.concatenate(
                [pmask, smask, jnp.zeros((B, T - S - S2), jnp.int32)], axis=1)
            lg_e, cache, pos = decoder.extend(params, cfg, cache, sfx, smask,
                                              cm, S)
            lg = lg_e
            for t in range(2):
                tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                cm = cm.at[:, S + S2 + t].set(1)
                lg, cache = decoder.decode_step(params, cfg, cache, tok,
                                                pos + t, S + S2 + t, cm,
                                                trunk_len=TRUNK)
            return lg_e, lg, cache

        lg_e, lg_d, cache = run(params, prefix, pmask, sfx, smask)
    finally:
        (decoder.FUSED_DECODE_INTERPRET_ON_CPU,
         decoder.SSM_INTERPRET_ON_CPU) = was
    pick = lambda a: np.asarray(a)[np.ix_(ROWS, COLS)].ravel()  # noqa: E731
    return pick(lg_e), pick(lg_d), cache


def _bits(logits):
    return np.asarray(logits, np.float32).view(np.uint32).tolist()


# Read off commit 2e99605 (PR 26): the float32 logits' bit patterns.
PINS = {
    ('mistral', False, False): (
        [0xbdf20845, 0xbd5ca86c, 0xbdb57caa,
         0x3da69d94, 0xbe3555b2, 0xbd7cf2de],
        [0xbdfc34b8, 0xbe117544, 0x3deedc17,
         0xbe8343df, 0xbe76c6e1, 0xbdbb6363]),
    ('mistral', False, True): (
        [0xbdf20845, 0xbd5ca86c, 0xbdb57caa,
         0x3da69d94, 0xbe3555b2, 0xbd7cf2de],
        [0xbdfc34b8, 0xbe117543, 0x3deedc18,
         0xbe8343df, 0xbe76c6e1, 0xbdbb6365]),
    ('mistral', True, False): (
        [0xbdf27678, 0xbd5caabb, 0xbdb72cea,
         0x3da56f00, 0xbe350cf3, 0xbd7cfc35],
        [0xbdfc227b, 0xbe116fa5, 0x3dede629,
         0xbe83bb51, 0xbe76a2dd, 0xbdba9f2c]),
    # The two "gelu" presets were read again in PR 29: the exact GELU goes
    # through 1 + erf where it went through erfc (decoder._act), which moves
    # float32 logits by a few ulps (0x3e5a9805 -> 04); the others did not move.
    ('falcon', False, True): (
        [0x3e5a9804, 0xbe2eeae0, 0x3dff12c4,
         0x3e634fe6, 0xbe837914, 0xbe3790d7],
        [0x3e631016, 0xbe445c6c, 0x3e06b03a,
         0x3e8141ec, 0xbeba28f7, 0xbe26b555]),
    ('bloom', False, False): (
        [0x3e080487, 0x3d288256, 0x3e160e1e,
         0x3d01d55d, 0xbe7bf5f4, 0xbd0c5ca6],
        [0x3e0718ac, 0x3d34a812, 0x3e15ac05,
         0x3cf49278, 0xbe78ea57, 0xbd122e78]),
    ('bloom', False, True): (
        [0x3e080487, 0x3d288256, 0x3e160e1e,
         0x3d01d55d, 0xbe7bf5f4, 0xbd0c5ca6],
        [0x3e0718ab, 0x3d34a80e, 0x3e15ac05,
         0x3cf49278, 0xbe78ea58, 0xbd122e78]),
    ('gptneox', False, False): (
        [0x3de401b7, 0x3c34096b, 0x3c4874e6,
         0xbed112e4, 0xbdc6379c, 0xbda10cc8],
        [0x3e6cf0e7, 0xbe8d3654, 0xbe77bf33,
         0x3cabe7ec, 0xbe90324f, 0xbd9d4aec]),
    ('falcon-h1', False, False): (
        [0x3bd04874, 0xbdc31584, 0x3d6f26b7,
         0xbdd8d73f, 0x3d0c5518, 0xbd59c413],
        [0x3ddf6f65, 0xbcd66cdb, 0xbde9e91b,
         0xbd39d086, 0xbe0bfc8b, 0xbd1a19ad]),
    ('falcon-h1', False, True): (
        [0x3bd0486f, 0xbdc31584, 0x3d6f26b5,
         0xbdd8d73e, 0x3d0c5518, 0xbd59c413],
        [0x3ddf6f62, 0xbcd66cd9, 0xbde9e919,
         0xbd39d082, 0xbe0bfc89, 0xbd1a19ae]),
}


@pytest.mark.parametrize("family,int8,kernels", CASES)
def test_extend_and_decode_step_are_the_parents_bit_for_bit(family, int8,
                                                            kernels):
    lg_e, lg_d, _ = _drive(family, int8, kernels)
    want_e, want_d = PINS[(family, int8, kernels)]
    assert _bits(lg_e) == want_e
    assert _bits(lg_d) == want_d


@pytest.mark.parametrize("family", ["mistral", "falcon-h1"])
def test_kernels_and_dense_routes_fill_the_same_cache(family):
    """The stacked cache the loop hands back is the same tree whichever
    route attends: same leaves, shapes and dtypes (the SSM state float32),
    K/V equal bit for bit (the slot writes do not depend on the route)."""
    _, _, dense = _drive(family, False, False)
    _, _, fused = _drive(family, False, True)
    assert jax.tree.structure(dense) == jax.tree.structure(fused)
    for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(fused)):
        assert a.shape == b.shape and a.dtype == b.dtype
    if family == "falcon-h1":
        assert dense[2].dtype == jnp.float32
    # Layer 0's K is written before any attention ran: route-independent.
    np.testing.assert_array_equal(np.asarray(dense[0][0]),
                                  np.asarray(fused[0][0]))


if __name__ == "__main__":
    for case in CASES:
        e, d, _ = _drive(*case)
        print(f"    {case!r}: (")
        print(f"        {[hex(v) for v in _bits(e)]},")
        print(f"        {[hex(v) for v in _bits(d)]}),")
