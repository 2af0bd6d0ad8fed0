"""Compile every Pallas entry point of the scoring path for a DESCRIBED
TPU v5e, from this CPU sandbox, at the published 7B head layouts.

Interpret mode (tests/test_kernels.py, test_cascade*.py) proves the
arithmetic; it cannot see what the chip's compiler refuses — block specs
whose minor pair is not (8, 128)-tileable, relayouts Mosaic does not
implement, blocks past the scoped VMEM limit. These tests hand the real
shapes to the real TPU compiler (no chip attached, nothing runs), so a
kernel that stops lowering fails here, at no chip time.

The topology is described inside a module-scoped fixture and nowhere
else: describing it loads the TPU library, which one process at a time
may hold, so it must happen only in the worker that runs this file —
never at import, in a ``skipif``, or in ``parametrize`` arguments. Keep
every such compile in THIS file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from lir_tpu.ops.cascade_prefill import cascade_attention
from lir_tpu.ops.flash_attention import flash_attention
from lir_tpu.ops.ssd_scan import ssd_scan, ssm_step
from lir_tpu.ops.flash_decode import (decode_extent, decode_split,
                                      flash_decode, flash_decode_mq,
                                      flash_decode_mq_trunk,
                                      flash_decode_trunk)

# Published widths of the three head layouts the zoo has:
# (query heads, kv heads, head dim).
LAYOUTS = {
    "mha": (32, 32, 128),     # llama-2-7b / qwen / baichuan (bloom: +ALiBi)
    "gqa": (32, 8, 128),      # mistral-7b
    "mqa": (71, 1, 64),       # falcon-7b — 71 is not a multiple of 8
}
BATCH = 40                    # the smoke's and the sweep's dispatch batch
WINDOW = 5                    # speculative verify window (spec_k + 1)


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, args, one_chip, **static):
    shaped = [None if a is None else
              jax.ShapeDtypeStruct(a[0], a[1], sharding=one_chip)
              for a in args]
    return jax.jit(functools.partial(fn, **static)).lower(*shaped).compile()


def _decode_args(layout, T, window=None, alibi=False):
    H, K, hd = LAYOUTS[layout]
    bf16, i32 = jnp.bfloat16, jnp.int32
    q = ((BATCH, H, hd) if window is None else (BATCH, window, H, hd), bf16)
    qpos = ((BATCH,) if window is None else (BATCH, window), i32)
    kv = ((K, T, BATCH, hd), bf16)
    mask = ((BATCH, T), i32)
    slopes = ((H,), jnp.float32) if alibi else None
    return [q, kv, kv, qpos, mask, mask, slopes]


def _cascade_args(layout, R, Tt, alibi=False):
    H, K, hd = LAYOUTS[layout]
    bf16, i32 = jnp.bfloat16, jnp.int32
    q = ((BATCH, R, H, hd), bf16)
    sfx = ((BATCH, R, K, hd), bf16)
    trunk = ((K, Tt, hd), bf16)
    rows = ((BATCH, R), i32)
    slopes = ((H,), jnp.float32) if alibi else None
    return [q, sfx, sfx, trunk, trunk, rows, rows, slopes]


# 552 = the 512 bucket + a 32-token suffix + the 8-token decode budget:
# the extent the sweep really decodes over (not a multiple of 128).
DECODE_CASES = [(lay, T, False) for lay in LAYOUTS for T in (256, 512, 552)]
DECODE_CASES.append(("mha", 512, True))            # bloom-7b1: ALiBi


@pytest.mark.parametrize("layout,T,alibi", DECODE_CASES)
def test_flash_decode_compiles(one_chip, layout, T, alibi):
    _compile(flash_decode, _decode_args(layout, T, alibi=alibi), one_chip)


# Trunk extents on the CascadeConfig.trunk_quantum = 16 grid: whole
# splits, a partial trailing split, and a trunk past the cache edge.
@pytest.mark.parametrize("trunk", [128, 208, 10_000])
@pytest.mark.parametrize("layout,T,alibi", DECODE_CASES)
def test_flash_decode_trunk_compiles(one_chip, layout, T, alibi, trunk):
    _compile(flash_decode_trunk, _decode_args(layout, T, alibi=alibi),
             one_chip, trunk_len=trunk)


@pytest.mark.parametrize("layout,T,alibi", DECODE_CASES)
def test_flash_decode_mq_compiles(one_chip, layout, T, alibi):
    _compile(flash_decode_mq,
             _decode_args(layout, T, window=WINDOW, alibi=alibi), one_chip)


@pytest.mark.parametrize("layout,T,alibi", DECODE_CASES)
def test_flash_decode_mq_trunk_compiles(one_chip, layout, T, alibi):
    _compile(flash_decode_mq_trunk,
             _decode_args(layout, T, window=WINDOW, alibi=alibi), one_chip,
             trunk_len=T - 128)


# The extents a sweep dispatch really allocates since the plan tightens
# its prefix edge and the cache extent sits on the decode kernel's grid
# (decode_extent): need 488 = the 448 edge + 40, 552 = the 512 edge + 40.
# The trunk is the sweep's 64-token shared head — narrower than mistral's
# split at these extents, one whole split under falcon's cap.
@pytest.mark.parametrize("need", [488, 552])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_flash_decode_compiles_at_the_planned_extent(one_chip, layout,
                                                     need):
    H, K, _ = LAYOUTS[layout]
    T = decode_extent(need, BATCH, H // K)
    assert decode_split(T, BATCH, H // K) >= 56
    _compile(flash_decode, _decode_args(layout, T), one_chip)


@pytest.mark.parametrize("need", [488, 552])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_flash_decode_trunk_compiles_at_the_planned_extent(one_chip, layout,
                                                           need):
    H, K, _ = LAYOUTS[layout]
    T = decode_extent(need, BATCH, H // K)
    _compile(flash_decode_trunk, _decode_args(layout, T), one_chip,
             trunk_len=64)


# (remainder window, trunk) pairs a 256/512 bucket plans on the quantum
# grid, at both the fused single-launch lowering and the two-leg one
# (float and in-kernel int8 QK^T prefix legs). (384, 64) is the 448 edge
# the plan tightens 420-token rows to, over the sweep's 64-token head;
# (448, 64) the ladder's own 512 edge.
CASCADE_SHAPES = [(64, 192), (64, 384), (128, 384), (48, 208), (448, 64),
                  (384, 64)]
CASCADE_CASES = [(lay, R, Tt, False) for lay in LAYOUTS
                 for R, Tt in CASCADE_SHAPES]
CASCADE_CASES.append(("mha", 64, 384, True))       # bloom-7b1: ALiBi


@pytest.mark.parametrize("layout,R,Tt,alibi", CASCADE_CASES)
def test_cascade_fused_compiles(one_chip, layout, R, Tt, alibi):
    _compile(cascade_attention, _cascade_args(layout, R, Tt, alibi),
             one_chip, fused_suffix=True)


@pytest.mark.parametrize("int8_qk", [False, True])
@pytest.mark.parametrize("layout,R,Tt,alibi", CASCADE_CASES)
def test_cascade_two_leg_compiles(one_chip, layout, R, Tt, alibi, int8_qk):
    _compile(cascade_attention, _cascade_args(layout, R, Tt, alibi),
             one_chip, fused_suffix=False, int8_qk=int8_qk)


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("masked", [False, True])
def test_flash_attention_compiles(one_chip, layout, masked):
    """The prefill flash kernel (off for every 7B preset, on by flag):
    it takes per-query-head k/v, as models/decoder._attention repeats
    them."""
    H, _, hd = LAYOUTS[layout]
    S = 512
    qkv = ((BATCH, S, H, hd), jnp.bfloat16)
    mask = ((BATCH, S), jnp.int32) if masked else None
    shaped = [jax.ShapeDtypeStruct(a[0], a[1], sharding=one_chip)
              for a in (qkv, qkv, qkv)]
    kw = {}
    if masked:
        kw["key_mask"] = jax.ShapeDtypeStruct(mask[0], mask[1],
                                              sharding=one_chip)
    jax.jit(functools.partial(flash_attention, causal=True)).lower(
        *shaped, **kw).compile()


# The mixer of falcon-h1-34b at its published sizes: 32 heads of 128, state
# 256, 2 groups; the sweep cell's scan windows (the 64-token trunk at one
# row, the 384-token remainder and the 32-token suffixes at batch 40, and
# the 8-row dispatch of the originals at its 128 edge) and its decode
# update.
MIXER = (32, 128, 2, 256)      # heads, head dim, groups, state


def _scan_args(batch, length=None):
    H, P, G, N = MIXER
    bf16, f32 = jnp.bfloat16, jnp.float32
    t = () if length is None else (length,)
    return [((batch, *t, H, P), bf16), ((batch, *t, H), f32), ((H,), f32),
            ((batch, *t, G, N), bf16), ((batch, *t, G, N), bf16),
            ((batch, H, P, N), f32)]


@pytest.mark.parametrize("batch,length", [(1, 64), (BATCH, 384),
                                          (BATCH, 32), (8, 128), (8, 40)])
def test_ssd_scan_compiles(one_chip, batch, length):
    text = _compile(ssd_scan, _scan_args(batch, length), one_chip).as_text()
    assert "ssd_scan" in text


@pytest.mark.parametrize("batch", [BATCH, 8, 1])
def test_ssm_step_compiles(one_chip, batch):
    text = _compile(ssm_step, _scan_args(batch), one_chip).as_text()
    assert "ssm_step" in text


def test_compiled_text_carries_the_kernel(one_chip):
    """The compile really went through Mosaic: the executable holds a
    ``tpu_custom_call`` (not an XLA fallback)."""
    for fn, args, static in (
            (flash_decode, _decode_args("gqa", 512), {}),
            (flash_decode_trunk, _decode_args("gqa", 512),
             {"trunk_len": 384}),
            (cascade_attention, _cascade_args("gqa", 64, 384), {})):
        text = _compile(fn, args, one_chip, **static).as_text()
        assert "tpu_custom_call" in text, fn


def _flash_attention_args():
    H, _, hd = LAYOUTS["gqa"]
    qkv = ((BATCH, 512, H, hd), jnp.bfloat16)
    return [qkv, qkv, qkv]


# Entry point, arguments, statics, and the name the compiled program (and
# so a profiler trace on the chip) must show for its Mosaic call. The
# benchmark's ``decode_kernel_roofline`` and ``cascade_prefill_roofline``
# find the kernels by these names (``^flash_decode\w*trunk``,
# ``^cascade_attention``): the name is pinned on the ``pallas_call``
# itself, not borrowed from whichever jitted wrapper encloses it.
KERNEL_NAMES = [
    ("flash_decode", flash_decode, lambda: _decode_args("gqa", 512), {}),
    ("flash_decode_trunk", flash_decode_trunk,
     lambda: _decode_args("gqa", 512), {"trunk_len": 384}),
    ("flash_decode_mq", flash_decode_mq,
     lambda: _decode_args("gqa", 512, window=WINDOW), {}),
    ("flash_decode_mq_trunk", flash_decode_mq_trunk,
     lambda: _decode_args("gqa", 512, window=WINDOW), {"trunk_len": 384}),
    ("cascade_attention", cascade_attention,
     lambda: _cascade_args("gqa", 64, 384), {}),
    ("cascade_attention_prefix", cascade_attention,
     lambda: _cascade_args("gqa", 64, 384), {"fused_suffix": False}),
    ("flash_attention", flash_attention, _flash_attention_args,
     {"causal": True}),
]


@pytest.mark.parametrize("name,fn,args,static", KERNEL_NAMES,
                         ids=[k[0] for k in KERNEL_NAMES])
def test_kernel_keeps_its_name_under_a_renamed_wrapper(one_chip, name, fn,
                                                       args, static):
    import re

    def some_other_wrapper(*a):
        return fn(*a, **static)

    shaped = [None if a is None else
              jax.ShapeDtypeStruct(a[0], a[1], sharding=one_chip)
              for a in args()]
    text = jax.jit(some_other_wrapper).lower(*shaped).compile().as_text()
    calls = [re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ", line).group(1)
             for line in text.splitlines() if "tpu_custom_call" in line
             and " custom-call(" in line]
    assert calls, "no Mosaic call in the compiled program"
    assert any(re.fullmatch(re.escape(name) + r"(\.\d+)*", c)
               for c in calls), (name, calls)
