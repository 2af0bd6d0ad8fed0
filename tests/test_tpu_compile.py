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

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from lir_tpu.engine import generate
from lir_tpu.models import decoder, quant, registry
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


def _stacked(fn, n_args):
    """``fn`` of ``n_args`` arguments with one more, trailing, argument:
    the layer index, traced (the caller stacks the operands it picks
    from)."""
    def call(*args, **static):
        return fn(*args[:n_args], layer=args[n_args], **static)
    return call


STACK = 4                      # layers of the stacked operand


def _stack(arg):
    return ((STACK,) + arg[0], arg[1])


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


# The same four entry points reading one layer out of the STACKED cache
# sides (L, K, T, B, hd), the layer index traced: the form the decode
# step's layer loop calls (models/decoder._block). 504 is the extent the
# sweep cells decode over.
STACKED_DECODE = [(flash_decode, None, {}),
                  (flash_decode_trunk, None, {"trunk_len": 64}),
                  (flash_decode_mq, WINDOW, {}),
                  (flash_decode_mq_trunk, WINDOW, {"trunk_len": 64})]


@pytest.mark.parametrize("fn,window,static", STACKED_DECODE,
                         ids=[c[0].__name__ for c in STACKED_DECODE])
@pytest.mark.parametrize("layout,T,alibi", [("gqa", 504, False),
                                            ("mqa", 504, False),
                                            ("mha", 512, True)])
def test_flash_decode_stacked_compiles(one_chip, layout, T, alibi, fn,
                                       window, static):
    args = _decode_args(layout, T, window=window, alibi=alibi)
    args[1], args[2] = _stack(args[1]), _stack(args[2])
    text = _compile(_stacked(fn, 7), args + [((), jnp.int32)], one_chip,
                    **static).as_text()
    assert "tpu_custom_call" in text
    # The kernel is handed the stacked sides themselves: no layer of
    # them is sliced out for it.
    K, hd = LAYOUTS[layout][1:]
    assert f"bf16[{K},{T},{BATCH},{hd}]" not in text


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


@pytest.mark.parametrize("batch,length", [(BATCH, 32), (BATCH, None)])
def test_scan_kernels_stacked_compile(one_chip, batch, length):
    """Both selective-scan kernels over the stacked (L, B, H, P, N) state
    with a traced layer index, state aliased in to out: the compiled
    program holds no copy and no slice of the state."""
    fn = ssm_step if length is None else ssd_scan
    args = _scan_args(batch, length)
    args[5] = _stack(args[5])
    shaped = [jax.ShapeDtypeStruct(a[0], a[1], sharding=one_chip)
              for a in args + [((), jnp.int32)]]
    # The state is donated, as a loop's carry is: the aliased output then
    # IS the argument's buffer.
    text = jax.jit(_stacked(fn, 6), donate_argnums=(5,)).lower(
        *shaped).compile().as_text()
    assert fn.__name__ in text
    H, P, _, N = MIXER
    assert f"f32[{batch},{H},{P},{N}]" not in text
    assert not re.search(rf"f32\[{STACK},{batch},{H},{P},{N}\]\S* copy\(",
                         text)


# MiniCPM-SALA at its published sizes, on the shapes of its 16k-document
# cell: the lightning layers' scan with a group a head (32 heads of 128, a
# 128 x 128 state), over the stacked state of 24 layers; the softmax layers'
# block-masked attention (32 query / 2 kv heads of 128, blocks of 64) over a
# 16,000-token trunk read by every row's queries, over one row's own 16,128
# keys, and a decode step's 40 single queries.
LIGHTNING = (32, 128)          # heads, head dim (= state width)


def _lightning_args(batch, length=None, layers=24):
    H, P = LIGHTNING
    bf16, f32 = jnp.bfloat16, jnp.float32
    t = () if length is None else (length,)
    qkv = ((batch, *t, H, P), bf16)
    return [qkv, ((batch, *t, H), f32), ((H,), f32), qkv, qkv,
            ((layers, batch, H, P, P), f32), ((), jnp.int32)]


@pytest.mark.parametrize("batch,length", [(1, 16000), (BATCH, 128),
                                          (BATCH, 32), (BATCH, None),
                                          (1, None)])
def test_lightning_kernels_compile(one_chip, batch, length):
    fn, name = ((ssm_step, "lightning_step") if length is None
                else (ssd_scan, "lightning_scan"))
    shaped = [jax.ShapeDtypeStruct(a[0], a[1], sharding=one_chip)
              for a in _lightning_args(batch, length)]
    text = jax.jit(functools.partial(_stacked(fn, 6), name=name),
                   donate_argnums=(5,)).lower(*shaped).compile().as_text()
    assert name in text and "tpu_custom_call" in text
    H, P = LIGHTNING
    assert f"f32[{batch},{H},{P},{P}]" not in text   # no layer sliced out


@pytest.mark.parametrize("case,rows,queries,keys", [
    ("trunk", 1, 16000, 16000), ("windows", 1, BATCH * 128, 16000),
    ("decode", 1, BATCH, 16000), ("own_prefix", 1, 16128, 16128),
    ("own_decode", 1, 1, 16128)])
def test_sparse_attention_kernel_compiles(one_chip, case, rows, queries,
                                          keys):
    from lir_tpu.ops import sparse_attention

    K, G, hd, layers = 2, 16, 128, 8
    bf16, i32 = jnp.bfloat16, jnp.int32
    kv = ((layers, rows, K, keys, hd), bf16)
    args = [((rows, K, G, queries, hd), bf16), kv, kv,
            ((rows, K, queries, -(-keys // 64)), jnp.bool_),
            ((rows, queries), i32), ((), i32)]
    name = "sparse_decode" if "decode" in case else "sparse_prefill"

    def fn(q, k, v, keep, bound, layer):
        return sparse_attention.attend_main(q, k, v, keep, bound, block=64,
                                            layer=layer, name=name)

    text = _compile(fn, args, one_chip).as_text()
    assert name in text and "tpu_custom_call" in text
    # The stacked keys are read where they lie: no layer of them copied.
    assert f"bf16[{rows},{K},{keys},{hd}]" not in text


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


# ---------------------------------------------------------------------------
# The decode loop updates the stacked cache where it lies
# ---------------------------------------------------------------------------
# models/decoder._scan_blocks carries the stacked cache through the layer
# loop and engine/generate._stepped carries it through the decode loop; what
# says that the mechanism engaged is the compiled program itself: inside its
# loops nothing produces an array of a stacked side's shape, or of one whole
# layer of it, but the in-place updates (the token-slot write, a kernel's
# aliased output).

def _computations(text):
    """{computation name: its instruction lines} of a compiled module's
    text."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$",
                        line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _inside_loops(comps):
    """Names of the computations a ``while`` body reaches (its fusions,
    its conditionals' branches, nested loops)."""
    todo = [name for lines in comps.values() for line in lines
            if re.search(r"\swhile\(", line)
            for name in re.findall(r"body=%?([\w.\-]+)", line)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            for key in ("body", "condition", "to_apply", "calls",
                        "true_computation", "false_computation"):
                todo += re.findall(key + r"=%?([\w.\-]+)", line)
            branches = re.search(r"branch_computations=\{([^}]*)\}", line)
            if branches:
                todo += [b.strip().lstrip("%")
                         for b in branches.group(1).split(",")]
    return seen


_INSTR = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)")


def _cache_moves(text, stacked, layers, whole_layer_reads=True):
    """What the loops of a compiled program do to the stacked cache beyond
    updating it in place, as a list of findings (empty: nothing).
    ``stacked`` / ``layers``: HLO shape strings of the stacked sides
    (``bf16[L,K,T,B,hd]``) and of one layer of each."""
    comps = _computations(text)
    loops = _inside_loops(comps)
    assert loops, "no loop in the compiled program"
    types = {}
    for lines in comps.values():
        for line in lines:
            m = _INSTR.match(line)
            if m:
                types[m.group(1)] = m.group(2)
    found = []
    for name in sorted(loops):
        for line in comps[name]:
            m = _INSTR.match(line)
            if not m:
                continue
            var, typ, op, rest = m.groups()
            if op in ("parameter", "get-tuple-element", "bitcast", "tuple",
                      "while", "conditional"):
                continue
            of_stack = any(typ.startswith(s) for s in stacked)
            if of_stack and op in ("copy", "copy-start"):
                found.append(f"copy of a stacked side: {var} = {typ}")
            if of_stack and "AllocateBuffer" in rest:
                found.append(f"second stacked buffer: {var} = {typ}")
            if whole_layer_reads and any(typ.startswith(s) for s in layers):
                found.append(f"one whole layer materialised: {var} = {typ} "
                             f"{op}")
            if op == "dynamic-update-slice":
                update = re.findall(r"%([\w.\-]+)", rest)[1]
                if any(types.get(update, "").startswith(s) for s in layers):
                    found.append(f"whole-layer write-back: {var} <- "
                                 f"{update} = {types[update]}")
    return found


DEPTH = 2                      # layers of the cut models below
EXTENT, SLOT, TRUNK = 504, 480, 64


def _loop_cfg(case):
    if case == "hybrid":
        return registry.falcon_h1_34b(n_layers=DEPTH)
    cfg = dataclasses.replace(registry.mistral_7b(), n_layers=DEPTH)
    return dataclasses.replace(cfg, kv_cache_int8=(case == "int8"))


def _shaped(tree, one_chip):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)


def _hlo_shape(leaf, drop=0):
    names = {"bfloat16": "bf16", "float32": "f32", "int8": "s8"}
    return (f"{names[str(leaf.dtype)]}"
            f"[{','.join(str(d) for d in leaf.shape[drop:])}]")


def _cache_shapes(cfg, cache):
    """(stacked, one-layer) shape strings of the K/V sides (payloads of an
    int8 cache) and of a mixer's SSM state; scales and the conv tail are
    small and read or written whole by design."""
    big = [leaf for leaf in jax.tree.leaves(cache) if leaf.ndim == 5]
    return ([_hlo_shape(leaf) for leaf in big],
            [_hlo_shape(leaf, 1) for leaf in big])


@pytest.fixture
def on_tpu(monkeypatch):
    """The decoder's gates ask the backend; the compile is for a described
    chip, so they are told what it is."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("case", ["gqa", "hybrid", "int8"])
def test_decode_loop_updates_the_stacked_cache_in_place(one_chip, on_tpu,
                                                         case):
    """``_fused_tail`` (two steps, early stop on) at mistral's and
    falcon-h1's published widths, batch 40, extent 504: the decode loop
    and the layer loop inside it hold no copy and no second buffer of a
    stacked side, materialise no whole layer of it (GQA + bf16 and the
    hybrid; the int8 cache attends dense and a fused read of the layer is
    XLA's to place) and write no whole layer back."""
    cfg = _loop_cfg(case)
    params = _shaped(jax.eval_shape(
        lambda k: quant.random_quantized_params(cfg, k),
        jax.random.PRNGKey(0)), one_chip)
    cache = _shaped(jax.eval_shape(
        lambda: decoder.init_cache(cfg, BATCH, EXTENT, jnp.bfloat16)),
        one_chip)
    V = cfg.vocab_size

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def tail(params, cache, logits0, mask, pos, ids, digit_ids, digit_vals,
             stop_mask, eos_id):
        return generate._fused_tail(
            params, cfg, logits0, cache, mask, pos, SLOT, ids, ids,
            digit_ids, digit_vals, 2, 20, stop_mask=stop_mask,
            eos_id=eos_id, decode_trunk=TRUNK)

    text = jax.jit(tail, donate_argnums=(1,)).lower(
        params, cache, sd((BATCH, V), jnp.float32),
        sd((BATCH, EXTENT), jnp.int32), sd((BATCH,), jnp.int32),
        sd((BATCH,), jnp.int32), sd((101,), jnp.int32),
        sd((101,), jnp.float32), sd((V,), jnp.int32),
        sd((), jnp.int32)).compile().as_text()
    if case != "int8":
        assert "flash_decode_trunk" in text
    if case == "hybrid":
        assert "ssm_step" in text
    stacked, layers = _cache_shapes(cfg, cache)
    assert len(stacked) == (3 if case == "hybrid" else 2)
    assert _cache_moves(text, stacked, layers,
                        whole_layer_reads=(case != "int8")) == []


def test_cascade_program_updates_the_stacked_cache_in_place(one_chip,
                                                            on_tpu):
    """The whole 40-row cascade dispatch program of a sweep cell (mistral's
    widths, cut depth; both branches, early stops armed, the donated
    scratch cache): what a two-step loop compiled alone cannot show is
    whether a program of a dispatch's size still carries the cache by
    loops alone. Held to the decode loops; the extension's dense read of a
    layer is XLA's to place."""
    cfg = _loop_cfg("gqa")
    params = _shaped(jax.eval_shape(
        lambda k: quant.random_quantized_params(cfg, k),
        jax.random.PRNGKey(0)), one_chip)
    V = cfg.vocab_size

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    args = (params, cfg,
            generate.Program(front="cascade", max_new=(4, 8), topk=20,
                             trunk=TRUNK, return_cache=True),
            generate.DispatchArgs(
                prefix=i32(BATCH, 448), prefix_mask=i32(BATCH, 448),
                sfx=(i32(BATCH, 32), i32(BATCH, 32)),
                sfx_mask=(i32(BATCH, 32), i32(BATCH, 32)),
                yes_ids=i32(BATCH), no_ids=i32(BATCH), digit_ids=i32(101),
                digit_vals=jax.ShapeDtypeStruct((101,), jnp.float32,
                                                sharding=one_chip),
                stops=generate.Stops(binary=i32(V), digits=i32(V),
                                     eos_id=i32())))
    fn = generate.greedy_decode_dispatch
    cache = fn.eval_shape(*args, scratch_cache=None)[-1]
    text = fn.lower(*args, scratch_cache=_shaped(cache, one_chip)
                    ).compile().as_text()
    assert cache[0].shape == (DEPTH, 8, EXTENT, BATCH, 128)
    stacked, layers = _cache_shapes(cfg, cache)
    found = _cache_moves(text, stacked, layers, whole_layer_reads=False)
    assert found == []


def test_mixed_layer_loop_updates_its_leaves_in_place(one_chip, on_tpu):
    """MiniCPM-SALA's widths, six layers in three rounds (a kind's run of
    one, two and none), the selection live past 512 tokens: the 40-row
    cascade program over a 1,024-token trunk. ``mixed._run_layers`` carries
    the six leaves through a scan over rounds and, inside it, a loop a
    kind whose trip count is the round's; no loop of the program may copy
    a stacked leaf, hold a second one, or write a whole layer back."""
    from lir_tpu.models import mixed

    cfg = dataclasses.replace(
        registry.minicpm_sala(), n_layers=6, layer_kinds=(
            "sparse", "lightning", "lightning", "sparse", "lightning",
            "sparse"), sparse_dense_len=512, sparse_window=256,
        sparse_topk=4)
    assert mixed.layer_rounds(cfg)[1].tolist() == [[1, 2], [1, 1], [1, 0]]
    params = _shaped(jax.eval_shape(
        lambda k: mixed.init_params(cfg, k, jnp.bfloat16),
        jax.random.PRNGKey(0)), one_chip)
    V, trunk = cfg.vocab_size, 1024

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    args = (params, cfg,
            generate.Program(front="cascade", max_new=(4, 8), topk=20,
                             trunk=trunk, return_cache=True),
            generate.DispatchArgs(
                prefix=i32(BATCH, trunk + 128),
                prefix_mask=i32(BATCH, trunk + 128),
                sfx=(i32(BATCH, 32), i32(BATCH, 32)),
                sfx_mask=(i32(BATCH, 32), i32(BATCH, 32)),
                yes_ids=i32(BATCH), no_ids=i32(BATCH), digit_ids=i32(101),
                digit_vals=jax.ShapeDtypeStruct((101,), jnp.float32,
                                                sharding=one_chip),
                stops=generate.Stops(binary=i32(V), digits=i32(V),
                                     eos_id=i32())))
    fn = generate.greedy_decode_dispatch
    cache = fn.eval_shape(*args, scratch_cache=None)[-1]
    text = fn.lower(*args, scratch_cache=_shaped(cache, one_chip)
                    ).compile().as_text()
    for kernel in ("lightning_scan", "lightning_step", "sparse_prefill",
                   "sparse_decode"):
        assert kernel in text
    big = [leaf for leaf in jax.tree.leaves(cache) if leaf.size > 1 << 19]
    assert len(big) == 5                      # all but the pooled keys
    found = _cache_moves(text, [_hlo_shape(leaf) for leaf in big],
                         [_hlo_shape(leaf, 1) for leaf in big],
                         whole_layer_reads=False)
    assert found == []


# ---------------------------------------------------------------------------
# The trunk as a value, at the published sizes (ISSUE 32)
# ---------------------------------------------------------------------------
# minicpm-sala.sweep-doc16k's three new programs, every layer, batch 40, a
# 16,000-token trunk, as the engine routes and the compile plan lowers
# them: the trunk program, and the dispatch program that takes its cache
# at 40 rows and at ONE row. The chip reports 16.9 GB to the program
# (PERF.md section 4); weights 9.5 of them.

HBM = 16.9e9
DOC_TRUNK, DOC_EDGE = 16_000, 16_128


def _doc16k_engine(one_chip):
    import json
    import sys
    from pathlib import Path

    bench = Path(__file__).resolve().parents[1] / "benchmarks"
    sys.path.insert(0, str(bench))
    from harness import builders
    from references import sala as ref

    raw = json.loads((bench / "configs" / "minicpm-sala.json").read_text())
    spec = ref.spec_from_config("minicpm-sala", raw)
    cfg = builders.program_config(spec, ref)
    params = _shaped(jax.eval_shape(lambda: builders.wrap_quantized(
        ref.weights(spec, ref.seed_key(0)))), one_chip)
    return builders.build_engine(params, cfg, raw["lir_tpu"]["runtime"])


@pytest.mark.parametrize("program", ["trunk", "held-40", "held-1"])
def test_the_held_trunk_programs_compile_at_the_published_sizes(
        one_chip, on_tpu, program):
    from lir_tpu.engine import compile_plan

    engine = _doc16k_engine(one_chip)
    doc = tuple(range(DOC_TRUNK))
    trunk_bytes = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(
            compile_plan.trunk_cache_avals(engine, DOC_TRUNK)))
    assert 0.18e9 < trunk_bytes < 0.2e9       # K/V 131, pooled 8, state 50 MB
    if program == "trunk":
        spec = compile_plan.trunk_spec(DOC_TRUNK)
    else:
        rows = 40 if program == "held-40" else 1
        route = engine.route(
            "shared", DOC_EDGE, rows, 0, 64, 64, 4, 8, True,
            [doc + (20_000 + r,) * 100 for r in range(rows)], rows, held=doc)
        assert route.held and not route.trunk_run and route.trunk == DOC_TRUNK
        (spec,) = route.planned(False)
        assert spec.held and spec.scratch      # donate_first: one variant
    compiled = compile_plan._lower(engine, spec).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    if program == "trunk":
        # It runs while the 40-row dispatch cache (2.2 GB) lies parked in
        # the handoff, and what it returns is the held trunk.
        assert 0 <= mem.output_size_in_bytes - trunk_bytes < 1 << 20
        assert held + trunk_bytes + 2.3e9 < HBM
        assert "lightning_scan" in text and "sparse_prefill" in text
        assert "lightning_step" not in text and "sparse_decode" not in text
        assert re.search(r"\[(1,)?16000,4096\]", text)
        return
    # The held trunk is one of the arguments; the donated cache comes back.
    assert held < HBM
    assert mem.alias_size_in_bytes > trunk_bytes
    assert 0 <= mem.output_size_in_bytes - mem.alias_size_in_bytes < 1 << 20
    for kernel in ("lightning_scan", "lightning_step", "sparse_prefill",
                   "sparse_decode"):
        assert kernel in text
    # No pass over 16,000 tokens is left in a dispatch program (the dense
    # one-row program and the trunk program hold hundreds of these).
    assert not re.search(r"\[(1,)?16(000|128),4096\]", text)
    cache = generate.greedy_decode_dispatch.eval_shape(
        engine.params, engine.cfg,
        compile_plan.dispatch_program(engine, spec),
        compile_plan.dispatch_args(engine, spec))[-1]
    assert cache[4].shape[1] == 1 and cache[4].shape[3] == DOC_TRUNK
    big = [leaf for leaf in jax.tree.leaves(cache) if leaf.size > 1 << 19]
    found = _cache_moves(text, [_hlo_shape(leaf) for leaf in big],
                         [_hlo_shape(leaf, 1) for leaf in big],
                         whole_layer_reads=False)
    assert found == []


# ---------------------------------------------------------------------------
# The exact GELU stays a short epilogue of the up-projection
# ---------------------------------------------------------------------------
# ``decoder._act(., "gelu")`` runs on every element the up-projection makes
# (falcon-7b: 15360 x 18176 a prefill call). Written through ``erfc`` (what
# jax.nn.gelu(approximate=False) is) XLA expands it inside the matmul's
# fusion into both of erfc's branches: 29 multiply + 21 add + 4 select + 4
# compare + 2 divide + 1 exponential an element, a quarter of the call's time
# on the chip. What says the short form is still the compiled one is the
# fusion itself; a jax upgrade that expands it again fails here.

_NOT_ARITHMETIC = {"parameter", "constant", "broadcast", "convert",
                   "convolution", "bitcast", "fusion", "copy", "reshape",
                   "transpose"}
_TRANSCENDENTAL = {"erf", "exponential", "exponential-minus-one", "log",
                   "log-plus-one", "tanh", "logistic", "power", "sqrt",
                   "rsqrt", "cbrt", "sine", "cosine", "atan2"}


def _up_projection(x, q, scale):
    up = quant.matmul(x, quant.QuantTensor(q=q, scale=scale))
    return decoder._act(up, "gelu")


@pytest.mark.parametrize("rows", [384, 32], ids=["prefill", "extend"])
def test_gelu_is_a_short_epilogue_of_the_up_projection(one_chip, rows):
    cfg = registry.falcon_7b()
    d, ffn = cfg.hidden_size, cfg.intermediate_size
    assert (d, ffn, cfg.activation) == (4544, 18176, "gelu")
    text = _compile(_up_projection,
                    [((BATCH, rows, d), jnp.bfloat16), ((d, ffn), jnp.int8),
                     ((ffn,), jnp.float32)], one_chip).as_text()
    comps = _computations(text)
    out = f"[{BATCH},{rows},{ffn}]"

    holders = [name for name, lines in comps.items()
               if any(" convolution(" in line for line in lines)]
    assert len(holders) == 1, holders
    ops = [m.group(3) for m in map(_INSTR.match, comps[holders[0]]) if m]
    arithmetic = [op for op in ops if op not in _NOT_ARITHMETIC]
    transcendental = [op for op in arithmetic if op in _TRANSCENDENTAL]
    assert len(arithmetic) > 1, ops     # beyond the scale: the activation
    assert "divide" not in ops, ops
    assert len(transcendental) <= 1, transcendental
    assert len(arithmetic) <= 30, sorted(arithmetic)

    calls = [line for lines in comps.values() for line in lines
             if re.search(rf"calls=%?{re.escape(holders[0])}\b", line)]
    assert len(calls) == 1 and "kind=kOutput" in calls[0], calls
    assert _INSTR.match(calls[0]).group(2).startswith("bf16" + out), calls[0]
    # ... and nothing of the output's extent in float32 leaves it for HBM.
    outside = [line.strip()[:120] for name, lines in comps.items()
               if name != holders[0] for line in lines if "f32" + out in line]
    assert outside == []
