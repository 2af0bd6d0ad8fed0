"""Operations and bytes MiniCPM-SALA's four kernels need, from shapes
alone: what ``readers.trace_kernel_roofline`` asks of a module that sizes
kernels (``CALLS``, ``window_calls``) for ``lightning_scan`` /
``lightning_step`` (the scalar-decay scan of the lightning layers) and
``sparse_prefill`` / ``sparse_decode`` (the softmax layers' attention over
the blocks a query keeps).

``spec`` is ``references/sala.SalaSpec``. Nothing here looks at what the
engine dispatched: the sizes come from the traffic, at ONE trunk a prompt
a call (one entry per shape of dispatch it needs: the trunk's own pass,
one row, once for each prompt with rows in the window; the originals, one
row each, and the rephrasings, in groups of ``group_rows``, which read
that trunk's ``head_words`` tokens and do not compute them), real rows and
real tokens only, so padding and every other choice of the engine count
against the kernel.

The scan: per token and head five operations a state element (decay, the
outer product's multiply, the add, the read's multiply-add); bytes are v
in and o out, k and q in (bfloat16), the step size in (float32), and the
float32 state read and written once a call and row.

The attention counts ONLY THE KEYS THE MODEL KEEPS: per query its first
block, the blocks reaching into its last ``window`` positions and its
``topk`` others (every causal key at or under ``dense_len``), both
products over them, plus, past ``dense_len``, the query's scores over the
pooled keys that lie before it (the selection's own). A kernel that
computes scores for blocks it then masks reads low here. Bytes: queries in
and outputs out, and each distinct key and value a call's queries keep
read once (a shared trunk once for all rows), with the pooled keys.
"""

from __future__ import annotations

import numpy as np


# ---------------------------------------------------------------------------
# What the traffic is, in tokens (1 word = 1 token)
# ---------------------------------------------------------------------------

def _pair_lengths(prompt, main: str) -> tuple:
    """(tokens the binary and confidence prompts share, the binary's own
    behind them, the confidence's own behind them)."""
    n = len(main.split())
    b, c = prompt.response_format.split(), prompt.confidence_format.split()
    same = 0
    while same < min(len(b), len(c)) - 1 and b[same] == c[same]:
        same += 1
    return n + same, len(b) - same, len(c) - same


def dispatch_shapes(spec, mix: dict, prompts: list, perts: list,
                    steps) -> list:
    """One entry a shape of dispatch: ``rows`` real rows whose prompts
    share ``shared`` tokens between their two formats, of which the first
    ``trunk`` are one document for all rows; ``sfx`` the two formats' own
    tokens; ``steps`` the greedy tokens read a branch; ``dispatches`` how
    many the window holds. First the trunk's own pass where the mix has a
    trunk: one row of ``head_words`` tokens and nothing behind them, once
    for each prompt (every prompt has its original in the window). The
    originals and the groups after it are ``held``: they read that pass
    and leave its calls to it."""
    def mean_lengths(pairs):
        got = np.asarray([_pair_lengths(p, m) for p, m in pairs], float)
        return [float(v) for v in got.mean(axis=0)]

    steps = tuple(steps or (0, 0))
    head = mix["head_words"]
    out = []
    if head and prompts:
        out.append({"rows": 1, "shared": head, "trunk": 0, "sfx": (),
                    "steps": (0, 0), "dispatches": len(prompts)})
    held = bool(out)
    originals = [(p, p.main) for p in prompts]
    if originals:
        n, a, b = mean_lengths(originals)
        out.append({"rows": 1, "shared": n, "trunk": head, "held": held,
                    "sfx": (a, b), "steps": steps,
                    "dispatches": len(originals)})
    long_rows = [(p, m) for p, mains in zip(prompts, perts) for m in mains]
    if long_rows:
        n, a, b = mean_lengths(long_rows)
        out.append({"rows": mix["group_rows"], "shared": n, "trunk": head,
                    "held": held, "sfx": (a, b), "steps": steps,
                    "dispatches": len(long_rows) / mix["group_rows"]})
    return out


# ---------------------------------------------------------------------------
# The lightning scan
# ---------------------------------------------------------------------------

def _state_elements(spec) -> int:
    return spec.l_heads * spec.l_head_dim * spec.l_head_dim


def _token_bytes(spec) -> float:
    return 4 * spec.l_heads * spec.l_head_dim * 2 + spec.l_heads * 4


def scan_window(spec, rows: float, tokens: float) -> tuple:
    """(FLOPs, bytes) of one scan call over ``tokens`` tokens in all, in
    ``rows`` rows with a state each."""
    return (5.0 * _state_elements(spec) * tokens,
            _token_bytes(spec) * tokens
            + 2.0 * rows * _state_elements(spec) * 4)


def scan_calls(spec, rows, shared, trunk, sfx, steps, held=False) -> list:
    """Each scan call a dispatch needs in a lightning layer: the rows' own
    prefix tokens, the trunk once at one row (not where it is ``held``:
    the trunk's own pass makes that call), the two format suffixes."""
    calls = [scan_window(spec, rows, rows * (shared - trunk))]
    if trunk and not held:
        calls.append(scan_window(spec, 1, trunk))
    return calls + [scan_window(spec, rows, rows * s) for s in sfx]


def step_calls(spec, rows, shared, trunk, sfx, steps) -> list:
    """Every decode step needs the same single-token update."""
    return [scan_window(spec, rows, rows)]


# ---------------------------------------------------------------------------
# The softmax layers' attention over the keys kept
# ---------------------------------------------------------------------------

def kept_keys(spec, positions, main_len: int) -> tuple:
    """Per query at ``positions`` over ``main_len`` main keys (slot ==
    position; keys past them are the query's own tail, all kept): (keys
    it attends, pooled kernels it scores)."""
    p = np.asarray(positions, np.int64)
    B = spec.block
    bound = np.minimum(p, main_len - 1)               # last main key seen
    tail = p - bound                                  # own keys behind them
    dense = p + 1 <= spec.dense_len
    first_local = np.maximum((p - spec.window + 2 + B - 1) // B - 1, 0)
    first_local = np.minimum(first_local, bound // B)
    local_keys = bound + 1 - first_local * B
    init = np.minimum(spec.init_blocks, first_local)
    others = first_local - init
    kept = local_keys + (init + np.minimum(others, spec.topk)) * B
    keys = np.where(dense, bound + 1, kept) + tail
    visible = np.clip((np.minimum(p, main_len + tail) - spec.kernel)
                      // spec.stride + 1, 0, None)
    return keys, np.where(dense, 0, visible)


def _attend(spec, positions, main_len: int, distinct: float) -> tuple:
    """(FLOPs, bytes) of one call whose queries sit at ``positions``."""
    keys, kernels = kept_keys(spec, positions, main_len)
    width = spec.heads * spec.head_dim
    flops = 4.0 * width * keys.sum() + 2.0 * width * kernels.sum()
    n = len(np.asarray(positions))
    kv_key = 2 * spec.kv_heads * spec.head_dim * 2
    pooled = (spec.kv_heads * spec.head_dim * 4
              * float(kernels.max(initial=0)))
    return flops, 2.0 * width * 2 * n + kv_key * distinct + pooled


def prefill_calls(spec, rows, shared, trunk, sfx, steps,
                  held=False) -> list:
    """Each ``sparse_prefill`` call a dispatch needs in a softmax layer:
    the trunk over itself (one row; not where it is ``held``: the trunk's
    own pass makes that call), the rows' own prefix tokens over the trunk
    and themselves, the two format suffixes over all before them."""
    shared, trunk, rows = int(round(shared)), int(trunk), int(rows)
    main = trunk or shared
    own = np.arange(trunk, shared)
    calls = []
    if trunk and not held:
        calls.append(_attend(spec, np.arange(trunk), trunk, trunk))
    calls.append(_attend(spec, np.tile(own, rows), main,
                         (trunk if trunk else 0) + rows * len(own)))
    for s in sfx:
        q = np.arange(shared, shared + int(round(s)))
        calls.append(_attend(spec, np.tile(q, rows), main,
                             main + rows * (shared - trunk + len(q))
                             if trunk else rows * (shared + len(q))))
    return calls


def decode_calls(spec, rows, shared, trunk, sfx, steps) -> list:
    """Each ``sparse_decode`` call: step ``j`` of a branch reads one query
    a row at position shared + its suffix + j."""
    shared, trunk, rows = int(round(shared)), int(trunk), int(rows)
    main = trunk or shared
    calls = []
    for s, n in zip(sfx, steps):
        for j in range(int(n)):
            p = shared + int(round(s)) + j
            distinct = main + rows * (p + 1 - main)
            calls.append(_attend(spec, np.full(rows, p), main, distinct))
    return calls


CALLS = {"lightning_scan_call": scan_calls,
         "lightning_step_call": step_calls,
         "sparse_prefill_call": prefill_calls,
         "sparse_decode_call": decode_calls}


def window_calls(spec, mix: dict, prompts: list, perts: list,
                 steps=None) -> dict:
    """The scan and the prefill attention read every shape of dispatch;
    the two decode kernels the shapes that decode (a trunk's own pass
    reads no token and makes them no call, so ``held`` says nothing to
    them and is left out). Empty for a model of another family."""
    if not hasattr(spec, "l_heads"):
        return {}
    shapes = dispatch_shapes(spec, mix, prompts, perts, steps)
    if not shapes:
        return {}
    decoding = [{k: v for k, v in s.items() if k != "held"}
                for s in shapes if any(s["steps"])]
    return {"lightning_scan_call": shapes, "sparse_prefill_call": shapes,
            "lightning_step_call": decoding, "sparse_decode_call": decoding}
