"""Operations and bytes the grouped expert matmul, the windowed decode
kernel and the cascade-prefill kernel of K-EXAONE need, from shapes alone:
what ``readers.trace_kernel_roofline`` asks of a module that sizes kernels
(``CALLS``, ``window_calls``) for ``metrics/expert_matmul_roofline.json``,
``metrics/windowed_decode_roofline.json`` and
``metrics/windowed_cascade_roofline.json``.

``spec`` is ``references/exaone_moe.MoESpec``. The expert matmul: a pass
of ``T`` tokens through an expert layer needs, under uniform routing,
``T * top_k * held / experts`` rows of the held experts' gated MLPs, in two
calls (``gate_up``: both input products, ``down``), and reads the weights
of the held experts those tokens reach: each held expert is missed by
all ``T`` tokens with probability ``(1 - top_k / experts) ** T``. A prefill
pass is cut into the fewest chunks of at most ``CHUNK_TOKENS`` tokens, a
call pair each, as the program cuts it (padded to equal chunks). The windowed decode
kernel runs in EVERY layer of such a model (a global layer's window is
no window): a window layer needs ``min(extent, window)`` keys a row, a
global layer the whole extent with the trunk's keys read once for the
batch, both products for one query a head, K/V in bfloat16. The
cascade-prefill kernel likewise: on a window layer a query needs at most
``window`` keys.

Nothing here looks at what the engine dispatched: padding rows, padded
row tiles and re-read weights count against the kernels, as
``harness/flops.py`` has it for the attention kernels.
"""

from __future__ import annotations

import statistics

CHUNK_TOKENS = 4096


def _chunks(tokens: float) -> int:
    """The fewest chunks of at most CHUNK_TOKENS a pass is cut into (the
    program pads the pass to equal chunks; padding routes nowhere)."""
    return max(-(-int(round(tokens)) // CHUNK_TOKENS), 1)


def expert_matmul_calls(spec, tokens: float) -> list:
    """(FLOPs, bytes) of the two calls (gate_up, down) one pass of
    ``tokens`` tokens makes in an expert layer."""
    first, count = spec.held
    rows = tokens * spec.top_k * count / spec.experts
    reached = count * (1.0 - (1.0 - spec.top_k / spec.experts) ** tokens)
    d, f = spec.d, spec.expert_ffn
    gate_up = (2.0 * rows * d * 2 * f,
               reached * 2 * d * f + rows * d * 2 + rows * f * 2)
    down = (2.0 * rows * f * d,
            reached * f * d + rows * f * 2 + rows * d * 2)
    return [gate_up, down]


def expert_matmul_call(spec, batch: int, length: float, trunk: int = 0,
                       suffix: float = 0.0, steps: tuple = ()) -> list:
    """Every call of the grouped matmul a dispatch of ``batch`` rows makes
    in an expert layer: the trunk's pass at one row, the rows' prefixes
    behind it (in chunks), two format suffixes of ``suffix`` tokens a
    row, and one decode step of ``batch`` tokens for each of ``steps``."""
    calls = []
    if trunk:
        calls += expert_matmul_calls(spec, trunk)
    rest = batch * (length - trunk)
    n = _chunks(rest)
    calls += expert_matmul_calls(spec, rest / n) * n
    if suffix:
        calls += expert_matmul_calls(spec, batch * suffix) * 2
    for _ in range(sum(steps)):
        calls += expert_matmul_calls(spec, batch)
    return calls


def windowed_decode_call(spec, batch: int, extent: float,
                         trunk: int = 0) -> list:
    """(FLOPs, bytes) of the decode kernel's call in each layer, for
    ``batch`` rows whose live extent is ``extent`` keys, the first
    ``trunk`` shared by all rows: a window layer reads the last
    ``min(extent, window)`` keys of each row (the trunk's among them only
    where the window reaches it), a global layer every key."""
    H, K, hd = spec.heads, spec.kv_heads, spec.head_dim
    qo = 2.0 * batch * H * hd * 2
    calls = []
    for w in spec.windows:
        if w:
            keys = min(extent, w)
            shared = max(0.0, min(trunk, keys - (extent - trunk)))
        else:
            keys, shared = extent, trunk
        kv = 2.0 * K * hd * 2 * (shared + batch * (keys - shared))
        calls.append((4.0 * batch * H * hd * keys, kv + qo))
    return calls


def _keys_before(n: float, window: float) -> float:
    """Keys the queries at positions 0 .. n - 1 attend, each at most
    ``window`` behind it (n may be a mean)."""
    if n <= window:
        return n * (n + 1) / 2.0
    return window * (window + 1) / 2.0 + window * (n - window)


def windowed_cascade_call(spec, batch: int, length: float,
                          trunk: int = 0) -> list:
    """(FLOPs, bytes) of the cascade-prefill kernel's call in each
    layer: ``batch`` rows of ``length`` queries behind ``trunk`` shared
    positions (computed and read once, as ``flops.cascade_prefill_call``
    counts them), a query on a window layer attending at most ``window``
    keys and reading the trunk's keys only where some row's window
    reaches them, a global layer every earlier key."""
    H, K, hd = spec.heads, spec.kv_heads, spec.head_dim
    tokens = trunk + batch * (length - trunk)
    qo = 2.0 * H * hd * 2 * tokens
    calls = []
    for w in spec.windows:
        span = w or float("inf")
        keys = (_keys_before(trunk, span) + batch * (
            _keys_before(length, span) - _keys_before(trunk, span)))
        read = min(trunk, span - 1) + batch * (length - trunk)
        calls.append((4.0 * H * hd * keys, 2.0 * K * hd * 2 * read + qo))
    return calls


CALLS = {"expert_matmul_call": expert_matmul_call,
         "windowed_decode_call": windowed_decode_call,
         "windowed_cascade_call": windowed_cascade_call}


def window_calls(spec, mix: dict, prompts: list, perts: list,
                 steps: tuple) -> dict:
    """Sizes of the two kernels' calls in a sweep window: per kernel one
    entry for each SHAPE of dispatch the traffic needs, with the number
    of such ``dispatches``: the originals, one row a prompt at their own
    lengths and no trunk, and the rephrasings in groups of ``group_rows``
    sharing ``head_words`` tokens. ``steps``: greedy tokens read from the
    binary and the confidence branch (a decode step of branch x at step j
    reads len(x) + j keys). Empty for a model without experts."""
    from . import tokenizer

    if not hasattr(spec, "held"):
        return {}

    def lengths(pairs):
        shared, suffix, bins, confs = [], [], [], []
        for p, main in pairs:
            b, c, n = tokenizer.encode_pair(p, main, spec.vocab)
            shared.append(n)
            suffix.append((len(b) + len(c) - 2 * n) / 2.0)
            bins.append(len(b))
            confs.append(len(c))
        mean = statistics.fmean
        new_bin, new_conf = steps
        extent = (new_bin * (mean(bins) + (new_bin + 1) / 2)
                  + new_conf * (mean(confs) + (new_conf + 1) / 2)
                  ) / max(new_bin + new_conf, 1)
        return mean(shared), mean(suffix), extent

    pairs = list(zip(prompts, perts))
    shapes = []
    if pairs:
        n, sfx, ext = lengths([(p, p.main) for p, _ in pairs])
        shapes.append(({"batch": len(pairs), "length": n, "trunk": 0,
                        "suffix": sfx, "dispatches": 1}, ext))
    long_rows = [(p, main) for p, mains in pairs for main in mains]
    if long_rows:
        n, sfx, ext = lengths(long_rows)
        shapes.append(({"batch": mix["group_rows"], "length": n,
                        "trunk": mix["head_words"], "suffix": sfx,
                        "dispatches": len(long_rows) / mix["group_rows"]},
                       ext))
    if not shapes:
        return {}
    return {"expert_matmul_call": [dict(s, steps=tuple(steps))
                                   for s, _ in shapes],
            "windowed_decode_call": [
                {"batch": s["batch"], "extent": ext, "trunk": s["trunk"],
                 "dispatches": s["dispatches"]}
                for s, ext in shapes],
            # Only a dispatch behind a shared trunk runs the cascade.
            "windowed_cascade_call": [
                {"batch": s["batch"], "length": s["length"],
                 "trunk": s["trunk"], "dispatches": s["dispatches"]}
                for s, _ in shapes if s["trunk"]]}
