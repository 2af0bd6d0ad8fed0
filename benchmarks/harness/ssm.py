"""Operations and bytes the selective scan of a state-space mixer needs,
from shapes alone, and the reader of its two kernels' roofline shares.

``spec`` carries the mixer's sizes (``references/hybrid.HybridSpec``):
``ssm_heads`` heads of ``ssm_head_dim``, a state of ``ssm_state`` per
head-dim element, ``ssm_groups`` groups sharing ``B`` and ``C``. The
recurrence costs, per token and head, five operations per state element
(decay, the outer product's multiply, the add, and the read's
multiply-add): ``5 * heads * head_dim * state``, ~5.2 MFLOP a token a
layer at the published sizes beside ~860 MFLOP of matmuls. The bytes are
what has to cross HBM once: ``x`` in and ``y`` out (bfloat16), ``B`` and
``C`` in, ``dt`` in (float32), and the float32 state read and written
once per call and row, whatever the window's length: that is the point of
the chunked form.

Nothing here looks at what the engine dispatched. The sizes come from the
traffic (``window_calls``: one entry per shape of dispatch the traffic
needs, the originals and the groups, weighted by how many of each the
window holds), so padding and every other choice of the engine count
against the kernel, as ``harness/flops.py`` has it for the attention
kernels.

Not registered in ``harness/readers.READERS`` (a closed dict, PERF.md §7):
``benchmarks/tests/ssm_trace.py`` hands ``trace_ssm_roofline`` the same
``context`` a registered reader gets.
"""

from __future__ import annotations

import re
import statistics

from . import flops


def _state_elements(spec) -> int:
    return spec.ssm_heads * spec.ssm_head_dim * spec.ssm_state


def _token_bytes(spec) -> float:
    """HBM bytes one token moves whatever the state does: x in, y out,
    B and C in (bfloat16), dt in (float32)."""
    return (2 * spec.ssm_heads * spec.ssm_head_dim * 2
            + 2 * spec.ssm_groups * spec.ssm_state * 2
            + spec.ssm_heads * 4)


def scan_window(spec, rows: float, tokens: float) -> tuple:
    """(FLOPs, bytes) of one chunked-scan call over ``tokens`` tokens in
    all, in ``rows`` rows with a state each."""
    return (5.0 * _state_elements(spec) * tokens,
            _token_bytes(spec) * tokens
            + 2.0 * rows * _state_elements(spec) * 4)


def scan_calls(spec, batch: int, length: float, trunk: int = 0,
               suffix: float = 0.0) -> list:
    """(FLOPs, bytes) of EACH scan call a dispatch of ``batch`` rows needs
    in a layer: the first ``trunk`` tokens once, at one row (shared by all
    rows); each row's ``length - trunk`` further prefix tokens; and, where
    ``suffix`` > 0, two format suffixes of ``suffix`` tokens a row, each
    continuing from the prefix's state."""
    calls = [scan_window(spec, batch, batch * (length - trunk))]
    if trunk:
        calls.append(scan_window(spec, 1, trunk))
    if suffix:
        calls += [scan_window(spec, batch, batch * suffix)] * 2
    return calls


def ssd_scan_call(spec, batch: int, length: float, trunk: int = 0,
                  suffix: float = 0.0) -> tuple:
    """Mean (FLOPs, bytes) of ONE of :func:`scan_calls`' calls (each runs
    once a layer in such a dispatch)."""
    calls = scan_calls(spec, batch, length, trunk, suffix)
    return (sum(c[0] for c in calls) / len(calls),
            sum(c[1] for c in calls) / len(calls))


def ssm_step_call(spec, batch: int) -> tuple:
    """(FLOPs, bytes) of one single-token update of ``batch`` rows: the
    whole state read once and written once."""
    return scan_window(spec, batch, batch)


# What one dispatch of a shape needs of each kernel in a layer, call by
# call (every decode step needs the same one).
CALLS = {"ssd_scan_call": scan_calls,
         "ssm_step_call": lambda spec, batch: [ssm_step_call(spec, batch)]}


def window_calls(spec, mix: dict, prompts: list, perts: list) -> dict:
    """Sizes of the two kernels' calls, keyed like ``window.kernel_calls``:
    per kernel one entry for each SHAPE of dispatch the traffic needs,
    with the number of such ``dispatches`` in the window. The originals
    are one dispatch of one row a prompt, at their own lengths, with no
    shared trunk; the rephrasings come in groups of ``group_rows`` rows
    sharing ``head_words`` tokens. Real rows, not the engine's padded
    batch: rows it adds count against the kernel."""
    from . import tokenizer

    def lengths(pairs):
        shared, suffix = [], []
        for p, main in pairs:
            b, c, n = tokenizer.encode_pair(p, main, spec.vocab)
            shared.append(n)
            suffix.append((len(b) + len(c) - 2 * n) / 2.0)
        return statistics.fmean(shared), statistics.fmean(suffix)

    pairs = list(zip(prompts, perts))
    shapes = []
    if pairs:
        n, sfx = lengths([(p, p.main) for p, _ in pairs])
        shapes.append({"batch": len(pairs), "length": n, "trunk": 0,
                       "suffix": sfx, "dispatches": 1})
    long_rows = [(p, main) for p, mains in pairs for main in mains]
    if long_rows:
        n, sfx = lengths(long_rows)
        shapes.append({"batch": mix["group_rows"], "length": n,
                       "trunk": mix["head_words"], "suffix": sfx,
                       "dispatches": len(long_rows) / mix["group_rows"]})
    if not shapes:
        return {}
    return {"ssd_scan_call": shapes,
            "ssm_step_call": [{"batch": s["batch"],
                               "dispatches": s["dispatches"]}
                              for s in shapes]}


def trace_ssm_roofline(context, pattern, shape):
    """``readers.trace_kernel_roofline`` for the two kernels here: the
    least time the chip could take for the calls of the device operations
    matching ``pattern`` over their summed device time. The trace gives
    the number of calls and their time; ``window.kernel_calls[shape]``
    gives the dispatch shapes the traffic needs, and ``CALLS[shape]`` what
    each needs call by call, so the least time of a call is the mean over
    the needed calls, each shape weighted by its dispatches. None where
    the trace holds no such operation (a program without a mixer) or the
    window no such sizes."""
    if not hasattr(context["spec"], "ssm_heads"):
        return None
    rx = re.compile(pattern)
    hits = [v for k, v in context["trace"]["ops"].items() if rx.search(k)]
    seconds, count = sum(v[0] for v in hits), sum(v[1] for v in hits)
    shapes = context["window"].get("kernel_calls", {}).get(shape)
    if not count or not shapes or not seconds:
        return None
    least = calls = 0.0
    for sizes in shapes:
        sizes = dict(sizes)
        dispatches = sizes.pop("dispatches")
        for f, b in CALLS[shape](context["spec"], **sizes):
            least += dispatches * flops.roofline_seconds(
                f, b, context["peaks"])[0]
            calls += dispatches
    return 100.0 * count * (least / calls) / seconds


READERS = {"trace_ssm_roofline": trace_ssm_roofline}
METRICS = {
    "ssd_scan_roofline": {"reader": "trace_ssm_roofline", "args": {
        "pattern": "^ssd_scan", "shape": "ssd_scan_call"}},
    "ssm_step_roofline": {"reader": "trace_ssm_roofline", "args": {
        "pattern": "^ssm_step", "shape": "ssm_step_call"}},
}
