"""Degradation ladder: isolate poison rows instead of failing a batch.

A dispatch that keeps failing after retries has two very different
causes with two very different remedies:

1. The DEVICE (or an executable) is broken — retrying subsets fails
   everywhere. The caller should fail the dispatch and let the circuit
   breaker take over.
2. One ROW is poison — a pathological prompt that crashes the kernel, a
   tokenizer edge case, a corrupt cache interaction. Failing the whole
   batch punishes every innocent neighbor, and under continuous
   batching the poison row re-queues with NEW neighbors and takes them
   down too: one bad request can wedge a whole service.

:func:`degrade_dispatch` tells them apart by bisection: retry the full
batch once (the caller has usually just dropped the AOT registry via
``ScoringEngine.degrade_to_lazy`` — a corrupt precompiled executable is
remedy zero), then split-and-recurse; rows that fail ALONE are poison
and come back as None, everything else comes back scored. Cost is
O(poison * log batch) extra dispatches, zero when the full-batch retry
succeeds.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

from .plan import InjectedPreemption  # noqa: F401  (re-export for callers)

# Status words the XLA/TPU runtime puts on an error that belongs to the
# PROGRAM it was handed, not to the moment it ran: the compiler refused
# it. Device trouble reads UNAVAILABLE / ABORTED / DEADLINE_EXCEEDED /
# DATA_LOSS / INTERNAL-without-these, and keeps every recovery it had.
_REFUSED_BY_COMPILER = ("INVALID_ARGUMENT", "UNIMPLEMENTED",
                        "Mosaic failed to compile", "failed to compile",
                        "Compilation failure")


def is_program_error(err: BaseException) -> bool:
    """True when a dispatch failed because its program is wrong — the
    tracer, the lowering or the compiler refused it — rather than
    because the device hiccuped. Such a failure is deterministic:
    retrying recompiles the same refusal, degrading to lazy jit compiles
    it again, and bisecting makes every row a "poison row". Every
    recovery site (sweep dispatch, the serve supervisors, the ladder
    below, and their retry loops through ``give_up=``) asks this first
    and lets the error surface.

    ValueError / TypeError / NotImplementedError are what JAX tracing
    and the Pallas TPU lowering raise; compiler-side refusals arrive as
    the runtime's error class carrying one of ``_REFUSED_BY_COMPILER``.
    Injected faults (RuntimeError), watchdog stalls and OOMs are none of
    these and recover as before."""
    if isinstance(err, (ValueError, TypeError, NotImplementedError)):
        return True
    if type(err).__name__ in ("JaxRuntimeError", "XlaRuntimeError"):
        msg = str(err)
        return any(tag in msg for tag in _REFUSED_BY_COMPILER)
    return False


def degrade_dispatch(score_fn: Callable[[list], List[dict]],
                     rows: Sequence,
                     log: Optional[Callable[[str], None]] = None,
                     ) -> List[Optional[dict]]:
    """Score ``rows`` through ``score_fn`` (which takes a row subset and
    returns one payload per row), bisecting on failure to isolate poison
    rows. Returns a list aligned with ``rows``: a payload dict, or None
    for rows that fail even in a batch of one.

    KeyboardInterrupt/SystemExit/InjectedPreemption always propagate —
    the ladder recovers work, it does not resist being killed. So does a
    program error (:func:`is_program_error`): a program the compiler
    refuses fails for every subset, and bisecting it would only report
    each innocent row as poison.
    """
    rows = list(rows)
    out: List[Optional[dict]] = [None] * len(rows)

    def solve(lo: int, hi: int) -> None:
        try:
            payloads = score_fn(rows[lo:hi])
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as err:  # noqa: BLE001 — bisect decides
            if is_program_error(err):
                raise
            if hi - lo == 1:
                if log is not None:
                    log(f"poison row isolated at index {lo}: {err!r}")
                return
            mid = (lo + hi) // 2
            solve(lo, mid)
            solve(mid, hi)
            return
        out[lo:hi] = list(payloads)

    if rows:
        solve(0, len(rows))
    return out
