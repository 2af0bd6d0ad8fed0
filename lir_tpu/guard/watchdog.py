"""Dispatch watchdog: stall detection for calls that never return.

A wedged device runtime (dead ICI link, stuck collective, runaway
kernel) parks the dispatching thread inside a C++ call that no signal
short of SIGKILL interrupts — ``except Exception`` recovery never runs
because nothing ever raises. :func:`watch_call` runs the call on a
disposable worker thread and polls it against a deadline from the
caller's thread; on expiry it dumps every live thread's stack (the
post-mortem a hung run otherwise never yields), ABANDONS the worker,
and raises :class:`DispatchStalled` — an ordinary ``RuntimeError`` so
the existing recovery machinery (sweep ladder retry, serve retry ->
degradation ladder -> breaker) treats a hang exactly like a raised
device fault: one deadline lost, not the run.

Deadlines come from :class:`DispatchWatchdog`, which prices each
dispatch through the SAME ``scheduler.bucket_cost()`` row-token model
the offline planner and online batcher use: the first successful
dispatch calibrates seconds-per-cost-unit (EWMA thereafter), and the
deadline is ``floor + multiple * predicted_seconds``
(``RuntimeConfig.watchdog_floor_s`` / ``watchdog_multiple``). Until
calibrated the watchdog observes without enforcing — a legitimate
first-dispatch compile can take minutes and must never be shot.

Abandonment is safe by construction: the only injected hang mode
(faults.SiteSchedule kind="hang") sleeps BEFORE touching the engine
and raises on release, so an abandoned worker never mutates the
KV-cache donation chain behind a live retry; a real wedged runtime
call is already beyond help and the recovery path's
``degrade_to_lazy()`` resets the donation chain anyway.
"""

from __future__ import annotations

import sys
import threading
import time
import traceback
from typing import Callable, Optional

from ..observe import tracing
from ..utils.logging import get_logger
from ..utils.profiling import GuardStats

log = get_logger(__name__)

DEFAULT_TICK_S = 0.05


class DispatchStalled(RuntimeError):
    """A watched call outlived its watchdog deadline. Synthetic on
    purpose: a real hang raises nothing, so this stands in for the
    device error the recovery machinery (ladder/breaker) expects."""


def dump_thread_stacks() -> str:
    """Every live thread's current stack, formatted — the post-mortem a
    hung process otherwise never produces. Pure introspection
    (sys._current_frames), safe to call from any thread."""
    frames = sys._current_frames()
    names = {t.ident: t for t in threading.enumerate()}
    parts = []
    for ident, frame in frames.items():
        t = names.get(ident)
        label = (f"{t.name} (daemon={t.daemon})" if t is not None
                 else f"ident={ident}")
        parts.append(f"--- thread {label} ---\n"
                     + "".join(traceback.format_stack(frame)))
    return "\n".join(parts)


def watch_call(fn: Callable, deadline_s: Optional[float],
               label: str = "call",
               on_tick: Optional[Callable[[], None]] = None,
               tick_s: float = DEFAULT_TICK_S):
    """Run ``fn()`` on a disposable daemon thread, polling every
    ``tick_s`` seconds from the caller's thread.

    - result / exception propagate to the caller (BaseException
      included — an injected preemption must unwind here exactly as it
      would inline);
    - ``on_tick`` runs on the CALLER's thread at every poll (the serve
      supervisor uses it to resolve in-flight rows whose deadline
      passed mid-dispatch — partial results immediately instead of
      waiting out the device call);
    - ``deadline_s=None`` waits forever (ticks still fire);
    - on expiry: dump all thread stacks to the log, abandon the worker
      (its eventual result or error is dropped and logged at INFO),
      raise :class:`DispatchStalled`;
    - the worker adopts the caller's open trace span as the parent of
      the spans ``fn`` opens (observe/tracing.adopt).
    """
    done = threading.Event()
    box: dict = {}
    state = {"abandoned": False}
    parent_span = tracing.current_span()

    def _run():
        tracing.adopt(parent_span)
        try:
            box["result"] = fn()
        except BaseException as err:  # noqa: BLE001 — re-raised by caller
            box["error"] = err
            if state["abandoned"]:
                log.info("abandoned %s eventually raised: %r", label, err)
        finally:
            if state["abandoned"] and "error" not in box:
                log.info("abandoned %s eventually completed; result "
                         "dropped", label)
            done.set()

    worker = threading.Thread(target=_run, name=f"watched:{label}",
                              daemon=True)
    start = time.monotonic()
    worker.start()
    while not done.wait(tick_s):
        if on_tick is not None:
            on_tick()
        if (deadline_s is not None
                and time.monotonic() - start >= deadline_s):
            state["abandoned"] = True
            log.error(
                "watchdog: %s exceeded its %.2fs deadline — abandoning "
                "the dispatch and surfacing DispatchStalled into the "
                "recovery path. Thread stacks:\n%s",
                label, deadline_s, dump_thread_stacks())
            raise DispatchStalled(
                f"{label} exceeded its {deadline_s:.2f}s watchdog "
                f"deadline (dispatch abandoned, thread stacks dumped)")
    if "error" in box:
        raise box["error"]
    return box["result"]


class DispatchWatchdog:
    """Deadline policy + calibration + counters for watched dispatches.

    ``multiple <= 0`` disables the watchdog entirely (every watch() is
    a plain call). Deadlines: ``floor_s + multiple * predicted``, where
    ``predicted`` is the calibrated seconds-per-cost-unit times the
    dispatch's ``bucket_cost`` (or, with no cost given, the EWMA of raw
    dispatch seconds). The floor is a hard minimum safety margin so a
    noisy calibration can never produce a hair-trigger deadline.
    """

    def __init__(self, multiple: float = 20.0, floor_s: float = 30.0,
                 stats: Optional[GuardStats] = None,
                 tick_s: float = DEFAULT_TICK_S,
                 seed_headroom: Optional[float] = None):
        self.multiple = float(multiple)
        self.floor_s = float(floor_s)
        self.stats = stats if stats is not None else GuardStats()
        self.tick_s = float(tick_s)
        # EWMA seed headroom, read from the scheduler's decode-floor
        # constants (scheduler.watchdog_seed_headroom — the fused/unfused
        # kernel spread): the FIRST calibration sample is inflated by
        # this ratio, so a deadline seeded on fast fused-kernel
        # dispatches never fires spuriously when a later dispatch
        # legitimately runs the slower dense decode path (a shape the
        # kernel can't fuse, or --no-fused-decode mid-fleet). The EWMA
        # tightens back within a few dispatches (0.7 decay).
        if seed_headroom is None:
            from ..engine import scheduler as _sched

            seed_headroom = _sched.watchdog_seed_headroom()
        self.seed_headroom = max(float(seed_headroom), 1.0)
        # Calibration EWMAs: observed from every dispatching thread
        # (sweep main thread, serve supervisor, AOT-wait paths), so
        # mutations hold the lock (enforced by lint/locks.py).
        self._rate: Optional[float] = None   # guarded-by: _lock
        self._flat: Optional[float] = None   # guarded-by: _lock
        self._lock = threading.Lock()

    @property
    def enabled(self) -> bool:
        return self.multiple > 0

    @property
    def calibrated(self) -> bool:
        with self._lock:
            return self._flat is not None

    def deadline_for(self, cost: Optional[float]) -> Optional[float]:
        """Seconds this dispatch may take before it counts as stalled,
        or None while uncalibrated (observe-only: the first dispatch of
        a fresh engine may legitimately compile for minutes)."""
        if not self.enabled:
            return None
        with self._lock:
            rate, flat = self._rate, self._flat
        if cost is not None and rate is not None:
            return self.floor_s + self.multiple * rate * max(float(cost),
                                                             1.0)
        if flat is not None:
            return self.floor_s + self.multiple * flat
        return None

    def observe(self, cost: Optional[float], elapsed: float) -> None:
        """Fold one successful dispatch into the calibration (EWMA,
        0.7 old / 0.3 new — adapts within a few dispatches but one
        outlier can't crater the deadline)."""
        with self._lock:
            if cost is not None and cost > 0:
                r = elapsed / max(float(cost), 1.0)
                self._rate = (r * self.seed_headroom if self._rate is None
                              else 0.7 * self._rate + 0.3 * r)
            self._flat = (elapsed * self.seed_headroom
                          if self._flat is None
                          else 0.7 * self._flat + 0.3 * elapsed)

    def watch(self, fn: Callable, cost: Optional[float] = None,
              site: str = "dispatch", label: str = "",
              on_tick: Optional[Callable[[], None]] = None):
        """Run one dispatch under the watchdog. Successful calls feed
        the calibration; expiries count into ``stats.stalls[site]`` and
        raise DispatchStalled for the caller's recovery machinery."""
        if not self.enabled:
            return fn()
        deadline = self.deadline_for(cost)
        if deadline is None and on_tick is None:
            # Uncalibrated and nobody needs ticks: run inline (no
            # thread), observe, enforce from the next dispatch on.
            t0 = time.monotonic()
            out = fn()
            self.observe(cost, time.monotonic() - t0)
            return out
        self.stats.site("watched", site)
        t0 = time.monotonic()
        try:
            out = watch_call(fn, deadline, label=label or site,
                             on_tick=on_tick, tick_s=self.tick_s)
        except DispatchStalled:
            self.stats.site("stalls", site)
            self.stats.count("stall_dumps")
            raise
        self.observe(cost, time.monotonic() - t0)
        return out
