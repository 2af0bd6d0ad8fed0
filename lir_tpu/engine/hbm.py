"""Unified HBM governor: one memory ledger, a pressure-driven
degradation ladder, and reclaim-and-retry OOM routing.

Before this module, HBM was governed by four uncoordinated mechanisms:
the WeightCache budget (models/weights.py — loud terminal
``WeightCacheOOM``), the page-pool size flag (models/paged.py), the
piggyback two-cache headroom gate (engine/runner.py), and spec-draft
pins (engine/fleet.py) — and a real device OOM mid-sweep simply
re-raised ("the batch ladder owns OOM" only in bench/tools). vLLM-class
servers treat this as table stakes: a single ledger of who holds HBM
and a reversible degradation order when it runs out. DistServe/
Mooncake-style disaggregation (ROADMAP item 2) additionally makes
per-replica memory a *placement* input, so the governor's pressure
gauge is exported to the router (serve/router.py) beside weight
residency.

Three pieces:

- **Ledger.** Every HBM consumer registers projected bytes under a
  stable name (``register``/``update``/``unregister``): engine params,
  the KV page pool, the dispatch/handoff donation caches, the one held
  trunk (``trunk:<model>``, engine/runner.py), spec-draft
  pins, fleet weight-cache residency, the streaming accumulator
  lattice. ``admit`` checks a projected allocation against the budget
  BEFORE the bytes exist (counters ``admits``/``denials``), and the
  ledger total / budget ratio is the **pressure** gauge, published
  into :class:`~lir_tpu.utils.profiling.MemStats` (the ``mem`` source
  of the unified metrics snapshot, next to ``device_memory_stats()``).
- **Degradation ladder.** Sustained pressure above
  ``GovernorConfig.engage_pressure`` walks one rung per
  ``sustain_ticks`` dispatches, in reclaim order:

  1. ``evict_weights`` — drop one idle (unreferenced, unpinned) LRU
     model from the fleet weight cache;
  2. ``evict_pages``   — evict cold radix pages from the KV page pool;
  3. ``no_piggyback``  — stop opening piggyback chains (a chain keeps
     TWO dispatch caches live);
  4. ``no_spec``       — disable speculative drafting (the sequential
     path is already bitwise-identical);
  5. ``batch_down``    — halve the serve batcher's dispatch rows;
  6. ``shed``          — backpressure: refuse new submits.

  Every rung is REVERSIBLE: pressure sustained below
  ``engage - hysteresis`` releases the most recent rung (counters
  ``rung_downs``/``rung_ups`` record both directions), so a cleared
  squeeze restores full throughput without a restart. None of the
  rungs can change results — eviction re-loads/re-prefills bitwise,
  piggyback/spec OFF are pinned bitwise-identical, and batch
  composition is masked out of every readout.
- **OOM routing.** ``handle_oom(site)`` is called by the sweep's
  dispatch recovery and the serve supervisor when
  ``is_oom_error(err)``: the governor force-engages the reclaim rungs
  (weights, pages, piggyback) immediately — no sustain wait — and
  returns True when anything was freed, telling the caller to retry
  the dispatch ONCE. A second OOM is the irreducible dispatch: the
  caller quarantines it (serve resolves its rows as errors WITHOUT
  feeding the circuit breaker — capacity is not device death; sweep
  raises :class:`HbmExhausted` with the full ledger arithmetic for the
  bench/tools batch ladder).

The seeded ``hbm_squeeze`` fault kind (faults/plan.py,
``wrap_governor``) shrinks the ledger budget mid-run and auto-restores
it, proving the whole walk down AND back up under chaos
(tools/chaos_smoke.py scenario 10, ``make mem-smoke``, bench.py's
"memory" headline key).
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Tuple

from ..config import GovernorConfig
from ..utils.logging import get_logger
from ..utils.profiling import MemStats

log = get_logger(__name__)

# Reclaim order — the ladder walks DOWN this list under pressure and
# back UP it (reverse order) when pressure clears. Indexes are the
# MemStats.rung gauge. With a tier store attached (serve/tiers.py),
# the first two rungs become reversible DEMOTIONS: evict_weights
# records the victim's staged tree to the disk tier before eviction
# (engine/fleet.py) and evict_pages exports the coldest radix leaves
# to the host/disk ladder before their pages leave HBM
# (engine/runner._evict_cold_pages) — same bytes freed, nothing
# deleted.
RUNGS: Tuple[str, ...] = ("evict_weights", "evict_pages", "no_piggyback",
                          "no_spec", "batch_down", "shed")
# Rungs that free bytes NOW — the set handle_oom force-engages.
RECLAIM_RUNGS: Tuple[str, ...] = ("evict_weights", "evict_pages",
                                  "no_piggyback")


class HbmExhausted(RuntimeError):
    """A dispatch OOMed even after the governor reclaimed everything
    reclaimable — the irreducible dispatch. Carries the full ledger
    arithmetic so the operator (or the bench's batch ladder) can size
    the fix instead of guessing."""


class OomSignal(BaseException):
    """Control-flow marker lifting a device OOM OUT of a generic
    ``except Exception`` retry boundary. BaseException on purpose,
    mirroring faults.InjectedPreemption's rationale: an exponential-
    backoff loop re-attempting the SAME allocation can only re-OOM —
    capacity is not transience — so the serve supervisor must see the
    OOM immediately and route it through the governor's
    reclaim-and-retry instead of burning its retry budget and feeding
    the circuit breaker. Always caught explicitly one frame up; never
    escapes the dispatch path."""

    def __init__(self, err: BaseException):
        super().__init__(str(err))
        self.err = err


def device_bytes_limit() -> Optional[int]:
    """The first device's reported HBM limit. None ONLY on the CPU
    backend (host RAM governs there and no budget is derived); on an
    accelerator a missing ``bytes_limit`` is an error — a governor that
    silently never engages hides the device."""
    import jax

    dev = jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if limit:
        return int(limit)
    if dev.platform == "cpu":
        return None
    raise RuntimeError(
        f"{dev.platform} device {dev.device_kind!r} reports no "
        f"memory_stats()['bytes_limit']; the HBM governor cannot derive "
        f"a budget (set GovernorConfig.budget_bytes explicitly)")


def device_budget_bytes(reserve_frac: float = 0.08) -> Optional[int]:
    """The device's reported HBM limit minus the reserve slack, or None
    on the CPU backend (CPU smoke — host RAM governs and the ladder
    never engages)."""
    limit = device_bytes_limit()
    if limit is None:
        return None
    return int(limit * (1.0 - reserve_frac))


class HbmGovernor:
    """One memory ledger + the pressure-driven degradation ladder.

    Host-side bookkeeping only (never holds device buffers, never
    blocks on device work). Thread-safe throughout: the sweep dispatch
    loop, the serve supervisor, fleet weight-cache listeners, and the
    router's pressure reads all touch it concurrently.
    """

    def __init__(self, config: Optional[GovernorConfig] = None,
                 stats: Optional[MemStats] = None,
                 budget_bytes: Optional[int] = None):
        self.cfg = config if config is not None else GovernorConfig()
        self.stats = stats if stats is not None else MemStats()
        if budget_bytes is None:
            budget_bytes = self.cfg.budget_bytes
            if budget_bytes is None and self.cfg.enabled:
                budget_bytes = device_budget_bytes(
                    self.cfg.hbm_reserve_frac)
        self._lock = threading.RLock()
        self._base_budget = budget_bytes       # guarded-by: _lock
        self._adopted_base = False             # guarded-by: _lock
        self._squeeze_frac = 1.0               # guarded-by: _lock
        self._squeeze_left = 0                 # guarded-by: _lock
        self._entries: Dict[str, int] = {}     # guarded-by: _lock
        self._level = 0                        # guarded-by: _lock
        self._over_ticks = 0                   # guarded-by: _lock
        self._under_ticks = 0                  # guarded-by: _lock
        # rung name -> (engage_fn() -> freed anything, release_fn)
        self._actions: Dict[str, Tuple[Optional[Callable[[], bool]],
                                       Optional[Callable[[], None]]]] \
            = {}                               # guarded-by: _lock
        self._publish_locked()

    # -- the ledger ----------------------------------------------------------

    def register(self, name: str, nbytes: int) -> None:
        """Make one consumer's projected bytes visible to the ledger
        (idempotent — re-registering replaces)."""
        with self._lock:
            self._entries[str(name)] = max(int(nbytes), 0)
            self._publish_locked()

    update = register

    def unregister(self, name: str) -> None:
        with self._lock:
            self._entries.pop(str(name), None)
            self._publish_locked()

    def ledger(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._entries)

    @property
    def ledger_bytes(self) -> int:
        with self._lock:
            return sum(self._entries.values())

    @property
    def budget_bytes(self) -> Optional[int]:
        """The CURRENT governed budget (squeeze applied), or None when
        unbounded."""
        with self._lock:
            return self._budget_locked()

    def _budget_locked(self) -> Optional[int]:  # guarded-by: _lock
        if self._base_budget is None:
            return None
        return int(self._base_budget * self._squeeze_frac)

    def headroom(self) -> Optional[int]:
        """Budget minus ledger (None when unbounded; floor 0)."""
        with self._lock:
            budget = self._budget_locked()
            if budget is None:
                return None
            return max(budget - sum(self._entries.values()), 0)

    def pressure(self) -> float:
        """ledger / budget (0.0 when unbounded — nothing to press
        against)."""
        with self._lock:
            budget = self._budget_locked()
            if not budget:
                return 0.0
            return sum(self._entries.values()) / budget

    def admit(self, name: str, nbytes: int) -> bool:
        """Admission check: would ``nbytes`` more for ``name`` fit the
        budget? Counts ``admits``/``denials``; advisory — the caller
        decides whether a denial is fatal (fleet boot validation) or a
        reclaim trigger (WeightCache insert)."""
        with self._lock:
            budget = self._budget_locked()
            if budget is None:
                self.stats.count("admits")
                return True
            projected = (sum(self._entries.values())
                         - self._entries.get(str(name), 0) + int(nbytes))
            if projected <= budget:
                self.stats.count("admits")
                return True
            self.stats.count("denials")
            return False

    def _publish_locked(self) -> None:  # guarded-by: _lock
        total = sum(self._entries.values())
        budget = self._budget_locked()
        self.stats.gauge("ledger_bytes", int(total))
        self.stats.gauge("budget_bytes", int(budget or 0))
        self.stats.gauge("pressure",
                         float(total / budget) if budget else 0.0)
        self.stats.gauge("rung", int(self._level))

    # -- rung actions --------------------------------------------------------

    def set_action(self, rung: str,
                   engage: Optional[Callable[[], bool]] = None,
                   release: Optional[Callable[[], None]] = None) -> None:
        """Attach reclaim callbacks to a rung (fleet: evict one idle LRU
        model; engine: evict cold radix pages). Flag rungs
        (no_piggyback/no_spec/batch_down/shed) need no callbacks —
        consumers poll :meth:`allows`/:meth:`batch_cap`/
        :meth:`should_shed` instead. ``engage`` returns True when it
        actually freed something (drives handle_oom's retry decision)."""
        assert rung in RUNGS, f"unknown governor rung {rung!r}"
        with self._lock:
            self._actions[rung] = (engage, release)

    def allows(self, feature: str) -> bool:
        """False while the named flag rung is engaged. ``feature`` is
        "piggyback" or "spec"."""
        rung = {"piggyback": "no_piggyback", "spec": "no_spec"}[feature]
        with self._lock:
            return self._level <= RUNGS.index(rung)

    def batch_cap(self, full: int) -> int:
        """The serve batcher's dispatch-row cap: halved while the
        batch_down rung is engaged (power-of-two preserved so the
        capped shape is one the precompile grid already covers)."""
        with self._lock:
            engaged = self._level > RUNGS.index("batch_down")
        return max(full // 2, 1) if engaged else full

    def should_shed(self) -> bool:
        """True while the terminal backpressure rung is engaged —
        submits then resolve shed instead of queueing behind memory
        that is not coming back this tick."""
        with self._lock:
            engaged = self._level > RUNGS.index("shed")
        if engaged:
            self.stats.count("sheds")
        return engaged

    @property
    def level(self) -> int:
        with self._lock:
            return self._level

    def engaged_rungs(self) -> List[str]:
        with self._lock:
            return list(RUNGS[: self._level])

    # -- the ladder ----------------------------------------------------------

    def _engage_locked(self, reason: str) -> bool:  # guarded-by: _lock
        """Walk one rung down; returns True when the rung's action
        freed bytes (flag rungs count as engaged-but-nothing-freed)."""
        if self._level >= len(RUNGS):
            return False
        rung = RUNGS[self._level]
        self._level += 1
        self.stats.site("rung_downs", rung)
        engage, _ = self._actions.get(rung, (None, None))
        freed = False
        if engage is not None:
            try:
                freed = bool(engage())
            except Exception:  # noqa: BLE001 — a broken reclaim hook
                # must not take the dispatch path down with it
                log.exception("governor rung %s engage action failed",
                              rung)
        log.warning("hbm governor: engaged rung %s (%s; pressure %.2f, "
                    "level %d/%d)", rung, reason, self.pressure(),
                    self._level, len(RUNGS))
        self._publish_locked()
        return freed

    def _release_locked(self) -> None:  # guarded-by: _lock
        if self._level <= 0:
            return
        self._level -= 1
        rung = RUNGS[self._level]
        self.stats.site("rung_ups", rung)
        _, release = self._actions.get(rung, (None, None))
        if release is not None:
            try:
                release()
            except Exception:  # noqa: BLE001
                log.exception("governor rung %s release action failed",
                              rung)
        log.info("hbm governor: released rung %s (pressure %.2f, level "
                 "%d/%d)", rung, self.pressure(), self._level,
                 len(RUNGS))
        self._publish_locked()

    def tick(self) -> None:
        """One dispatch boundary: re-read pressure, walk the ladder.
        Sustained over-pressure (``sustain_ticks`` consecutive ticks
        above ``engage_pressure``) engages one rung; sustained
        under-pressure (below ``engage - hysteresis``) releases one —
        the hysteresis band between the two is quiet, so a rung can
        never flap on the threshold itself. An active squeeze counts
        down here and restores the budget when it expires."""
        if not self.cfg.enabled:
            return
        with self._lock:
            if self._squeeze_left > 0:
                self._squeeze_left -= 1
                if self._squeeze_left == 0:
                    self._squeeze_frac = 1.0
                    if self._adopted_base:
                        # The base was adopted from the ledger for the
                        # squeeze's sake (unbounded governor): give the
                        # unboundedness back, or pressure would sit at
                        # exactly 1.0 forever.
                        self._base_budget = None
                        self._adopted_base = False
                    log.info("hbm governor: squeeze expired — budget "
                             "restored")
            p = (0.0 if not self._budget_locked()
                 else sum(self._entries.values()) / self._budget_locked())
            sustain = max(int(self.cfg.sustain_ticks), 1)
            if p >= self.cfg.engage_pressure:
                self._over_ticks += 1
                self._under_ticks = 0
                if self._over_ticks >= sustain:
                    self._over_ticks = 0
                    self._engage_locked(f"pressure {p:.2f}")
            elif p <= self.cfg.engage_pressure - self.cfg.hysteresis:
                self._under_ticks += 1
                self._over_ticks = 0
                if self._under_ticks >= sustain and self._level > 0:
                    self._under_ticks = 0
                    self._release_locked()
            else:
                self._over_ticks = 0
                self._under_ticks = 0
            self._publish_locked()

    # -- OOM routing ---------------------------------------------------------

    def handle_oom(self, site: str) -> bool:
        """A real device OOM reached the dispatch path: force-engage
        the reclaim rungs immediately (no sustain wait — the device
        already told us the ledger lies) and report whether anything
        was actually freed, i.e. whether a single retry is worth the
        caller's time. The engaged rungs release through the ordinary
        hysteresis walk once pressure clears."""
        self.stats.site("oom_events", site)
        import jax

        # What the DEVICE says it holds, beside what the ledger says
        # (on a TPU, bytes_reservable_limit is what program temp regions
        # may still take — the ledger does not model it).
        log.warning("device OOM at %s: memory_stats %s; ledger %s", site,
                    jax.devices()[0].memory_stats(), self.ledger())
        if not self.cfg.enabled:
            return False
        freed = False
        with self._lock:
            target = RUNGS.index(RECLAIM_RUNGS[-1]) + 1
            while self._level < target:
                freed = self._engage_locked(f"device OOM at {site}") \
                    or freed
        if freed:
            self.stats.count("oom_reclaims")
        else:
            self.stats.count("oom_exhausted")
        return freed

    def oom_message(self, site: str, err: BaseException) -> str:
        """The HbmExhausted arithmetic: who holds what against which
        budget, so the irreducible dispatch is sized, not guessed."""
        with self._lock:
            entries = dict(self._entries)
            budget = self._budget_locked()
        held = ", ".join(f"{k}={v / 2**30:.2f} GiB"
                         for k, v in sorted(entries.items())) or "nothing"
        total = sum(entries.values())
        return (f"device OOM at {site} survived governor reclaim "
                f"(ledger {total / 2**30:.2f} GiB"
                f"{'' if budget is None else f' / budget {budget / 2**30:.2f} GiB'}; "
                f"holders: {held}; engaged rungs: "
                f"{','.join(self.engaged_rungs()) or 'none'}): {err!r}")

    # -- chaos ---------------------------------------------------------------

    def squeeze(self, frac: float, calls: int = 8) -> None:
        """Shrink the governed budget to ``frac`` of its base for the
        next ``calls`` ticks (the seeded ``hbm_squeeze`` fault kind's
        entry point — faults/plan.wrap_governor). Auto-restores, so
        the ladder's walk back up is part of the same proof. A governor
        with no base budget adopts the current ledger total as one
        (the CPU-smoke path: squeezing 'unbounded' must still bite)."""
        with self._lock:
            if self._base_budget is None:
                self._base_budget = max(sum(self._entries.values()), 1)
                self._adopted_base = True
            self._squeeze_frac = max(float(frac), 0.01)
            self._squeeze_left = max(int(calls), 1)
            self.stats.count("squeezes")
            self._publish_locked()
        log.warning("hbm governor: budget squeezed to %.0f%% for %d "
                    "ticks (pressure now %.2f)", frac * 100, calls,
                    self.pressure())

    def summary(self) -> Dict[str, object]:
        out = self.stats.summary()
        out["ledger"] = {k: int(v) for k, v in self.ledger().items()}
        out["engaged"] = self.engaged_rungs()
        return out


def validate_fleet_budget(model_id: str, nbytes: int,
                          budget_bytes: Optional[int],
                          governor: Optional[HbmGovernor] = None) -> None:
    """Fleet-boot budget validation: a weight-cache budget smaller than
    one configured model can NEVER hold it — every sweep would die
    mid-run as a WeightCacheOOM. Fail construction instead, with the
    full HBM arithmetic (per-model bytes, what else the ledger holds —
    page-pool reservation included — and the remaining headroom)."""
    if budget_bytes is None or nbytes <= budget_bytes:
        if governor is not None:
            governor.admit(f"weights:{model_id}", nbytes)
        return
    held = ""
    headroom = budget_bytes - nbytes
    if governor is not None:
        governor.stats.count("denials")
        entries = {k: v for k, v in governor.ledger().items()
                   if not k.startswith("weights")}
        if entries:
            held = ("; other HBM holders: "
                    + ", ".join(f"{k}={v / 2**30:.2f} GiB"
                                for k, v in sorted(entries.items())))
            headroom -= sum(entries.values())
    raise ValueError(
        f"weight-cache budget {budget_bytes / 2**30:.2f} GiB cannot hold "
        f"model {model_id!r} ({nbytes / 2**30:.2f} GiB) even empty — "
        f"headroom after the model would be {headroom / 2**30:.2f} GiB"
        f"{held}. Raise --weight-cache-gb above the largest configured "
        f"model (DEPLOY.md §1o sizing arithmetic) or drop the model "
        f"from the fleet.")
