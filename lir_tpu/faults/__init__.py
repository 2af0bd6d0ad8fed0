"""Fault-injection harness + self-healing primitives.

The failure path engineered like the hot path (ROADMAP north star): a
deterministic, seeded fault-injection layer (plan.FaultPlan) that wraps
the engine and serve boundaries, and the three recovery mechanisms it
proves out —

- breaker.CircuitBreaker: the serve health flag as a real closed/open/
  half-open breaker, so a transient device outage no longer kills the
  server forever (serve/server.py);
- ladder.degrade_dispatch: bisect a failing batch to isolate poison
  rows, resolve only the culprits as errors (serve/server.py, after the
  AOT->lazy fallback runner.ScoringEngine.degrade_to_lazy);
- crash-consistent resume: torn-tail-tolerant fsync'd manifest appends
  (utils/manifest.py), results-seeded done-sets (engine/sweep.py), and
  the serve SIGTERM state checkpoint (server.shutdown_checkpoint).

Silent failure kinds (``SiteSchedule.hang_at`` / ``nan_at``) exercise
the third reliability layer, lir_tpu/guard: the dispatch watchdog must
stall-out an injected hang into THESE recovery mechanisms, and the
numerics guard must quarantine injected-NaN rows as error:numerics.

Chaos drivers: ``make chaos-smoke`` (tools/chaos_smoke.py) and
``python bench.py --chaos`` run sweeps and serve sessions under seeded
kill/fault schedules and assert zero lost / zero duplicated / zero
corrupted rows vs a fault-free run; counters land in
profiling.FaultStats and profiling.GuardStats.
"""

from .breaker import CLOSED, HALF_OPEN, OPEN, CircuitBreaker
from .ladder import degrade_dispatch, is_program_error
from .plan import (KINDS, SITES, FaultPlan, InjectedFault,
                   InjectedPreemption, InjectedReplicaKill, SiteSchedule,
                   corrupt_export_chunks, corrupt_result_nan,
                   tear_jsonl_tail, wrap_engine, wrap_governor,
                   wrap_migrator, wrap_replica, wrap_server, wrap_tiers)

__all__ = [
    "is_program_error",
    "FaultPlan", "SiteSchedule", "InjectedFault", "InjectedPreemption",
    "InjectedReplicaKill",
    "SITES", "KINDS", "wrap_engine", "wrap_server", "wrap_replica",
    "wrap_governor", "wrap_migrator", "wrap_tiers", "tear_jsonl_tail",
    "corrupt_result_nan", "corrupt_export_chunks",
    "CircuitBreaker", "CLOSED", "OPEN", "HALF_OPEN",
    "degrade_dispatch",
]
