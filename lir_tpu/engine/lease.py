"""Lease-based work-stealing sweep shards (ROADMAP item 1, offline
half).

Static ``multihost.host_shard`` partitioning has a failure mode the
PR-5 liveness machinery only *detects*: a slow host strangles the shard
fence (every fast host idles at the barrier), and a dead host's shard
is simply lost until an operator relaunches. This module converts
statically partitioned shards into LEASED shards:

- the pending grid is split into small shards
  (:func:`partition_shards`);
- shard ownership is a lease record — ``{holder, expiry, seq, done}``
  — riding the PR-9 manifest machinery's ``{"__meta__": ...}`` lines
  in a SHARED ``<results>.leases.jsonl`` log (one file all hosts
  append; the SweepManifest append discipline — single fsync'd write,
  torn trailing line tolerated and truncated on the next append —
  carries over verbatim, so a kill mid-claim leaves a resumable log);
- a holder RENEWS its lease at every manifest flush
  (:meth:`LeaseManager.attach_manifest` — renew-on-flush), so "alive"
  means "making durable progress", not merely "process exists";
- expiry is WALL-CLOCK (``time.time``): leases compare across hosts,
  and wall time is the only clock hosts share. (The serve-side
  breakers are the opposite case — per-process cooldowns on
  ``time.monotonic``; see faults/breaker.py.)
- a live host that runs out of unclaimed shards STEALS shards whose
  lease expired (holder dead or straggling) — and because PR 9's
  slot-scatter folds are idempotent, the stolen shard's re-scored rows
  land bitwise on the same accumulator cells, so the fence merge
  (``stats/streaming.merge_accums(..., allow_identical_overlap=True)``)
  still produces a lattice bitwise-identical to an uninterrupted
  static run (pinned by tests/test_lease.py and bench.py's "elastic"
  key).

Single-process runs degrade cleanly: one holder claims every shard in
order, and the lease log doubles as a shard-progress record.
"""

from __future__ import annotations

import time
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from ..utils.logging import get_logger
from ..utils.manifest import SweepManifest
from ..utils.profiling import LeaseStats

log = get_logger(__name__)

LEASE_SUFFIX = ".leases.jsonl"
LEASE_PREFIX = "lease:"

# The lease log is a SweepManifest used for its __meta__ machinery
# only; ordinary done-lines never appear, but the class needs key
# fields to construct.
_LEASE_KEY_FIELDS = ("shard",)


def partition_shards(cells: Sequence, cells_per_shard: int,
                     n_holders: int = 1) -> List[List]:
    """Split the pending cell list into contiguous shards of
    ``cells_per_shard`` cells (the stealing granularity). ``<= 0``
    derives ~4 shards per holder so every host has steal targets
    without the lease log dominating."""
    cells = list(cells)
    if not cells:
        return []
    if cells_per_shard <= 0:
        cells_per_shard = max(1, len(cells) // max(4 * n_holders, 1))
    return [cells[i:i + cells_per_shard]
            for i in range(0, len(cells), cells_per_shard)]


class LeaseManager:
    """One holder's view of the shared shard-lease log.

    Thread discipline: one sweep thread per holder drives it (claims,
    renews, steals); the only cross-thread caller is the manifest-flush
    wrapper installed by :meth:`attach_manifest`, which runs on the
    sweep writer thread — renews are therefore internally idempotent
    and cheap. Cross-HOST concurrency is resolved by the log itself:
    every decision re-reads the log first (:meth:`refresh`), and the
    append order on a shared filesystem arbitrates near-simultaneous
    claims (last write wins, seq strictly increases — the loser's next
    renew sees a foreign live lease and reports the lease LOST rather
    than continuing blind).
    """

    def __init__(self, path, holder: str, ttl_s: float = 300.0,
                 clock=time.time, stats: Optional[LeaseStats] = None):
        self.path = Path(path)
        self.holder = str(holder)
        self.ttl_s = float(ttl_s)
        self.clock = clock
        self.stats = stats if stats is not None else LeaseStats()
        self.held: set = set()       # shard ids this holder believes it owns
        self.n_shards: Optional[int] = None
        self._man = SweepManifest(self.path, _LEASE_KEY_FIELDS)

    # -- the log -------------------------------------------------------------

    def refresh(self) -> None:
        """Re-read the shared log (another host may have appended).
        A fresh SweepManifest parse keeps the torn-tail tolerance: a
        kill mid-append leaves a fragment the next parse skips and the
        next append truncates."""
        self._man = SweepManifest(self.path, _LEASE_KEY_FIELDS)
        self.stats.count("refreshes")

    def record(self, shard_id: int) -> Optional[Dict]:
        rec = self._man.meta.get(f"{LEASE_PREFIX}{int(shard_id)}")
        return dict(rec) if isinstance(rec, dict) else None

    def _write(self, shard_id: int, expiry: float, seq: int,
               done: bool = False) -> None:
        self._man.set_meta(f"{LEASE_PREFIX}{int(shard_id)}", {
            "holder": self.holder, "expiry": float(expiry),
            "seq": int(seq), "done": bool(done)})

    def expired(self, rec: Dict) -> bool:
        return float(rec.get("expiry", 0.0)) <= self.clock()

    def register_shards(self, n: int) -> None:
        self.n_shards = int(n)

    # -- claim / renew / steal / release -------------------------------------

    def claim(self, shard_id: int, steal: bool = False) -> bool:
        """Take the shard's lease. Refused (False) when the shard is
        done, or another holder's lease is still LIVE (double-claim
        refusal). An expired foreign lease needs ``steal=True`` — the
        explicit work-stealing event, counted separately."""
        self.refresh()
        rec = self.record(shard_id)
        now = self.clock()
        if rec is None:
            self._write(shard_id, now + self.ttl_s, 0)
            self.held.add(int(shard_id))
            self.stats.count("claims")
            return True
        if rec.get("done"):
            return False
        foreign = rec.get("holder") != self.holder
        if foreign and not self.expired(rec):
            self.stats.count("refused")
            return False
        if foreign:
            self.stats.count("expired_seen")
            if not steal:
                self.stats.count("refused")
                return False
            self.stats.count("steals")
            log.warning("lease: stealing shard %d from %s (lease "
                        "expired %.1fs ago)", shard_id, rec.get("holder"),
                        now - float(rec.get("expiry", 0.0)))
        else:
            self.stats.count("claims")
        self._write(shard_id, now + self.ttl_s,
                    int(rec.get("seq", 0)) + 1)
        self.held.add(int(shard_id))
        return True

    def renew(self, shard_id: int) -> bool:
        """Extend a held lease (called at flush boundaries). Returns
        False — and drops the shard from ``held`` — when the lease was
        stolen out from under this holder (it expired and a live host
        took it): the holder should stop spending device time on a
        shard it no longer owns (its folds so far are harmless —
        bitwise no-ops under the idempotent lattice)."""
        self.refresh()
        rec = self.record(shard_id)
        now = self.clock()
        if rec is not None and rec.get("holder") != self.holder \
                and not self.expired(rec):
            self.stats.count("lost")
            self.held.discard(int(shard_id))
            log.warning("lease: shard %d lost to %s (stolen after "
                        "expiry); abandoning it", shard_id,
                        rec.get("holder"))
            return False
        self._write(shard_id, now + self.ttl_s,
                    int((rec or {}).get("seq", 0)) + 1)
        self.held.add(int(shard_id))
        self.stats.count("renews")
        return True

    def renew_held(self) -> None:
        """Renew every held lease — the renew-on-flush hook."""
        for sid in sorted(self.held):
            self.renew(sid)

    def mark_done(self, shard_id: int) -> None:
        """Shard completed and durably flushed: the done record is the
        cross-host skip signal (a done shard is never claimable or
        stealable again)."""
        self._write(shard_id, self.clock(),
                    int((self.record(shard_id) or {}).get("seq", 0)) + 1,
                    done=True)
        self.held.discard(int(shard_id))
        self.stats.count("releases")
        self.stats.count("shards_done")

    def is_done(self, shard_id: int) -> bool:
        rec = self.record(shard_id)
        return bool(rec and rec.get("done"))

    def all_done(self, n_shards: Optional[int] = None) -> bool:
        n = self.n_shards if n_shards is None else int(n_shards)
        assert n is not None, "register_shards first"
        self.refresh()
        return all(self.is_done(s) for s in range(n))

    # -- iteration (the sweep driver's loop) ---------------------------------

    def claim_loop(self, shards: Sequence[Sequence]
                   ) -> Iterator[Tuple[int, Sequence]]:
        """Yield ``(shard_id, cells)`` for every shard this holder can
        take — unclaimed/own shards plus steals of expired foreign
        leases — repeated until nothing is claimable (remaining shards
        are done or held live elsewhere; the lease-aware fence owns
        waiting on those). Each holder scans from its own stable offset
        so simultaneously-starting hosts spread over the shard list
        instead of racing the same first claim."""
        import hashlib

        self.register_shards(len(shards))
        n = len(shards)
        if n == 0:
            return
        start = int(hashlib.md5(self.holder.encode()).hexdigest(),
                    16) % n
        order = list(range(start, n)) + list(range(0, start))
        while True:
            progressed = False
            for sid in order:
                if sid in self.held or self.is_done(sid):
                    continue
                if self.claim(sid, steal=True):
                    progressed = True
                    yield sid, shards[sid]
            if not progressed:
                return

    def steal_expired(self, shards: Sequence[Sequence]
                      ) -> Optional[Tuple[int, Sequence]]:
        """One steal attempt (the lease-aware fence's work unit):
        claim the first not-done shard whose lease is expired (or was
        never claimed). None when every remaining shard is held live."""
        self.refresh()
        for sid, cells in enumerate(shards):
            if self.is_done(sid) or sid in self.held:
                continue
            if self.claim(sid, steal=True):
                return sid, cells
        return None

    # -- renew-on-flush ------------------------------------------------------

    def attach_manifest(self, manifest) -> None:
        """Wrap the sweep manifest's ``mark_done_many`` so every flush
        (rows durably appended + marked) renews the held leases —
        progress IS the heartbeat."""
        inner = manifest.mark_done_many

        def marked(records):
            inner(records)
            self.renew_held()

        manifest.mark_done_many = marked
