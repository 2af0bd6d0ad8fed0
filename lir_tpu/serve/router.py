"""Elastic multi-replica serving: the failover router (ROADMAP item 1).

A single :class:`~lir_tpu.serve.server.ScoringServer` (or fleet server)
is a single point of failure: PR 5's heartbeat machinery *detects* a
dead peer, but a lost server still costs the run. Production
disaggregated stacks (Mooncake-style separation of placement from
execution, Orca-style continuous batching behind a router) treat replica
death and stragglers as the steady state. This module is that front
process: a :class:`ReplicaRouter` spreads one request stream over N
replica servers, each wrapped in its own router-side
:class:`~lir_tpu.faults.breaker.CircuitBreaker`.

Placement reads three live signals per replica:

- **queue depth** (queue + bucketed rows) — the load signal;
- **breaker state** — a replica that keeps erroring (or was observed
  dead) stops receiving traffic until its cooldown probe;
- **weight residency** — for fleet replicas, the WeightCache's
  ``add_listener`` insert/evict events feed a router-side residency
  map, so a model's requests land on the replica already holding its
  weights (weight residency as a first-class routing signal), with an
  SLO term (the replica's oldest queued-row wait against the request's
  remaining deadline) keeping deadline-tight requests away from stale
  backlogs.

Failover is the headline contract:

- a replica that answers ``error`` (or sheds) triggers re-admission to
  the next-best replica while the deadline allows — ``failovers``;
- a replica KILLED mid-dispatch (:meth:`ReplicaRouter.kill_replica`, or
  a ``replica_kill`` fault schedule) has its in-flight requests
  re-admitted to survivors immediately — ``re_admitted`` — and its
  breaker force-opens (``trip``), so recovery after a rejoin flows
  through the ordinary open -> half_open -> closed probe;
- EXACTLY-ONCE resolution: every request resolves through one
  :class:`~lir_tpu.serve.queue.ServeFuture` (first resolution wins) and
  payloads are content-addressed with the existing ResultCache key, so
  a late payload from a zombie replica can never double-resolve — it is
  counted (``zombie_payloads``) and dropped. Because every replica runs
  the same engine configuration, the winning payload is bitwise the
  payload any replica would have produced (pinned by
  tests/test_router.py) — PAPER.md's axis results cannot depend on
  which replica scored a row;
- requests inside the deadline whisker (``RouterConfig.hedge_s``) are
  HEDGED onto a second replica with first-payload-wins resolution.

Disaggregated prefill/decode (ROADMAP item 2; serve/migrate.py): with
``roles`` splitting the pool into PREFILL-role and DECODE-role replicas
and ``MigrationConfig.enabled``, a long prompt prefills on a prefill
replica, its KV pages stream to the chosen decode replica (chunked,
double-buffered, checksummed), and decode resumes there bitwise-
identically to a colocated run. The cluster-wide prefix index
(engine/prefix_tree.ClusterPrefixIndex, fed by every replica tree's
page listener events exactly like the residency map above) adds PAGE
residency to ``_pick``'s signals — a prefix prefilled anywhere is warm
everywhere, and a request whose pages sit on some replica PULLS them
instead of re-prefilling. A stalled or corrupted transfer falls back to
local re-prefill on the decode replica (``refetch_fallbacks``) — never
a wrong answer.

Everything here is host-side; replicas are ordinary servers (in-process
today — the JSONL/network hop is a transport detail the router's
contract does not depend on).
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

from ..config import MigrationConfig, RouterConfig
from ..engine import prefix_tree
from ..engine import tokens as tok
from ..faults import CircuitBreaker
from ..observe import registry as metrics_mod
from ..observe import tracing
from ..utils.logging import get_logger
from ..utils.profiling import MigrationStats, RouterStats, ServeStats
from . import migrate as migrate_mod
from .cache import ResultCache, content_key
from .queue import (STATUS_ERROR, STATUS_OK, STATUS_SHED, ServeFuture,
                    ServeRequest, ServeResult)

log = get_logger(__name__)

# The measurement fields a payload carries — what the router's dedup
# cache stores and what ServeResult(**payload) re-expands (the same
# projection ScoringServer._resolve_ok caches).
PAYLOAD_FIELDS = ("model_response", "model_confidence_response",
                  "token_1_prob", "token_2_prob", "log_probabilities",
                  "confidence_value", "weighted_confidence")


def _payload_of(res: ServeResult) -> Dict:
    return {f: getattr(res, f) for f in PAYLOAD_FIELDS}


class _Replica:
    """Router-side state for one replica server."""

    def __init__(self, replica_id: str, server, breaker: CircuitBreaker,
                 role: str = "both"):
        assert role in ("prefill", "decode", "both"), role
        self.replica_id = replica_id
        self.server = server
        self.breaker = breaker
        self.alive = True
        # Disaggregated serving (serve/migrate.py): "prefill" replicas
        # absorb long-prompt prefill-only dispatches and receive decode
        # traffic only as a last resort (every decode-capable replica
        # dead); "decode"/"both" replicas serve scoring traffic.
        self.role = role
        self.is_fleet = hasattr(server, "fleet")
        self._lock = threading.Lock()
        # Requests currently attempted on this replica, by pending id —
        # the re-admission set when this replica dies.
        self.inflight: Dict[int, "_Pending"] = {}  # guarded-by: _lock
        # Residency map fed by WeightCache listener events (may fire
        # under the cache lock: cheap set ops only).
        self.resident: Set[str] = set()  # guarded-by: _lock

    def seed_resident(self, models) -> None:
        with self._lock:
            self.resident = set(models)

    def on_weight_event(self, event: str, model_id: str) -> None:
        with self._lock:
            if event == "insert":
                self.resident.add(model_id)
            elif event == "evict":
                self.resident.discard(model_id)

    def resident_view(self) -> Set[str]:
        with self._lock:
            return set(self.resident)

    def track(self, pending: "_Pending") -> None:
        with self._lock:
            self.inflight[id(pending)] = pending

    def untrack(self, pending: "_Pending") -> None:
        with self._lock:
            self.inflight.pop(id(pending), None)

    def take_inflight(self) -> List["_Pending"]:
        with self._lock:
            victims = list(self.inflight.values())
            self.inflight.clear()
        return victims

    @property
    def depth(self) -> int:
        try:
            return int(self.server.queue_depth)
        except Exception:  # noqa: BLE001 — a dying replica reads as deep
            return 1 << 20

    def oldest_wait(self, now: float) -> float:
        fn = getattr(self.server, "oldest_wait", None)
        if fn is None:
            return 0.0
        try:
            return float(fn(now))
        except Exception:  # noqa: BLE001
            return 0.0

    @property
    def pressure(self) -> float:
        """The replica's HBM-governor ledger pressure (engine/hbm.py) —
        memory as a placement signal beside queue depth and weight
        residency: a squeezed replica is a worse home for new work even
        when its queue looks shallow. 0 when ungoverned/unbounded."""
        try:
            return float(getattr(self.server, "hbm_pressure", 0.0))
        except Exception:  # noqa: BLE001
            return 0.0


class _Pending:
    """One routed request's lifecycle across attempts."""

    __slots__ = ("request", "model_id", "future", "key", "t_submit",
                 "t_deadline", "tried", "hedged", "resolved", "lock")

    def __init__(self, request: ServeRequest, model_id: str, key: str,
                 t_submit: float, t_deadline: float):
        self.request = request
        self.model_id = model_id
        self.future = ServeFuture()
        self.key = key
        self.t_submit = t_submit
        self.t_deadline = t_deadline
        self.tried: Set[str] = set()   # guarded-by: lock
        self.hedged = False            # guarded-by: lock
        self.resolved = False          # guarded-by: lock
        self.lock = threading.Lock()

    def claim_resolution(self) -> bool:
        """True exactly once — the winning attempt's right to resolve."""
        with self.lock:
            if self.resolved:
                return False
            self.resolved = True
            return True


class _Migration:
    """One disaggregated handoff chain's lifecycle (prefill -> export
    -> transfer -> import -> score), claimable exactly once: whichever
    of {chain completion, failure fallback, tick timeout, replica
    kill} claims first decides where the request scores — the others
    become no-ops (a late-landing import merely warms the pool with
    verified pages)."""

    __slots__ = ("pending", "dst", "src", "bucket", "prefix_ids",
                 "dst_tokens", "t_deadline", "_claimed", "_lock")

    def __init__(self, pending: _Pending, dst: "_Replica",
                 src: "_Replica", bucket: int,
                 prefix_ids: Tuple[int, ...], dst_tokens: int,
                 t_deadline: float):
        self.pending = pending
        self.dst = dst
        self.src = src
        self.bucket = int(bucket)
        self.prefix_ids = prefix_ids
        self.dst_tokens = int(dst_tokens)
        self.t_deadline = t_deadline
        self._claimed = False          # guarded-by: _lock
        self._lock = threading.Lock()

    @property
    def claimed(self) -> bool:
        with self._lock:
            return self._claimed

    def claim(self) -> bool:
        with self._lock:
            if self._claimed:
                return False
            self._claimed = True
            return True


class ReplicaRouter:
    """Failover router over N replica servers (module docstring).

    ``replicas`` is ``[(replica_id, server), ...]`` — servers are
    started/stopped by the caller (they may be shared with other
    routers or direct clients); :meth:`start`/:meth:`stop` only own the
    router's tick thread (hedging scans + breaker promotion +
    migration timeouts).

    ``roles`` maps replica ids to "prefill" / "decode" / "both"
    (default "both" — the role-less PR-12 router exactly). With at
    least one prefill-role and one decode-capable replica and
    ``migrate.enabled``, the router serves DISAGGREGATED: long prompts
    prefill on a prefill replica, their KV pages migrate to the chosen
    decode replica (serve/migrate.py), and decode resumes there
    bitwise-identically to a colocated run. The cluster-wide prefix
    index (engine/prefix_tree.ClusterPrefixIndex) is fed by every
    replica tree's page listener events, so a prefix prefilled
    anywhere is warm everywhere — page residency joins weight
    residency and hbm_pressure in :meth:`_pick`, and a request whose
    pages already sit on some replica PULLS them instead of
    re-prefilling.
    """

    def __init__(self, replicas: Sequence[Tuple[str, object]],
                 config: Optional[RouterConfig] = None,
                 stats: Optional[RouterStats] = None,
                 clock: Callable[[], float] = time.monotonic,
                 roles: Optional[Dict[str, str]] = None,
                 migrate: Optional[MigrationConfig] = None,
                 migrate_stats: Optional[MigrationStats] = None):
        assert replicas, "a router needs at least one replica"
        self.config = config or RouterConfig()
        self.stats = stats if stats is not None else RouterStats()
        self.migrate_config = migrate or MigrationConfig()
        self.migrate_stats = (migrate_stats if migrate_stats is not None
                              else MigrationStats())
        self.migrator = migrate_mod.PageMigrator(
            self.migrate_config, self.migrate_stats, clock=clock)
        self.clock = clock
        self._lock = threading.Lock()
        self._handles: Dict[str, _Replica] = {}
        self._pending: Dict[int, _Pending] = {}  # guarded-by: _lock
        self._migrations: Dict[int, _Migration] = {}  # guarded-by: _lock
        self._rr = 0                             # guarded-by: _lock
        roles = dict(roles or {})
        for rid, server in replicas:
            assert rid not in self._handles, f"duplicate replica {rid}"
            breaker = CircuitBreaker(
                failure_threshold=self.config.replica_failure_threshold,
                cooldown_s=self.config.replica_cooldown_s, clock=clock)
            handle = _Replica(str(rid), server, breaker,
                              role=roles.get(str(rid), "both"))
            # Residency map: seed from the current resident set, then
            # ride the WeightCache's insert/evict listener events.
            cache = getattr(getattr(server, "fleet", None), "cache", None)
            if cache is not None and hasattr(cache, "add_listener"):
                resident = getattr(server, "resident_models", None)
                if callable(resident):
                    handle.seed_resident(resident())
                cache.add_listener(handle.on_weight_event)
            # Sentinel gating (observe/sentinel.py): a fleet replica
            # exposes the ROUTER-side breaker so the scheduler pauses
            # sentinel sweeps while the replica is failing over.
            if getattr(server, "breaker", "absent") is None:
                server.breaker = breaker
            self._handles[handle.replica_id] = handle
        # Cluster-wide prefix index (engine/prefix_tree.py): every
        # replica engine's radix tree feeds page insert/evict listener
        # events into ONE router-side index — fed exactly the way the
        # weight-residency map above is fed by WeightCache events — so
        # placement and migration can ask "who holds this prefix's
        # pages?" without touching any replica.
        page_size = 16
        for handle in self._handles.values():
            tree = getattr(getattr(handle.server, "engine", None),
                           "prefix_cache", None)
            if tree is not None:
                page_size = tree.page_size
                break
        self.cluster_tree = prefix_tree.ClusterPrefixIndex(page_size)
        self._have_page_index = False
        for rid, handle in self._handles.items():
            tree = getattr(getattr(handle.server, "engine", None),
                           "prefix_cache", None)
            if tree is not None:
                self._have_page_index = True
                tree.add_listener(
                    functools.partial(self.cluster_tree.on_event, rid))
        # Tier-residency feed (serve/tiers.py): each replica's tiered
        # page store reports host/disk movement into the SAME cluster
        # index under its tier dimension, and a restart-warm replica
        # joining the router announces what its surviving host/disk
        # entries can re-serve (emit_residency) — so placement sees
        # "demoted but promotable here" as warmer than cold.
        self._tier_bonus: Dict[str, float] = {"host": 0.5, "disk": 0.25}
        for rid, handle in self._handles.items():
            store = getattr(handle.server, "tiers", None)
            if store is None:
                continue
            self._tier_bonus = {"host": store.cfg.host_bonus,
                                "disk": store.cfg.disk_bonus}
            store.add_listener(
                functools.partial(self.cluster_tree.on_tier_event, rid))
            store.emit_residency()
        # Router-level content-addressed dedup: the exactly-once
        # backstop. The cache's own ServeStats is private; RouterStats
        # carries the router-visible dedup counter.
        self.cache = ResultCache(self.config.cache_entries, ServeStats())
        self._engine_key = self._derive_engine_key()
        self.metrics = metrics_mod.MetricsRegistry()
        self.metrics.register("router", self.stats)
        self.metrics.register("migrate", self.migrate_stats)
        for rid, handle in self._handles.items():
            rstats = getattr(handle.server, "stats", None)
            if rstats is not None:
                self.metrics.register(f"replica:{rid}:serve", rstats)
        rec = tracing.get_recorder()
        if rec is not None:
            self.metrics.register("trace", rec)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _derive_engine_key(self) -> str:
        for handle in self._handles.values():
            key = getattr(handle.server, "_engine_key", None)
            if key is None:
                eng = getattr(handle.server, "engine", None)
                key = getattr(eng, "cache_manifest_key", None)
            if key is None and handle.is_fleet:
                key = "fleet:" + ",".join(handle.server.model_ids)
            if key:
                return str(key)
        return "router"

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "ReplicaRouter":
        assert self._thread is None, "router already started"
        self._thread = threading.Thread(target=self._loop,
                                        name="replica-router",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: Optional[float] = None) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(self.config.tick_s):
            try:
                self._tick()
            except Exception:  # noqa: BLE001 — the tick is advisory
                # (hedges/promotion); it must never take routing down.
                log.exception("router tick failed; continuing")

    # -- introspection -------------------------------------------------------

    @property
    def replica_ids(self) -> List[str]:
        return list(self._handles)

    def handle(self, replica_id: str) -> _Replica:
        return self._handles[replica_id]

    def breaker_of(self, replica_id: str) -> CircuitBreaker:
        return self._handles[replica_id].breaker

    def alive_replicas(self) -> List[str]:
        return [rid for rid, h in self._handles.items() if h.alive]

    def stats_summary(self) -> Dict:
        now = self.clock()
        return {
            "router": self.stats.summary(),
            "migrate": self.migrate_stats.summary(),
            "replicas": {
                rid: {
                    "alive": h.alive,
                    "role": h.role,
                    "breaker": h.breaker.state,
                    "queue_depth": h.depth,
                    "oldest_wait_s": round(h.oldest_wait(now), 4),
                    "hbm_pressure": round(h.pressure, 4),
                    "resident": sorted(h.resident_view()),
                    "tiers": (h.server.tiers.summary()
                              if getattr(h.server, "tiers", None)
                              is not None else None),
                }
                for rid, h in self._handles.items()
            },
        }

    # -- placement -----------------------------------------------------------

    def _tier_priced(self, bucket: int, prefix: Tuple[int, ...],
                     hbm_match: Dict[str, int]) -> Dict[str, float]:
        """Effective page-equivalents per replica: HBM pages at full
        price plus host/disk tier pages discounted by the tier bonuses
        (TierConfig.host_bonus / disk_bonus) — a demoted prefix is
        warmer than a cold replica, but a promote is not free. Feeds
        :meth:`_pick` only; pull/prefill decisions keep the exact HBM
        match."""
        priced: Dict[str, float] = dict(hbm_match)
        for rid, by_tier in self.cluster_tree.match_tiers(
                bucket, prefix).items():
            for tier, pages in by_tier.items():
                bonus = self._tier_bonus.get(tier, 0.0)
                if bonus:
                    priced[rid] = priced.get(rid, 0) + bonus * pages
        return priced

    def _pick(self, model_id: str, exclude: Set[str],
              remaining_s: Optional[float] = None,
              page_match: Optional[Dict[str, float]] = None
              ) -> Optional[_Replica]:
        """The placement decision: among live replicas whose breaker
        admits traffic (and not in ``exclude``), the lowest-scoring one
        — queue depth, minus the residency bonus when the model's
        weights are already there, MINUS the page-residency bonus per
        cluster-index-matched prefix page (``page_match``, pages per
        replica id — a decode replica already holding the prompt's
        pages wins placement over an equally-loaded cold one), plus the
        SLO term (oldest queued-row wait against the request's
        remaining deadline) and the HBM-pressure penalty. Prefill-role
        replicas receive scoring traffic only when no decode-capable
        replica survives (never a dropped request over role purity).
        Round-robin rotation breaks ties so equal replicas share
        load."""
        now = self.clock()
        with self._lock:
            self._rr += 1
            order = list(self._handles.values())
            order = order[self._rr % len(order):] \
                + order[:self._rr % len(order)]
        cands = [h for h in order
                 if h.alive and h.replica_id not in exclude
                 and h.breaker.allow()]
        decode_capable = [h for h in cands if h.role != "prefill"]
        cands = decode_capable or cands
        if not cands:
            return None

        def score(h: _Replica) -> float:
            s = float(h.depth)
            if model_id and model_id in h.resident_view():
                s -= self.config.residency_bonus
            if page_match:
                # Cluster prefix-tree match as a placement signal
                # (serve/migrate.py): every page already resident on
                # the replica is prefill the dispatch never re-pays.
                s -= (self.migrate_config.page_bonus
                      * page_match.get(h.replica_id, 0))
            if self.config.slo_wait_weight > 0 and remaining_s:
                s += (self.config.slo_wait_weight * h.oldest_wait(now)
                      / max(remaining_s, 0.1))
            if self.config.pressure_weight > 0:
                # Memory pressure as a placement input (the HBM
                # governor's gauge): a replica mid-squeeze — ladder
                # walking, batches halved — should absorb LESS new
                # work than an equally-deep replica with headroom.
                s += self.config.pressure_weight * h.pressure
            return s

        return min(cands, key=score)

    def _pick_prefill(self, exclude: Set[str]) -> Optional[_Replica]:
        """Least-loaded live prefill-role replica (with a page pool to
        export from), or None — the migration chain's prefill leg."""
        cands = [h for h in self._handles.values()
                 if h.alive and h.role == "prefill"
                 and h.replica_id not in exclude and h.breaker.allow()
                 and getattr(getattr(h.server, "engine", None),
                             "prefix_cache", None) is not None]
        if not cands:
            return None
        return min(cands, key=lambda h: h.depth)

    def _disagg_active(self) -> bool:
        """Disaggregated placement is live: migration enabled, a page
        index exists, and both a live prefill-role and a live
        decode-capable replica are present."""
        if not (self.migrate_config.enabled and self._have_page_index):
            return False
        have_prefill = any(h.alive and h.role == "prefill"
                           for h in self._handles.values())
        have_decode = any(h.alive and h.role != "prefill"
                          for h in self._handles.values())
        return have_prefill and have_decode

    def _tokenize_prefix(self, request: ServeRequest
                         ) -> Optional[Tuple[Tuple[int, ...], int]]:
        """(shared token prefix, ladder bucket) for the placement /
        migration probes — computed EXACTLY the way the replica's own
        admission computes them (ScoringServer._submit: shared prefix
        of the two format prompts, snapped to the engine's ladder), so
        the cluster index, the migrated pages, and the eventual
        dispatch all speak the same (bucket, ids) namespace. Uses the
        first replica engine with a page pool (replicas are
        config-identical); None when tokenization is unavailable."""
        for h in self._handles.values():
            eng = getattr(h.server, "engine", None)
            if eng is None or getattr(eng, "prefix_cache", None) is None:
                continue
            try:
                with eng._tok_lock:
                    bin_ids = [int(i) for i in eng.tokenizer(
                        request.binary_prompt).input_ids]
                    conf_ids = [int(i) for i in eng.tokenizer(
                        request.confidence_prompt).input_ids]
            except Exception:  # noqa: BLE001 — probe only; the replica
                # will tokenize (and fail loudly) at admission.
                return None
            lcp = tok.shared_prefix_len(bin_ids, conf_ids)
            if lcp <= 0:
                return None
            bucket = tok.assign_bucket(max(lcp, 1), eng.buckets)
            return tuple(bin_ids[:lcp]), int(bucket)
        return None

    def _deadline_for(self, request: ServeRequest) -> float:
        if request.deadline_s is not None:
            return float(request.deadline_s)
        for h in self._handles.values():
            cfg = getattr(h.server, "config", None)
            if cfg is not None and hasattr(cfg, "deadline_for"):
                return float(cfg.deadline_for(request.klass))
        return 300.0

    # -- client side ---------------------------------------------------------

    def submit(self, request: ServeRequest,
               model_id: str = "") -> ServeFuture:
        """Route one request: dedup, place, attempt. The returned
        future resolves exactly once with the first winning payload
        (primary, failover, hedge, or re-admission — whichever answers
        first)."""
        now = self.clock()
        key = content_key(
            self._engine_key if not model_id
            else f"{self._engine_key}|{model_id}", request)
        if self.cache.max_entries > 0:
            hit = self.cache.get(key)
            if hit is not None:
                self.stats.count("dedup_hits")
                self.stats.count("completed")
                fut = ServeFuture()
                fut.resolve(ServeResult(
                    request_id=request.request_id, status=STATUS_OK,
                    cached=True, latency_s=self.clock() - now, **hit))
                return fut
        deadline_s = self._deadline_for(request)
        pending = _Pending(request, model_id, key, now,
                           now + deadline_s)
        with tracing.span("router/route",
                          request_id=request.request_id):
            # Cluster prefix-tree probe: which replicas already hold
            # this prompt's prefix pages (single-model traffic only —
            # the fleet path keeps its own per-model trees colocated).
            prefix: Optional[Tuple[int, ...]] = None
            bucket = 0
            page_match: Dict[str, int] = {}
            pick_match: Dict[str, float] = {}
            if self._have_page_index and not model_id:
                info = self._tokenize_prefix(request)
                if info is not None:
                    prefix, bucket = info
                    page_match = self.cluster_tree.match_pages(bucket,
                                                               prefix)
                    # Placement prices host/disk-tier pages at a
                    # discount (promotable, not free); migration
                    # decisions below keep the exact HBM-only match —
                    # only HBM pages are exportable.
                    pick_match = self._tier_priced(bucket, prefix,
                                                   page_match)
            handle = self._pick(model_id, exclude=set(),
                                remaining_s=deadline_s,
                                page_match=pick_match or page_match)
            if handle is None:
                self.stats.count("no_replica_sheds")
                pending.claim_resolution()
                pending.future.resolve(ServeResult(
                    request_id=request.request_id, status=STATUS_SHED,
                    note="no live replica available (all dead or "
                         "breaker-open)"))
                return pending.future
            self.stats.count("routed")
            if model_id and model_id in handle.resident_view():
                self.stats.count("routed_resident")
            with self._lock:
                self._pending[id(pending)] = pending
            if prefix is not None and self._disagg_active() \
                    and handle.role != "prefill":
                if self._route_disaggregated(pending, handle, bucket,
                                             prefix, page_match):
                    return pending.future
            self._attempt(pending, handle, "primary")
        return pending.future

    def _route_disaggregated(self, pending: _Pending, dst: _Replica,
                             bucket: int, prefix: Tuple[int, ...],
                             page_match: Dict[str, int]) -> bool:
        """The disaggregation decision for one request (True = a
        migration chain owns it now):

        - prefix fully page-resident on the chosen decode replica —
          route straight there (``cluster_tree_hits``: warm anywhere
          became warm HERE without re-prefilling);
        - some OTHER replica holds at least as many pages as the
          prompt needs — PULL them (export -> transfer -> import), no
          prefill anywhere;
        - prefix long enough (``min_prefix_tokens``) and a prefill
          replica lives — prefill THERE, then pull;
        - otherwise: colocated scoring on the decode replica (the
          handoff would cost more than the prefill it saves)."""
        ps = self.cluster_tree.page_size
        want_pages = len(prefix) // ps
        have = page_match.get(dst.replica_id, 0)
        if want_pages <= 0:
            return False
        if have >= want_pages:
            self.migrate_stats.count("cluster_tree_hits")
            return False                 # already warm on dst: just score
        src: Optional[_Replica] = None
        need_prefill = False
        src_rid, src_pages = self.cluster_tree.best_holder(
            bucket, prefix, exclude=(dst.replica_id,))
        if (src_rid is not None and src_pages >= want_pages
                and self._handles[src_rid].alive
                and getattr(getattr(self._handles[src_rid].server,
                                    "engine", None),
                            "prefix_cache", None) is not None):
            src = self._handles[src_rid]   # warm elsewhere: pure pull
        elif len(prefix) >= self.migrate_config.min_prefix_tokens:
            src = self._pick_prefill(exclude={dst.replica_id})
            need_prefill = src is not None
        if src is None:
            return False
        self._start_migration(pending, dst, src, bucket, prefix,
                              dst_tokens=have * ps,
                              need_prefill=need_prefill)
        return True

    # -- attempt machinery ---------------------------------------------------

    def _attempt(self, pending: _Pending, handle: _Replica,
                 kind: str) -> None:
        with pending.lock:
            pending.tried.add(handle.replica_id)
        handle.track(pending)
        self.stats.placed(handle.replica_id)
        try:
            if handle.is_fleet and pending.model_id:
                inner = handle.server.submit(pending.request,
                                             pending.model_id)
            else:
                inner = handle.server.submit(pending.request)
        except Exception as err:  # noqa: BLE001 — a replica whose
            # submit path itself raises is as dead as one that errors.
            handle.untrack(pending)
            self._on_result(pending, handle, kind, ServeResult(
                request_id=pending.request.request_id,
                status=STATUS_ERROR,
                note=f"replica {handle.replica_id} submit raised: "
                     f"{err!r}"))
            return
        inner.add_done_callback(
            lambda res, p=pending, h=handle, k=kind:
            self._on_result(p, h, k, res))

    def _forget(self, pending: _Pending) -> None:
        with self._lock:
            self._pending.pop(id(pending), None)

    def _on_result(self, pending: _Pending, handle: _Replica,
                   kind: str, res: ServeResult) -> None:
        """One attempt resolved on ``handle`` (runs on the replica's
        resolving thread). Winner resolves the router future and feeds
        the dedup cache; losers are classified (zombie payload / hedge
        loss) and dropped — resolve-once is the double-resolution
        proof."""
        handle.untrack(pending)
        if res.status == STATUS_OK:
            if handle.alive:
                # A DEAD replica's late success must not move its
                # breaker: recovery is the revive + half-open probe's
                # job, not a zombie payload's.
                handle.breaker.record_success()
            if not pending.claim_resolution():
                # A payload for an already-resolved request: the hedge
                # race's loser, or a zombie — late from a replica that
                # was killed (possibly since revived) after the work
                # was re-admitted. Either way it is dropped here —
                # never double-resolved — and the cache.put below is
                # idempotent by content address (replicas are
                # config-identical, so the payload is bitwise the
                # winner's).
                with pending.lock:
                    was_hedged = pending.hedged
                self.stats.count("hedge_losses"
                                 if handle.alive and was_hedged
                                 else "zombie_payloads")
                self.cache.put(pending.key, _payload_of(res))
                return
            self.cache.put(pending.key, _payload_of(res))
            self.stats.count("completed")
            if kind == "hedge":
                self.stats.count("hedge_wins")
            pending.future.resolve(dataclasses.replace(
                res, latency_s=self.clock() - pending.t_submit))
            self._forget(pending)
            return
        if res.status in (STATUS_ERROR, STATUS_SHED):
            if res.status == STATUS_ERROR:
                self.stats.count("replica_errors")
                opened = (handle.breaker.record_failure()
                          if handle.alive else False)
                if opened:
                    log.warning("router: replica %s breaker OPEN "
                                "(cooldown %.1fs)", handle.replica_id,
                                self.config.replica_cooldown_s)
            else:
                self.stats.count("replica_sheds")
            with pending.lock:
                if pending.resolved:
                    return
            now = self.clock()
            remaining = pending.t_deadline - now
            if remaining > 0:
                nxt = self._pick(pending.model_id,
                                 exclude=set(pending.tried),
                                 remaining_s=remaining)
                if nxt is not None:
                    self.stats.count("failovers")
                    self._attempt(pending, nxt, "failover")
                    return
            if not pending.claim_resolution():
                return
            self.stats.count("errors")
            pending.future.resolve(res)
            self._forget(pending)
            return
        # expired/partial statuses resolve through: the deadline is
        # gone — another replica could only answer later still.
        if pending.claim_resolution():
            pending.future.resolve(res)
            self._forget(pending)

    # -- the migration chain (disaggregated handoff; serve/migrate.py) -------

    def _start_migration(self, pending: _Pending, dst: _Replica,
                         src: _Replica, bucket: int,
                         prefix: Tuple[int, ...], dst_tokens: int,
                         need_prefill: bool) -> None:
        """Launch one handoff chain: [prefill on src ->] export(src) ->
        transfer -> import(dst) -> score(dst). Every hop is a page op
        on the owning replica's supervisor thread, linked by completion
        callbacks; the chain deadline (`MigrationConfig.timeout_s`,
        policed by the tick) and every failure path end in
        :meth:`_mig_fallback` — local re-prefill on a decode replica,
        never a wrong or dropped answer."""
        mig = _Migration(pending, dst, src, bucket, prefix, dst_tokens,
                         self.clock() + self.migrate_config.timeout_s)
        with self._lock:
            self._migrations[id(mig)] = mig
        if need_prefill:
            self.migrate_stats.count("prefill_ops")
            fut = src.server.submit_prefill(bucket, prefix)
            fut.add_done_callback(
                lambda f, m=mig: self._mig_prefilled(m, f))
        else:
            self._mig_export(mig)

    def _mig_prefilled(self, mig: _Migration,
                       fut: migrate_mod.OpFuture) -> None:
        if mig.claimed:
            return
        if fut.error is not None:
            self._mig_fallback(mig, f"prefill failed: {fut.error!r}")
            return
        self._mig_export(mig)

    def _mig_export(self, mig: _Migration) -> None:
        cfg, clock = self.migrate_config, self.clock
        fut = mig.src.server.submit_page_op(
            lambda eng, m=mig: migrate_mod.export_prefix(
                eng, m.bucket, m.prefix_ids, from_token=m.dst_tokens,
                config=cfg, clock=clock))
        fut.add_done_callback(
            lambda f, m=mig: self._mig_exported(m, f))

    def _mig_exported(self, mig: _Migration,
                      fut: migrate_mod.OpFuture) -> None:
        if mig.claimed:
            return
        if fut.error is not None:
            self._mig_fallback(mig, f"export failed: {fut.error!r}")
            return
        export = fut.value
        if export is None:
            self._mig_fallback(
                mig, f"nothing cached to export on {mig.src.replica_id}")
            return
        try:
            # The wire hop — the chaos fault seam (migration_stall
            # sleeps here past the chain deadline; migration_corrupt
            # flips chunk bytes under the checksums).
            export = self.migrator.transfer(export)
        except Exception as err:  # noqa: BLE001 — any wire failure
            # has the same answer: local re-prefill.
            self.migrate_stats.count("stalls")
            self._mig_fallback(mig, f"transfer failed: {err!r}")
            return
        cfg, clock = self.migrate_config, self.clock
        fut2 = mig.dst.server.submit_page_op(
            lambda eng, e=export: migrate_mod.import_prefix(
                eng, e, config=cfg, clock=clock))
        fut2.add_done_callback(
            lambda f, m=mig, e=export: self._mig_imported(m, e, f))

    def _mig_imported(self, mig: _Migration,
                      export: migrate_mod.PageExport,
                      fut: migrate_mod.OpFuture) -> None:
        if fut.error is not None:
            if isinstance(fut.error, migrate_mod.MigrationError) \
                    and "checksum" in str(fut.error):
                self.migrate_stats.count("corrupt_chunks")
            self._mig_fallback(mig, f"import failed: {fut.error!r}")
            return
        if not mig.claim():
            return          # timed out meanwhile; the pages (verified)
            # still landed — the pool is simply warmer for the fallback.
        with self._lock:
            self._migrations.pop(id(mig), None)
        imp = fut.value
        if imp.pages > 0:
            self.migrator.account(export, imp)
        else:
            self.migrate_stats.count("cluster_tree_hits")
        self._attempt(mig.pending, mig.dst, "migrated")

    def _mig_fallback(self, mig: _Migration, reason: str) -> None:
        """Abandon a chain: the request scores with a LOCAL re-prefill
        on the decode replica (or any survivor) — the stalled/corrupt
        transfer cost latency, never correctness."""
        if not mig.claim():
            return
        with self._lock:
            self._migrations.pop(id(mig), None)
        self.migrate_stats.count("refetch_fallbacks")
        log.warning("router: migration abandoned for request %s (%s); "
                    "falling back to local re-prefill",
                    mig.pending.request.request_id, reason)
        dst: Optional[_Replica] = mig.dst
        if not (dst.alive and dst.breaker.allow()):
            dst = self._pick(
                mig.pending.model_id,
                exclude={mig.dst.replica_id},
                remaining_s=max(mig.pending.t_deadline - self.clock(),
                                0.0))
        if dst is None:
            if mig.pending.claim_resolution():
                self.stats.count("errors")
                mig.pending.future.resolve(ServeResult(
                    request_id=mig.pending.request.request_id,
                    status=STATUS_ERROR,
                    note=f"migration failed ({reason}) and no replica "
                         f"survives to re-prefill locally"))
                self._forget(mig.pending)
            return
        self._attempt(mig.pending, dst, "refetch")

    # -- failover ------------------------------------------------------------

    def kill_replica(self, replica_id: str) -> int:
        """A replica observed DEAD (process gone, host lost, chaos
        schedule): force its breaker open, stop placing traffic on it,
        and re-admit its unresolved in-flight requests to survivors —
        exactly once each (the zombie's late payloads are dropped by
        resolve-once + content dedup). Returns how many were
        re-admitted."""
        handle = self._handles[replica_id]
        handle.alive = False
        handle.breaker.trip()
        self.stats.count("kills")
        # Migration chains touching the dead replica fail over NOW
        # (kill-mid-migration): their requests re-prefill locally on a
        # survivor instead of waiting out the chain deadline.
        with self._lock:
            migs = [m for m in self._migrations.values()
                    if replica_id in (m.src.replica_id,
                                      m.dst.replica_id)]
        for m in migs:
            self._mig_fallback(
                m, f"replica {replica_id} died mid-migration")
        victims = handle.take_inflight()
        n = 0
        t0 = self.clock()
        for p in victims:
            with p.lock:
                if p.resolved:
                    continue
            nxt = self._pick(p.model_id, exclude={replica_id},
                             remaining_s=max(p.t_deadline - t0, 0.0))
            if nxt is None:
                if p.claim_resolution():
                    self.stats.count("errors")
                    p.future.resolve(ServeResult(
                        request_id=p.request.request_id,
                        status=STATUS_ERROR,
                        note=f"replica {replica_id} died with no "
                             f"survivor to re-admit to"))
                    self._forget(p)
                continue
            n += 1
            self.stats.count("re_admitted")
            self._attempt(p, nxt, "re_admit")
        log.warning("router: replica %s killed; %d in-flight request(s) "
                    "re-admitted to survivors", replica_id, n)
        return n

    def revive_replica(self, replica_id: str) -> None:
        """The replica rejoined: mark it placeable again. Its breaker
        stays OPEN until the cooldown elapses, so the first request it
        sees is the ordinary half-open probe — success closes the
        breaker, failure re-opens it."""
        handle = self._handles[replica_id]
        handle.alive = True
        self.stats.count("revives")
        log.info("router: replica %s revived (breaker %s; probe after "
                 "cooldown)", replica_id, handle.breaker.state)

    # -- the tick (hedging) --------------------------------------------------

    def _tick(self) -> None:
        now = self.clock()
        # Reading state lazily promotes OPEN -> HALF_OPEN breakers.
        for h in self._handles.values():
            h.breaker.state  # noqa: B018 — promotion side effect
        # Migration chains past their deadline fall back to local
        # re-prefill (a stalled transfer costs one timeout, not the
        # request — the migration_stall chaos contract).
        with self._lock:
            stale = [m for m in self._migrations.values()
                     if now >= m.t_deadline]
        for m in stale:
            if not m.claimed:
                self.migrate_stats.count("stalls")
                self._mig_fallback(
                    m, f"chain exceeded "
                       f"{self.migrate_config.timeout_s:.1f}s deadline")
        if self.config.hedge_s <= 0:
            return
        with self._lock:
            pendings = list(self._pending.values())
        for p in pendings:
            remaining = p.t_deadline - now
            if remaining > self.config.hedge_s:
                continue
            with p.lock:
                if p.resolved or p.hedged:
                    continue
                tried = set(p.tried)
            nxt = self._pick(p.model_id, exclude=tried,
                             remaining_s=max(remaining, 0.0))
            if nxt is None:
                continue
            with p.lock:
                if p.resolved or p.hedged:
                    continue
                p.hedged = True
            self.stats.count("hedged")
            self._attempt(p, nxt, "hedge")
