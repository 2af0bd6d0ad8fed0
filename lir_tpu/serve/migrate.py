"""KV-page migration between replica pools (disaggregated serving).

ROADMAP item 2 made the page the unit of KV *ownership* (models/paged
.KVPagePool + engine/prefix_tree.RadixPrefixCache); this module makes it
the unit of *placement*: the KV pages of a prefix prefilled on one
replica stream to another replica's pool, land in its radix tree, and
back that replica's decode dispatches bitwise-identically to pages it
would have computed itself. That is the DistServe/Mooncake handoff —
a long prompt prefills on a PREFILL-role replica, decode resumes on a
DECODE-role replica — expressed in this engine's own primitives:

- **Export** (:func:`export_prefix`): the source tree pins the deepest
  cached match (ordinary lookup reference discipline — eviction cannot
  free a page mid-export), then the pool pages stream device->host in
  fixed-size chunks with a bounded in-flight window — the SAME chunked
  double-buffered transfer discipline ``models/weights.stream_params``
  uses for weight streaming, pointed at KV pages. Each chunk carries a
  CRC so corruption on the wire is detectable at import.
- **Import** (:func:`import_prefix`): the destination tree allocates
  pages + nodes through its ordinary ``plan_insert`` (so the cluster
  index hears about them exactly like locally-produced pages), then the
  chunks land host->device double-buffered, each ``jax.device_put``
  taking the destination pool leaf's own sharding — pages arrive
  already partitioned for the destination mesh, no post-hoc reshard.
  Any failure (checksum mismatch, device error) ROLLS BACK: the fresh
  nodes leave the tree (:meth:`RadixPrefixCache.forget_tail`) and their
  pages return to the free list, so a dispatch can never gather a
  half-filled page — the never-a-wrong-answer contract.
- **Page ops** (:class:`PageOp`/:class:`OpFuture`): every tree/pool
  touch runs on the OWNING replica's supervisor thread (the tree's
  single-threaded contract), queued through
  ``ScoringServer.submit_page_op`` and chained by the router with
  completion callbacks — the handoff protocol is a pipeline of ops,
  never a cross-thread mutation.
- **Fault seam** (:meth:`PageMigrator.transfer`): the host-side hop
  between export and import, where the seeded chaos kinds inject —
  ``migration_stall`` sleeps past the chain deadline and
  ``migration_corrupt`` flips transferred bytes (faults/plan.py). Both
  end in the router's fallback: the decode replica re-prefills locally.

Everything here is advisory-index tolerant: the export re-looks pages
up with a pin, the import re-plans against the destination tree's
actual state, and a migration that cannot complete costs a local
re-prefill (``MigrationStats.refetch_fallbacks``), never a wrong or
dropped request.

The export/import legs are also the MOVEMENT ENGINE of the tiered
memory ladder (serve/tiers.py): a demotion is an ``export_prefix`` kept
in host DRAM or spilled to disk instead of shipped to a peer, and a
promotion is the same ``import_prefix`` — checksum verify, plan_insert,
skip-what's-resident, rollback — pointed back at the exporting
replica's own tree. One transfer discipline, three directions.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import zlib
from collections import deque
from typing import Any, Callable, List, Optional, Tuple

import jax
import numpy as np

from ..config import MigrationConfig
from ..models import decoder
from ..utils.logging import get_logger

log = get_logger(__name__)


class MigrationError(RuntimeError):
    """A page transfer that must not land (checksum mismatch, layout
    disagreement, vanished source pages). The router's reaction is
    always the same: abandon the chain, re-prefill locally."""


# ---------------------------------------------------------------------------
# Page ops: engine work queued onto the owning supervisor thread
# ---------------------------------------------------------------------------


class OpFuture:
    """Generic completion handle for one page op: resolves exactly once
    with a value OR an exception; callbacks run on the resolving
    (supervisor) thread. The migration chain's links are these
    callbacks — no waiter thread per hop."""

    def __init__(self) -> None:
        self._done = threading.Event()
        self._lock = threading.Lock()
        self.value: Any = None
        self.error: Optional[BaseException] = None
        self._callbacks: List[Callable[["OpFuture"], None]] = []  # guarded-by: _lock

    def _resolve(self, value: Any, error: Optional[BaseException]) -> None:
        with self._lock:
            if self._done.is_set():
                return
            self.value, self.error = value, error
            self._done.set()
            callbacks, self._callbacks = self._callbacks, []
        for fn in callbacks:
            fn(self)

    def set_result(self, value: Any) -> None:
        self._resolve(value, None)

    def set_exception(self, error: BaseException) -> None:
        self._resolve(None, error)

    def done(self) -> bool:
        return self._done.is_set()

    def add_done_callback(self, fn: Callable[["OpFuture"], None]) -> None:
        with self._lock:
            if not self._done.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def result(self, timeout: Optional[float] = None) -> Any:
        if not self._done.wait(timeout):
            raise TimeoutError("page op not resolved in time")
        if self.error is not None:
            raise self.error
        return self.value


class PageOp:
    """One unit of tree/pool work bound for a replica's supervisor
    thread (``ScoringServer.submit_page_op``)."""

    def __init__(self, fn: Callable[[Any], Any]):
        self.fn = fn
        self.future = OpFuture()

    def run(self, engine) -> None:
        try:
            self.future.set_result(self.fn(engine))
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as err:  # noqa: BLE001 — the chain's fallback
            # decides what a failed op means; the supervisor must live.
            self.future.set_exception(err)


# ---------------------------------------------------------------------------
# The transfer payload
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PageExport:
    """One prefix's pages, staged on the host for the wire hop.

    ``ids`` is the page-aligned token prefix the pages cover (the
    import side re-plans against it); ``start_tokens`` is where the
    export begins (the destination already held ``[0, start_tokens)``
    at probe time). ``chunks`` holds ``(host block tree, real pages)``
    pairs at a stable ``chunk_pages`` width (trailing pad entries are
    trash-page blocks); ``checksums`` carries one CRC32 per chunk,
    computed at export — the import side's corruption detector."""

    bucket: int
    ids: Tuple[int, ...]
    start_tokens: int
    page_size: int
    n_pages: int
    chunk_pages: int
    chunks: List[Tuple[Any, int]]
    checksums: List[int]
    nbytes: int
    wall_s: float = 0.0
    serial_s: float = 0.0


@dataclasses.dataclass
class ImportResult:
    """What one import landed: pages written, device bytes, and the
    wall/serial split the overlap accounting reads."""

    pages: int
    nbytes: int
    wall_s: float = 0.0
    serial_s: float = 0.0


def chunk_checksum(block_tree: Any) -> int:
    """CRC32 over every leaf's raw bytes, leaf order — cheap enough to
    run per chunk, strong enough that a flipped transfer byte cannot
    land silently."""
    crc = 0
    for leaf in jax.tree.leaves(block_tree):
        crc = zlib.crc32(np.ascontiguousarray(leaf).tobytes(), crc)
    return crc


# ---------------------------------------------------------------------------
# Export / import legs
# ---------------------------------------------------------------------------


def export_prefix(engine, bucket: int, ids, from_token: int = 0,
                  config: Optional[MigrationConfig] = None,
                  clock: Callable[[], float] = time.monotonic
                  ) -> Optional[PageExport]:
    """Stage the cached pages of ``ids``' deepest match (from
    ``from_token`` on) to host chunks. Runs on the SOURCE replica's
    supervisor thread (a page op); the match is pinned for the
    duration, so eviction cannot free a page mid-copy. Returns None
    when nothing beyond ``from_token`` is cached — the chain falls back
    to a local prefill."""
    decoder.refuse_recurrent(engine.cfg, "page migration")
    cfg = config or MigrationConfig()
    tree = getattr(engine, "prefix_cache", None)
    if tree is None:
        return None
    match = tree.lookup(bucket, ids, record=False)
    gov_key = None
    try:
        ps = tree.page_size
        from_page = max(int(from_token), 0) // ps
        pages = list(match.pages[from_page:])
        if not pages:
            return None
        # Transfer staging is real memory: ledger it for the duration
        # (the PR-14 HBM governor sees migration buffers next to the
        # pool reservation, so a squeeze accounts for in-flight
        # exports too).
        gov = getattr(engine, "governor", None)
        if gov is not None:
            gov_key = ("migrate_buf:"
                       f"{getattr(engine.cfg, 'name', 'model')}")
            gov.register(gov_key,
                         tree.pool.page_nbytes() * len(pages))
        chunk_n = max(int(cfg.chunk_pages), 1)
        window = max(int(cfg.inflight_chunks), 1)
        t0 = clock()
        serial = 0.0
        chunks: List[Tuple[Any, int]] = []
        sums: List[int] = []
        pending: deque = deque()

        def consume() -> None:
            nonlocal serial
            blocks, n, t_disp = pending.popleft()
            # Owned, writable host copies: the chunk may cross a
            # process/wire boundary (and the corruption chaos kind
            # mutates it in place).
            host = jax.tree.map(lambda a: np.array(a),
                                jax.device_get(blocks))
            serial += clock() - t_disp
            chunks.append((host, n))
            sums.append(chunk_checksum(host))

        for k in range(0, len(pages), chunk_n):
            pc = pages[k:k + chunk_n]
            # Dispatch the next chunk's device gather BEFORE consuming
            # the previous one — the double-buffered in-flight window
            # (stream_params' discipline, device->host direction).
            pending.append((tree.pool.extract(pc, pad_to=chunk_n),
                            len(pc), clock()))
            while len(pending) >= window + 1:
                consume()
        while pending:
            consume()
        wall = clock() - t0
        return PageExport(
            bucket=int(bucket),
            ids=tuple(int(t) for t in ids[:match.tokens]),
            start_tokens=from_page * ps, page_size=ps,
            n_pages=len(pages), chunk_pages=chunk_n, chunks=chunks,
            checksums=sums,
            nbytes=tree.pool.page_nbytes() * len(pages),
            wall_s=wall, serial_s=serial)
    finally:
        if gov_key is not None:
            engine.governor.unregister(gov_key)
        tree.release(match)


def import_prefix(engine, export: PageExport,
                  config: Optional[MigrationConfig] = None,
                  clock: Callable[[], float] = time.monotonic
                  ) -> ImportResult:
    """Land an export in the DESTINATION replica's pool + tree. Runs on
    the destination's supervisor thread (a page op), atomically from
    any dispatch's point of view: the tree nodes appear and their pages
    fill inside one op, or — on any failure — roll back entirely
    (refcounts restored, nodes removed, pages freed). Raises
    :class:`MigrationError` on checksum mismatch / layout disagreement;
    the router's fallback then re-prefills locally."""
    decoder.refuse_recurrent(engine.cfg, "page migration")
    cfg = config or MigrationConfig()
    tree = getattr(engine, "prefix_cache", None)
    if tree is None:
        raise MigrationError("destination replica has no page pool")
    if tree.page_size != export.page_size:
        raise MigrationError(
            f"page-size mismatch: export {export.page_size} vs "
            f"destination {tree.page_size}")
    if cfg.verify:
        for ci, (host, _) in enumerate(export.chunks):
            if chunk_checksum(host) != export.checksums[ci]:
                raise MigrationError(
                    f"transfer chunk {ci} checksum mismatch — pages "
                    f"corrupted in flight, refusing to land them")
    ps = export.page_size
    t0 = clock()
    start_tok, new_pages = tree.plan_insert(export.bucket, export.ids)
    if not new_pages:
        return ImportResult(pages=0, nbytes=0, wall_s=clock() - t0)
    if start_tok < export.start_tokens:
        # The destination lost pages between probe and import; the
        # export cannot fill the gap — a torn prefix must never enter
        # the tree.
        tree.forget_tail(export.bucket, export.ids, len(new_pages))
        raise MigrationError(
            f"export starts at token {export.start_tokens} but the "
            f"destination needs from {start_tok} (pages evicted since "
            f"the probe)")
    # Transfer pin: fresh pages are unevictable until their data lands.
    tree.pool.incref(new_pages)
    skip = (start_tok - export.start_tokens) // ps
    window = max(int(cfg.inflight_chunks), 1)
    serial = 0.0
    # In-flight device_put blocks are real memory on the destination:
    # ledger them for the import's duration (PR-14 HBM governor).
    gov = getattr(engine, "governor", None)
    gov_key = None
    if gov is not None:
        gov_key = f"migrate_buf:{getattr(engine.cfg, 'name', 'model')}"
        gov.register(gov_key,
                     tree.pool.page_nbytes() * len(new_pages))
    try:
        shardings = jax.tree.map(lambda l: l.sharding, tree.pool.leaves)
        pending: deque = deque()

        def land() -> None:
            nonlocal serial
            dev, dst_ids, t_disp = pending.popleft()
            tree.pool.insert(dev, dst_ids)
            serial += clock() - t_disp

        idx = 0
        for host, n in export.chunks:
            lo = max(idx, skip)
            hi = min(idx + n, skip + len(new_pages))
            if hi > lo:
                s0, s1 = lo - idx, hi - idx
                block = jax.tree.map(lambda a: a[:, :, s0:s1], host)
                # Pages land already partitioned for the destination
                # mesh: each leaf's device_put takes the destination
                # pool leaf's own sharding (the pjit-resharding
                # pattern stream_params uses for weights).
                dev = jax.tree.map(
                    lambda b, sh: jax.device_put(b, sh),
                    block, shardings)
                pending.append(
                    (dev, new_pages[lo - skip:hi - skip], clock()))
                while len(pending) >= window + 1:
                    land()
            idx += n
        while pending:
            land()
    except BaseException:
        tree.pool.decref(new_pages)           # the transfer pin
        tree.forget_tail(export.bucket, export.ids, len(new_pages))
        raise
    finally:
        if gov_key is not None:
            gov.unregister(gov_key)
    tree.pool.decref(new_pages)
    return ImportResult(
        pages=len(new_pages),
        nbytes=tree.pool.page_nbytes() * len(new_pages),
        wall_s=clock() - t0, serial_s=serial)


# ---------------------------------------------------------------------------
# The migrator (router-held; the chaos fault seam)
# ---------------------------------------------------------------------------


class PageMigrator:
    """The router's migration policy object: config + stats + the
    ``transfer`` wire hop between export and import.

    ``transfer`` is deliberately an identity function on one object —
    it exists so the transport (today an in-process handoff; a DCN hop
    in a multi-process deployment) and the chaos kinds
    (``faults.wrap_migrator``: ``migration_stall`` sleeps past the
    chain deadline, ``migration_corrupt`` flips chunk bytes under the
    checksums) have one seam to wrap."""

    def __init__(self, config: Optional[MigrationConfig] = None,
                 stats=None,
                 clock: Callable[[], float] = time.monotonic):
        from ..utils.profiling import MigrationStats

        self.config = config or MigrationConfig()
        self.stats = stats if stats is not None else MigrationStats()
        self.clock = clock

    def transfer(self, export: PageExport) -> PageExport:
        """The wire hop (module docstring). In-process: a no-op."""
        return export

    def account(self, export: PageExport, imp: ImportResult) -> None:
        """Fold one completed chain into MigrationStats: exposed =
        critical-path wall seconds, hidden = in-flight seconds the
        double-buffered window overlapped away (serial sum minus
        wall, per leg)."""
        self.stats.add_transfer(
            pages=imp.pages, nbytes=imp.nbytes,
            chunks=len(export.chunks),
            exposed_s=export.wall_s + imp.wall_s,
            hidden_s=(max(export.serial_s - export.wall_s, 0.0)
                      + max(imp.serial_s - imp.wall_s, 0.0)))
