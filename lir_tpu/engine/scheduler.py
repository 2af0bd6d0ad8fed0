"""Length-aware ragged sweep scheduler: bucket ladder + slot refill +
cross-cell prefix reuse.

The perturbation grid is a *ragged* workload: real rephrasings of a legal
prompt vary ~2-4x in tokenized length, while the engine's decode programs
are fixed-shape. The legacy path batched cells in todo order and padded
every batch to the longest row's bucket — on a mixed-length grid nearly
every batch contains one long prompt, so nearly every batch pays the
largest bucket and short prompts burn their FLOPs on left-padding. This
module sits between the grid and the engine and plans the sweep's
dispatches up front (the grid is fully known — there is no online arrival
process), the whole grid at once or, where a token cap keeps the rows of
different prompts apart anyway, a closed window of prompts at a time
(:meth:`RaggedScheduler.closed`, :class:`EdgeFloors`):

1. **Bucket ladder** (tokens.bucket_ladder): cells are sorted into
   ~sqrt(2)-spaced prompt-length buckets by their real tokenized prefix
   length, so a 90-token rephrasing prefills 128 slots, not 1024. Each
   bucket's shape compiles once and serves every dispatch in the bucket.
2. **Slot refill**: batches are drained per bucket queue, so batch slots
   that the todo-order path would have wasted as ragged-tail padding are
   refilled with the next same-bucket cells; when a bucket's queue can no
   longer fill a batch, its tail is promoted into the next bucket's queue
   whenever the cost model says the promoted rows are cheaper than a
   padded tail dispatch — the sweep then pays for at most one ragged tail
   instead of one per bucket. (In-scan retirement is already handled by
   the early stop: the decode loop ends once every row is done,
   generate._stepped; the retire positions feed the decode-occupancy
   counter, profiling.OccupancyStats.)
3. **Cross-cell prefix reuse**: cells whose tokenized prompts agree on a
   long prefix (the sweep formats x rephrasings of one base prompt, when
   rephrasings preserve the opening tokens) are grouped; each group's
   prefix is prefilled ONCE and every member row extends from a
   row-gathered copy of that cache (generate.greedy_decode_dispatch's
   grouped layout)
   — generalizing decode_fused_shared's pairwise binary/confidence
   sharing to arbitrary fan-out.

The scheduler is pure host-side planning — deterministic, total (every
cell lands in exactly one dispatch), and engine-agnostic (items carry an
opaque payload). Shapes it plans are stable per bucket, which is what
lets the runner's cache handoff keep one donated KV buffer per bucket
(see generate: ``scratch_cache``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..utils.profiling import OccupancyStats
from . import tokens as tok

SUFFIX_BUCKETS = (8, 16, 32, 64, 128, 256)
# Grid a plan tightens each prefix edge to (RaggedScheduler.schedule): the
# ladder decides which rows ride together, the longest prefix a key's
# dispatches carry decides the shape, rounded up to this. 64 bounds an
# engine to a handful of prefix shapes (eight up to 512), keeps a cascade
# remainder window (edge - trunk, the trunk on its 16 grid) a multiple of
# 16, and keeps calls whose longest row wanders by a few tokens on one
# executable. An engine whose prefill runs the Pallas flash kernel plans
# on that kernel's block instead (tokens.FLASH_BLOCK, which its ladder
# already sits on): an edge off it would drop the prefill to dense
# attention.
PREFIX_EDGE_GRID = 64


def _grid_edge(length: int, bucket: int, grid: int) -> int:
    """``length`` rounded up to ``grid``, never above ``bucket``."""
    return min(bucket, -(-max(length, 1) // grid) * grid)


def prefix_edges(length: int, bucket: int,
                 grid: int = PREFIX_EDGE_GRID) -> Tuple[int, ...]:
    """Every prefix extent a row of ``length`` prefix tokens queued under
    ladder ``bucket`` may be dispatched at — which one is known only once
    the plan is final (the key's longest row decides). The radix cache
    keeps one namespace per extent, so a plan-time probe for a row's warm
    pages looks under each of these (engine/sweep._ragged_planner)."""
    return tuple(range(_grid_edge(length, bucket, grid), bucket,
                       grid)) + (bucket,)

# Decode-floor price constants: how many prefill row-tokens one
# decode-scan token is worth. Recalibrated against the PR-7 fused kernel
# timings (flash-decode + int8 matmul fusion): with the fused kernels a
# decode step's device time tracks one prefill row-token closely (the
# score row, softmax, and probability row stay in VMEM), so the fused
# price is 1.0 — which also keeps every pre-existing plan byte-identical.
# The UNFUSED dense lowering pays ~3x that in HBM round-trips per step;
# engines running --no-fused-decode price their decode floor (and hence
# their watchdog deadlines) with the slower constant so the planner
# doesn't over-promote tails and the watchdog doesn't shoot legitimate
# dense decodes timed against a fused-kernel calibration.
DECODE_TOKEN_COST_FUSED = 1.0
DECODE_TOKEN_COST_UNFUSED = 3.0
# Speculative decode (engine/spec.py): a verify forward checks spec_k
# positions at once, so with healthy accept rates a decode token costs a
# fraction of a sequential step. 0.5 prices the conservative ≥2x
# dispatch-reduction target rather than the full-accept best case; a
# zero-accept dispatch legitimately falls back to ~sequential cost,
# which is why watchdog_seed_headroom() covers the UNFUSED/SPEC spread.
DECODE_TOKEN_COST_SPEC = 0.5


def decode_token_cost(fused_decode: bool = True,
                      spec_decode: bool = False) -> float:
    """The decode-floor constant for a kernel mode (see above).
    ``spec_decode`` prices a speculating dispatch; the default keeps
    every pre-existing (non-spec) plan byte-identical."""
    if spec_decode:
        return DECODE_TOKEN_COST_SPEC
    return (DECODE_TOKEN_COST_FUSED if fused_decode
            else DECODE_TOKEN_COST_UNFUSED)


# Cascade DECODE discount (ops/flash_decode trunk variants): the share
# of a fused decode step's cost that is KV-cache HBM streaming — the
# only term the trunk dedup removes (weights/activations stream either
# way). The discount scales by the trunk's fraction of the cache extent
# and by the deduped-row fraction (slots - 1) / slots, so a batch-1 or
# trunkless dispatch prices byte-identically to the flat kernel.
CASCADE_DECODE_KV_SHARE = 0.3

# Cascade-prefill watchdog spread (watchdog_seed_headroom): a cascade
# engine's deadlines calibrate on cascade-discounted dispatches, but an
# ineligible dispatch (short LCP, too few rows) legitimately runs the
# FULL dense prefill — up to the whole trunk re-paid per row. 2.0 covers
# the worst eligible-vs-fallback prefill ratio the eligibility gates
# admit (trunk < bucket, so the dense prefill is at most ~2x the
# cascade-discounted price the deadline was calibrated on).
CASCADE_PREFILL_SPREAD = 2.0


def watchdog_seed_headroom(spec_decode: bool = False,
                           cascade: bool = False) -> float:
    """EWMA seed headroom for the dispatch watchdog (guard/watchdog.py):
    the spread between the decode pricing a deadline is calibrated on
    and the most expensive mode a dispatch may legitimately fall back
    to (the unfused dense path). The watchdog's first calibration
    sample is inflated by this ratio so a deadline calibrated on
    fused-kernel dispatches never fires spuriously on a dense
    fallback. A SPECULATING engine (``spec_decode``) widens the seed
    to the UNFUSED/SPEC spread: its dispatches are priced at the
    speculative decode floor, and a zero-accept dispatch that
    degenerates to the sequential scan — possibly on the dense
    fallback path — must never trip a spec-calibrated deadline.
    Non-spec engines keep the original fused/unfused spread (their
    deadlines owe speculation nothing). A CASCADE engine
    (``cascade``) additionally multiplies in the cascade/dense
    PREFILL spread (CASCADE_PREFILL_SPREAD): its deadlines calibrate
    on trunk-discounted dispatches, and an ineligible dispatch that
    falls back to the full dense prefill must never trip a
    cascade-calibrated deadline. The spreads compose — a spec+cascade
    engine can hit both fallbacks on one dispatch."""
    seed = (DECODE_TOKEN_COST_UNFUSED / DECODE_TOKEN_COST_SPEC
            if spec_decode
            else DECODE_TOKEN_COST_UNFUSED / DECODE_TOKEN_COST_FUSED)
    if cascade:
        seed *= CASCADE_PREFILL_SPREAD
    return seed


def _tail_batch(n: int, cap: int) -> int:
    """Smallest power of two >= n, capped (mirrors runner._tail_batch)."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def decode_floor(n_rows: int, batch_size: int, decode_cost: int,
                 fused_decode: bool = True,
                 spec_decode: bool = False,
                 decode_trunk_frac: float = 0.0) -> float:
    """The decode-scan floor of a dispatch's price: every padded slot runs
    the full decode budget whether it carries work or padding, priced at
    the kernel mode's decode-floor constant. Cached prefill can never
    push a dispatch below this (bucket_cost); the piggyback path prices
    a parked dispatch's pending scans with exactly this term.
    ``spec_decode`` prices a speculating dispatch's verify forwards.

    ``decode_trunk_frac`` (trunk tokens / cache extent, 0..1) prices the
    cascade-DECODE dedup: a trunk-aware dispatch streams its trunk K/V
    tiles once per step instead of once per row, shaving
    CASCADE_DECODE_KV_SHARE x trunk-fraction x deduped-row-fraction off
    the floor. The default keeps every pre-existing plan
    byte-identical."""
    slots = _tail_batch(n_rows, batch_size)
    floor = (slots * decode_cost
             * decode_token_cost(fused_decode, spec_decode))
    if decode_trunk_frac > 0.0 and slots > 1:
        frac = min(max(float(decode_trunk_frac), 0.0), 1.0)
        floor *= 1.0 - CASCADE_DECODE_KV_SHARE * frac * (slots - 1) / slots
    return floor


def bucket_cost(n_rows: int, bucket_edge: int, batch_size: int,
                decode_cost: int, cached_tokens: int = 0,
                fused_decode: bool = True,
                spec_decode: bool = False,
                cascade: bool = False,
                trunk_tokens: int = 0,
                decode_trunk_frac: float = 0.0) -> float:
    """Row-token cost of dispatching ``n_rows`` cells at ``bucket_edge``:
    a padded power-of-two batch prefilled at the edge, plus the fixed
    decode floor (:func:`decode_floor` — the steps run whether the slots
    carry work or padding, priced per kernel mode).

    This is THE decode-cost price model (linear param term dominates at
    7B scale: prefill ~ bucket edge per row, each decode step ~ 1 token
    per slot under the fused kernels). The offline planner's slot-refill
    rule (:meth:`RaggedScheduler._plan_shared`), the online continuous
    batcher's bucket-selection policy (serve/batcher.py), AND the
    dispatch watchdog's deadline predictions (guard/watchdog.py) price
    dispatches through this one helper so the three can't drift apart.

    ``cached_tokens`` are prefix tokens the cross-request radix cache
    (engine/prefix_tree.py) already holds for the candidate rows —
    FREE prefill: a paged dispatch gathers them from the page pool
    instead of recomputing, so they come off the prefill term. The
    decode scan is the floor: cached prefill can never make a dispatch
    cheaper than its decode steps.

    ``cascade``/``trunk_tokens`` price the shared-trunk cascade
    discount (ops/cascade_prefill): a cascade dispatch prefills its
    ``trunk_tokens``-token trunk ONCE instead of once per slot, so
    ``(slots - 1) * trunk_tokens`` comes off the prefill term — on top
    of any radix-cached tokens (a warm trunk discounts through
    ``cached_tokens`` too; the max(0) clamp keeps double-counting from
    going negative). ``decode_trunk_frac`` prices the cascade-DECODE
    dedup through :func:`decode_floor`. Defaults price the dense path
    byte-identically."""
    slots = _tail_batch(n_rows, batch_size)
    prefill = slots * bucket_edge - int(cached_tokens)
    if cascade and trunk_tokens > 0:
        prefill -= (slots - 1) * int(trunk_tokens)
    prefill = max(prefill, 0)
    return prefill + decode_floor(n_rows, batch_size, decode_cost,
                                  fused_decode, spec_decode,
                                  decode_trunk_frac=decode_trunk_frac)


@dataclasses.dataclass(frozen=True)
class SweepItem:
    """One grid cell, tokenized. ``lcp`` is the binary/confidence shared
    token prefix (tokens.shared_prefix_len) — the row's prefill length."""

    cell: Any
    bin_ids: Tuple[int, ...]
    conf_ids: Tuple[int, ...]
    lcp: int

    @property
    def prefix_len(self) -> int:
        return max(self.lcp, 1)


@dataclasses.dataclass(frozen=True)
class PrefixGroup:
    """Cells sharing ``plen`` leading tokens; prefilled once as one row."""

    items: Tuple[SweepItem, ...]
    plen: int


@dataclasses.dataclass
class Dispatch:
    """One engine call. ``kind`` is "shared" (pairwise prefix sharing,
    decode_fused_shared) or "grouped" (cross-cell prefix reuse,
    decode_fused_grouped). ``refilled`` counts cells promoted here from a
    smaller bucket's ragged tail. ``bucket`` is the LADDER edge the rows
    were queued under; ``edge`` is the prefix extent the dispatch runs at
    — the one field every consumer of the shape reads (dispatch, compile
    plan, cascade trunk clamp, radix namespace): the bucket tightened to
    the longest prefix its ``(kind, bucket)`` key carries
    (PREFIX_EDGE_GRID), the bucket itself where a row reaches it. Edges,
    prefix and suffix, are planned per key (not per dispatch) so every
    dispatch of a bucket shares one compiled shape and one handoff cache
    buffer."""

    kind: str
    bucket: int
    items: List[SweepItem]
    refilled: int = 0
    groups: Optional[List[PrefixGroup]] = None
    sfx_bucket_a: int = 0
    sfx_bucket_b: int = 0
    edge: int = 0

    @property
    def cells(self) -> List[Any]:
        return [it.cell for it in self.items]

    def padded_rows(self, batch_size: int) -> Tuple[int, int]:
        """(prefill rows, member rows) after the runner's power-of-two
        tail padding — the EXACT shapes the engine will dispatch, so the
        compile plan (engine/compile_plan.py) can lower every executable
        before the first dispatch. Shared dispatches prefill and decode
        the same padded batch; grouped dispatches prefill one row per
        group and decode two member rows ([bin, conf]) per cell."""
        n = len(self.items)
        if self.kind == "shared":
            b = batch_size if n == batch_size else _tail_batch(n, batch_size)
            return b, b
        return (_tail_batch(len(self.groups), batch_size),
                _tail_batch(2 * n, 2 * batch_size))


@dataclasses.dataclass
class EdgeFloors:
    """The longest format suffixes and the longest prefix each ``(kind,
    bucket)`` key has carried so far in a call that is planned a window
    at a time (engine/sweep._fill_windows). :meth:`RaggedScheduler.
    schedule` plans at no less and raises them to what it planned, so
    the edges of a call only ever grow and a key keeps one compiled
    shape from window to window wherever the rows allow it."""

    sfx_a: Dict[Tuple[str, int], int] = dataclasses.field(
        default_factory=dict)
    sfx_b: Dict[Tuple[str, int], int] = dataclasses.field(
        default_factory=dict)
    longest: Dict[Tuple[str, int], int] = dataclasses.field(
        default_factory=dict)

    def carry(self, key: Tuple[str, int], la: int, lb: int,
              lp: int) -> None:
        self.sfx_a[key] = max(self.sfx_a.get(key, 1), la)
        self.sfx_b[key] = max(self.sfx_b.get(key, 1), lb)
        self.longest[key] = max(self.longest.get(key, 1), lp)


def build_items(bin_ids: Sequence[Sequence[int]],
                conf_ids: Sequence[Sequence[int]],
                cells: Sequence[Any]) -> List[SweepItem]:
    """Pair pre-tokenized prompt ids with their cells (total: one item per
    cell, in input order)."""
    items = []
    for c, b, f in zip(cells, bin_ids, conf_ids):
        b, f = tuple(int(i) for i in b), tuple(int(i) for i in f)
        items.append(SweepItem(cell=c, bin_ids=b, conf_ids=f,
                               lcp=tok.shared_prefix_len(b, f)))
    return items


def _lcp(a: Sequence[int], b: Sequence[int]) -> int:
    return tok.lcp(a, b)


class RaggedScheduler:
    """Plans a sweep's dispatches from tokenized items.

    Parameters
    ----------
    buckets: prefix bucket ladder (tokens.bucket_ladder edges).
    batch_size: cells per dispatch (member rows are 2x this in grouped
        dispatches — one binary + one confidence row per cell).
    new_budget: max decode tokens any row runs (bounds the cache extent
        the learned-position check reasons about).
    decode_cost: per-slot decode tokens a dispatch pays regardless of
        prompt length (both branches' budgets; the sweep passes
        new_tokens + conf_tokens). Defaults to new_budget. The slot
        refill cost model charges a kept tail dispatch this on top of
        its prefill — decode steps are the fixed price of dispatching
        at all, which is what promotion avoids.
    suffix_buckets: right-pad edges for format suffixes.
    edge_grid: grid the plan tightens prefix edges to (PREFIX_EDGE_GRID;
        the sweep passes tokens.FLASH_BLOCK for a flash-prefill engine).
    max_extent: position ceiling (learned-position tables); None = no cap.
    min_group_prefix / min_group_cells: cross-cell grouping engages only
        for >= min_group_cells cells agreeing on >= min_group_prefix
        tokens AND on at least half of each member's prefill — shorter
        shared prefixes don't amortize the extra suffix-extension FLOPs.
    group_cells: 0 disables cross-cell grouping entirely.
    token_cap: 0, or the most tokens one pass of a shared dispatch may
        hold (RuntimeConfig.dispatch_tokens): the rows of a dispatch times
        what each runs beyond the prefix they all share. Long rows then
        ride together only where they share a trunk (the cascade front
        runs the trunk once, at one row), and a row that shares none
        with its neighbours is dispatched alone.
    cached_probe: optional ``(item, bucket_edge) -> cached tokens`` hook
        into the cross-request radix prefix cache (engine/prefix_tree.
        match_len). The slot-refill rule then prices cached-prefix
        tokens as FREE prefill — and since the radix namespaces are
        per prefix extent, promoting a tail into the next bucket
        honestly loses this bucket's cached pages, which the probe
        reflects (it looks under every extent the bucket may be
        tightened to, :func:`prefix_edges`).
    """

    def __init__(self, buckets: Sequence[int], batch_size: int, *,
                 new_budget: int = 8, decode_cost: Optional[int] = None,
                 suffix_buckets: Sequence[int] = SUFFIX_BUCKETS,
                 edge_grid: int = PREFIX_EDGE_GRID,
                 max_extent: Optional[int] = None,
                 min_group_prefix: int = 16, min_group_cells: int = 4,
                 group_cells: bool = True,
                 cached_probe=None,
                 fused_decode: bool = True,
                 stats: Optional[OccupancyStats] = None,
                 token_cap: int = 0):
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.batch = int(batch_size)
        self.new_budget = int(new_budget)
        self.decode_cost = int(new_budget if decode_cost is None
                               else decode_cost)
        self.fused_decode = bool(fused_decode)
        self.suffix_buckets = tuple(sorted(suffix_buckets))
        self.edge_grid = int(edge_grid)
        self.max_extent = max_extent
        self.min_group_prefix = int(min_group_prefix)
        self.min_group_cells = int(min_group_cells)
        self.group_cells = group_cells
        self.cached_probe = cached_probe
        self.token_cap = int(token_cap)
        self.stats = stats if stats is not None else OccupancyStats()

    def _cached_tokens(self, items: Sequence[Tuple[SweepItem, bool]],
                       edge: int) -> int:
        """Radix-cached prefix tokens across ``items`` at ``edge``'s
        namespace (0 without a probe — the legacy price)."""
        if self.cached_probe is None:
            return 0
        return sum(self.cached_probe(it, edge) for it, _ in items)

    # -- cross-cell prefix grouping -----------------------------------------

    def _fits_grouped(self, plen: int, items: Sequence[SweepItem]) -> bool:
        """A candidate group must keep every member's suffix inside the
        suffix ladder, leave >= 1 real suffix token per member row, and
        (learned positions) keep bucket + suffix + decode inside the
        table."""
        max_sfx = max(max(len(it.bin_ids), len(it.conf_ids)) - plen
                      for it in items)
        min_sfx = min(min(len(it.bin_ids), len(it.conf_ids)) - plen
                      for it in items)
        if min_sfx < 1 or max_sfx > self.suffix_buckets[-1]:
            return False
        bucket = tok.assign_bucket(plen, self.buckets)
        if bucket < plen:           # prefix exceeds the largest bucket
            return False
        if self.max_extent is not None:
            sfx_bucket = tok.pick_bucket([max_sfx], self.suffix_buckets)
            if bucket + sfx_bucket + self.new_budget > self.max_extent:
                return False
        return True

    def _form_groups(self, items: List[SweepItem]
                     ) -> Tuple[List[PrefixGroup], List[SweepItem]]:
        """Greedy grouping over sort order: sorting by token sequence puts
        shared-prefix cells adjacent, so one linear merge pass finds every
        maximal run agreeing on a long-enough prefix. Deterministic (sort
        key is the token tuple; ties broken by input order via stable
        sort) and total (non-grouped items pass through untouched)."""
        order = sorted(range(len(items)), key=lambda i: items[i].bin_ids)
        groups: List[PrefixGroup] = []
        rest: List[SweepItem] = []
        run: List[SweepItem] = []
        run_plen = 0

        def flush():
            nonlocal run, run_plen
            if len(run) >= self.min_group_cells:
                groups.append(PrefixGroup(items=tuple(run), plen=run_plen))
            else:
                rest.extend(run)
            run, run_plen = [], 0

        for i in order:
            it = items[i]
            if not run:
                run, run_plen = [it], it.prefix_len
                continue
            # Joint prefix if `it` joins: common tokens with the run,
            # capped by each side's own binary/confidence split point.
            p = min(run_plen, _lcp(run[-1].bin_ids, it.bin_ids), it.lcp)
            ok = (p >= self.min_group_prefix
                  and len(run) < self.batch
                  # the shared prefix must carry at least half of every
                  # member's prefill or grouping re-pays it in suffixes
                  and all(2 * p >= m.prefix_len for m in run + [it])
                  and self._fits_grouped(p, run + [it]))
            if ok:
                run.append(it)
                run_plen = p
            else:
                flush()
                run, run_plen = [it], it.prefix_len
        flush()
        # Restore input order among non-grouped items (stable downstream
        # bucket queues).
        pos = {id(it): i for i, it in enumerate(items)}
        rest.sort(key=lambda it: pos[id(it)])
        return groups, rest

    # -- bucket queues + slot refill ----------------------------------------

    def _chunk_rows(self, q: List[Tuple[SweepItem, bool]], edge: int) -> int:
        """Rows the next dispatch takes off the front of a bucket's queue:
        a batch, or under a token cap as many as fit one pass beside the
        first row at what they all share with it."""
        n = min(self.batch, len(q))
        if not self.token_cap:
            return n
        first = q[0][0]
        head = first.bin_ids[:first.lcp]
        rows = 1
        while rows < n:
            it = q[rows][0]
            shared = _lcp(head, it.bin_ids[:it.lcp])
            if (rows + 1) * (edge - shared) > self.token_cap:
                break
            head = head[:shared]
            rows += 1
        return rows

    def _plan_shared(self, items: List[SweepItem]) -> List[Dispatch]:
        queues: Dict[int, List[Tuple[SweepItem, bool]]] = {
            b: [] for b in self.buckets}
        for it in items:
            queues[tok.assign_bucket(it.prefix_len, self.buckets)].append(
                (it, False))

        out: List[Dispatch] = []
        B = self.batch
        for bi, edge in enumerate(self.buckets):
            q = queues[edge]
            while len(q) >= B or (self.token_cap and q):
                n = self._chunk_rows(q, edge)
                if n == len(q) < B:
                    break                  # the ragged tail, priced below
                chunk, q = q[:n], q[n:]
                out.append(Dispatch(
                    kind="shared", bucket=edge,
                    items=[it for it, _ in chunk],
                    refilled=sum(1 for _, r in chunk if r)))
            if not q:
                continue
            nxt = self.buckets[bi + 1] if bi + 1 < len(self.buckets) else None
            # Slot refill under the shared price model (bucket_cost).
            # Keeping the tail pays a WHOLE extra dispatch: a padded
            # power-of-two batch prefilled at this edge plus its fixed
            # decode scan. Promoting pays len(tail) rows at the next
            # edge, where they fill slots of dispatches that run anyway
            # (and cascade upward the same way). With a prefix-cache
            # probe, cached tokens discount each side: a tail whose
            # prefixes are warm in THIS bucket's radix namespace is
            # cheap to keep and expensive to promote (the next bucket's
            # namespace holds different pages).
            if (nxt is not None
                    and len(q) * nxt - self._cached_tokens(q, nxt)
                    < bucket_cost(len(q), edge, B, self.decode_cost,
                                  cached_tokens=self._cached_tokens(q, edge),
                                  fused_decode=self.fused_decode)):
                queues[nxt] = [(it, True) for it, _ in q] + queues[nxt]
            else:
                out.append(Dispatch(
                    kind="shared", bucket=edge,
                    items=[it for it, _ in q],
                    refilled=sum(1 for _, r in q if r)))
        return out

    def _plan_grouped(self, groups: List[PrefixGroup]) -> List[Dispatch]:
        """Pack prefix groups into dispatches: groups sharing a prefix
        bucket ride together until the member-row capacity (2 rows per
        cell, capped at 2*batch) fills."""
        by_bucket: Dict[int, List[PrefixGroup]] = {}
        for g in groups:
            by_bucket.setdefault(
                tok.assign_bucket(g.plen, self.buckets), []).append(g)
        out: List[Dispatch] = []
        cap = 2 * self.batch
        for edge in sorted(by_bucket):
            cur: List[PrefixGroup] = []
            rows = 0
            for g in by_bucket[edge]:
                if cur and rows + 2 * len(g.items) > cap:
                    out.append(self._grouped_dispatch(edge, cur))
                    cur, rows = [], 0
                cur.append(g)
                rows += 2 * len(g.items)
            if cur:
                out.append(self._grouped_dispatch(edge, cur))
        return out

    def _grouped_dispatch(self, edge: int,
                          groups: List[PrefixGroup]) -> Dispatch:
        return Dispatch(
            kind="grouped", bucket=edge,
            items=[it for g in groups for it in g.items], groups=groups)

    # -- public entry --------------------------------------------------------

    def closed(self, items: Sequence[SweepItem]) -> bool:
        """True where no row of ``items`` could share a dispatch with a
        row it shares no trunk with: under the token cap two such rows
        do not fit one pass (the rule of :meth:`_chunk_rows` at nothing
        shared). A plan of these rows alone is then the part of a larger
        plan that holds them, up to the edges (:class:`EdgeFloors`), so
        the sweep plans and dispatches them before it has tokenized what
        comes after. Never without a cap, nor where cells are grouped
        across rows (groups of several prompts share a dispatch)."""
        if not self.token_cap or (self.group_cells
                                  and self.min_group_cells > 1):
            return False
        return all(
            2 * tok.assign_bucket(it.prefix_len, self.buckets)
            > self.token_cap for it in items)

    def foresee(self, items: Sequence[SweepItem],
                floors: EdgeFloors) -> None:
        """Raise ``floors`` to what ``items`` would carry as shared rows
        of their own buckets: rows a later window will plan, looked at
        early so that the windows before it already run at their edges
        (a prompt's format suffixes are the same on every one of its
        rows, so one row a prompt tells them all)."""
        for it in items:
            floors.carry(
                ("shared", tok.assign_bucket(it.prefix_len, self.buckets)),
                len(it.bin_ids) - it.lcp, len(it.conf_ids) - it.lcp,
                it.prefix_len)

    def schedule(self, items: Sequence[SweepItem],
                 floors: Optional[EdgeFloors] = None) -> List[Dispatch]:
        """Plan every dispatch for ``items``. Total and deterministic:
        each item appears in exactly one dispatch; identical inputs plan
        identical schedules. ``floors``: the edges of the windows planned
        before this one, raised in place to this plan's."""
        items = list(items)
        if self.group_cells and self.min_group_cells > 1:
            groups, rest = self._form_groups(items)
        else:
            groups, rest = [], items
        dispatches = self._plan_shared(rest) + self._plan_grouped(groups)

        # Plan the edges PER PREFIX BUCKET (shape/handoff stability), from
        # the lengths the final queues hold: the suffix edges from the
        # longest suffix, the prefix edge from the longest prefix on the
        # edge grid — never above the ladder bucket, which is also what an
        # over-long row (truncated into the largest bucket) keeps.
        edges = floors if floors is not None else EdgeFloors()
        for d in dispatches:
            if d.kind == "shared":
                la = max(len(it.bin_ids) - it.lcp for it in d.items)
                lb = max(len(it.conf_ids) - it.lcp for it in d.items)
                lp = max(it.prefix_len for it in d.items)
            else:
                la = lb = max(
                    max(len(it.bin_ids), len(it.conf_ids)) - g.plen
                    for g in d.groups for it in g.items)
                lp = max(g.plen for g in d.groups)
            edges.carry((d.kind, d.bucket), la, lb, lp)
        for d in dispatches:
            key = (d.kind, d.bucket)
            d.sfx_bucket_a = tok.pick_bucket([edges.sfx_a[key]],
                                             self.suffix_buckets)
            d.sfx_bucket_b = tok.pick_bucket([edges.sfx_b[key]],
                                             self.suffix_buckets)
            d.edge = _grid_edge(edges.longest[key], d.bucket,
                                self.edge_grid)

        self._account(dispatches)
        return dispatches

    def _account(self, dispatches: List[Dispatch]) -> None:
        """Charge the occupancy counters for the shapes DISPATCHED: slot
        tokens at the planned ``edge`` (so padding waste is what the
        device pads), under the ladder bucket's name, with what the
        tightening took off counted beside it (``trimmed_slots``)."""
        for d in dispatches:
            n = len(d.items)
            if d.kind == "shared":
                slots = _tail_batch(n, self.batch)
                real = sum(it.prefix_len for it in d.items)
                self.stats.add_dispatch(d.edge, n, slots, real,
                                        refilled=d.refilled,
                                        bucket=d.bucket)
            else:
                g_pad = _tail_batch(len(d.groups), self.batch)
                m_pad = _tail_batch(2 * n, 2 * self.batch)
                real = sum(grp.plen for grp in d.groups)
                self.stats.add_dispatch(d.edge, n, m_pad, real,
                                        used_slots=2 * n,
                                        prefill_slots=g_pad,
                                        bucket=d.bucket)
                self.stats.grouped_cells += n
                self.stats.grouped_prefill_rows += g_pad
