"""Central configuration for lir_tpu.

The reference scatters configuration across module-level CAPITALIZED constants,
``.env`` secrets, and hard-coded personal paths (reference:
analysis/perturb_prompts.py:19-65, analysis/config.py:1-16,
analysis/compare_base_vs_instruct.py:129-132). Here all of it is one dataclass
tree with a single ``backend`` switch ("tpu" | "api") as mandated by the north
star (BASELINE.json). No secrets live in code: the optional API backend reads
keys from the environment at call time.
"""

from __future__ import annotations

import dataclasses
import os
from pathlib import Path
from typing import Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device-mesh shape for pjit sharding.

    Axis names follow the scaling-book convention: ``data`` for batch/grid
    parallelism, ``model`` for tensor parallelism (attention heads / MLP
    columns), ``seq`` for sequence (ring/context) parallelism. Any axis can be
    1. The product must equal the number of devices used.
    """

    data: int = 1
    model: int = 1
    seq: int = 1

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return ("data", "model", "seq")

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.data, self.model, self.seq)

    @property
    def n_devices(self) -> int:
        return self.data * self.model * self.seq


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Numerics + execution knobs for the inference engine."""

    dtype: str = "bfloat16"           # parameter/activation dtype on TPU
    logits_dtype: str = "float32"     # final logits always accumulated in fp32
    max_new_tokens: int = 50          # reference: compare_base_vs_instruct.py:253
    scan_positions: int = 10          # MAX_LOOK_AHEAD, compare_base_vs_instruct.py:187
    topk_match: int = 2               # top-2 yes/no match rule, :270-273
    batch_size: int = 32              # padded scoring batch per device step
    # 0, or the most tokens one pass of a sweep dispatch may hold: its rows
    # times what each runs beyond the prefix they all share. Long rows
    # (a 16k-token document) then ride together only where they share a
    # trunk, which the cascade front runs once at one row; a row that
    # shares none is dispatched alone (scheduler.RaggedScheduler).
    dispatch_tokens: int = 0
    # True: the first dispatch of a shape is handed an empty cache to
    # donate, so a shape has ONE program (the donated variant) and not two.
    # The program never reads what it is handed (generate.
    # greedy_decode_dispatch); a 9B model's program is a minute of
    # compiling a variant.
    donate_first: bool = False
    max_seq_len: int = 1024           # legal prompt + format ≲ 700 tokens (SURVEY §5)
    remat: bool = False               # jax.checkpoint the blocks for big models

    # Perturbation-sweep decode budget. The sweep's numeric readouts consume
    # ONLY position 0 (Token_1/2_Prob, top-20 map, E[v] — perturb_prompts.py:
    # 474-526), so by default each binary cell decodes a few tokens instead
    # of the full `max_new_tokens`=50 — a ~10x cut in decode-step compute.
    # The confidence call keeps a larger budget: its *parsed* integer may sit
    # several tokens into a verbose reply ("I am about 85% sure"), and a
    # truncated decode would silently null 'Confidence Value'. The 8-token
    # default is measured, not guessed: across the reference's committed
    # real-model outputs (18 base/instruct + 10 instruct models,
    # data/*_comparison_results.csv), the answer token sits at word 0-1 for
    # every perturbation-zoo family (96.4% of base rows and 100% of
    # instruct rows inside 8 words — SCALE.md "confidence decode budget").
    # A truncated integer is never recorded wrong (the parse rejects
    # budget-edge integers), and the C26 confidence-compliance gate flags a
    # model that needs a bigger budget; with `sweep_early_stop` a generous
    # re-run budget costs only actual response length.
    # `sweep_full_completions=True` restores 50-token 'Model Response' /
    # 'Model Confidence Response' text parity with the reference.
    sweep_decode_tokens: int = 4
    sweep_confidence_tokens: int = 8
    # Stop the confidence decode scan once every row has emitted EOS or a
    # complete first integer (a digit token followed by a digit-free one) —
    # the only thing the confidence parse reads. Needs per-token strings
    # (HF tokenizers) + an EOS id; silently off otherwise.
    sweep_early_stop: bool = True
    sweep_full_completions: bool = False

    # Ragged sweep scheduler (engine/scheduler.py). ON: grid cells are
    # tokenized up front, sorted into a ~sqrt(2) prompt-length bucket
    # ladder (engine/tokens.bucket_ladder), drained per-bucket with slot
    # refill, and cells sharing a long token prefix score through one
    # shared prefill (cross-cell prefix reuse). OFF restores the legacy
    # todo-order batching whose every mixed-length batch pads to its
    # longest row (the bench's single-bucket baseline). Per-cell results
    # are identical either way — left/right padding is masked out of
    # every readout (pinned by tests/test_scheduler.py).
    ragged_scheduler: bool = True
    # Cross-cell prefix grouping engages for >= min_cells cells agreeing
    # on >= min_prefix leading tokens AND on at least half their prefill
    # (see scheduler.RaggedScheduler). 0 cells disables grouping.
    sweep_group_min_prefix: int = 16
    sweep_group_min_cells: int = 4

    # Compile plan (engine/compile_plan.py). With the ragged scheduler the
    # whole sweep's dispatch shapes are known before the first dispatch,
    # so every bucket executable is lowered + compiled CONCURRENTLY in
    # background threads while the first bucket streams, and dispatches
    # consume precompiled executables instead of paying trace-on-first-
    # call serially inside the sweep. 0 workers = one per CPU core
    # (capped at the shape count). OFF restores lazy per-shape jit.
    aot_precompile: bool = True       # host-only (plan policy, not shapes)
    precompile_workers: int = 0       # host-only

    # Cross-request radix prefix cache over the paged KV allocator
    # (models/paged.py + engine/prefix_tree.py). ON: the engine keeps a
    # device-resident pool of `prefix_cache_pages` KV pages of
    # `prefix_page_size` token positions each, indexed by a per-bucket
    # radix tree over tokenized prefixes; a warm dispatch gathers its
    # rows' cached prefix pages into the exact slots the left-padded
    # prefill would fill and recomputes only a small remainder window
    # (across requests AND across batches — the production workload
    # re-asks variations of ~5 legal prompts, so warm traffic prefills
    # suffixes only). Results are bitwise-identical to the unpaged path
    # (pinned by tests/test_prefix_cache.py). Pool HBM = pages x
    # models/paged.kv_page_bytes (512 pages x 16 tokens covers the 5
    # legal prompts at ~700 tokens several times over; DEPLOY.md §1g).
    # Offline sweeps default OFF (the ragged scheduler's prefix groups
    # already share within a plan; opt in for repeated grids on one
    # engine via --prefix-cache); serving defaults ON
    # (ServeConfig.prefix_cache).
    prefix_cache: bool = False
    prefix_cache_pages: int = 512
    prefix_page_size: int = 16

    # Fused decode kernels (ops/flash_decode.py). ON: single-query decode
    # steps run the Pallas flash-decode kernel — K-split online softmax
    # over the cache with a log-sum-exp combine, so the score row, the
    # fp32 softmax, and the probability row never round-trip HBM between
    # XLA kernels. Greedy decode stays argmax-identical to the dense path
    # (pinned by tests/test_kernels.py); OFF (--no-fused-decode) restores
    # the dense decode lowering exactly. The engine threads this onto
    # ModelConfig.fused_decode; CPU runs keep the dense path either way
    # (Pallas lowers on TPU; the interpreter hook is test-only).
    fused_decode: bool = True

    # Chunked prefill/decode piggybacking (Sarathi-Serve-style): the
    # ragged sweep fuses the pending decode scan of the in-flight
    # dispatch into the NEXT same-shape dispatch's prefill call
    # (engine/generate.py shared_piggyback_*), so the dispatch stream
    # pays one device round-trip per dispatch instead of two and decode
    # never waits on a host gap behind a full prefill. Results are
    # identical per row to the sequential path (pinned by tests/
    # test_kernels.py). Piggybacking keeps TWO dispatch caches live, so
    # the engine engages it only when params + 2 caches fit the device
    # memory budget; --no-piggyback opts out entirely.
    piggyback_prefill: bool = True

    # Guard layer (lir_tpu/guard): silent-failure detection.
    # Dispatch watchdog — every device dispatch runs on a watched
    # executor whose deadline is floor + multiple * predicted seconds,
    # where "predicted" comes from the scheduler.bucket_cost() price
    # model calibrated against this engine's own observed dispatch rate
    # (guard/watchdog.py). A dispatch that outlives its deadline is
    # abandoned with a full thread-stack dump and surfaces
    # DispatchStalled into the ordinary recovery machinery (ladder
    # retry -> breaker), so a wedged runtime call costs one deadline
    # instead of the run. multiple <= 0 disables; the floor is a hard
    # minimum so a fast calibration can never produce a hair-trigger
    # deadline. The first (uncalibrated) dispatch is observe-only — a
    # legitimate cold compile must never be shot. The same deadline
    # (floor * multiple) bounds how long a dispatch waits on a
    # background AOT compile before falling back to lazy jit.
    watchdog_multiple: float = 20.0   # host-only (deadline policy)
    watchdog_floor_s: float = 30.0    # host-only; cli: --watchdog-floor
    # Numerics guard — validate every row's readouts at score-extraction
    # time (probs finite and in [0,1], P(Yes)+P(No) <= 1, weighted
    # confidence in [0,100], logprob map NaN-free) and quarantine
    # offenders as error:numerics instead of writing garbage
    # (guard/numerics.py).
    numerics_guard: bool = True       # host-only (validates host readouts)
    # Streaming statistics (engine/stream_stats.py + stats/streaming):
    # every scoring dispatch folds its position-0 readouts into a
    # device-resident accumulator lattice with ONE fused update (no
    # per-row device->host transfer), checkpointed at flush boundaries
    # and merged across hosts at the shard fences; grid -> percentile/
    # kappa/bootstrap-CI estimates come straight off the accumulator
    # (live mid-run via the serve `stats` endpoint, final via
    # StreamSink.finalize). The bootstrap key is recorded in the sweep
    # manifest so CIs reproduce across resume and re-runs. OFF restores
    # the csv-reload-only pipeline (which always remains available for
    # parity — DEPLOY.md §1j).
    streaming_stats: bool = True      # host-only (sink policy, not shapes)
    # With streaming stats ON, the per-row results artifact (csv/xlsx
    # rows + manifest union resume) becomes OPTIONAL schema parity:
    # row_artifact=False skips materializing rows entirely — the
    # dispatch loop then transfers NO per-row payloads through the host
    # (resume runs off the manifest + accumulator checkpoint alone).
    # Ignored (rows always written) when streaming_stats is off.
    row_artifact: bool = True         # host-only

    # Multihost liveness — sweep shard boundaries run a heartbeat
    # allgather + barrier bounded by this timeout; a dead peer host
    # then raises HostDesyncError on the survivors (manifest already
    # flushed -> resumable) instead of parking them in ICI/DCN forever
    # (parallel/multihost.py). <= 0 restores unbounded barriers.
    barrier_timeout_s: float = 900.0  # host-only; cli: --barrier-timeout

    # Leased sweep shards (engine/lease.py; DEPLOY.md §1m). ON: the
    # pending grid is split into small shards whose ownership is a
    # LEASE record riding the manifest's {"__meta__": ...} lines
    # ({holder, expiry, seq}; renewed at every flush) in a shared
    # <results>.leases.jsonl log, instead of the static host_shard
    # partition. A live host claims unclaimed shards, then STEALS
    # shards whose lease expired (holder dead or straggling) — re-done
    # rows fold into the streaming accumulator as bitwise no-ops (slot
    # idempotence), so rebalancing can never corrupt the merged
    # lattice, and the shard fence drains leases instead of waiting on
    # the slowest static shard. Single-process runs work identically
    # (one holder claims every shard in order).
    lease_shards: bool = False        # host-only
    # Speculative scoring decode (engine/spec.py + the speculative tail
    # of generate.greedy_decode_dispatch; DEPLOY.md §1n). ON: shared-path
    # dispatches draft up to spec_k tokens ahead (prompt-lookup from
    # the radix tree's token history + n-gram self-lookup, or a small
    # fleet draft model when spec_draft_model names one) and VERIFY
    # them in one multi-query pass through the decode attention path —
    # the ≤10-token sequential scan collapses to ~T/k verify forwards
    # when drafts land. Greedy acceptance keeps every consumed result
    # (scored rows, serve payloads: position-0 readouts + generated
    # text) BITWISE identical to the sequential scan (pinned by
    # tests/test_spec_decode.py); a rejected draft only costs
    # re-verification. Piggyback chains take precedence offline
    # (--no-piggyback makes every shared dispatch eligible); the
    # drafting-policy knobs live on Config.spec (SpecConfig).
    spec_decode: bool = True
    # Verify window: tokens checked per verify forward (1 emission + up
    # to spec_k-1 accepted drafts). < 2 disables speculation.
    spec_k: int = 4
    # Fleet model id that drafts for this engine (acquired through the
    # PR-10 WeightCache so drafting never evicts the verifier
    # mid-dispatch). Empty = self-drafting (tree + n-gram lookup).
    spec_draft_model: str = ""
    # Shared-prefix cascade prefill (ops/cascade_prefill + the cascade
    # front of generate.greedy_decode_dispatch; DEPLOY.md §1q). ON: a shared
    # dispatch whose rows all begin with the same trunk (LCP across the
    # dispatch, snapped to CascadeConfig.trunk_quantum) prefills that
    # trunk ONCE at batch 1 — or gathers it warm from the radix page
    # pool at zero recompute — and extends the per-row remainders over
    # it via cascade attention: prefix leg = one dense GEMM per kv head
    # against the shared trunk KV (optionally int8 QK^T fused in-kernel),
    # suffix leg = causal window, exact log-sum-exp merge. Results are
    # argmax-identical to the dense shared path (tolerance-bound interior
    # floats — the PR-7 bar, pinned by tests/test_cascade.py);
    # --no-cascade-prefill restores the dense path exactly. Cascade
    # takes precedence over speculation and piggybacking for eligible
    # dispatches (it removes the prefill those paths would chain/draft
    # around); ineligible dispatches fall back dense and count
    # CascadeStats.dense_fallbacks. Eligibility knobs live on
    # Config.cascade (CascadeConfig).
    cascade_prefill: bool = True      # cli: --no-cascade-prefill
    # Cascade DECODE (ops/flash_decode trunk variants; DEPLOY.md §1r):
    # on a shared-trunk dispatch, every decode step's trunk-key splits
    # read their K/V from the FIRST batch block for every block's
    # queries — the trunk tiles stream from HBM once per step instead
    # of once per batch block — and only the tail splits read each
    # block's own rows; it is the flat kernel with another index map,
    # so the result is the flat kernel's (tests/test_cascade_decode.py
    # pins it, speculative verify windows ride flash_decode_mq_trunk
    # the same way). Independent of
    # cascade_prefill: a dense-prefill or paged-warm dispatch dedups its
    # decode too. --no-cascade-decode restores the flat kernels exactly
    # (the flag mirrors into the static ModelConfig, re-keying every
    # decode executable). Trunk eligibility shares CascadeConfig.
    cascade_decode: bool = True       # cli: --no-cascade-decode
    # Fused single-kernel cascade prefill (ops/cascade_prefill): prefix
    # leg + suffix leg + log-sum-exp merge in ONE Pallas launch — no HBM
    # round-trip for the per-leg partials. BITWISE the two-leg path at
    # every trunk extent (tests/test_cascade.py); --no-cascade-fused-
    # suffix restores the two-leg lowering exactly (mirrored into the
    # static ModelConfig like cascade_decode). float QK^T only — the
    # int8_qk cascade keeps the two-leg path.
    cascade_fused_suffix: bool = True  # cli: --no-cascade-fused-suffix
    # Lease time-to-live in WALL-CLOCK seconds (leases compare across
    # hosts, so the shared clock is time.time, not monotonic). A holder
    # renews on every flush; a lease older than this is stealable.
    lease_ttl_s: float = 300.0        # host-only; cli: --lease-ttl
    # Grid cells per leased shard (the stealing granularity): smaller
    # shards rebalance finer but renew/claim more often. <= 0 derives
    # ~4 shards per host from the grid.
    lease_cells_per_shard: int = 0    # host-only; cli: --lease-cells


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative-decode DRAFTING policy (engine/spec.py; DEPLOY.md
    §1n). These knobs steer where draft tokens come from — they can
    change speed, never results (greedy acceptance keeps every accepted
    token identical to the sequential scan's, so outputs are bitwise
    regardless of draft quality). The on/off switch and verify-window
    size live on RuntimeConfig (``spec_decode``/``spec_k``/
    ``spec_draft_model``) because those change compiled shapes."""

    # N-gram match length for the prompt-lookup fallback drafter: the
    # verify scan drafts the tokens that followed the most recent
    # earlier occurrence of the last `ngram` context tokens (prompt +
    # already-accepted emissions).
    ngram: int = 2                    # cli: --spec-ngram
    # Probe the radix prefix tree's token history for a whole-window
    # draft of the dispatch's continuation (prefix_tree.continuation)
    # before falling back to n-gram matching. Needs the prefix cache
    # (the tree) to be enabled on the engine; silently off otherwise.
    tree_probe: bool = True           # cli: --no-spec-tree-probe
    # Continuation tails recorded per radix node (host memory only, LRU
    # beyond this): each completed dispatch records its prompt's
    # observed continuation so a repeat visit drafts the whole reply.
    tree_tails_per_node: int = 32     # cli: --spec-tree-tails


@dataclasses.dataclass(frozen=True)
class CascadeConfig:
    """Cascade-prefill ELIGIBILITY policy (ops/cascade_prefill +
    engine/runner cascade routing; DEPLOY.md §1q). These knobs steer
    WHICH shared dispatches take the cascade split — they can change
    speed, never results (the cascade is argmax-identical to the dense
    path it replaces, and an ineligible dispatch runs the dense path
    verbatim). The on/off switch lives on RuntimeConfig
    (``cascade_prefill``) because it changes compiled shapes."""

    # Minimum shared-trunk length (tokens, post-snap) worth the split:
    # below this the prefix-leg GEMM is too thin to beat the dense
    # prefill's one fused pass, so short-LCP dispatches fall back dense
    # (counted in CascadeStats.dense_fallbacks).
    min_trunk: int = 32               # cli: --cascade-min-trunk
    # Trunk lengths snap DOWN to this grid before compilation: the trunk
    # extent is a STATIC shape (compile_plan keys executables on it), so
    # a coarse quantum keeps the executable population bounded while a
    # few unshared tail tokens just ride the per-row remainder.
    trunk_quantum: int = 16           # cli: --cascade-trunk-quantum
    # Minimum REAL rows in the dispatch: the cascade dedups trunk work
    # across rows, so a 1-row dispatch has nothing to dedup and the
    # dense path wins on dispatch overhead alone.
    min_rows: int = 2                 # cli: --cascade-min-rows
    # Fuse int8 QK^T inside the prefix-leg kernel (models/quant.py's
    # dynamic rule applied to q/trunk-k blocks in VMEM; softmax and PV
    # stay fp32). Halves the kernel's VMEM read traffic on the score
    # matmul; scores are tolerance-bound, argmax parity is pinned by
    # tests/test_cascade.py. OFF by default: exact-fp32 scores unless
    # opted in.
    int8_qk: bool = False             # cli: --cascade-int8-qk


@dataclasses.dataclass(frozen=True)
class PerturbationConfig:
    """Perturbation-sweep scale parameters (reference: perturb_prompts.py)."""

    sessions_per_prompt: int = 100      # :787-788
    rephrasings_per_session: int = 20   # numbered 1..20
    rephrase_temperature: float = 0.9   # :802
    reasoning_model_runs: int = 10      # REASONING_MODEL_RUNS, :47
    max_batch_size: int = 50_000        # MAX_BATCH_SIZE, :29
    subset_size: Optional[int] = None   # PROCESS_RANDOM_SUBSET/SUBSET_SIZE, :31-33
    seed: int = 42                      # RANDOM_SEED, :34


@dataclasses.dataclass(frozen=True)
class StatsConfig:
    """Bootstrap / MC budgets (BASELINE.md table)."""

    bootstrap_large: int = 10_000   # simulated-individual CIs, diff CIs, family MC
    bootstrap_standard: int = 1_000 # Pearson CIs, corr matrices, kappa CIs, QQ bands
    bootstrap_small: int = 100      # cross-prompt, respondent-resample
    truncnorm_samples: int = 100_000  # analyze_perturbation_results.py:113
    truncnorm_max_iter: int = 30
    truncnorm_damping: float = 0.5
    truncnorm_tol: float = 1e-4
    seed: int = 42


@dataclasses.dataclass(frozen=True)
class RetryConfig:
    """Exponential-backoff policy (reference: perturb_prompts.py:72-106).

    ``full_jitter=True`` switches the multiplicative 0.8-1.2 jitter to
    AWS-style full jitter (wait ~ U[0, delay]) — the right mode when many
    clients retry against one contended resource (the serve supervisor's
    device retries). ``max_elapsed`` caps the TOTAL time spent inside the
    retry loop (attempts + sleeps): once another sleep would cross it, the
    last failure is re-raised instead — so a retried call can never
    overrun its caller's deadline. None keeps the reference's unbounded
    behavior (the API backend's 24 h batch windows don't want a cap).
    """

    max_retries: int = 10
    initial_delay: float = 60.0
    max_delay: float = 300.0
    backoff_factor: float = 1.5
    jitter: Tuple[float, float] = (0.8, 1.2)
    full_jitter: bool = False
    max_elapsed: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Online serving layer knobs (lir_tpu/serve).

    - ``queue_depth``: admission-control bound. A submit into a full queue
      either sheds the incoming request or (deadline-aware) evicts the
      queued request with the LATEST deadline when the newcomer is more
      urgent — bounded memory and bounded worst-case queueing delay.
    - ``classes``: (name, deadline seconds) pairs. A request names its
      class; its deadline defaults to the class deadline unless it carries
      an explicit ``deadline_s``. Unknown classes fall back to
      ``default_class``.
    - ``linger_s``: continuous-batching window — a partially filled bucket
      dispatches once its oldest request has waited this long (a full
      batch dispatches immediately).
    - ``cache_entries``: content-addressed result-cache capacity (LRU).
      0 disables dedup.
    - ``max_consecutive_failures``: after this many back-to-back dispatch
      failures (each already retried per ``retry``) the circuit breaker
      OPENS (faults/breaker.py): the queue drains with error results and
      submits shed until the breaker recovers — but unlike the pre-PR4
      one-way health flag, after ``breaker_cooldown_s`` the breaker goes
      HALF-OPEN and lets one probe dispatch through; probe success closes
      it (healthy again), probe failure re-opens it for another cooldown.
      A transient device outage costs one cheap probe per cooldown
      instead of the whole process.
    - ``breaker_cooldown_s``: how long the breaker stays open before the
      half-open probe. Tune to the expected outage shape: ~30 s covers
      driver restarts and preempted-neighbor wobbles; sub-second values
      are for tests and chaos drivers (DEPLOY.md §1e).
    - ``degrade_ladder``: on a dispatch that fails all its retries,
      degrade instead of erroring the whole batch — drop the AOT
      registry (lazy jit re-trace excludes a corrupt executable), retry
      once, then bisect the batch to isolate poison rows; only the
      culprit rows resolve as errors (faults/ladder.py).
    - ``retry``: device-dispatch retry policy. Short, full-jitter, and
      elapsed-capped — a transient XLA/runtime hiccup is retried inside
      the request deadlines; a persistent fault fails fast into the
      breaker path.
    """

    queue_depth: int = 256
    # Live streaming-statistics window (engine/stream_stats.py
    # ServeStreamSink): the `stats` endpoint reports percentile/kappa
    # estimates over the last `stream_window` resolved rows, grouped by
    # target pair; folded idempotently by content address so SIGTERM
    # checkpoint/resume never double-counts a row. Gated on
    # RuntimeConfig.streaming_stats; 0 disables the ring.
    stream_window: int = 4096
    # Cross-request radix prefix cache (engine/prefix_tree.py over
    # models/paged.py): ON by default for serving — an arriving request
    # whose tokenized prefix is already resident pays prefill only for
    # its unshared suffix, across requests and across batches. The pool
    # is sized by RuntimeConfig.prefix_cache_pages; results stay
    # bitwise-identical to the unpaged path. OFF restores the PR-3
    # behavior (exact-match dedup only).
    prefix_cache: bool = True
    classes: Tuple[Tuple[str, float], ...] = (
        ("interactive", 10.0), ("batch", 300.0))
    # Fallback CLASS name for unknown request classes — set through
    # --deadline CLASS=SECS entries, not a flag of its own.
    default_class: str = "batch"    # lint: allow(config-drift)
    linger_s: float = 0.02
    # Pad every dispatch to the FULL configured batch instead of the
    # offline sweep's power-of-two tail: serving wants shape stability
    # more than tail FLOP savings — one executable per (bucket, suffix)
    # pair means no mid-traffic compiles, and degenerate tiny-batch
    # programs are avoided (measured on the CPU smoke: a warm batch-1
    # shared decode runs ~2.5x SLOWER than the warm batch-4 program).
    # The batcher's online slot-refill promotion (serve/batcher.py)
    # keeps the padding waste bounded the same way the offline
    # planner's does.
    pad_full: bool = True
    cache_entries: int = 4096
    max_consecutive_failures: int = 3
    breaker_cooldown_s: float = 30.0
    degrade_ladder: bool = True
    # Composite policy object (utils/retry.RetryConfig): tuned in code
    # next to the failure-domain story, not flag-by-flag.
    retry: RetryConfig = dataclasses.field(  # lint: allow(config-drift)
        default_factory=lambda: RetryConfig(
        max_retries=2, initial_delay=0.25, max_delay=2.0,
        backoff_factor=2.0, full_jitter=True, max_elapsed=8.0))

    def deadline_for(self, klass: str) -> float:
        table = dict(self.classes)
        if klass in table:
            return table[klass]
        return table.get(self.default_class,
                         max(table.values()) if table else 300.0)


@dataclasses.dataclass(frozen=True)
class ObserveConfig:
    """Reliability-observatory + telemetry knobs (lir_tpu/observe;
    DEPLOY.md §1l).

    The observatory re-scores a sentinel grid on a schedule (and on
    weight-cache residency change), folds results into time-windowed
    accumulator lattices, and raises σ-threshold drift alerts on
    per-window κ / per-model mean / valid-fraction movement — all
    queryable live through the serve ``stats``/``metrics`` endpoints.
    """

    # Seconds between scheduled sentinel re-scorings. A weight-cache
    # residency change (model evicted/re-streamed) forces an immediate
    # sweep regardless of the interval.
    sentinel_interval_s: float = 60.0    # cli: --sentinel-interval
    # Drift-window width in seconds: sweeps landing in the same window
    # fold into one lattice; κ/CI/mean are compared ACROSS windows.
    sentinel_window_s: float = 600.0     # cli: --sentinel-window
    # Lattice capacity per window (columns = sweeps x sentinels); a
    # window that fills logs and skips further sweeps rather than
    # silently overwriting slots.
    max_sweeps_per_window: int = 32      # cli: --sentinel-max-sweeps
    # Alert threshold: |window metric - baseline mean| > drift_sigma *
    # max(baseline std, floor). 3σ is the classic control-chart bound.
    drift_sigma: float = 3.0             # cli: --drift-sigma
    # Clean windows required before drift detection arms (a baseline of
    # one window has no variance to threshold against).
    drift_min_windows: int = 2           # cli: --drift-min-windows
    # Window lattices kept on device / summaries kept queryable; the
    # oldest drop beyond this (their summaries persist in history).
    history_windows: int = 64            # cli: --observe-history
    # Trace-span ring capacity for --trace-out recording.
    trace_buffer: int = 65536            # cli: --trace-buffer


@dataclasses.dataclass(frozen=True)
class GovernorConfig:
    """Unified HBM governor knobs (engine/hbm.py; DEPLOY.md §1o).

    Every HBM consumer (weight cache, KV page pool, dispatch/handoff
    caches, spec-draft pins, accumulator lattice) registers projected
    bytes into ONE ledger; sustained pressure against the budget walks
    a reversible degradation ladder (evict idle weights → evict cold
    radix pages → disable piggyback chaining → disable spec drafting →
    step the batch ladder down → shed), each rung re-arming with
    hysteresis once pressure clears. Real device OOMs route through
    the governor's reclaim-and-retry instead of killing the run or
    feeding the circuit breaker.
    """

    # Master switch: OFF leaves every consumer self-governed exactly as
    # before the governor existed (measurement baseline).
    enabled: bool = True                 # cli: --no-hbm-governor
    # Governed HBM budget in GiB. 0 derives the budget from the
    # device's reported bytes_limit (with `hbm_reserve_frac` held
    # back); on backends without memory stats (CPU) 0 means unbounded
    # — the ladder then never engages and behavior is identical to
    # governor-off.
    hbm_budget_gb: float = 0.0           # cli: --hbm-budget-gb
    # Fraction of the device bytes_limit held back from a derived
    # budget (runtime scratch, fragmentation slack).
    hbm_reserve_frac: float = 0.08       # cli: --hbm-reserve-frac
    # Ledger pressure (ledger_bytes / budget) at which the ladder
    # engages its next rung, and the hysteresis band below it at which
    # the most recent rung re-arms (releases). engage 0.9 / hysteresis
    # 0.15 means: walk down above 0.9, walk back up below 0.75 — a
    # rung can never flap on the threshold itself.
    engage_pressure: float = 0.9         # cli: --hbm-engage-pressure
    hysteresis: float = 0.15             # cli: --hbm-hysteresis
    # Consecutive over-pressure ticks (one tick per dispatch) before a
    # rung engages — transient spikes (one oversized dispatch) don't
    # walk the ladder; sustained pressure does. The same count of
    # under-pressure ticks releases.
    sustain_ticks: int = 2               # cli: --hbm-sustain-ticks
    # Radix pages evicted per evict_pages rung engagement.
    evict_pages_per_step: int = 32       # cli: --hbm-evict-pages

    @property
    def budget_bytes(self) -> Optional[int]:
        return (int(self.hbm_budget_gb * 2**30)
                if self.hbm_budget_gb > 0 else None)


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    """Elastic multi-replica serving knobs (serve/router.py;
    DEPLOY.md §1m).

    The router is a front process spreading one request stream over N
    replica servers. Placement reads three live signals per replica:
    queue depth (queue + bucketed rows), the router-side circuit
    breaker (one per replica — a replica that keeps erroring stops
    receiving traffic until its cooldown probe), and — for fleet
    replicas — WEIGHT RESIDENCY (WeightCache listener events feed a
    router-side residency map, so a model's requests land on the
    replica already holding its weights). Failover re-admits a dead or
    erroring replica's in-flight requests to survivors exactly once
    (ServeFuture first-resolution-wins + the content-address dedup
    key), and requests inside the deadline whisker are HEDGED to a
    second replica with first-payload-wins resolution.
    """

    # In-process replica count for `lir_tpu serve --replicas N`
    # (single-model serving only; each replica is a full ScoringServer
    # with its own breaker/ladder). 1 = no router.
    replicas: int = 1                      # cli: --replicas
    # Hedge whisker in seconds: an in-flight request whose deadline is
    # closer than this is duplicated onto a second replica
    # (first-payload-wins; the loser is dropped by resolve-once).
    # 0 disables hedging.
    hedge_s: float = 0.0                   # cli: --hedge-threshold
    # Router-side per-replica breaker: consecutive error results from
    # one replica before its breaker OPENS (routing avoids it), and how
    # long it stays open before the half-open probe (the next routed
    # request). Timed on time.monotonic — wall steps can't hold a
    # breaker open.
    replica_failure_threshold: int = 2     # cli: --replica-failure-threshold
    replica_cooldown_s: float = 5.0        # cli: --replica-cooldown
    # Placement score bonus (in queue-row equivalents) for a replica
    # whose WeightCache already holds the request's model — weight
    # residency as a first-class routing signal.
    residency_bonus: float = 8.0           # cli: --residency-bonus
    # Memory-pressure placement penalty (queue-row equivalents per unit
    # of HBM-governor pressure): a replica whose ledger is squeezed
    # reads as a worse home than an equally-loaded replica with
    # headroom — the governor's pressure gauge as a routing signal,
    # the seam ROADMAP item 2's page migration stands on. 0 disables.
    pressure_weight: float = 6.0           # cli: --pressure-weight
    # SLO-aware placement: weight on a replica's oldest queued-row wait
    # relative to the request's remaining deadline, so deadline-tight
    # requests avoid replicas with stale backlogs. 0 disables.
    slo_wait_weight: float = 4.0           # cli: --slo-wait-weight
    # Router supervisor tick (hedging scans + breaker promotion).
    tick_s: float = 0.02                   # cli: --router-tick
    # Router-level content-addressed dedup cache (the exactly-once
    # backstop: a late payload from a zombie replica can never
    # double-resolve a content address). 0 disables.
    cache_entries: int = 4096              # cli: --router-cache-entries


@dataclasses.dataclass(frozen=True)
class MigrationConfig:
    """Disaggregated prefill/decode serving knobs (serve/migrate.py;
    DEPLOY.md §1p).

    The router splits its replica pool into PREFILL-role and
    DECODE-role replicas: a long prompt prefills on a prefill replica,
    its KV pages stream to a decode replica as chunked double-buffered
    transfers (the weight-streaming discipline of models/weights.
    stream_params applied to the §1g page pool), and decode resumes
    there bitwise-identically to a colocated run. The cluster-wide
    prefix index (engine/prefix_tree.ClusterPrefixIndex) makes a
    prefix prefilled ANYWHERE warm EVERYWHERE: page residency joins
    weight residency and HBM pressure as a placement signal, and a
    migration pulls matching pages instead of re-prefilling. A stalled
    or corrupted transfer falls back to local re-prefill on the decode
    replica — never a wrong answer, never a dropped request.
    """

    # Master switch for page migration + disaggregated placement. OFF
    # restores the PR-12 role-less router exactly.
    enabled: bool = True                # cli: --no-migrate
    # Replicas (of `--replicas N`) dedicated to the PREFILL role: they
    # absorb long-prompt prefills and never serve decode traffic while
    # a decode-role replica survives. 0 = colocated (every replica
    # does both phases — the pre-disaggregation behavior).
    prefill_replicas: int = 0           # cli: --migrate-prefill-replicas
    # KV pages per transfer chunk: the unit of the double-buffered
    # device<->host hop (page bytes: models/paged.kv_page_bytes).
    chunk_pages: int = 8                # cli: --migrate-chunk-pages
    # Transfer chunks kept in flight (2 = classic double buffering:
    # chunk i+1 streams while chunk i lands).
    inflight_chunks: int = 2            # cli: --migrate-inflight-chunks
    # Minimum tokenized shared-prefix length worth a remote prefill +
    # migration; shorter prompts score colocated on a decode replica
    # (the handoff overhead would exceed the prefill saved).
    min_prefix_tokens: int = 32         # cli: --migrate-min-prefix
    # Placement bonus (queue-row equivalents) per cluster-index-matched
    # PAGE a replica already holds for the request's prefix — page
    # residency as a first-class routing signal beside weight residency
    # and hbm_pressure (serve/router.ReplicaRouter._pick).
    page_bonus: float = 0.5             # cli: --migrate-page-bonus
    # Verify a per-chunk checksum at import: a corrupted transfer is
    # detected BEFORE its pages enter the decode replica's radix tree
    # and falls back to local re-prefill (chaos kind
    # ``migration_corrupt``). Disabling trades the integrity check for
    # one CRC pass per chunk.
    verify: bool = True                 # cli: --no-migrate-verify
    # Wall-clock budget for one whole migration chain (prefill ->
    # export -> transfer -> import). Past it the router abandons the
    # chain and the decode replica re-prefills locally (chaos kind
    # ``migration_stall``); a late-landing import is harmless (it only
    # warms the pool with verified pages).
    timeout_s: float = 30.0             # cli: --migrate-timeout


@dataclasses.dataclass(frozen=True)
class TierConfig:
    """Tiered KV + weight store knobs (serve/tiers.py; DEPLOY.md §1s).

    Mooncake's observation applied to this engine: HBM pressure should
    DEMOTE cached state down a tier ladder (HBM -> pinned host DRAM ->
    local disk), not delete it. The governor's reclaim rungs become
    reversible — ``evict_weights`` records the victim's staged host
    tree to the disk tier before eviction, ``evict_pages`` exports the
    coldest radix leaves (serve/migrate.py's chunked checksummed
    transfer discipline) into a byte-budgeted host pool whose own LRU
    overflow spills to an on-disk page store with an append-only JSONL
    index (the manifest kill-mid-append discipline). Promotion back to
    HBM runs through the ordinary paged-warm import path, so payloads
    stay bitwise; a corrupt or stalled tier read falls back to local
    re-prefill — never a wrong answer. The disk tier survives process
    death: a restarted server re-seeds its radix tree and weight cache
    from it (restart-warm).
    """

    # Master switch. OFF restores the PR-14 delete-on-pressure rungs
    # exactly (and serve restarts start cold).
    enabled: bool = False               # cli: --tiered
    # Pinned-host-DRAM pool budget for demoted KV pages, MiB. LRU
    # overflow spills to the disk tier (or is dropped when no disk_dir
    # is configured). Size against models/paged.kv_page_bytes.
    host_budget_mb: float = 256.0       # cli: --tier-host-mb
    # Disk tier root directory ("" disables the disk leg: demotions
    # stop at host DRAM and restart-warm is off). One page store +
    # one weight store per serving process live under it.
    disk_dir: str = ""                  # cli: --tier-disk-dir
    # Disk tier budget, MiB; oldest spilled entries are dropped past it
    # (tombstoned in the index, file unlinked).
    disk_budget_mb: float = 1024.0      # cli: --tier-disk-mb
    # Radix pages demoted per evict_pages rung engagement — replaces
    # GovernorConfig.evict_pages_per_step deletions when tiering is ON.
    demote_pages_per_step: int = 32     # cli: --tier-demote-pages
    # Verify per-chunk checksums at promote: a corrupted host/disk
    # chunk is refused BEFORE its pages enter the radix tree and the
    # request re-prefills (chaos kind ``tier_corrupt``).
    verify: bool = True                 # cli: --no-tier-verify
    # Wall-clock budget for one disk-tier read; past it the promote is
    # abandoned and the request re-prefills locally (chaos kind
    # ``disk_stall``). The entry stays — a transient stall is not
    # corruption.
    disk_timeout_s: float = 10.0        # cli: --tier-disk-timeout
    # Re-seed the radix tree + weight cache from the disk tier at
    # server construction (restart-warm serving). Needs disk_dir.
    restart_warm: bool = True           # cli: --no-restart-warm
    # Placement bonus per HOST-tier-matched page as a fraction of
    # MigrationConfig.page_bonus ("warm on host at replica 2" prices
    # between HBM-warm and cold in ReplicaRouter._pick).
    host_bonus: float = 0.5             # cli: --tier-host-bonus
    # Same for DISK-tier-matched pages (cheaper than host, dearer
    # than a cold re-prefill).
    disk_bonus: float = 0.25            # cli: --tier-disk-bonus

    @property
    def host_budget_bytes(self) -> int:
        return int(self.host_budget_mb * 2**20)

    @property
    def disk_budget_bytes(self) -> int:
        return int(self.disk_budget_mb * 2**20)


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Multi-model fleet knobs (engine/fleet.py over models/weights.py).

    The fleet layer serves/sweeps N co-resident models off one engine
    cluster: an HBM-budgeted LRU weight cache holds as many model param
    trees as fit, an async streamer prefetches the next model's weights
    behind the current model's compute, and serve grows the
    ``fleet_score`` request class (one question across every resident
    model, answered with per-model P(yes)/P(no) + pairwise
    kappa/disagreement). DEPLOY.md §1k has the sizing arithmetic.

    - ``fleet_models``: the model ids served by ``lir_tpu serve
      --fleet-models`` (comma-separated on the CLI). Empty = single-
      model serving (the pre-fleet ScoringServer path).
    - ``weight_cache_gb``: HBM budget for co-resident model weights.
      0 = unbounded (every model stays resident — correct whenever the
      fleet fits; the CPU smoke default). When a model would not fit,
      the LRU model with no in-flight dispatch is evicted; a budget
      smaller than the single largest model is a loud error.
    - ``weight_prefetch``: stream the next model's weights on a
      background worker while the current model scores
      (``--no-weight-prefetch`` serializes every swap — measurement
      baseline, the pre-fleet drop-and-reload behavior).
    - ``fleet_deadline_s``: default deadline for fleet_score fan-outs
      (each per-model sub-request inherits it unless the request
      carries an explicit ``deadline_s``).
    """

    fleet_models: Tuple[str, ...] = ()
    weight_cache_gb: float = 0.0
    weight_prefetch: bool = True
    fleet_deadline_s: float = 60.0   # cli: --fleet-deadline

    @property
    def weight_cache_bytes(self) -> Optional[int]:
        return (int(self.weight_cache_gb * 2**30)
                if self.weight_cache_gb > 0 else None)


@dataclasses.dataclass(frozen=True)
class Config:
    """Top-level framework configuration."""

    backend: str = "tpu"  # "tpu" (local JAX inference) | "api" (remote, optional)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    runtime: RuntimeConfig = dataclasses.field(default_factory=RuntimeConfig)
    spec: SpecConfig = dataclasses.field(default_factory=SpecConfig)
    cascade: CascadeConfig = dataclasses.field(default_factory=CascadeConfig)
    perturbation: PerturbationConfig = dataclasses.field(default_factory=PerturbationConfig)
    stats: StatsConfig = dataclasses.field(default_factory=StatsConfig)
    retry: RetryConfig = dataclasses.field(default_factory=RetryConfig)
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)
    fleet: FleetConfig = dataclasses.field(default_factory=FleetConfig)
    observe: ObserveConfig = dataclasses.field(
        default_factory=ObserveConfig)
    router: RouterConfig = dataclasses.field(
        default_factory=RouterConfig)
    migrate: MigrationConfig = dataclasses.field(
        default_factory=MigrationConfig)
    governor: GovernorConfig = dataclasses.field(
        default_factory=GovernorConfig)
    tiers: TierConfig = dataclasses.field(default_factory=TierConfig)

    # Paths: everything under one results root; no personal gdrive paths.
    results_dir: Path = Path("results")
    data_dir: Path = Path("data")
    checkpoint_dir: Path = Path("checkpoints")

    # Models under test (HF repo ids or registry names).
    models: Sequence[str] = ()

    def __post_init__(self) -> None:
        if self.backend not in ("tpu", "api"):
            raise ValueError(f"backend must be 'tpu' or 'api', got {self.backend!r}")

    @staticmethod
    def api_key(name: str) -> str:
        """Read a secret from the environment (reference: analysis/config.py:6-16).

        Raised lazily, only when the optional API backend is actually used.
        """
        val = os.environ.get(name, "")
        if not val:
            raise RuntimeError(
                f"{name} not set. The 'api' backend needs it; the default 'tpu' "
                "backend performs zero external API calls."
            )
        return val


DEFAULT_CONFIG = Config()
