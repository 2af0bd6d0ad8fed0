"""Unified command-line interface for the framework.

The reference is driven by running eleven standalone scripts with hard-coded
personal paths (SURVEY.md §5 config: "no argparse anywhere"). Here every
experiment and analysis is one subcommand of ``python -m lir_tpu``:

  sweep        word-meaning model-comparison sweep -> D1/D2 CSVs
  perturb      perturbation grid sweep (with resume) -> D6 workbook
  serve        online scoring service (continuous batching, JSONL io)
  rephrase     generate/refresh perturbations.json with a local model
  analyze      all statistical analyses over existing artifacts
  survey       human-survey pipeline -> every survey JSON artifact
  bench        the prompts/sec/chip benchmark (end-to-end sweep path)
  precompile   warm the persistent compile cache for a model/ladder
  lint         graft-lint static analysis (JAX/XLA invariants, seconds)
  concat-shards  merge per-host .hostN sweep shards into the final artifact

Every command runs with the persistent XLA compilation cache ON (compiled
executables survive process restarts — utils/compile_cache.py; the
directory is $JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache;
--no-compile-cache opts out).

Model weights must be local checkpoint directories (zero egress); pass
--checkpoints pointing at a root containing ``<org>__<name>`` dirs.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path
from typing import List, Optional

from .utils.logging import get_logger

log = get_logger(__name__)


def _add_multihost_flag(p) -> None:
    p.add_argument("--multihost", action="store_true",
                   help="bring up jax.distributed for a multi-host pod "
                        "before loading models; each host then sweeps its "
                        "shard (perturb: grid cells, sweep: models) into "
                        "per-host .hostN artifacts that concatenate "
                        "row-wise; errors if bring-up fails rather than "
                        "silently degrading")


def _maybe_init_multihost(args) -> None:
    if getattr(args, "multihost", False):
        from .parallel import multihost

        multihost.initialize(required=True)


def _add_sweep(sub) -> None:
    p = sub.add_parser("sweep", help="word-meaning model comparison (D1/D2)")
    p.add_argument("--checkpoints", type=Path, required=True)
    p.add_argument("--models", nargs="+", required=True,
                   help="repo ids; suffix ':base' or ':instruct' "
                        "(default instruct)")
    p.add_argument("--out", type=Path, default=Path("results/comparison"))
    p.add_argument("--sweep-kind", choices=["base_vs_instruct", "instruct_only"],
                   default="base_vs_instruct")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--mesh", type=str, default=None,
                   help="dataxmodel[xseq], e.g. 1x8 for 8-way tensor "
                        "parallel, 1x1x8 for sequence-parallel prefill "
                        "(long prompts)")
    p.add_argument("--param-cache", type=Path, default=None,
                   help="orbax cache root: convert HF weights once, restore "
                        "fast afterwards")
    p.add_argument("--int8", action="store_true",
                   help="weight-only int8 quantization (7B fits one chip)")
    p.add_argument("--int8-dynamic", action="store_true",
                   help="with --int8: quantize activations per token and "
                        "run s8xs8 MXU matmuls (LLM.int8()-style vector-"
                        "wise mode, no outlier decomposition)")
    p.add_argument("--kv-cache-int8", action="store_true",
                   help="store the KV cache int8 with per-vector scales: "
                        "half the cache HBM (longer contexts / bigger "
                        "batches on one chip), s8 decode attention dots")
    _add_fleet_flags(p, with_models=False)
    _add_multihost_flag(p)


def _positive_int(text: str) -> int:
    """argparse type for decode budgets: a 0/negative budget would run an
    empty decode scan whose position-0 readout is silently garbage."""
    value = int(text)
    if value < 1:
        raise argparse.ArgumentTypeError(f"{value} is not >= 1")
    return value


def _add_perturb(sub) -> None:
    p = sub.add_parser("perturb", help="perturbation grid sweep (D6)")
    p.add_argument("--checkpoints", type=Path, required=True)
    p.add_argument("--model", required=True)
    p.add_argument("--perturbations", type=Path,
                   default=Path("perturbations.json"))
    p.add_argument("--out", type=Path,
                   default=Path("results/perturbation_results.xlsx"))
    p.add_argument("--subset-size", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--mesh", type=str, default=None)
    p.add_argument("--param-cache", type=Path, default=None)
    p.add_argument("--int8", action="store_true")
    p.add_argument("--int8-dynamic", action="store_true")
    p.add_argument("--kv-cache-int8", action="store_true")
    p.add_argument("--full-completions", action="store_true",
                   help="decode the reference's full 50-token Model "
                        "Response / Model Confidence Response text per "
                        "cell instead of the short 4/8-token budgets — "
                        "exact D6 text parity at ~1/4 the throughput "
                        "(measured 5.8 vs 23.9 p/s/chip; use "
                        "--batch-size 24, batch 40 OOMs with the larger "
                        "cache). Disables the early stops")
    p.add_argument("--sweep-decode-tokens", type=_positive_int,
                   default=None,
                   help="binary-format decode budget per cell (default 4; "
                        "the numeric readout consumes position 0 only)")
    p.add_argument("--sweep-confidence-tokens", type=_positive_int,
                   default=None,
                   help="confidence-format decode budget per cell "
                        "(default 8 — covers the measured answer "
                        "positions, SCALE.md; with the early stop armed a "
                        "generous budget costs actual response length, "
                        "so size this for the WORST answer)")
    p.add_argument("--no-early-stop", action="store_true",
                   help="disable the digit/EOS early stops and always "
                        "decode the full budgets (stops change no "
                        "recorded value — PARITY.md; this flag exists "
                        "for measurement, not correctness)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="enable the cross-request radix prefix cache for "
                        "the OFFLINE sweep (paged KV pool + radix tree; "
                        "serving enables it by default): repeated grids "
                        "on one engine resume shared prefixes from the "
                        "page pool, bitwise-identical results")
    p.add_argument("--no-row-artifact", action="store_true",
                   help="with streaming stats ON, skip materializing "
                        "the per-row csv/xlsx artifact entirely: the "
                        "sweep transfers NO per-row payloads through "
                        "the host — distributions come straight off "
                        "the device accumulator (resume runs on the "
                        "manifest + accumulator checkpoint). CSV stays "
                        "the schema-parity default (DEPLOY.md §1j)")
    p.add_argument("--lease-shards", action="store_true",
                   help="lease-based work-stealing shards instead of "
                        "the static host split: shard ownership rides "
                        "lease records ({holder, expiry} __meta__ "
                        "lines in a shared <results>.leases.jsonl), "
                        "renewed at every flush; a live host steals "
                        "shards whose lease expired, so a slow or "
                        "dead host rebalances instead of strangling "
                        "the shard fence (DEPLOY.md §1m; pair with "
                        "--no-row-artifact on pods)")
    p.add_argument("--lease-ttl", type=float, default=None,
                   help="shard-lease time-to-live in wall-clock "
                        "seconds (default 300): a lease older than "
                        "this is stealable — size it a few flush "
                        "intervals above the slowest healthy shard")
    p.add_argument("--lease-cells", type=int, default=None,
                   help="grid cells per leased shard (the stealing "
                        "granularity; default 0 derives ~4 shards per "
                        "host)")
    _add_prefix_pool_flags(p)
    _add_engine_tuning_flags(p)
    _add_guard_flags(p)
    _add_governor_flags(p)
    _add_kernel_flags(p)
    _add_spec_flags(p)
    _add_cascade_flags(p)
    _add_trace_flags(p)
    p.add_argument("--barrier-timeout", type=float, default=None,
                   help="multihost liveness bound in seconds: a shard-"
                        "boundary barrier a peer never reaches raises "
                        "HostDesyncError (resumable exit) instead of "
                        "hanging forever (default 900; <= 0 restores "
                        "unbounded barriers)")
    _add_multihost_flag(p)


def _add_prefix_pool_flags(p) -> None:
    """Page-pool sizing knobs for the cross-request prefix cache
    (models/paged.py + engine/prefix_tree.py), shared by perturb and
    serve."""
    p.add_argument("--prefix-cache-pages", type=_positive_int, default=None,
                   help="KV page pool size in pages (default 512; each "
                        "page holds --prefix-page-size token positions "
                        "and costs models/paged.kv_page_bytes of HBM — "
                        "DEPLOY.md §1g sizing arithmetic)")
    p.add_argument("--prefix-page-size", type=_positive_int, default=None,
                   help="token positions per KV page (default 16; also "
                        "the radix tree's edge granularity — prefixes "
                        "cache in full pages, tails recompute)")


def _prefix_rt_kw(args, rt_kw: dict) -> None:
    if getattr(args, "prefix_cache", False):
        rt_kw["prefix_cache"] = True
    if getattr(args, "prefix_cache_pages", None) is not None:
        rt_kw["prefix_cache_pages"] = args.prefix_cache_pages
    if getattr(args, "prefix_page_size", None) is not None:
        rt_kw["prefix_page_size"] = args.prefix_page_size


def _add_engine_tuning_flags(p) -> None:
    """Engine-shape knobs (RuntimeConfig) shared by perturb and serve —
    surfaced so no config field needs a source edit to change
    (lint/configdrift.py enforces the coverage)."""
    p.add_argument("--max-seq-len", type=_positive_int, default=None,
                   help="prompt-length ceiling in tokens (default 1024): "
                        "tops the bucket ladder and sizes every KV "
                        "cache; legal prompt + format is ≲700 tokens")
    p.add_argument("--max-new-tokens", type=_positive_int, default=None,
                   help="full-completion decode budget (default 50; the "
                        "short sweep budgets are --sweep-decode-tokens/"
                        "--sweep-confidence-tokens — this one gates "
                        "--full-completions text parity and rephrasing)")
    p.add_argument("--no-ragged-scheduler", action="store_true",
                   help="disable the ragged bucket-ladder scheduler and "
                        "restore legacy todo-order batching (every "
                        "mixed-length batch pads to its longest row — "
                        "the bench's single-bucket baseline; results "
                        "identical per cell)")
    p.add_argument("--sweep-group-min-prefix", type=_positive_int,
                   default=None,
                   help="cross-cell prefix grouping: minimum shared "
                        "leading tokens (default 16; see DEPLOY.md §1b)")
    p.add_argument("--sweep-group-min-cells", type=int, default=None,
                   help="cross-cell prefix grouping: minimum cells per "
                        "group (default 4; 0 disables grouping)")
    p.add_argument("--dispatch-tokens", type=int, default=None,
                   help="most tokens one pass of a sweep dispatch may "
                        "hold: its rows times what each runs beyond the "
                        "prefix they share (default 0 = uncapped; set it "
                        "for 16k-token documents, DEPLOY.md §1b)")
    p.add_argument("--donate-first", action="store_true",
                   help="hand a shape's first dispatch an empty cache to "
                        "donate: one program a shape, not two (DEPLOY.md "
                        "§1b)")
    p.add_argument("--no-aot-precompile", action="store_true",
                   help="disable background AOT precompilation of the "
                        "planned dispatch shapes (every shape then pays "
                        "lazy trace-on-first-call inside the sweep)")
    p.add_argument("--precompile-workers", type=int, default=None,
                   help="AOT precompile thread count (default 0 = one "
                        "per CPU core, capped at the shape count)")
    p.add_argument("--dtype", default=None,
                   choices=["bfloat16", "float32", "float16"],
                   help="parameter/activation dtype on device (default "
                        "bfloat16; float32 for parity audits — "
                        "DEPLOY.md §1a)")
    p.add_argument("--logits-dtype", default=None,
                   choices=["float32", "bfloat16"],
                   help="final-logits accumulation dtype (default "
                        "float32; the softmax readouts assume fp32 "
                        "accuracy — lower only for measurement)")
    p.add_argument("--scan-positions", type=_positive_int, default=None,
                   help="generated positions scanned for the yes/no "
                        "top-k match (default 10 = the reference's "
                        "MAX_LOOK_AHEAD; the D6 sweep reads position 0 "
                        "regardless)")
    p.add_argument("--topk-match", type=_positive_int, default=None,
                   help="top-k membership rule for the scan-position "
                        "readout (default 2 = the reference's top-2 "
                        "rule)")
    p.add_argument("--remat", action="store_true",
                   help="jax.checkpoint the decoder blocks "
                        "(rematerialize activations — slower, fits "
                        "bigger models per chip)")
    p.add_argument("--no-streaming-stats", action="store_true",
                   help="disable the device-resident streaming-"
                        "statistics sink (per-dispatch accumulator "
                        "fold, live percentile/kappa estimates, "
                        "accumulator checkpoints); analysis then runs "
                        "only off the row artifact (DEPLOY.md §1j)")


def _engine_rt_kw(args, rt_kw: dict) -> None:
    if getattr(args, "max_seq_len", None) is not None:
        rt_kw["max_seq_len"] = args.max_seq_len
    if getattr(args, "max_new_tokens", None) is not None:
        rt_kw["max_new_tokens"] = args.max_new_tokens
    if getattr(args, "no_ragged_scheduler", False):
        rt_kw["ragged_scheduler"] = False
    if getattr(args, "sweep_group_min_prefix", None) is not None:
        rt_kw["sweep_group_min_prefix"] = args.sweep_group_min_prefix
    if getattr(args, "sweep_group_min_cells", None) is not None:
        rt_kw["sweep_group_min_cells"] = args.sweep_group_min_cells
    if getattr(args, "dispatch_tokens", None) is not None:
        rt_kw["dispatch_tokens"] = args.dispatch_tokens
    if getattr(args, "donate_first", False):
        rt_kw["donate_first"] = True
    if getattr(args, "no_aot_precompile", False):
        rt_kw["aot_precompile"] = False
    if getattr(args, "precompile_workers", None) is not None:
        rt_kw["precompile_workers"] = args.precompile_workers
    if getattr(args, "dtype", None) is not None:
        rt_kw["dtype"] = args.dtype
    if getattr(args, "logits_dtype", None) is not None:
        rt_kw["logits_dtype"] = args.logits_dtype
    if getattr(args, "scan_positions", None) is not None:
        rt_kw["scan_positions"] = args.scan_positions
    if getattr(args, "topk_match", None) is not None:
        rt_kw["topk_match"] = args.topk_match
    if getattr(args, "remat", False):
        rt_kw["remat"] = True
    if getattr(args, "no_streaming_stats", False):
        rt_kw["streaming_stats"] = False


def _add_fleet_flags(p, with_models: bool) -> None:
    """Multi-model fleet knobs (config.FleetConfig — engine/fleet.py
    over models/weights.py; DEPLOY.md §1k)."""
    if with_models:
        p.add_argument("--fleet-models", default=None,
                       help="comma-separated model ids to serve as a "
                            "FLEET: all models co-resident up to the "
                            "weight-cache budget, per-model dispatch "
                            "queues, and the {\"op\": \"fleet_score\"} "
                            "request class — one question scored under "
                            "every model, answered with per-model "
                            "P(yes)/P(no) plus pairwise kappa/"
                            "disagreement (DEPLOY.md §1k)")
        p.add_argument("--fleet-deadline", type=float, default=None,
                       help="default deadline in seconds for fleet_score "
                            "fan-outs (default 60; per-request "
                            "\"deadline_s\" overrides)")
    p.add_argument("--weight-cache-gb", type=float, default=None,
                   help="HBM budget for co-resident model weights in the "
                        "fleet's LRU weight cache (default 0 = "
                        "unbounded; size it so budget >= largest model, "
                        "see DEPLOY.md §1k arithmetic)")
    p.add_argument("--no-weight-prefetch", action="store_true",
                   help="disable async weight streaming: every model "
                        "swap then serializes its host->device load "
                        "with compute (the pre-fleet drop-and-reload "
                        "behavior; measurement baseline)")


def _add_kernel_flags(p) -> None:
    """Fused-kernel knobs (ops/flash_decode + piggybacking), shared by
    perturb and serve (precompile follows the serving defaults)."""
    p.add_argument("--no-fused-decode", action="store_true",
                   help="disable the fused Pallas flash-decode kernel and "
                        "restore the dense decode-attention lowering "
                        "exactly (the pre-PR7 path; greedy results are "
                        "argmax-identical either way)")
    p.add_argument("--no-piggyback", action="store_true",
                   help="disable chunked prefill/decode piggybacking "
                        "(each dispatch then runs its own prefill + "
                        "decode call; results identical)")


def _kernel_rt_kw(args, rt_kw: dict) -> None:
    if getattr(args, "no_fused_decode", False):
        rt_kw["fused_decode"] = False
    if getattr(args, "no_piggyback", False):
        rt_kw["piggyback_prefill"] = False


def _add_spec_flags(p) -> None:
    """Speculative-decode knobs (engine/spec.py + RuntimeConfig.
    spec_decode/spec_k/spec_draft_model, Config.spec SpecConfig),
    shared by perturb and serve."""
    p.add_argument("--no-spec-decode", action="store_true",
                   help="disable speculative scoring decode (draft k "
                        "tokens, verify in one multi-query forward; ON "
                        "by default for self-drafting — consumed "
                        "results are bitwise either way, DEPLOY.md §1n)")
    p.add_argument("--spec-k", type=_positive_int, default=None,
                   help="speculative verify window: tokens checked per "
                        "verify forward (1 emission + up to k-1 drafts; "
                        "default 4, < 2 disables)")
    p.add_argument("--spec-draft-model", type=str, default=None,
                   help="fleet model id that DRAFTS for the scored "
                        "model (same tokenizer required; acquired "
                        "through the weight cache so drafting never "
                        "evicts the verifier). Empty = self-drafting "
                        "(radix-tree + n-gram prompt lookup)")
    p.add_argument("--spec-ngram", type=_positive_int, default=None,
                   help="n-gram match length for the prompt-lookup "
                        "fallback drafter (default 2)")
    p.add_argument("--no-spec-tree-probe", action="store_true",
                   help="skip the radix prefix tree's token-history "
                        "continuation probe when drafting (n-gram "
                        "lookup only)")
    p.add_argument("--spec-tree-tails", type=_positive_int, default=None,
                   help="continuation tails recorded per radix node for "
                        "drafting, LRU beyond this (default 32; host "
                        "memory only)")


def _spec_rt_kw(args, rt_kw: dict) -> None:
    if getattr(args, "no_spec_decode", False):
        rt_kw["spec_decode"] = False
    if getattr(args, "spec_k", None) is not None:
        rt_kw["spec_k"] = args.spec_k
    if getattr(args, "spec_draft_model", None) is not None:
        rt_kw["spec_draft_model"] = args.spec_draft_model


def _spec_config_from_args(args):
    from .config import SpecConfig

    kw = {}
    if getattr(args, "spec_ngram", None) is not None:
        kw["ngram"] = args.spec_ngram
    if getattr(args, "no_spec_tree_probe", False):
        kw["tree_probe"] = False
    if getattr(args, "spec_tree_tails", None) is not None:
        kw["tree_tails_per_node"] = args.spec_tree_tails
    return SpecConfig(**kw)


def _add_cascade_flags(p) -> None:
    """Shared-prefix cascade-prefill knobs (ops/cascade_prefill +
    RuntimeConfig.cascade_prefill, Config.cascade CascadeConfig),
    shared by perturb and serve (DEPLOY.md §1q)."""
    p.add_argument("--no-cascade-prefill", action="store_true",
                   help="disable shared-prefix cascade prefill and "
                        "restore the dense shared-dispatch path exactly "
                        "(cascade results are argmax-identical; dense is "
                        "the measurement baseline)")
    p.add_argument("--cascade-min-trunk", type=_positive_int,
                   default=None,
                   help="shortest shared trunk (tokens, post-snap) worth "
                        "the cascade split; shorter trunks dispatch "
                        "densely (default 32 — below it the extra "
                        "launch + merge beats the deduped prefill)")
    p.add_argument("--cascade-trunk-quantum", type=_positive_int,
                   default=None,
                   help="trunk lengths snap DOWN to this multiple so "
                        "near-identical prefixes share one compiled "
                        "cascade shape (default 16)")
    p.add_argument("--cascade-min-rows", type=_positive_int,
                   default=None,
                   help="fewest real rows sharing the trunk before "
                        "cascade engages (default 2; one row has "
                        "nothing to dedupe)")
    p.add_argument("--cascade-int8-qk", action="store_true",
                   help="quantize the cascade prefix leg's QK^T to int8 "
                        "inside the kernel (models/quant.py scales; "
                        "softmax + PV stay fp32 — tolerance-bound, "
                        "argmax-identical in tests)")
    p.add_argument("--no-cascade-decode", action="store_true",
                   help="disable the trunk-aware flash-decode split "
                        "dedup and restore the flat decode kernels "
                        "exactly (cascade-decode payloads are BITWISE "
                        "the flat kernels'; flat is the measurement "
                        "baseline — DEPLOY.md §1r)")
    p.add_argument("--no-cascade-fused-suffix", action="store_true",
                   help="run the cascade prefill as two kernel launches "
                        "plus an HBM merge round-trip instead of the "
                        "fused single-kernel path (the same scores, "
                        "summed in another order; the two-leg path is "
                        "the fused kernel's verification baseline)")


def _cascade_rt_kw(args, rt_kw: dict) -> None:
    if getattr(args, "no_cascade_prefill", False):
        rt_kw["cascade_prefill"] = False
    if getattr(args, "no_cascade_decode", False):
        rt_kw["cascade_decode"] = False
    if getattr(args, "no_cascade_fused_suffix", False):
        rt_kw["cascade_fused_suffix"] = False


def _cascade_config_from_args(args):
    from .config import CascadeConfig

    kw = {}
    if getattr(args, "cascade_min_trunk", None) is not None:
        kw["min_trunk"] = args.cascade_min_trunk
    if getattr(args, "cascade_trunk_quantum", None) is not None:
        kw["trunk_quantum"] = args.cascade_trunk_quantum
    if getattr(args, "cascade_min_rows", None) is not None:
        kw["min_rows"] = args.cascade_min_rows
    if getattr(args, "cascade_int8_qk", False):
        kw["int8_qk"] = True
    return CascadeConfig(**kw)


def _add_trace_flags(p) -> None:
    """Structured-tracing knobs (lir_tpu/observe/tracing.py), shared by
    perturb and serve."""
    p.add_argument("--trace-out", type=Path, default=None,
                   help="record per-request/per-dispatch trace spans "
                        "(admit -> queue -> batch-form -> dispatch -> "
                        "readout -> resolve, weight swaps, stream "
                        "folds) and write Chrome/Perfetto trace-event "
                        "JSON here at exit — open in chrome://tracing "
                        "or ui.perfetto.dev; span names match the "
                        "jax.profiler device-trace annotations")
    p.add_argument("--trace-buffer", type=int, default=None,
                   help="trace-span ring capacity (default 65536; "
                        "oldest spans drop beyond it, drops counted in "
                        "the metrics snapshot)")


def _add_router_flags(p) -> None:
    """Elastic multi-replica router knobs (config.RouterConfig —
    serve/router.py; DEPLOY.md §1m)."""
    p.add_argument("--replicas", type=int, default=None,
                   help="run N in-process replica servers behind the "
                        "failover router (single-model serving): "
                        "queue-depth/breaker-aware placement, "
                        "exactly-once failover of a dead replica's "
                        "in-flight requests, deadline-whisker hedging "
                        "(default 1 = no router)")
    p.add_argument("--hedge-threshold", type=float, default=None,
                   help="hedge whisker in seconds: an in-flight "
                        "request this close to its deadline is "
                        "duplicated onto a second replica, first "
                        "payload wins (default 0 = hedging off)")
    p.add_argument("--replica-failure-threshold", type=int, default=None,
                   help="consecutive error results from one replica "
                        "before its router-side breaker opens "
                        "(default 2)")
    p.add_argument("--replica-cooldown", type=float, default=None,
                   help="router-side replica breaker open->half-open "
                        "cooldown in seconds (default 5; monotonic-"
                        "clocked — wall steps can't hold it open)")
    p.add_argument("--residency-bonus", type=float, default=None,
                   help="placement bonus (queue-row equivalents) for a "
                        "replica whose WeightCache already holds the "
                        "request's model (default 8)")
    p.add_argument("--pressure-weight", type=float, default=None,
                   help="placement penalty (queue-row equivalents) per "
                        "unit of a replica's HBM-governor pressure — "
                        "memory as a routing signal (default 6; "
                        "0 disables)")
    p.add_argument("--slo-wait-weight", type=float, default=None,
                   help="SLO placement term: weight on a replica's "
                        "oldest queued-row wait relative to the "
                        "request's remaining deadline (default 4; "
                        "0 disables)")
    p.add_argument("--router-tick", type=float, default=None,
                   help="router supervisor tick in seconds (hedging "
                        "scans + breaker promotion; default 0.02)")
    p.add_argument("--router-cache-entries", type=int, default=None,
                   help="router-level content-addressed dedup cache "
                        "capacity — the exactly-once backstop against "
                        "zombie-replica payloads (default 4096; "
                        "0 disables)")


def _router_cfg(args):
    """RouterConfig from the flags (None = dataclass default)."""
    from .config import RouterConfig

    kw = {}
    if getattr(args, "replicas", None) is not None:
        kw["replicas"] = args.replicas
    if getattr(args, "hedge_threshold", None) is not None:
        kw["hedge_s"] = args.hedge_threshold
    if getattr(args, "replica_failure_threshold", None) is not None:
        kw["replica_failure_threshold"] = args.replica_failure_threshold
    if getattr(args, "replica_cooldown", None) is not None:
        kw["replica_cooldown_s"] = args.replica_cooldown
    if getattr(args, "residency_bonus", None) is not None:
        kw["residency_bonus"] = args.residency_bonus
    if getattr(args, "slo_wait_weight", None) is not None:
        kw["slo_wait_weight"] = args.slo_wait_weight
    if getattr(args, "pressure_weight", None) is not None:
        kw["pressure_weight"] = args.pressure_weight
    if getattr(args, "router_tick", None) is not None:
        kw["tick_s"] = args.router_tick
    if getattr(args, "router_cache_entries", None) is not None:
        kw["cache_entries"] = args.router_cache_entries
    return RouterConfig(**kw)


def _add_migrate_flags(p) -> None:
    """Disaggregated prefill/decode serving knobs
    (config.MigrationConfig — serve/migrate.py; DEPLOY.md §1p)."""
    p.add_argument("--no-migrate", action="store_true",
                   help="disable KV-page migration + disaggregated "
                        "placement entirely (MigrationConfig.enabled; "
                        "restores the role-less replica router)")
    p.add_argument("--migrate-prefill-replicas", type=int, default=None,
                   help="of --replicas N, dedicate the first K to the "
                        "PREFILL role: long prompts prefill there and "
                        "their KV pages migrate to decode-role "
                        "replicas (default 0 = colocated)")
    p.add_argument("--migrate-chunk-pages", type=int, default=None,
                   help="KV pages per transfer chunk of the double-"
                        "buffered page migration (default 8)")
    p.add_argument("--migrate-inflight-chunks", type=int, default=None,
                   help="transfer chunks kept in flight (default 2 = "
                        "double buffering)")
    p.add_argument("--migrate-min-prefix", type=int, default=None,
                   help="minimum tokenized shared-prefix length worth "
                        "a remote prefill + migration; shorter prompts "
                        "score colocated (default 32)")
    p.add_argument("--migrate-page-bonus", type=float, default=None,
                   help="placement bonus (queue-row equivalents) per "
                        "cluster-index-matched page a replica already "
                        "holds for the request's prefix (default 0.5)")
    p.add_argument("--no-migrate-verify", action="store_true",
                   help="skip the per-chunk transfer checksums "
                        "(MigrationConfig.verify) — corruption then "
                        "lands undetected; only for measurement")
    p.add_argument("--migrate-timeout", type=float, default=None,
                   help="wall-clock budget in seconds for one whole "
                        "migration chain before the router falls back "
                        "to local re-prefill (default 30)")


def _migrate_cfg(args):
    """MigrationConfig from the flags (None = dataclass default)."""
    from .config import MigrationConfig

    kw = {}
    if getattr(args, "no_migrate", False):
        kw["enabled"] = False
    if getattr(args, "migrate_prefill_replicas", None) is not None:
        kw["prefill_replicas"] = args.migrate_prefill_replicas
    if getattr(args, "migrate_chunk_pages", None) is not None:
        kw["chunk_pages"] = args.migrate_chunk_pages
    if getattr(args, "migrate_inflight_chunks", None) is not None:
        kw["inflight_chunks"] = args.migrate_inflight_chunks
    if getattr(args, "migrate_min_prefix", None) is not None:
        kw["min_prefix_tokens"] = args.migrate_min_prefix
    if getattr(args, "migrate_page_bonus", None) is not None:
        kw["page_bonus"] = args.migrate_page_bonus
    if getattr(args, "no_migrate_verify", False):
        kw["verify"] = False
    if getattr(args, "migrate_timeout", None) is not None:
        kw["timeout_s"] = args.migrate_timeout
    return MigrationConfig(**kw)


def _add_tier_flags(p) -> None:
    """Tiered-memory knobs (config.TierConfig — serve/tiers.py;
    DEPLOY.md §1s)."""
    p.add_argument("--tiered", action="store_true",
                   help="enable the tiered memory ladder "
                        "(TierConfig.enabled): the HBM governor's "
                        "reclaim rungs demote KV radix pages and idle "
                        "fleet weights to pinned host DRAM and local "
                        "disk instead of deleting them; promotes ride "
                        "the checksummed paged-warm import (bitwise)")
    p.add_argument("--tier-host-mb", type=float, default=None,
                   help="host-DRAM tier budget in MiB "
                        "(TierConfig.host_budget_mb, default 256); "
                        "overflow spills to the disk tier, LRU first")
    p.add_argument("--tier-disk-dir", type=str, default=None,
                   help="local directory for the disk tier "
                        "(TierConfig.disk_dir; empty = host tier only, "
                        "no spill and no restart-warm)")
    p.add_argument("--tier-disk-mb", type=float, default=None,
                   help="disk tier budget in MiB "
                        "(TierConfig.disk_budget_mb, default 1024); "
                        "oldest entries drop at the budget")
    p.add_argument("--tier-demote-pages", type=int, default=None,
                   help="max KV pages one evict_pages rung engagement "
                        "demotes (TierConfig.demote_pages_per_step, "
                        "default 32)")
    p.add_argument("--no-tier-verify", action="store_true",
                   help="skip promote-side chunk checksums "
                        "(TierConfig.verify) — tier corruption then "
                        "lands undetected; only for measurement")
    p.add_argument("--tier-disk-timeout", type=float, default=None,
                   help="seconds a disk-tier promote may take before "
                        "the store abandons it and the request "
                        "re-prefills (TierConfig.disk_timeout_s, "
                        "default 10)")
    p.add_argument("--no-restart-warm", action="store_true",
                   help="do NOT reseed the radix tree / weight cache "
                        "from the disk tier at server construction "
                        "(TierConfig.restart_warm)")
    p.add_argument("--tier-host-bonus", type=float, default=None,
                   help="placement price of one host-tier page in "
                        "HBM-page equivalents (TierConfig.host_bonus, "
                        "default 0.5)")
    p.add_argument("--tier-disk-bonus", type=float, default=None,
                   help="placement price of one disk-tier page in "
                        "HBM-page equivalents (TierConfig.disk_bonus, "
                        "default 0.25)")


def _tier_cfg(args):
    """TierConfig from the flags (None = dataclass default)."""
    from .config import TierConfig

    kw = {}
    if getattr(args, "tiered", False):
        kw["enabled"] = True
    if getattr(args, "tier_host_mb", None) is not None:
        kw["host_budget_mb"] = args.tier_host_mb
    if getattr(args, "tier_disk_dir", None) is not None:
        kw["disk_dir"] = args.tier_disk_dir
    if getattr(args, "tier_disk_mb", None) is not None:
        kw["disk_budget_mb"] = args.tier_disk_mb
    if getattr(args, "tier_demote_pages", None) is not None:
        kw["demote_pages_per_step"] = args.tier_demote_pages
    if getattr(args, "no_tier_verify", False):
        kw["verify"] = False
    if getattr(args, "tier_disk_timeout", None) is not None:
        kw["disk_timeout_s"] = args.tier_disk_timeout
    if getattr(args, "no_restart_warm", False):
        kw["restart_warm"] = False
    if getattr(args, "tier_host_bonus", None) is not None:
        kw["host_bonus"] = args.tier_host_bonus
    if getattr(args, "tier_disk_bonus", None) is not None:
        kw["disk_bonus"] = args.tier_disk_bonus
    return TierConfig(**kw)


def _add_observatory_flags(p) -> None:
    """Reliability-observatory knobs (lir_tpu/observe; fleet serving
    only — the sentinel grid fans across every fleet model)."""
    p.add_argument("--sentinels", type=Path, default=None,
                   help="JSONL sentinel grid ({\"prompt\": ...} or "
                        "{\"binary_prompt\", \"confidence_prompt\"}, "
                        "optional \"targets\") re-scored across the "
                        "whole fleet on --sentinel-interval and on any "
                        "weight-cache residency change; per-window "
                        "kappa/CI/mean drift alerts ride the stats "
                        "endpoint (DEPLOY.md §1l)")
    p.add_argument("--sentinel-interval", type=float, default=None,
                   help="seconds between scheduled sentinel sweeps "
                        "(default 60)")
    p.add_argument("--sentinel-window", type=float, default=None,
                   help="drift-window width in seconds (default 600): "
                        "sweeps in one window fold into one "
                        "accumulator lattice; kappa/CI/mean compare "
                        "ACROSS windows")
    p.add_argument("--sentinel-max-sweeps", type=int, default=None,
                   help="lattice capacity in sweeps per window "
                        "(default 32; a full window skips further "
                        "sweeps loudly rather than overwriting slots)")
    p.add_argument("--drift-sigma", type=float, default=None,
                   help="alert threshold: |window metric - baseline "
                        "mean| > sigma * max(std, floor) (default 3)")
    p.add_argument("--drift-min-windows", type=int, default=None,
                   help="clean windows required before drift detection "
                        "arms (default 2)")
    p.add_argument("--observe-history", type=int, default=None,
                   help="window lattices kept on device / summaries "
                        "queryable (default 64; oldest drop beyond it)")


def _observe_cfg(args):
    """ObserveConfig from the flags (None = dataclass default)."""
    from .config import ObserveConfig

    kw = {}
    if getattr(args, "sentinel_interval", None) is not None:
        kw["sentinel_interval_s"] = args.sentinel_interval
    if getattr(args, "sentinel_window", None) is not None:
        kw["sentinel_window_s"] = args.sentinel_window
    if getattr(args, "sentinel_max_sweeps", None) is not None:
        kw["max_sweeps_per_window"] = args.sentinel_max_sweeps
    if getattr(args, "drift_sigma", None) is not None:
        kw["drift_sigma"] = args.drift_sigma
    if getattr(args, "drift_min_windows", None) is not None:
        kw["drift_min_windows"] = args.drift_min_windows
    if getattr(args, "observe_history", None) is not None:
        kw["history_windows"] = args.observe_history
    if getattr(args, "trace_buffer", None) is not None:
        kw["trace_buffer"] = args.trace_buffer
    return ObserveConfig(**kw)


def _maybe_start_tracing(args):
    """Install the process trace recorder under --trace-out; returns it
    (or None). The caller exports at exit."""
    if getattr(args, "trace_out", None) is None:
        return None
    from .observe import tracing

    rec = tracing.TraceRecorder(capacity=_observe_cfg(args).trace_buffer)
    tracing.set_recorder(rec)
    return rec


def _finish_tracing(rec, args) -> None:
    if rec is None:
        return
    rec.export_chrome(args.trace_out)
    log.info("trace: wrote %d spans (%d dropped) -> %s", len(rec),
             rec.dropped, args.trace_out)


def _add_governor_flags(p) -> None:
    """Unified HBM-governor knobs (config.GovernorConfig —
    engine/hbm.py; DEPLOY.md §1o), shared by perturb and serve."""
    p.add_argument("--no-hbm-governor", action="store_true",
                   help="disable the unified HBM governor (enabled): "
                        "no ledger, no degradation ladder, OOMs "
                        "re-raise raw — the pre-governor baseline")
    p.add_argument("--hbm-budget-gb", type=float, default=None,
                   help="governed HBM budget in GiB (hbm_budget_gb; "
                        "default 0 derives it from the device "
                        "bytes_limit minus the reserve; on CPU 0 "
                        "means unbounded — the ladder never engages)")
    p.add_argument("--hbm-reserve-frac", type=float, default=None,
                   help="fraction of the device limit held back from "
                        "a derived budget (hbm_reserve_frac, default "
                        "0.08 — runtime scratch + fragmentation slack)")
    p.add_argument("--hbm-engage-pressure", type=float, default=None,
                   help="ledger/budget pressure at which the "
                        "degradation ladder engages its next rung "
                        "(engage_pressure, default 0.9)")
    p.add_argument("--hbm-hysteresis", type=float, default=None,
                   help="release band below the engage pressure "
                        "(hysteresis, default 0.15): rungs re-arm "
                        "below engage - hysteresis, so the ladder "
                        "can never flap on one threshold")
    p.add_argument("--hbm-sustain-ticks", type=int, default=None,
                   help="consecutive over-pressure dispatch ticks "
                        "before a rung engages (sustain_ticks, "
                        "default 2 — spikes don't walk the ladder, "
                        "sustained pressure does)")
    p.add_argument("--hbm-evict-pages", type=int, default=None,
                   help="radix pages evicted per evict_pages rung "
                        "engagement (evict_pages_per_step, default 32)")


def _governor_cfg(args):
    """GovernorConfig from the flags (None = dataclass default)."""
    from .config import GovernorConfig

    kw = {}
    if getattr(args, "no_hbm_governor", False):
        kw["enabled"] = False
    if getattr(args, "hbm_budget_gb", None) is not None:
        kw["hbm_budget_gb"] = args.hbm_budget_gb
    if getattr(args, "hbm_reserve_frac", None) is not None:
        kw["hbm_reserve_frac"] = args.hbm_reserve_frac
    if getattr(args, "hbm_engage_pressure", None) is not None:
        kw["engage_pressure"] = args.hbm_engage_pressure
    if getattr(args, "hbm_hysteresis", None) is not None:
        kw["hysteresis"] = args.hbm_hysteresis
    if getattr(args, "hbm_sustain_ticks", None) is not None:
        kw["sustain_ticks"] = args.hbm_sustain_ticks
    if getattr(args, "hbm_evict_pages", None) is not None:
        kw["evict_pages_per_step"] = args.hbm_evict_pages
    return GovernorConfig(**kw)


def _add_guard_flags(p) -> None:
    """Guard-layer knobs (lir_tpu/guard) shared by perturb and serve."""
    p.add_argument("--watchdog-multiple", type=float, default=None,
                   help="dispatch watchdog deadline = floor + multiple x "
                        "predicted dispatch seconds (bucket_cost-priced, "
                        "self-calibrated; default 20). <= 0 disables "
                        "stall detection")
    p.add_argument("--watchdog-floor", type=float, default=None,
                   help="hard minimum watchdog deadline in seconds "
                        "(default 30) — the safety margin a noisy "
                        "calibration can never undercut")
    p.add_argument("--no-numerics-guard", action="store_true",
                   help="disable the score-extraction numerics guard "
                        "(NaN/Inf/out-of-range rows are then written "
                        "verbatim instead of quarantined as "
                        "error:numerics — measurement only)")


def _add_precompile(sub) -> None:
    p = sub.add_parser(
        "precompile",
        help="warm the compile cache for a model/ladder ahead of serving: "
             "AOT-compile every bucket-ladder executable (in parallel) "
             "into the persistent cache, so the serving process — or "
             "every restarted/autoscaled worker — deserializes instead "
             "of compiling. Run once per host (caches are per-host).")
    p.add_argument("--checkpoints", type=Path, required=True)
    p.add_argument("--model", required=True)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--mesh", type=str, default=None)
    p.add_argument("--param-cache", type=Path, default=None)
    p.add_argument("--int8", action="store_true")
    p.add_argument("--int8-dynamic", action="store_true")
    p.add_argument("--kv-cache-int8", action="store_true")
    p.add_argument("--sweep-decode-tokens", type=_positive_int, default=None)
    p.add_argument("--sweep-confidence-tokens", type=_positive_int,
                   default=None)
    p.add_argument("--sfx-buckets", default="8,16",
                   help="suffix bucket edges to warm per ladder edge "
                        "(default 8,16 — the edges short sweep format "
                        "instructions land in)")
    p.add_argument("--workers", type=int, default=0,
                   help="parallel compile threads (0 = one per core)")


def _add_serve(sub) -> None:
    p = sub.add_parser(
        "serve",
        help="online scoring service: continuous-batching request queue "
             "over the bucket ladder (lir_tpu/serve). Reads JSONL "
             "requests from --requests (default stdin), writes one JSONL "
             "result per line to stdout, ServeStats to stderr on exit. "
             "Request lines: {\"id\", \"binary_prompt\", "
             "\"confidence_prompt\"} or {\"prompt\"} with optional "
             "\"response_format\"/\"confidence_format\", plus optional "
             "\"targets\": [t1, t2], \"class\", \"deadline_s\". With "
             "--fleet-models, lines score under EVERY fleet model "
             "({\"op\": \"fleet_score\"} or any line without a "
             "\"model\" key) and return per-model P(yes)/P(no) plus "
             "pairwise kappa/disagreement; a \"model\" key routes a "
             "line to that one model's dispatch queue")
    p.add_argument("--checkpoints", type=Path, required=True)
    p.add_argument("--model", default=None,
                   help="single-model serving (the full ScoringServer: "
                        "breaker/ladder/checkpoint); exactly one of "
                        "--model / --fleet-models is required")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--mesh", type=str, default=None)
    p.add_argument("--param-cache", type=Path, default=None)
    p.add_argument("--int8", action="store_true")
    p.add_argument("--int8-dynamic", action="store_true")
    p.add_argument("--kv-cache-int8", action="store_true")
    p.add_argument("--sweep-decode-tokens", type=_positive_int, default=None)
    p.add_argument("--sweep-confidence-tokens", type=_positive_int,
                   default=None)
    p.add_argument("--requests", type=str, default="-",
                   help="JSONL request file, or '-' for stdin (default)")
    p.add_argument("--queue-depth", type=int, default=256,
                   help="admission-control bound; a submit into a full "
                        "queue sheds the least-urgent request")
    p.add_argument("--linger-ms", type=float, default=20.0,
                   help="continuous-batching window: a partial bucket "
                        "dispatches once its oldest request waited this "
                        "long")
    p.add_argument("--cache-entries", type=int, default=4096,
                   help="content-addressed result cache capacity "
                        "(0 disables dedup)")
    p.add_argument("--deadline", action="append", default=None,
                   metavar="CLASS=SECONDS",
                   help="deadline class override, repeatable (default: "
                        "interactive=10, batch=300)")
    p.add_argument("--no-precompile", action="store_true",
                   help="skip the boot AOT precompile of every "
                        "(ladder, suffix, batch) executable")
    p.add_argument("--breaker-cooldown", type=float, default=30.0,
                   help="circuit-breaker open->half-open cooldown in "
                        "seconds: after max_consecutive_failures the "
                        "server sheds for this long, then probes the "
                        "device with one dispatch and recovers on "
                        "success (DEPLOY.md §1e)")
    p.add_argument("--state-checkpoint", type=Path, default=None,
                   help="crash-consistent state file: SIGTERM stops the "
                        "supervisor and atomically writes every "
                        "unresolved request here; on boot, an existing "
                        "file is re-submitted (dedup-deduplicated "
                        "against anything already served)")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable the cross-request radix prefix cache "
                        "(serving default ON: arriving requests pay "
                        "prefill only for their unshared suffix, results "
                        "bitwise-identical; OFF restores PR-3 exact-"
                        "match dedup only)")
    p.add_argument("--no-pad-full", action="store_true",
                   help="pad serve dispatches to the offline sweep's "
                        "power-of-two tail instead of the full batch "
                        "(saves tail FLOPs, costs extra executables and "
                        "slow tiny-batch programs — DEPLOY.md §1d)")
    p.add_argument("--no-degrade-ladder", action="store_true",
                   help="on a dispatch that exhausts its retries, error "
                        "the whole batch instead of degrading to lazy "
                        "jit and bisecting out poison rows")
    p.add_argument("--max-consecutive-failures", type=_positive_int,
                   default=None,
                   help="full dispatch failures in a row before the "
                        "circuit breaker opens (default 3)")
    p.add_argument("--stream-window", type=int, default=None,
                   help="live streaming-statistics ring size (default "
                        "4096): a JSONL request line {\"op\": "
                        "\"stats\"} returns in-progress percentile/"
                        "kappa estimates over the last N served rows "
                        "without touching the device; 0 disables "
                        "(DEPLOY.md §1j)")
    _add_prefix_pool_flags(p)
    _add_engine_tuning_flags(p)
    _add_guard_flags(p)
    _add_governor_flags(p)
    _add_kernel_flags(p)
    _add_spec_flags(p)
    _add_cascade_flags(p)
    _add_trace_flags(p)
    _add_observatory_flags(p)
    _add_router_flags(p)
    _add_migrate_flags(p)
    _add_tier_flags(p)
    _add_fleet_flags(p, with_models=True)


def _add_rephrase(sub) -> None:
    p = sub.add_parser("rephrase", help="generate perturbations.json locally")
    p.add_argument("--checkpoints", type=Path, required=True)
    p.add_argument("--model", required=True,
                   help="instruct model acting as the rephraser")
    p.add_argument("--out", type=Path, default=Path("perturbations.json"))
    p.add_argument("--sessions", type=int, default=100)
    p.add_argument("--per-session", type=int, default=20)


def _add_analyze(sub) -> None:
    p = sub.add_parser("analyze", help="statistical analyses over artifacts")
    p.add_argument("--perturbation-results", type=Path, default=None,
                   help="D6 workbook -> perturbation distribution suite")
    p.add_argument("--base-csv", type=Path, default=None,
                   help="D1 -> base-vs-instruct deltas")
    p.add_argument("--instruct-csv", type=Path, default=None,
                   help="D2 -> model graph suite (+ kappa combiner when the "
                        "D6 workbook is also given)")
    p.add_argument("--out", type=Path, default=Path("results/analysis"))
    p.add_argument("--no-figures", action="store_true")
    p.add_argument("--n-simulations", type=int, default=100_000)


def _add_repro(sub) -> None:
    p = sub.add_parser(
        "repro",
        help="regenerate the full published analysis from a reference-style "
             "data directory (D1/D2/D3 CSVs) in one shot",
    )
    p.add_argument("--data", type=Path, required=True,
                   help="directory holding model_comparison_results.csv, "
                        "instruct_model_comparison_results.csv, "
                        "word_meaning_survey_results.csv")
    p.add_argument("--perturbation-results", type=Path, default=None,
                   help="optional D6 workbook for the perturbation suite")
    p.add_argument("--out", type=Path, default=Path("results/repro"))
    p.add_argument("--quick", action="store_true")
    p.add_argument("--no-figures", action="store_true")


def _add_lint(sub) -> None:
    from .lint import cli as lint_cli

    p = sub.add_parser(
        "lint",
        help="graft-lint: AST static analysis proving the engine's "
             "JAX/XLA invariants — donation-safety, trace-hazard, "
             "host-sync, lock-discipline, config-drift. Zero new "
             "findings outside tools/lint_baseline.json or exit 1 "
             "(DEPLOY.md §1i). Runs in seconds; wired into `make "
             "verify` and the pre-push hook.")
    lint_cli.build_parser(p)


def cmd_lint(args) -> None:
    from .lint import cli as lint_cli

    sys.exit(lint_cli.run(args))


def _add_survey(sub) -> None:
    p = sub.add_parser("survey", help="human-survey analysis pipeline")
    p.add_argument("--survey", type=Path, required=True)
    p.add_argument("--instruct", type=Path, required=True)
    p.add_argument("--base", type=Path, default=None)
    p.add_argument("--out", type=Path, default=Path("results/survey"))
    p.add_argument("--quick", action="store_true")


def _parse_mesh(spec: Optional[str]):
    if not spec:
        return None
    from .config import MeshConfig

    dims = [int(x) for x in spec.lower().split("x")]
    if len(dims) == 2:
        dims.append(1)
    if len(dims) != 3:
        raise SystemExit(
            f"--mesh must be DATAxMODEL or DATAxMODELxSEQ, got {spec!r}")
    data, model, seq = dims
    return MeshConfig(data=data, model=model, seq=seq)


def _parse_models(items: List[str]):
    from .engine.multi import ModelSpec

    specs = []
    for item in items:
        name, _, kind = item.partition(":")
        specs.append(ModelSpec(name, kind or "instruct"))
    return specs


def cmd_sweep(args) -> None:
    _maybe_init_multihost(args)
    from .config import RuntimeConfig
    from .engine.multi import run_model_comparison_sweep
    from .models.factory import engine_factory

    factory = engine_factory(
        args.checkpoints, RuntimeConfig(batch_size=args.batch_size),
        _parse_mesh(args.mesh), cache_root=args.param_cache,
        quantize_int8=args.int8, int8_dynamic=args.int8_dynamic,
        kv_cache_int8=args.kv_cache_int8,
    )
    run_model_comparison_sweep(
        _parse_models(args.models), factory, args.out,
        sweep_kind=args.sweep_kind,
        weight_prefetch=not args.no_weight_prefetch,
        weight_cache_bytes=(int(args.weight_cache_gb * 2**30)
                            if args.weight_cache_gb else None),
    )


def _guard_rt_kw(args, rt_kw: dict) -> None:
    """Fold the guard-layer flags into a RuntimeConfig kwargs dict."""
    if getattr(args, "watchdog_multiple", None) is not None:
        rt_kw["watchdog_multiple"] = args.watchdog_multiple
    if getattr(args, "watchdog_floor", None) is not None:
        rt_kw["watchdog_floor_s"] = args.watchdog_floor
    if getattr(args, "no_numerics_guard", False):
        rt_kw["numerics_guard"] = False


def cmd_perturb(args) -> None:
    _maybe_init_multihost(args)
    from .config import RuntimeConfig
    from .data.prompts import LEGAL_PROMPTS
    from .engine.rephrase import load_or_generate_perturbations
    from .engine.sweep import run_perturbation_sweep
    from .models.factory import engine_factory

    if args.full_completions and (args.sweep_decode_tokens is not None
                                  or args.sweep_confidence_tokens is not None):
        raise SystemExit(
            "--full-completions decodes the reference's full 50-token "
            "responses unconditionally; it cannot combine with "
            "--sweep-decode-tokens / --sweep-confidence-tokens")
    rt_kw = dict(batch_size=args.batch_size,
                 sweep_full_completions=args.full_completions,
                 sweep_early_stop=not args.no_early_stop)
    if args.sweep_decode_tokens is not None:
        rt_kw["sweep_decode_tokens"] = args.sweep_decode_tokens
    if args.sweep_confidence_tokens is not None:
        rt_kw["sweep_confidence_tokens"] = args.sweep_confidence_tokens
    _engine_rt_kw(args, rt_kw)
    _guard_rt_kw(args, rt_kw)
    _kernel_rt_kw(args, rt_kw)
    _spec_rt_kw(args, rt_kw)
    _cascade_rt_kw(args, rt_kw)
    _prefix_rt_kw(args, rt_kw)
    if args.no_row_artifact:
        rt_kw["row_artifact"] = False
    if args.barrier_timeout is not None:
        rt_kw["barrier_timeout_s"] = args.barrier_timeout
    if args.lease_shards:
        rt_kw["lease_shards"] = True
    if args.lease_ttl is not None:
        rt_kw["lease_ttl_s"] = args.lease_ttl
    if args.lease_cells is not None:
        rt_kw["lease_cells_per_shard"] = args.lease_cells
    factory = engine_factory(
        args.checkpoints,
        RuntimeConfig(**rt_kw),
        _parse_mesh(args.mesh), cache_root=args.param_cache,
        quantize_int8=args.int8, int8_dynamic=args.int8_dynamic,
        kv_cache_int8=args.kv_cache_int8,
        spec_config=_spec_config_from_args(args),
        governor_config=_governor_cfg(args),
        cascade_config=_cascade_config_from_args(args),
    )
    entries = load_or_generate_perturbations(
        args.perturbations, LEGAL_PROMPTS, None
    )
    perturbations = [rephrasings for _, rephrasings in entries]
    rec = _maybe_start_tracing(args)
    engine = factory(args.model)
    try:
        rows = run_perturbation_sweep(
            engine, args.model, LEGAL_PROMPTS, perturbations, args.out,
            subset_size=args.subset_size,
        )
    finally:
        _finish_tracing(rec, args)
    log.info("perturbation sweep wrote %d rows", len(rows))


def cmd_serve(args) -> None:
    import json

    from .config import RuntimeConfig, ServeConfig
    from .data.prompts import LEGAL_PROMPTS
    from .models.factory import engine_factory
    from .serve import ScoringServer, ServeRequest

    rt_kw = dict(batch_size=args.batch_size)
    if args.sweep_decode_tokens is not None:
        rt_kw["sweep_decode_tokens"] = args.sweep_decode_tokens
    if args.sweep_confidence_tokens is not None:
        rt_kw["sweep_confidence_tokens"] = args.sweep_confidence_tokens
    _engine_rt_kw(args, rt_kw)
    _guard_rt_kw(args, rt_kw)
    _kernel_rt_kw(args, rt_kw)
    _spec_rt_kw(args, rt_kw)
    _cascade_rt_kw(args, rt_kw)
    _prefix_rt_kw(args, rt_kw)
    classes = dict(ServeConfig().classes)
    for spec in args.deadline or ():
        name, sep, secs = spec.partition("=")
        try:
            classes[name] = float(secs)
        except ValueError:
            sep = ""
        if not sep or not name:
            raise SystemExit(f"--deadline {spec!r} must be CLASS=SECONDS")
    serve_kw = {}
    if args.max_consecutive_failures is not None:
        serve_kw["max_consecutive_failures"] = args.max_consecutive_failures
    if args.stream_window is not None:
        serve_kw["stream_window"] = args.stream_window
    serve_cfg = ServeConfig(
        queue_depth=args.queue_depth, classes=tuple(classes.items()),
        linger_s=args.linger_ms / 1000.0,
        cache_entries=args.cache_entries,
        breaker_cooldown_s=args.breaker_cooldown,
        prefix_cache=not args.no_prefix_cache,
        pad_full=not args.no_pad_full,
        degrade_ladder=not args.no_degrade_ladder, **serve_kw)
    if bool(args.model) == bool(args.fleet_models):
        raise SystemExit("serve needs exactly one of --model (single-"
                         "model) or --fleet-models (multiplexed fleet)")
    n_replicas = args.replicas if args.replicas is not None else 1
    if n_replicas > 1 and args.fleet_models:
        raise SystemExit("--replicas fronts single-model replica "
                         "servers; combine it with --model (fleet "
                         "replicas: run N fleet serve processes behind "
                         "an external router)")
    if n_replicas > 1 and args.state_checkpoint is not None:
        raise SystemExit("--state-checkpoint is per-server state; with "
                         "--replicas the router's failover replaces it "
                         "(a dead replica's in-flight work re-admits "
                         "to survivors)")
    n_prefill = args.migrate_prefill_replicas or 0
    if n_prefill and n_prefill >= n_replicas:
        raise SystemExit("--migrate-prefill-replicas must leave at "
                         "least one decode-role replica (got "
                         f"{n_prefill} of {n_replicas})")
    if args.sentinels is not None and not args.fleet_models:
        raise SystemExit("--sentinels needs --fleet-models: the "
                         "observatory re-scores the sentinel grid "
                         "across a fleet (single-model drift has no "
                         "agreement axis to watch)")
    # Install the trace recorder BEFORE server construction so the
    # server registers it as a metrics source.
    rec = _maybe_start_tracing(args)
    factory = engine_factory(
        args.checkpoints, RuntimeConfig(**rt_kw), _parse_mesh(args.mesh),
        cache_root=args.param_cache, quantize_int8=args.int8,
        int8_dynamic=args.int8_dynamic, kv_cache_int8=args.kv_cache_int8,
        spec_config=_spec_config_from_args(args),
        governor_config=_governor_cfg(args),
        cascade_config=_cascade_config_from_args(args))
    if args.fleet_models:
        try:
            _run_fleet_serve(args, serve_cfg, factory)
        finally:
            _finish_tracing(rec, args)
        return
    if n_replicas > 1:
        try:
            _run_router_serve(args, serve_cfg, factory, n_replicas)
        finally:
            _finish_tracing(rec, args)
        return
    engine = factory(args.model)
    server = ScoringServer(engine, args.model, serve_cfg,
                           precompile=not args.no_precompile,
                           tiers=_tier_cfg(args)).start()

    futures = []
    if args.state_checkpoint is not None:
        import signal

        def _on_sigterm(signum, frame):
            n = server.shutdown_checkpoint(args.state_checkpoint)
            log.warning("SIGTERM: checkpointed %d pending requests -> %s"
                        "; exiting", n, args.state_checkpoint)
            sys.exit(0)

        signal.signal(signal.SIGTERM, _on_sigterm)
        if args.state_checkpoint.exists():
            # Resume the previous incarnation's unresolved requests
            # BEFORE reading new traffic (their results print first).
            futures.extend(server.resume_from_checkpoint(
                args.state_checkpoint))

    # Default formats: the canonical legal-prompt pair, so a bare
    # {"prompt": ...} line scores exactly like a sweep cell.
    default_rf = LEGAL_PROMPTS[0].response_format
    default_cf = LEGAL_PROMPTS[0].confidence_format
    stream = (sys.stdin if args.requests == "-"
              else open(args.requests, encoding="utf-8"))
    try:
        for i, line in enumerate(stream):
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            if obj.get("op") == "stats":
                # Live streaming-statistics readout: in-progress
                # percentile/kappa estimates over the served window,
                # answered immediately from the host-side ring (no
                # device work, no queueing).
                print(json.dumps({"op": "stats",
                                  "stats": server.stream_summary()}),
                      flush=True)
                continue
            if obj.get("op") == "metrics":
                # The unified metrics snapshot (observe/registry):
                # every registered *Stats source + HBM gauges, live.
                print(json.dumps({"op": "metrics",
                                  "metrics": server.metrics.snapshot()}),
                      flush=True)
                continue
            prompt = obj.get("prompt")
            req = ServeRequest(
                binary_prompt=obj.get(
                    "binary_prompt",
                    f"{prompt} {obj.get('response_format', default_rf)}"),
                confidence_prompt=obj.get(
                    "confidence_prompt",
                    f"{prompt} {obj.get('confidence_format', default_cf)}"),
                targets=tuple(obj.get("targets", ("Yes", "No"))),
                klass=obj.get("class", serve_cfg.default_class),
                deadline_s=obj.get("deadline_s"),
                request_id=str(obj.get("id", i)))
            futures.append(server.submit(req))
    finally:
        if stream is not sys.stdin:
            stream.close()
    for fut in futures:
        r = fut.result()
        print(json.dumps({k: v for k, v in vars(r).items()
                          if not k.startswith("_")}), flush=True)
    server.stop()
    _finish_tracing(rec, args)
    if args.state_checkpoint is not None and args.state_checkpoint.exists():
        args.state_checkpoint.unlink()   # clean drain: nothing pending
    log.info("serve stats: %s", json.dumps(server.stats.summary()))
    # Exit metrics snapshot — includes the per-device HBM gauges, so
    # WeightCache/page-pool budget pressure is on the record even when
    # nothing ever OOMed.
    log.info("serve metrics: %s", json.dumps(server.metrics.snapshot()))
    if server.stream is not None:
        log.info("serve stream stats: %s",
                 json.dumps(server.stream_summary()))
    if engine.prefix_cache is not None:
        log.info("serve prefix cache: %s",
                 json.dumps(engine.prefix_stats.summary()))
    log.info("serve faults: %s", json.dumps(server.faults.summary()))
    if not server.healthy:
        sys.exit(1)


def _run_router_serve(args, serve_cfg, factory, n_replicas: int) -> None:
    """Elastic serving loop (``serve --model X --replicas N``): N
    in-process replica ScoringServers behind a ReplicaRouter
    (serve/router.py) — queue-depth/breaker-aware placement,
    exactly-once failover of a dead replica's in-flight requests, and
    deadline-whisker hedging. The JSONL surface is the single-model
    one; {"op": "stats"} answers the router's per-replica health view
    (DEPLOY.md §1m)."""
    import json

    from .data.prompts import LEGAL_PROMPTS
    from .serve import ReplicaRouter, ScoringServer, ServeRequest

    from .parallel.sharding import replica_devices

    servers = []
    tcfg = _tier_cfg(args)
    mesh_cfg = _parse_mesh(args.mesh)
    per_replica = mesh_cfg.n_devices if mesh_cfg is not None else 1
    for i in range(n_replicas):
        # Replica i on its own device(s): built by one factory, every
        # replica would otherwise land on device 0.
        engine = factory(args.model,
                         devices=replica_devices(i, per_replica))
        # Each in-process replica owns its own disk-tier directory —
        # the on-disk index is per-store, never shared.
        rep_tiers = tcfg
        if tcfg.enabled and tcfg.disk_dir:
            import dataclasses as _dc
            rep_tiers = _dc.replace(
                tcfg, disk_dir=str(Path(tcfg.disk_dir) / f"r{i}"))
        servers.append(ScoringServer(
            engine, args.model, serve_cfg,
            precompile=not args.no_precompile, tiers=rep_tiers).start())
    # Disaggregated roles (serve/migrate.py; DEPLOY.md §1p): the first
    # --migrate-prefill-replicas servers take the prefill role, the
    # rest decode; 0 keeps every replica colocated ("both").
    n_prefill = getattr(args, "migrate_prefill_replicas", None) or 0
    roles = {f"r{i}": ("prefill" if i < n_prefill else "decode")
             for i in range(n_replicas)} if n_prefill else None
    router = ReplicaRouter(
        [(f"r{i}", s) for i, s in enumerate(servers)],
        config=_router_cfg(args), roles=roles,
        migrate=_migrate_cfg(args)).start()
    log.info("router: %d replica servers for %s (%d prefill-role)",
             n_replicas, args.model, n_prefill)
    default_rf = LEGAL_PROMPTS[0].response_format
    default_cf = LEGAL_PROMPTS[0].confidence_format
    stream = (sys.stdin if args.requests == "-"
              else open(args.requests, encoding="utf-8"))
    futures = []
    try:
        for i, line in enumerate(stream):
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            if obj.get("op") == "stats":
                print(json.dumps({"op": "stats",
                                  **router.stats_summary()}),
                      flush=True)
                continue
            if obj.get("op") == "metrics":
                print(json.dumps({"op": "metrics",
                                  "metrics": router.metrics.snapshot()}),
                      flush=True)
                continue
            prompt = obj.get("prompt")
            futures.append(router.submit(ServeRequest(
                binary_prompt=obj.get(
                    "binary_prompt",
                    f"{prompt} {obj.get('response_format', default_rf)}"),
                confidence_prompt=obj.get(
                    "confidence_prompt",
                    f"{prompt} {obj.get('confidence_format', default_cf)}"),
                targets=tuple(obj.get("targets", ("Yes", "No"))),
                klass=obj.get("class", serve_cfg.default_class),
                deadline_s=obj.get("deadline_s"),
                request_id=str(obj.get("id", i)))))
    finally:
        if stream is not sys.stdin:
            stream.close()
    for fut in futures:
        r = fut.result()
        print(json.dumps({k: v for k, v in vars(r).items()
                          if not k.startswith("_")}), flush=True)
    router.stop()
    for s in servers:
        s.stop()
    log.info("router stats: %s", json.dumps(router.stats_summary()))
    log.info("router metrics: %s",
             json.dumps(router.metrics.snapshot()))
    if not router.alive_replicas():
        sys.exit(1)


def _run_fleet_serve(args, serve_cfg, factory) -> None:
    """Fleet serving loop (``serve --fleet-models``): every JSONL line
    without a "model" key (or with {"op": "fleet_score"}) fans across
    all fleet models and prints one aggregated agreement payload —
    per-model P(yes)/P(no)/decision, pairwise kappa/disagreement
    through the stats/streaming contingency path; a "model" key routes
    the line to that one model's dispatch queue (DEPLOY.md §1k)."""
    import json

    from .data.prompts import LEGAL_PROMPTS
    from .engine.fleet import ModelFleet
    from .serve import FleetScoringServer, ServeRequest

    if args.state_checkpoint is not None:
        raise SystemExit(
            "--state-checkpoint is not supported with --fleet-models; "
            "run fleet serving behind an external retry layer")
    models = [m for m in args.fleet_models.split(",") if m]
    if not models:
        raise SystemExit("--fleet-models needs at least one model id")
    # Engines load at boot (tokenizer/buckets are submit-time state);
    # WEIGHT residency is the cache's call from here on — under a
    # budget, boot itself evicts down to what fits and later acquires
    # re-stream from the pinned host staging.
    fleet = ModelFleet.from_engines(
        [(m, factory(m)) for m in models],
        cache_budget_bytes=(int(args.weight_cache_gb * 2**30)
                            if args.weight_cache_gb else None),
        prefetch=not args.no_weight_prefetch)
    server = FleetScoringServer(
        fleet, serve_cfg,
        fleet_deadline_s=(args.fleet_deadline
                          if args.fleet_deadline is not None else 60.0),
        tiers=_tier_cfg(args),
    ).start()
    default_rf = LEGAL_PROMPTS[0].response_format
    default_cf = LEGAL_PROMPTS[0].confidence_format
    scheduler = None
    if args.sentinels is not None:
        from .observe import SentinelScheduler

        sentinels = _load_sentinels(args.sentinels, default_rf,
                                    default_cf)
        scheduler = SentinelScheduler(server, sentinels,
                                      cfg=_observe_cfg(args))
        server.attach_observatory(scheduler)
        scheduler.start()
        log.info("observatory: %d sentinels every %.0fs, %.0fs windows,"
                 " %.1f-sigma alerts", len(sentinels),
                 scheduler.cfg.sentinel_interval_s,
                 scheduler.cfg.sentinel_window_s,
                 scheduler.cfg.drift_sigma)
    stream = (sys.stdin if args.requests == "-"
              else open(args.requests, encoding="utf-8"))
    futures = []
    try:
        for i, line in enumerate(stream):
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            if obj.get("op") == "stats":
                # Serve + fleet counters, plus the observatory's window
                # history and drift alerts when a sentinel grid runs.
                print(json.dumps({"op": "stats",
                                  **server.stats_summary()}),
                      flush=True)
                continue
            if obj.get("op") == "metrics":
                print(json.dumps({"op": "metrics",
                                  "metrics": server.metrics.snapshot()}),
                      flush=True)
                continue
            prompt = obj.get("prompt")
            req = ServeRequest(
                binary_prompt=obj.get(
                    "binary_prompt",
                    f"{prompt} {obj.get('response_format', default_rf)}"),
                confidence_prompt=obj.get(
                    "confidence_prompt",
                    f"{prompt} {obj.get('confidence_format', default_cf)}"),
                targets=tuple(obj.get("targets", ("Yes", "No"))),
                klass=obj.get("class", serve_cfg.default_class),
                deadline_s=obj.get("deadline_s"),
                request_id=str(obj.get("id", i)))
            if obj.get("model"):
                futures.append(("single",
                                server.submit(req, obj["model"])))
            else:
                futures.append(("fleet", server.submit_fleet(req)))
    finally:
        if stream is not sys.stdin:
            stream.close()
    for kind, fut in futures:
        r = fut.result()
        print(json.dumps(r if kind == "fleet"
                         else {k: v for k, v in vars(r).items()
                               if not k.startswith("_")}), flush=True)
    if scheduler is not None:
        # Stop sentinel traffic first, then drain client traffic; the
        # final partial window finalizes so a drift that landed minutes
        # before shutdown still alerts.
        scheduler.stop()
    server.stop()
    fleet.shutdown()
    log.info("serve stats: %s", json.dumps(server.stats.summary()))
    log.info("fleet stats: %s", json.dumps(server.fleet_summary()))
    log.info("serve metrics: %s", json.dumps(server.metrics.snapshot()))
    if scheduler is not None:
        obs = scheduler.summary()
        log.info("observatory: %d sweeps over %d finalized windows, "
                 "%d drift alert(s)", obs["sweeps"], len(obs["windows"]),
                 len(obs["alerts"]))
        for alert in obs["alerts"]:
            log.warning("drift alert: %s", json.dumps(alert))


def _load_sentinels(path: Path, default_rf: str, default_cf: str):
    """Sentinel grid from a JSONL file (request-line schema minus the
    serving metadata)."""
    import json

    from .serve import ServeRequest

    sentinels = []
    for i, line in enumerate(path.read_text(encoding="utf-8")
                             .splitlines()):
        line = line.strip()
        if not line:
            continue
        obj = json.loads(line)
        prompt = obj.get("prompt")
        sentinels.append(ServeRequest(
            binary_prompt=obj.get(
                "binary_prompt",
                f"{prompt} {obj.get('response_format', default_rf)}"),
            confidence_prompt=obj.get(
                "confidence_prompt",
                f"{prompt} {obj.get('confidence_format', default_cf)}"),
            targets=tuple(obj.get("targets", ("Yes", "No"))),
            request_id=f"sentinel-{i}"))
    if not sentinels:
        raise SystemExit(f"--sentinels {path}: no sentinel lines found")
    return sentinels


def cmd_precompile(args) -> None:
    import time

    from .config import RuntimeConfig
    from .engine import compile_plan
    from .models.factory import engine_factory

    rt_kw = dict(batch_size=args.batch_size)
    if args.sweep_decode_tokens is not None:
        rt_kw["sweep_decode_tokens"] = args.sweep_decode_tokens
    if args.sweep_confidence_tokens is not None:
        rt_kw["sweep_confidence_tokens"] = args.sweep_confidence_tokens
    try:
        sfx = tuple(int(b) for b in args.sfx_buckets.split(","))
    except ValueError:
        sfx = ()
    if not sfx or any(b <= 0 for b in sfx):
        raise SystemExit(f"--sfx-buckets {args.sfx_buckets!r} must be "
                         "comma-separated positive ints (e.g. 8,16)")
    factory = engine_factory(
        args.checkpoints, RuntimeConfig(**rt_kw), _parse_mesh(args.mesh),
        cache_root=args.param_cache, quantize_int8=args.int8,
        int8_dynamic=args.int8_dynamic, kv_cache_int8=args.kv_cache_int8,
        spec_config=_spec_config_from_args(args),
        cascade_config=_cascade_config_from_args(args))
    engine = factory(args.model)
    specs = compile_plan.sweep_specs_for_ladder(engine, sfx_buckets=sfx)
    t0 = time.perf_counter()
    registry = compile_plan.precompile_async(engine, specs,
                                             max_workers=args.workers)
    ok = registry.wait()
    stats = engine.compile_stats
    log.info("precompiled %d/%d executables in %.1fs wall "
             "(%.1fs compile total; manifest %s); per-shape: %s",
             ok, len(specs), time.perf_counter() - t0, stats.compile_s,
             registry.manifest_key,
             {k: round(v, 2) for k, v in sorted(stats.shapes.items())})
    if ok < len(specs):
        sys.exit(1)


def cmd_rephrase(args) -> None:
    import jax

    from .data.prompts import LEGAL_PROMPTS
    from .engine.rephrase import (
        load_or_generate_perturbations,
        rephraser_from_engine,
    )
    from .models.factory import engine_factory

    engine = engine_factory(args.checkpoints)(args.model)
    load_or_generate_perturbations(
        args.out, LEGAL_PROMPTS, rephraser_from_engine(engine),
        jax.random.PRNGKey(42),
        sessions_per_prompt=args.sessions,
        rephrasings_per_session=args.per_session,
    )


def cmd_analyze(args) -> None:
    from .utils.profiling import ensure_cpu_backend

    ensure_cpu_backend()  # host statistics: leave the chip to its one process
    ran = False
    if args.perturbation_results:
        from .analysis.perturbation import analyze_all_models

        analyze_all_models(
            args.perturbation_results, args.out / "perturbation",
            n_simulations=args.n_simulations,
            make_figures=not args.no_figures,
        )
        ran = True
    if args.base_csv:
        from .analysis.base_vs_instruct import run_base_vs_instruct_analysis

        run_base_vs_instruct_analysis(
            args.base_csv, args.out / "base_vs_instruct",
            make_figures=not args.no_figures,
        )
        ran = True
    if args.instruct_csv:
        from .analysis.model_graph import run_model_graph_analysis

        run_model_graph_analysis(
            args.instruct_csv, args.out / "model_graph",
            make_figures=not args.no_figures,
        )
        ran = True
        if args.perturbation_results:
            from .analysis.kappa_combined import run_kappa_analysis

            run_kappa_analysis(
                args.instruct_csv, args.perturbation_results,
                args.out / "kappa", make_figures=not args.no_figures,
            )
    if not ran:
        log.error("analyze: give at least one of --perturbation-results, "
                  "--base-csv, --instruct-csv")
        sys.exit(2)


def cmd_repro(args) -> None:
    """Survey pipeline + every CSV-driven analysis in one pass."""
    from .utils.profiling import ensure_cpu_backend

    ensure_cpu_backend()
    from .analysis.base_vs_instruct import run_base_vs_instruct_analysis
    from .analysis.model_graph import run_model_graph_analysis
    from .survey.run import run_survey_pipeline

    data = args.data
    base_csv = data / "model_comparison_results.csv"
    instruct_csv = data / "instruct_model_comparison_results.csv"
    survey_csv = data / "word_meaning_survey_results.csv"
    figures = not args.no_figures

    kwargs = {}
    if args.quick:
        kwargs = dict(n_bootstrap_standard=50, n_bootstrap_small=20,
                      n_bootstrap_large=200)
    run_survey_pipeline(
        survey_csv, instruct_csv,
        base_csv if base_csv.exists() else None,
        args.out / "survey", **kwargs,
    )
    if base_csv.exists():
        run_base_vs_instruct_analysis(
            base_csv, args.out / "base_vs_instruct", make_figures=figures)
    run_model_graph_analysis(
        instruct_csv, args.out / "model_graph",
        n_bootstrap=50 if args.quick else 1000, make_figures=figures)
    if args.perturbation_results:
        from .analysis.kappa_combined import run_kappa_analysis
        from .analysis.perturbation import analyze_all_models

        analyze_all_models(
            args.perturbation_results, args.out / "perturbation",
            n_simulations=2000 if args.quick else 100_000,
            make_figures=figures,
        )
        run_kappa_analysis(
            instruct_csv, args.perturbation_results, args.out / "kappa",
            n_bootstrap=100 if args.quick else 1000, make_figures=figures,
        )
    log.info("repro complete; artifacts under %s", args.out)


def cmd_survey(args) -> None:
    from .utils.profiling import ensure_cpu_backend

    ensure_cpu_backend()  # host statistics: leave the chip to its one process
    from .survey.run import run_survey_pipeline

    kwargs = {}
    if args.quick:
        kwargs = dict(n_bootstrap_standard=50, n_bootstrap_small=20,
                      n_bootstrap_large=200)
    run_survey_pipeline(args.survey, args.instruct, args.base, args.out,
                        **kwargs)


def cmd_concat_shards(args) -> None:
    """Merge per-host .hostN result shards into the final artifact — the
    manual gather for pods WITHOUT a shared filesystem (copy every host's
    shard + manifest next to --results first; with a shared filesystem the
    sweep's host 0 runs this merge automatically after its barrier)."""
    from .data import schemas

    # Pod hosts and the merge machine may disagree on openpyxl (shards are
    # written in the POD's resolved container) — probe the requested
    # suffix, then the alternate, before declaring the shards missing.
    candidates = [args.results]
    if args.results.suffix in (".xlsx", ".csv"):
        candidates.append(args.results.with_suffix(
            ".csv" if args.results.suffix == ".xlsx" else ".xlsx"))
    merged = out = None
    for cand in candidates:
        merged = schemas.concat_host_shards(cand, n_hosts=args.hosts)
        if merged is not None:
            out = schemas.resolve_results_path(cand)
            break
    if merged is None:
        probed = ", ".join(
            str(schemas.resolve_results_path(c).with_name(
                f"{schemas.resolve_results_path(c).stem}.host0"
                f"{schemas.resolve_results_path(c).suffix}"))
            for c in candidates)
        raise SystemExit(
            f"no mergeable shards for {args.results} — expected "
            f"{args.hosts or 'host0..hostN'} consecutive shard files "
            f"(probed: {probed}, ...)")
    manifest = out.with_suffix(".manifest.jsonl")
    manifest_note = (
        f"(+ union manifest {manifest.name})" if manifest.exists() else
        "(WARNING: no shard manifests found next to the shards — resume "
        "state NOT merged; copy the .hostN.manifest.jsonl files too)")
    print(f"merged {len(merged)} rows -> {out} {manifest_note}")


def cmd_bench(args) -> None:
    import runpy

    # bench.py parses sys.argv itself; hand it a clean argv so the CLI's
    # own subcommand tokens don't reach its parser.
    bench_path = Path(__file__).resolve().parent.parent / "bench.py"
    fwd = []
    if getattr(args, "allow_ungated", False):
        fwd.append("--allow-ungated")
    fwd += getattr(args, "bench_extra", [])
    old_argv = sys.argv
    sys.argv = [str(bench_path)] + fwd
    try:
        runpy.run_path(str(bench_path), run_name="__main__")
    finally:
        sys.argv = old_argv


def main(argv: Optional[List[str]] = None) -> None:
    parser = argparse.ArgumentParser(prog="lir_tpu", description=__doc__)
    parser.add_argument("--no-compile-cache", action="store_true",
                        help="disable the persistent compile cache (every "
                             "process then recompiles from scratch)")
    sub = parser.add_subparsers(dest="command", required=True)
    _add_sweep(sub)
    _add_perturb(sub)
    _add_serve(sub)
    _add_precompile(sub)
    _add_rephrase(sub)
    _add_analyze(sub)
    _add_repro(sub)
    _add_survey(sub)
    _add_lint(sub)
    bench_p = sub.add_parser(
        "bench", help="prompts/sec/chip benchmark (end-to-end sweep path); "
                      "unrecognized flags are forwarded to bench.py "
                      "verbatim (--model, --sweep-batches, ... — see "
                      "`python bench.py --help`)")
    bench_p.add_argument("--allow-ungated", action="store_true",
                         help="report even when the chip kind has no MFU "
                              "peak-table entry (default: abort)")

    cs = sub.add_parser(
        "concat-shards",
        help="merge per-host .hostN sweep shards + manifests into the "
             "final results artifact (manual gather for pods without a "
             "shared filesystem)")
    cs.add_argument("--results", type=Path, required=True,
                    help="the FINAL results path the sweep was given "
                         "(shards live next to it as <stem>.hostN.<ext>)")
    cs.add_argument("--hosts", type=int, default=None,
                    help="expected shard count (default: walk host0, "
                         "host1, ... until the first gap)")

    # bench.py owns its flag surface (it parses sys.argv itself); unknown
    # flags on the bench subcommand are forwarded verbatim instead of
    # hand-mirroring every bench.py option here. Every other subcommand
    # still rejects unknowns — and so does anything typed BEFORE the
    # `bench` subcommand (a typo of the CLI's own flags must fail with
    # THIS parser's usage message, not bench.py's; ADVICE r5, cli.py:470).
    args, extra = parser.parse_known_args(argv)
    if extra:
        argv_seq = list(sys.argv[1:] if argv is None else argv)
        pre_bench = (argv_seq[:argv_seq.index("bench")]
                     if args.command == "bench" else argv_seq)
        bad = [t for t in extra if t in pre_bench]
        if args.command != "bench" or bad:
            parser.error("unrecognized arguments: "
                         f"{' '.join(bad or extra)}")
    args.bench_extra = extra
    if getattr(args, "int8_dynamic", False) and not getattr(args, "int8", False):
        parser.error("--int8-dynamic requires --int8 (it selects HOW int8 "
                     "matmuls run, not whether weights are quantized)")
    if not args.no_compile_cache and args.command != "lint":
        # lint is pure host-side ast analysis — never touch jax (the
        # pre-push hook runs it in containers without an accelerator).
        from .utils import compile_cache

        compile_cache.enable_persistent_cache()
    {
        "sweep": cmd_sweep,
        "perturb": cmd_perturb,
        "serve": cmd_serve,
        "precompile": cmd_precompile,
        "rephrase": cmd_rephrase,
        "analyze": cmd_analyze,
        "repro": cmd_repro,
        "survey": cmd_survey,
        "lint": cmd_lint,
        "bench": cmd_bench,
        "concat-shards": cmd_concat_shards,
    }[args.command](args)


if __name__ == "__main__":
    main()
