# Convenience targets. `make verify` is the pre-ship gate: it runs the
# tier-1 suite as the driver does (six workers, one file each) and fails
# if the pass count drops below the driver's floor, read from
# PERF_LEDGER.jsonl (tools/check_tier1.py holds no floor of its own).

.PHONY: verify test bench lint serve-smoke prefix-smoke chaos-smoke \
	kernel-smoke stats-smoke fleet-smoke observe-smoke elastic-smoke \
	spec-smoke mem-smoke disagg-smoke cascade-smoke \
	cascade-decode-smoke tiered-smoke chip-smoke install-hooks

verify: lint cascade-smoke cascade-decode-smoke tiered-smoke
	python tools/check_tier1.py

# graft-lint: AST static analysis proving the engine's JAX/XLA
# invariants — donation-safety, trace-hazard, host-sync,
# lock-discipline, config-drift (lir_tpu/lint, DEPLOY.md §1i). Fails on
# any finding outside tools/lint_baseline.json; runs in ~2 s with no
# jax import, so it gates verify and the pre-push hook first.
lint:
	python -m lir_tpu.lint

# The raw tier-1 suite without the floor gate (interactive debugging).
test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider \
		-p no:xdist -p no:randomly

bench:
	python bench.py

# Chip smoke (NOT part of verify: it needs a TPU and refuses to start
# without one): mistral-7b at full width and depth through the sweep,
# the server, the kernels-vs-dense check and the CLI, on one chip; the
# last stdout line is {"ok": true, "device": {...}}. `python
# chip_smoke.py --chips 4` runs the mesh and replica paths instead.
# No platform is set here: JAX finds the TPU or the script exits non-zero.
chip-smoke:
	python chip_smoke.py

# Online-serving smoke: boot the server on the fake backend, push 50
# requests (incl. duplicate re-asks), assert zero sheds + nonzero dedup
# hit rate + all-ok (tools/serve_smoke.py).
serve-smoke:
	JAX_PLATFORMS=cpu python tools/serve_smoke.py

# Prefix-cache smoke: serve the shared-prefix workload (variations of 5
# long bases) on the fake backend with the cross-request radix prefix
# cache ON vs OFF — assert nonzero prefill-tokens-avoided on the warm
# pass, per-request payloads bitwise-identical to the unpaged path, and
# page refcounts sane after drain (tools/prefix_smoke.py).
prefix-smoke:
	JAX_PLATFORMS=cpu python tools/prefix_smoke.py

# Chaos smoke: seeded fault schedule on the fake backend — a sweep under
# injected device errors + a mid-sweep kill + a torn manifest tail must
# resume bitwise-identical (zero lost/duplicated rows); the serve
# circuit breaker must trip and recover via its half-open probe; the
# degradation ladder must isolate a poison row; a SIGTERM-style state
# checkpoint must hand every pending request to a fresh server; an
# injected HANG must be stalled-out by the watchdog within its deadline
# and recovered via the ladder; injected-NaN rows must quarantine as
# error:numerics with every clean row bitwise-identical (zero corrupted
# rows); a simulated dead peer must raise HostDesyncError within the
# liveness timeout instead of hanging (tools/chaos_smoke.py).
chaos-smoke:
	JAX_PLATFORMS=cpu python tools/chaos_smoke.py

# Kernel smoke: the PR-7 fused layer vs its references on CPU — the
# Pallas flash-decode kernel under interpret mode must be greedy
# argmax-identical to the dense decode path, the fused s8xs8 matmul must
# match the dequantized reference (static + dynamic + shared-quant), and
# a piggybacked dispatch chain must reproduce the sequential sweep's
# rows exactly while its chain counters move (tools/kernel_smoke.py).
kernel-smoke:
	JAX_PLATFORMS=cpu python tools/kernel_smoke.py

# Streaming-statistics smoke: the grid -> CIs device pipeline on the
# fake backend — the accumulator finalize must equal the csv-reload
# pipeline (counts/kappa bitwise, moments/CIs within FLOAT_TOL), a
# streaming-only pass must fold every row on device with zero result
# rows written (host-sync lint clean over the sink module), and the
# serve `stats` endpoint must answer live mid-workload
# (tools/stats_smoke.py).
stats-smoke:
	JAX_PLATFORMS=cpu python tools/stats_smoke.py

# Fleet smoke: the multi-model fleet layer on the fake backend — a
# 3-model sweep must book nonzero prefetch overlap (swap_s_hidden > 0,
# exactly one exposed load), per-model rows must be bitwise-identical
# to standalone single-model engines, and a fleet_score serve fan-out
# must answer per-model P(yes)/P(no) with kappa exactly equal to the
# analysis layer's within_group_kappa (tools/fleet_smoke.py).
fleet-smoke:
	JAX_PLATFORMS=cpu python tools/fleet_smoke.py

# Observatory smoke: the reliability observatory + telemetry spine on
# the fake backend — a 2-model fleet re-scores a sentinel grid across 3
# time windows; the two clean windows raise no alert, a seeded
# fault-plan NaN injection in window 3 raises EXACTLY one drift alert
# naming window 3 and the injected model, per-window kappa is bitwise
# the analysis layer's within_group_kappa, and the unified metrics
# snapshot is non-empty for every registered stats source
# (tools/observe_smoke.py).
observe-smoke:
	JAX_PLATFORMS=cpu python tools/observe_smoke.py

# Speculative-decode smoke: confidence-tail grid on the fake backend,
# scored twice — pass 2 drafts each row's whole continuation from the
# radix tree's token history and verifies it in one multi-query
# forward. Asserts nonzero accepted tokens, >= 2x fewer decode
# dispatches per row on the warm pass, and speculation-ON == OFF
# payloads bitwise (tools/spec_smoke.py; DEPLOY.md §1n).
spec-smoke:
	JAX_PLATFORMS=cpu python tools/spec_smoke.py

# Memory-governance smoke: the unified HBM governor under a seeded
# hbm_squeeze on the fake backend — the degradation ladder must walk
# down during the squeeze and back up after it (rung_downs == rung_ups,
# level 0) in BOTH the sweep and serve paths, with zero crashed
# dispatches and rows/payloads bitwise-identical to unpressured runs;
# governor gauges must ride the metrics snapshot (tools/mem_smoke.py;
# DEPLOY.md §1o).
mem-smoke:
	JAX_PLATFORMS=cpu python tools/mem_smoke.py

# Elastic-serving smoke: 3 in-process replicas behind the failover
# router on the fake backend — a seeded replica_kill mid-run must lose
# and duplicate ZERO requests (in-flight re-admitted to survivors,
# zombie payloads dropped by resolve-once + content dedup), the killed
# replica's breaker must walk open -> half_open -> closed across the
# rejoin, and a shard lease abandoned by a dead holder must be stolen
# within one TTL with the stolen shard's lattice merge bitwise-
# identical (tools/elastic_smoke.py).
elastic-smoke:
	JAX_PLATFORMS=cpu python tools/elastic_smoke.py

# Cascade-prefill smoke: shared-trunk grid (3 long bases x 8 tail
# rephrasings) served on the fake backend with cascade prefill ON vs
# OFF — the trunk's attention must be computed once per dispatch
# (nonzero cascade dispatches / trunk rows deduped / analytic prefix
# FLOPs saved in CascadeStats), every argmax-derived payload field
# identical between the two servers and float probabilities within
# tolerance (the PR-7 parity bar), and the dense server must never
# cascade (tools/cascade_smoke.py; DEPLOY.md §1q).
cascade-smoke:
	JAX_PLATFORMS=cpu python tools/cascade_smoke.py

# Cascade-decode smoke: the same shared-trunk grid served with cascade
# DECODE on vs off (prefill dense on both) — nonzero trunk-aware decode
# dispatches AND analytic trunk bytes deduped in CascadeStats, every
# payload field BITWISE-identical between the two servers (the trunk
# kernels compute the flat kernels' exact partials), and the flat
# server never counting a cascade-decode dispatch
# (tools/cascade_decode_smoke.py; DEPLOY.md §1r).
cascade-decode-smoke:
	JAX_PLATFORMS=cpu python tools/cascade_decode_smoke.py

# Disaggregated-serving smoke: 1 prefill-role + 2 decode-role replicas
# behind the router on the fake backend — scoring lands only on decode
# replicas, a nonzero number of KV pages migrates (prefill -> export ->
# transfer -> import), every payload is bitwise-identical to a
# colocated single server's, and a replica killed mid-migration falls
# back to local re-prefill with nothing dropped (tools/disagg_smoke.py;
# DEPLOY.md §1p).
disagg-smoke:
	JAX_PLATFORMS=cpu python tools/disagg_smoke.py

# Tiered-memory smoke: a shared-prefix working set larger than the HBM
# page budget on the HBM -> host DRAM -> disk KV ladder — nonzero
# demotions AND promotions, every payload bitwise-identical to the
# untiered server's, and a restarted server re-seeds its radix tree
# from the disk index with nonzero prefill tokens avoided
# (tools/tiered_smoke.py; DEPLOY.md §1s).
tiered-smoke:
	JAX_PLATFORMS=cpu python tools/tiered_smoke.py

# Run graft-lint (seconds) then the tier-1 guard before every
# `git push` — lint first so an invariant break fails in two seconds,
# not after the full suite.
install-hooks:
	printf '#!/bin/sh\npython -m lir_tpu.lint || exit 1\nexec python tools/check_tier1.py\n' > .git/hooks/pre-push
	chmod +x .git/hooks/pre-push
	@echo "pre-push hook installed: graft-lint + tier-1 guard run before every push"
