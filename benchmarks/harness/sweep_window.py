"""Window driver for traffic of kind ``sweep``.

The entry the window drives is ``engine.sweep.run_perturbation_sweep``:
one call over whole groups of rephrasings, to a fresh results path. Set-up
makes two warm passes through the same entry (the first loads or compiles
every program, the second is timed to size the window); the window is ONE
call sized from that rate to last about ``--seconds``, and the metric is
all its grid cells over its whole wall time, the last row read back.
"""

from __future__ import annotations

import statistics
import time
from pathlib import Path

from . import builders, flops, tokenizer, traffic
from .compare import Answer


def _program_prompts(prompts: list) -> tuple:
    from lir_tpu.data.prompts import LegalPrompt

    return tuple(LegalPrompt(main=p.main, response_format=p.response_format,
                             target_tokens=p.target_tokens,
                             confidence_format=p.confidence_format)
                 for p in prompts)


def _sweep(engine, prompts, perts, path: Path) -> tuple:
    """(rows, seconds) of one sweep call, every row checked as it comes."""
    from lir_tpu.engine.sweep import run_perturbation_sweep

    t0 = time.perf_counter()
    rows = run_perturbation_sweep(engine, engine.cfg.name, prompts,
                                  tuple(perts), path)
    return rows, time.perf_counter() - t0


def _wait_for_plan(engine) -> None:
    """Every program a sweep call planned is compiled in the background;
    none may still be compiling when the window opens."""
    if engine.exec_registry is not None:
        engine.exec_registry.wait()


def needed_flops(spec, mix: dict, prompts: list, perts: list,
                 new_bin: int, new_conf: int) -> tuple:
    """(FLOPs, prompt tokens offered, kernel call sizes) of the window's
    traffic: per
    prompt its original (a group of one) and its rephrasings in groups of
    ``group_rows`` whose shared head is counted once."""
    total, offered = 0.0, 0
    head = mix["head_words"]
    sizes = {"shared": [], "bin": [], "conf": []}
    for p, mains in zip(prompts, perts):
        for k, main in enumerate([p.main] + list(mains)):
            b, c, shared = tokenizer.encode_pair(p, main, spec.vocab)
            first_of_group = k == 0 or (k - 1) % mix["group_rows"] == 0
            trunk = 0 if first_of_group else min(head, shared)
            total += flops.scoring_cell_flops(
                spec, shared, len(b), len(c), new_bin, new_conf, trunk)
            offered += len(b) + len(c) - shared
            if k:                      # a long row (not the original)
                sizes["shared"].append(shared)
                sizes["bin"].append(len(b))
                sizes["conf"].append(len(c))
    # Mean sizes of the attention kernels' calls over the long rows: a
    # decode call of branch x at step j reads len(x) + j keys.
    mean = lambda v: statistics.fmean(v) if v else 0.0  # noqa: E731
    steps = new_bin + new_conf
    extent = (new_bin * (mean(sizes["bin"]) + (new_bin + 1) / 2)
              + new_conf * (mean(sizes["conf"]) + (new_conf + 1) / 2)) / steps
    kernel_calls = {
        "decode_attention_call": {"batch": mix["group_rows"],
                                  "extent": extent, "trunk": head},
        "cascade_prefill_call": {"batch": mix["group_rows"],
                                 "length": mean(sizes["shared"]),
                                 "trunk": head}}
    return total, offered, kernel_calls


def run(ctx) -> dict:
    """``ctx``: spec, ref, mix, seed, seconds, out (a fresh directory),
    trace (a ``Tracer`` or None), setup_done (callable marking the end of
    set-up). Returns the window's record."""
    from lir_tpu.observe import registry as metrics_mod

    spec, mix, seed = ctx.spec, ctx.mix, ctx.seed
    prompts = traffic.load_prompts(mix)
    cfg = builders.program_config(spec, ctx.check_config)
    params = builders.build_params(spec, ctx.ref, seed)
    engine = builders.build_engine(params, cfg, ctx.runtime)
    prog_prompts = _program_prompts(prompts)
    rows_per_group = mix["group_rows"]

    cap = mix["max_groups_per_prompt"]
    # Warm pass 1: loads or compiles the programs the window will use
    # (two long dispatches: the first of a call and the ones after it).
    warm = traffic.sweep_groups(mix, prompts, seed, min(2, cap), stream=0)
    _, load_s = _sweep(engine, prog_prompts, warm, ctx.out / "warm1.csv")
    _wait_for_plan(engine)
    # Warm pass 2, timed: a grid of the window's shape (the anchor prompt
    # full), and the rate the window is sized from.
    warm = traffic.sweep_groups(mix, prompts, seed, cap, stream=1)
    rows, warm_s = _sweep(engine, prog_prompts, warm, ctx.out / "warm2.csv")
    _wait_for_plan(engine)
    warm_rate = len(rows) / warm_s
    seconds = (min(ctx.seconds, mix["trace_seconds"]) if ctx.trace
               else ctx.seconds)
    n_groups = min(cap * len(prompts), max(cap, int(round(
        seconds * warm_rate / rows_per_group))))
    perts = traffic.sweep_groups(mix, prompts, seed, n_groups, stream=2)
    attempted = sum(1 + len(p) for p in perts)
    builders.assert_no_recovery(engine, "warm")
    before = metrics_mod.engine_registry(engine).snapshot(device_memory=False)
    compiled0 = dict(builders.COMPILE)

    ctx.setup_done()
    if ctx.trace:
        ctx.trace.start()
    rows, window_s = _sweep(engine, prog_prompts, perts,
                            ctx.out / "window.csv")
    if ctx.trace:
        ctx.trace.stop()

    builders.assert_no_recovery(engine, "window")
    after = metrics_mod.engine_registry(engine).snapshot(device_memory=False)
    good = [r for r in rows
            if r.token_1_prob is not None and r.token_2_prob is not None]
    target_of = {p.main: p.target_tokens for p in prompts}
    answers = [Answer(r.full_rephrased_prompt, r.full_confidence_prompt,
                      tuple(target_of[r.original_main]), r.model_response,
                      r.model_confidence_response, r.token_1_prob,
                      r.token_2_prob, r.log_probabilities) for r in good]
    new_bin = min(engine.rt.sweep_decode_tokens, engine.rt.max_new_tokens)
    new_conf = min(engine.rt.sweep_confidence_tokens,
                   engine.rt.max_new_tokens)
    need, offered, kernel_calls = needed_flops(spec, mix, prompts, perts,
                                               new_bin, new_conf)
    record = {
        "attempted": attempted,
        "failed": attempted - len(good),
        "end_to_end": {"prompts_per_s": len(good) / window_s},
        "answers": answers,
        "counters": {"before": before, "after": after},
        "window": {
            "seconds": window_s, "groups": n_groups, "cells": attempted,
            "needed_flops": need, "prompt_tokens_offered": offered,
            "head_tokens": mix["head_words"], "kernel_calls": kernel_calls,
            "warm_load_s": load_s, "warm_rate": warm_rate,
            "compiles_in_window":
                builders.COMPILE["programs"] - compiled0["programs"],
            "compile_seconds_in_window":
                builders.COMPILE["seconds"] - compiled0["seconds"],
            "batch": engine.rt.batch_size, "new_bin": new_bin,
            "new_conf": new_conf},
    }
    engine.stream_sink = None
    del engine, params
    return record
