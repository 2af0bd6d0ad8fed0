"""Window driver for traffic of kind ``sweep``.

The entry the window drives is ``engine.sweep.run_perturbation_sweep``:
one call over whole groups of rephrasings, to a fresh results path. Set-up
makes two warm passes through the same entry (the first loads or compiles
every program, the second runs a grid of the window's shape and is
timed); the window is ONE call of the groups the MIX names
(``window_groups``; ``trace_groups`` in a traced run), dealt to the
prompts in one order for every seed, and the metric is all its grid cells
over its whole wall time, the last row read back. Nothing the program
does sizes the window: neither the warm pass's rate (printed as
``window.warm_rate``, and that is all) nor ``--seconds``.
"""

from __future__ import annotations

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
    """(FLOPs, prompt tokens offered) of the window's traffic, at ONE
    trunk a prompt a call: a prompt's first row of the call (its original)
    is counted whole, every other row of that prompt, the first row of
    each group included, behind the ``head_words`` tokens it shares with
    the original. That is the least the traffic needs, whatever the
    program does."""
    total, offered = 0.0, 0
    head = mix["head_words"]
    for p, mains in zip(prompts, perts):
        for k, main in enumerate([p.main] + list(mains)):
            b, c, shared = tokenizer.encode_pair(p, main, spec.vocab)
            trunk = min(head, shared) if k else 0
            total += flops.scoring_cell_flops(
                spec, shared, len(b), len(c), new_bin, new_conf, trunk)
            offered += len(b) + len(c) - shared
    return total, offered


def run(ctx) -> dict:
    """``ctx``: spec, ref, mix, seed, out (a fresh directory), trace (a
    ``Tracer`` or None), setup_done (callable marking the end of set-up),
    elapsed (seconds since the process started). Returns the window's
    record."""
    from lir_tpu.observe import registry as metrics_mod

    spec, mix, seed = ctx.spec, ctx.mix, ctx.seed
    # Seconds each stage of set-up took, by the clock ``setup_s`` is on:
    # harness stamps around calls into the program, no metric of their own.
    stages, last = {}, 0.0

    def stamp(name: str) -> None:
        nonlocal last
        now = ctx.elapsed()
        stages[name], last = now - last, now

    stamp("imports_and_devices")
    prompts = traffic.load_prompts(mix)
    cfg = builders.program_config(spec, ctx.ref, ctx.check_config)
    params = builders.build_params(spec, ctx.ref, seed)
    stamp("weights")
    engine = builders.build_engine(params, cfg, ctx.runtime)
    prog_prompts = _program_prompts(prompts)
    stamp("engine")

    cap = mix["max_groups_per_prompt"]
    # Warm pass 1: loads or compiles the programs the window will use
    # (two long dispatches: the first of a call and the ones after it).
    warm = traffic.sweep_groups(mix, prompts, seed, min(2, cap), stream=0)
    _, load_s = _sweep(engine, prog_prompts, warm, ctx.out / "warm1.csv")
    stamp("warm_pass_1")
    _wait_for_plan(engine)
    stamp("plan_wait")
    # Warm pass 2, timed: a grid of the window's shape (the anchor prompt
    # full). Its rate is printed; it sizes nothing.
    warm = traffic.sweep_groups(mix, prompts, seed, cap, stream=1)
    rows_warm, warm_s = _sweep(engine, prog_prompts, warm,
                               ctx.out / "warm2.csv")
    _wait_for_plan(engine)
    stamp("warm_pass_2")
    n_groups = mix["trace_groups" if ctx.trace else "window_groups"]
    perts = traffic.sweep_groups(mix, prompts, seed, n_groups, stream=2)
    attempted = sum(1 + len(p) for p in perts)
    builders.assert_no_recovery(engine, "warm")
    before = metrics_mod.engine_registry(engine).snapshot(device_memory=False)
    compiled0 = dict(builders.COMPILE)
    stamp("window_dealt")

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
    need, offered = needed_flops(spec, mix, prompts, perts, new_bin,
                                 new_conf)
    record = {
        "attempted": attempted,
        "failed": attempted - len(good),
        "end_to_end": {"prompts_per_s": len(good) / window_s},
        "answers": answers,
        "counters": {"before": before, "after": after},
        # What a module that sizes kernels takes (its ``window_calls``).
        "traffic": {"mix": mix, "prompts": prompts, "perts": perts,
                    "steps": (new_bin, new_conf)},
        "window": {
            "seconds": window_s, "groups": n_groups, "cells": attempted,
            # Groups a prompt, in grid order: two runs that print the same
            # deal held the same window.
            "deal": [len(p) // mix["group_rows"] for p in perts],
            "needed_flops": need, "prompt_tokens_offered": offered,
            "head_tokens": mix["head_words"],
            "warm_load_s": load_s, "warm_rate": len(rows_warm) / warm_s,
            "setup_stages": stages,
            "setup_compile_s": compiled0["seconds"],
            "setup_programs": compiled0["programs"],
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
