"""Window driver for traffic of kind ``serve``.

The entry the window drives is ``serve.ScoringServer.submit`` on one
server at its defaults. A thread of this process sends an open-loop
schedule made from the seed; every request DUE in the window is sent when
its time comes (late if the generator is late, and that lateness is
reported), and the run ends when the last of them has resolved. Latency
is from the instant the schedule said to send to the instant the result
resolved. A request shed, expired, late past its deadline or in error
counts in ``failed`` and misses.
"""

from __future__ import annotations

import statistics
import threading
import time

from . import builders, flops, tokenizer, traffic
from .compare import Answer
from .readers import percentile

WAIT_PAST_CLOSE_S = 60.0


def _request(prompt, main: str, rid: str, deadline_s: float):
    from lir_tpu.serve import ServeRequest

    return ServeRequest(binary_prompt=prompt.binary(main),
                        confidence_prompt=prompt.confidence(main),
                        targets=tuple(prompt.target_tokens), request_id=rid,
                        deadline_s=deadline_s)


def _send(server, prompts, schedule, deadline_s: float, tag: str) -> list:
    """Send ``schedule`` open-loop; returns per request
    (arrival, sent-late seconds, latency seconds or None, result)."""
    done = [None] * len(schedule)
    events = [threading.Event() for _ in schedule]
    late = [0.0] * len(schedule)
    t0 = time.perf_counter()

    def on_done(i, due):
        def fn(result):
            done[i] = (time.perf_counter() - t0 - due, result)
            events[i].set()
        return fn

    def generator():
        for i, a in enumerate(schedule):
            wait = a.due_s - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            late[i] = max(time.perf_counter() - t0 - a.due_s, 0.0)
            fut = server.submit(_request(prompts[a.prompt], a.main,
                                         f"{tag}-{i}", deadline_s))
            fut.add_done_callback(on_done(i, a.due_s))

    thread = threading.Thread(target=generator, name="load-generator")
    thread.start()
    thread.join()
    close = schedule[-1].due_s if schedule else 0.0
    for ev in events:
        remaining = close + WAIT_PAST_CLOSE_S - (time.perf_counter() - t0)
        ev.wait(max(remaining, 0.0))
    wall = time.perf_counter() - t0
    return [(a, late[i], done[i]) for i, a in enumerate(schedule)], wall


def needed_flops(spec, prompts, schedule, new_bin: int, new_conf: int
                 ) -> tuple:
    """(FLOPs, prompt tokens offered): every request on its own, nothing
    shared between requests (sharing is the cache's to find)."""
    total, offered = 0.0, 0
    for a in schedule:
        b, c, shared = tokenizer.encode_pair(prompts[a.prompt], a.main,
                                             spec.vocab)
        total += flops.scoring_cell_flops(spec, shared, len(b), len(c),
                                          new_bin, new_conf)
        offered += len(b) + len(c) - shared
    return total, offered


class ServeCell:
    """The engine and one server at its defaults, built once; ``run``
    drives one window through it, ``tests/knee.py`` several."""

    def __init__(self, ctx):
        from lir_tpu.serve import ScoringServer

        self.ctx, self.spec, self.mix = ctx, ctx.spec, ctx.mix
        self.prompts = traffic.load_prompts(ctx.mix)
        cfg = builders.program_config(ctx.spec, ctx.check_config)
        params = builders.build_params(ctx.spec, ctx.ref, ctx.seed)
        self.engine = builders.build_engine(params, cfg, ctx.runtime)
        self.server = ScoringServer(self.engine, cfg.name).start()

    def warm(self, rate: float) -> dict:
        """The window's own mix at the window's own rate, twice over, so
        every bucket and batch shape it will dispatch is loaded or
        compiled. The deadline is the watchdog's floor: cold compiles ride
        requests."""
        mix, seed = self.mix, self.ctx.seed
        seconds = mix["warm_requests"] / rate
        compiled0 = dict(builders.COMPILE)
        for stream in (0, 1):
            warm = traffic.serve_schedule(mix, self.prompts, seed, seconds,
                                          stream=stream, rate_per_s=rate)
            _send(self.server, self.prompts, warm,
                  builders.WATCHDOG_FLOOR_S, f"warm{stream}")
        builders.assert_no_recovery(self.engine, "warm")
        return {"programs": builders.COMPILE["programs"]
                - compiled0["programs"],
                "seconds": builders.COMPILE["seconds"] - compiled0["seconds"]}

    def measure(self, rate: float, seconds: float, stream: int,
                on_open=lambda: None, trace=None) -> dict:
        spec, mix, prompts = self.spec, self.mix, self.prompts
        server, engine = self.server, self.engine
        deadline = float(mix["deadline_s"])
        schedule = traffic.serve_schedule(mix, prompts, self.ctx.seed,
                                          seconds, stream=stream,
                                          rate_per_s=rate)
        before = server.metrics.snapshot(device_memory=False)
        compiled0 = dict(builders.COMPILE)
        on_open()
        if trace:
            trace.start()
        sent, wall = _send(server, prompts, schedule, deadline, f"w{stream}")
        if trace:
            trace.stop()
        after = server.metrics.snapshot(device_memory=False)
        compiles = builders.COMPILE["programs"] - compiled0["programs"]
        compile_s = builders.COMPILE["seconds"] - compiled0["seconds"]
        builders.assert_no_recovery(engine, "window")
        latencies, answers, failed = [], [], 0
        for a, _, got in sent:
            ok = (got is not None and got[1].status == "ok"
                  and got[0] <= deadline and not got[1].cached
                  and got[1].token_1_prob is not None)
            if not ok:
                failed += 1
                latencies.append(float("inf") if got is None else
                                 max(got[0], deadline))
                continue
            latencies.append(got[0])
            r, p = got[1], prompts[a.prompt]
            answers.append(Answer(
                p.binary(a.main), p.confidence(a.main),
                tuple(p.target_tokens), r.model_response,
                r.model_confidence_response, r.token_1_prob, r.token_2_prob,
                r.log_probabilities))
        new_bin = server.batcher.new_tokens
        new_conf = server.batcher.conf_tokens
        need, offered = needed_flops(spec, prompts, schedule, new_bin,
                                     new_conf)
        finite = [x for x in latencies if x != float("inf")]
        last_due = schedule[-1].due_s
        return {
            "attempted": len(schedule),
            "failed": failed,
            "end_to_end": {
                "latency_p50_ms": 1000.0 * percentile(latencies, 50),
                "latency_p95_ms": 1000.0 * percentile(latencies, 95)},
            "answers": answers,
            "counters": {"before": before, "after": after},
            "samples": {"gen_late_s": [late for _, late, _ in sent],
                        "latency_s": finite},
            "window": {
                "seconds": wall, "requests": len(schedule),
                "rate_per_s": rate, "needed_flops": need,
                "prompt_tokens_offered": offered,
                "completed_per_s": (len(schedule) - failed) / wall,
                "drain_s": wall - last_due,
                "latency_mean_ms": (1000.0 * statistics.fmean(finite)
                                    if finite else None),
                "server": server.stats.summary(),
                "compiles_in_window": compiles,
                "compile_seconds_in_window": compile_s,
                "batch": engine.rt.batch_size, "new_bin": new_bin,
                "new_conf": new_conf},
        }

    def close(self) -> None:
        self.server.stop()
        self.engine.stream_sink = None
        del self.server, self.engine


def run(ctx) -> dict:
    cell = ServeCell(ctx)
    rate = float(ctx.mix["rate_per_s"])
    cell.warm(rate)
    seconds = (min(ctx.seconds, ctx.mix["trace_seconds"]) if ctx.trace
               else ctx.seconds)
    record = cell.measure(rate, seconds, stream=2, on_open=ctx.setup_done,
                          trace=ctx.trace)
    cell.close()
    return record
