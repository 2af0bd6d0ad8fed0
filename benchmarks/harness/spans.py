"""Readers of what the program names: phase scopes and causal spans.

Three inputs, all the program's own (``lir_tpu/observe/tracing.py``,
``lir_tpu/engine/compile_plan.py``), next to the profiler's planes:

* ``spans``: the events of the program's ``TraceRecorder`` (name, ``t0``
  and ``t1`` on ``time.monotonic``, ``id``, ``parent``, ``cause``,
  ``thread``, ``args``), installed at process start in a traced run;
* two ``lir/clock_anchor`` pairs (``tracing.clock_anchor()`` right after
  the profiler starts and right before it stops): the same instant as a
  host-plane event on the profiler's clock and as a recorder span, so a
  recorder span, set-up included, can be laid on the device timeline;
* ``scope_tables``: ``ExecutableRegistry.scope_tables(engine)``, for each
  dispatch program ``{HLO instruction name: lir.<phase>}``. A device
  operation's event is named by its HLO line, so its instruction name
  is the key; operations of a program under no scope are ``other``.

``planes`` has the form ``harness/trace.reduce_planes`` takes, read by
:func:`read_planes` here: ``trace.read_planes`` keys a plane's lines by
name, and every Python thread's line is named ``python3``, so it keeps
one of them and drops the others with the program's spans on them (seen
on the v5e, PR 24: that, not a lost annotation, is why the ledger's idle
gaps carry no program name). A reader that finds no scope, no span or
no anchor returns None, never 0.

The readers at the bottom have the signature ``harness/readers.py``
calls (``reader(context, **args)``) and want ``context["planes"]``,
``context["spans"]`` and ``context["scope_tables"]``. ``run.py`` does
not put those there yet (PERF.md, open questions):
``tests/phase_trace.py`` builds the same context by hand and prints
what they read.
"""

from __future__ import annotations

import re

from . import trace

CLOCK_ANCHOR = "lir/clock_anchor"
OTHER = "other"


def read_planes(path) -> dict:
    """``trace.read_planes`` with every line kept: a line whose name was
    already taken is keyed ``<name>#<n>``."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    planes = {}
    for p in data.planes:
        lines = planes.setdefault(p.name, {})
        for ln in p.lines:
            key, n = ln.name, 1
            while key in lines:
                n += 1
                key = f"{ln.name}#{n}"
            lines[key] = trace._events(ln)
    return planes


def anchor_offsets(planes: dict, spans: list) -> list:
    """Seconds to add to a recorder stamp to land on the profiler's
    clock, one per anchor pair in time order ([] without a pair). Two
    pairs that differ by more than a millisecond mean a clock drifted or
    an anchor was matched to the wrong event."""
    host = sorted((start + dur / 2) / 1e9
                  for line in planes.get(trace.HOST_PLANE, {}).values()
                  for name, start, dur in line if name == CLOCK_ANCHOR)
    mine = sorted((ev["t0"] + ev["t1"]) / 2 for ev in spans
                  if ev["name"] == CLOCK_ANCHOR)
    if len(host) != len(mine):
        return []
    return [h - m for h, m in zip(host, mine)]


def self_seconds(spans: list) -> dict:
    """{span id: its duration minus the union of its children's} (a
    child is a span whose ``parent`` it is; one stamped by hand may
    start before its parent and is clipped to it)."""
    kids = {}
    for ev in spans:
        if "parent" in ev:
            kids.setdefault(ev["parent"], []).append(ev)
    out = {}
    for ev in spans:
        inner = [(max(k["t0"], ev["t0"]) * 1e9, min(k["t1"], ev["t1"]) * 1e9)
                 for k in kids.get(ev["id"], [])]
        covered, _ = trace.union_seconds([(a, b) for a, b in inner if b > a])
        out[ev["id"]] = ev["t1"] - ev["t0"] - covered
    return out


def instruction(event_name: str) -> str:
    """``%fusion.1081 = bf16[...] fusion(...)`` -> ``fusion.1081``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def phase_seconds(planes: dict, tables: list, pattern: str,
                  chips: int = 1) -> dict | None:
    """Device seconds of the programs whose XLA module name matches
    ``pattern``, split by the phase scope their operations were built
    under. Every run of such a program (an ``XLA Modules`` event) takes
    the operations that start inside it; the table used is the one of
    that module name whose instructions best match the names seen (the
    fresh and the donated variant of one program share a name). Loops
    and conditionals are left out, as in ``trace.reduce_planes``.

    ``{"runs", "module_s", "ops_s", "scopes": {scope: s}, "other_s",
    "unmatched_runs"}`` per chip, or None where no such program ran or
    no table names a scope."""
    rx = re.compile(pattern)
    by_module = {}
    for t in tables:
        by_module.setdefault(t["module"], []).append(t["scopes"])
    devices = sorted(n for n in planes if trace.DEVICE_PLANE.match(n))[:chips]
    runs = unmatched = 0
    module_s = ops_s = other_s = 0.0
    scopes = {}
    for plane in devices:
        lines = planes[plane]
        ops = sorted((s, d, instruction(name))
                     for name, s, d in lines.get(trace.OPS_LINE, [])
                     if not trace.CONTAINER.match(name))
        i = 0
        for name, ms, md in sorted(lines.get(trace.MODULES_LINE, []),
                                   key=lambda e: e[1]):
            while i < len(ops) and ops[i][0] < ms:
                i += 1
            j = i
            while j < len(ops) and ops[j][0] < ms + md:
                j += 1
            inside, i = ops[i:j], j
            base = trace.strip_id(name)
            if not rx.search(base):
                continue
            runs += 1
            module_s += md / 1e9
            seen = {n for _, _, n in inside}
            table = max(by_module.get(base, [{}]),
                        key=lambda t: len(seen & t.keys()))
            if not seen & table.keys():
                unmatched += 1
            for _, d, n in inside:
                ops_s += d / 1e9
                scope = table.get(n)
                if scope is None:
                    other_s += d / 1e9
                else:
                    scopes[scope] = scopes.get(scope, 0.0) + d / 1e9
    if not runs or not scopes:
        return None
    n = len(devices)
    return {"runs": runs / n, "module_s": module_s / n, "ops_s": ops_s / n,
            "scopes": {k: v / n for k, v in sorted(scopes.items())},
            "other_s": other_s / n, "unmatched_runs": unmatched / n}


def device_tail_seconds(planes: dict, spans: list,
                        call: str = "sweep/call") -> float | None:
    """From the end of the last device operation of the trace to the end
    of the last ``call`` span, on the anchored clock."""
    offsets = anchor_offsets(planes, spans)
    calls = [ev for ev in spans if ev["name"] == call]
    ends = [s + d for n in planes if trace.DEVICE_PLANE.match(n)
            for _, s, d in planes[n].get(trace.OPS_LINE, [])]
    if not offsets or not calls or not ends:
        return None
    return max(ev["t1"] for ev in calls) + offsets[-1] - max(ends) / 1e9


# --- readers, for harness/readers.py to register --------------------------

def trace_phase_time(context, scopes, pattern="^jit_greedy_decode",
                     per="count", scale=1000.0):
    """Device seconds under the listed ``scopes`` (``lir.prefill`` ...,
    or ``other``) of the programs matching ``pattern``, per run."""
    split = phase_seconds(context.get("planes", {}),
                          context.get("scope_tables", []), pattern,
                          context["trace"]["chips"])
    if split is None:
        return None
    seconds = sum(split["other_s"] if s == OTHER
                  else split["scopes"].get(s, 0.0) for s in scopes)
    return scale * (seconds / split["runs"] if per == "count" else seconds)


def trace_tail(context, call="sweep/call", scale=1000.0):
    tail = device_tail_seconds(context.get("planes", {}),
                               context.get("spans", []), call)
    return None if tail is None else scale * tail


def span_seconds(context, name, what="total", per=None, before=None,
                 scale=1.0):
    """Summed seconds of the spans called ``name``: ``total`` duration,
    ``self`` time, or the ``union`` of their intervals; over the count of
    the spans called ``per`` if given; only spans that ended before the
    first span called ``before`` if given."""
    spans = context.get("spans", [])
    cut = min((ev["t0"] for ev in spans if ev["name"] == before),
              default=None) if before else None
    if before and cut is None:
        return None
    mine = [ev for ev in spans if ev["name"] == name
            and (cut is None or ev["t1"] <= cut)]
    if not mine:
        return None
    if what == "self":
        own = self_seconds(spans)
        seconds = sum(own[ev["id"]] for ev in mine)
    elif what == "union":
        seconds, _ = trace.union_seconds(
            [(ev["t0"] * 1e9, ev["t1"] * 1e9) for ev in mine])
    else:
        seconds = sum(ev["t1"] - ev["t0"] for ev in mine)
    if per:
        n = sum(1 for ev in spans if ev["name"] == per)
        if not n:
            return None
        seconds /= n
    return scale * seconds


READERS = {f.__name__: f for f in (trace_phase_time, trace_tail,
                                   span_seconds)}
