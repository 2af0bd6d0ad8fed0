"""One general traffic generator. A mix is a data file under ``traffic/``;
``kind`` picks the window driver, every other key is a parameter here.

A sweep window is what the mix says (``window_groups``, ``trace_groups``),
dealt to the prompts in one order for every seed: two seeds give the same
groups a prompt in the same grid order, and only the resampled words
differ. A serve window's request lengths and the gaps between arrivals are
fixed multisets shuffled by the seed. So no seed makes a run do more work
than another, and no sweep seed does it in another order.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]          # benchmarks/


@dataclasses.dataclass(frozen=True)
class Prompt:
    """One legal-interpretation stimulus, as data (a copy of the five the
    repo's users sweep, ``lir_tpu.data.LEGAL_PROMPTS``)."""

    main: str
    response_format: str
    target_tokens: tuple
    confidence_format: str

    def binary(self, main: str) -> str:
        return f"{main} {self.response_format}"

    def confidence(self, main: str) -> str:
        return f"{main} {self.confidence_format}"


def load_mix(name: str) -> dict:
    mix = json.loads((ROOT / "traffic" / f"{name}.json").read_text())
    if mix["kind"] not in ("sweep", "serve"):
        raise ValueError(f"traffic {name}: unknown kind {mix['kind']!r}")
    return mix


def mix_names(kind: str) -> list:
    """The mixes of one kind, by file name."""
    docs = {path.stem: json.loads(path.read_text())
            for path in (ROOT / "traffic").glob("*.json")}
    return sorted(name for name, doc in docs.items()
                  if isinstance(doc, dict) and doc.get("kind") == kind)


def load_prompts(mix: dict) -> list:
    rows = json.loads((ROOT / "traffic" / mix["prompts"]).read_text())
    return [Prompt(r["main"], r["response_format"],
                   tuple(r["target_tokens"]), r["confidence_format"])
            for r in rows]


def rephrase(prompt: Prompt, n_words: int, head_words: int, rng) -> str:
    """A word-level variation of the prompt's main part: its first
    ``head_words`` words verbatim (the trunk a group shares), then words
    resampled from the prompt's own vocabulary up to ``n_words``."""
    words = prompt.main.split()
    head = words[:head_words]
    return " ".join(head + list(rng.choice(words, n_words - len(head))))


def sweep_groups(mix: dict, prompts: list, seed: int, n_groups: int,
                 stream: int) -> list:
    """Per prompt, the rephrasings of one sweep call: ``n_groups`` whole
    groups of ``group_rows``.

    The program compiles for the shape of the grid it is given: the
    widest answer format among the long rows, and prompts x rephrasing
    slots for its accumulator. So the ANCHOR prompt (the one with the
    longest answer format) gets ``max_groups_per_prompt`` groups first, in
    every call, and the rest are dealt round to the other prompts in
    index order, whatever the seed: every call from that many groups up to
    all prompts full then has the same shapes, and a warm pass of the
    anchor alone warms them all. The seed draws the words alone;
    ``stream`` keeps the calls of one run on different words."""
    cap = mix["max_groups_per_prompt"]
    if n_groups > cap * len(prompts):
        raise ValueError(f"{n_groups} groups do not fit {len(prompts)} "
                         f"prompts of at most {cap}")
    rng = np.random.default_rng([int(seed), int(stream)])
    anchor = max(range(len(prompts)),
                 key=lambda i: len(prompts[i].response_format.split()))
    others = [i for i in range(len(prompts)) if i != anchor]
    counts = [0] * len(prompts)
    counts[anchor] = min(cap, n_groups)
    left, i = n_groups - counts[anchor], 0
    while left:
        pi = others[i % len(others)]
        if counts[pi] < cap:
            counts[pi] += 1
            left -= 1
        i += 1
    return [[rephrase(p, mix["rephrasing_words"], mix["head_words"], rng)
             for _ in range(n * mix["group_rows"])]
            for p, n in zip(prompts, counts)]


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float            # seconds after the window opens
    prompt: int
    main: str


def serve_schedule(mix: dict, prompts: list, seed: int, seconds: float,
                   stream: int, rate_per_s: float | None = None) -> list:
    """Open-loop arrivals due in ``seconds``: ``round(rate * seconds)``
    requests whose gaps are the exponential distribution's own quantiles
    (a Poisson process's gaps, every seed the same multiset, scaled to
    fill the window) in a seeded order; lengths are ``base_words`` times
    the decile table, each decile equally often, in a seeded order."""
    rate = float(mix["rate_per_s"] if rate_per_s is None else rate_per_s)
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([int(seed), int(stream)])
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    due = np.cumsum(rng.permutation(gaps)) - gaps.min() / 2
    factors = np.resize(np.asarray(mix["length_factors"], float), n)
    lengths = np.clip(np.rint(mix["base_words"] * rng.permutation(factors)),
                      mix["head_words"] + 1, mix["max_words"]).astype(int)
    which = rng.permutation(np.resize(np.arange(len(prompts)), n))
    return [Arrival(float(max(t, 0.0)), int(pi),
                    rephrase(prompts[int(pi)], int(w), mix["head_words"],
                             rng))
            for t, pi, w in zip(due, which, lengths)]
