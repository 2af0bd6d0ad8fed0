"""The comparison that decides ``correct``.

After the window has closed, a sample of the answers it produced (drawn
from the seed, the longest among them) is held against the plain
reference run once over each prompt with its served tokens:

``logprob_gap``  the widest |served log-probability - reference's| over
                 the two target tokens and the twenty listed tokens at the
                 first answer position of each sampled binary prompt;
``token_gap``    the widest gap by which a served (greedy) token's
                 reference logit lies below the reference's best, over
                 every served token of both branches.

Each has a limit of its own in ``limits/<cell>.json``. ``correct`` also
needs every attempted answer to have come.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path

import numpy as np

from . import tokenizer

ROOT = Path(__file__).resolve().parents[1]          # benchmarks/


@dataclasses.dataclass(frozen=True)
class Answer:
    """What the timed path said about one grid cell or request."""

    binary_prompt: str
    confidence_prompt: str
    targets: tuple            # the two target words
    response: str             # decoded greedy tokens, binary branch
    confidence_response: str
    token_1_prob: float
    token_2_prob: float
    log_probabilities: str    # JSON {token id: logprob}, first position


def load_limits(cell: str) -> dict:
    path = ROOT / "limits" / f"{cell}.json"
    if not path.exists():
        raise FileNotFoundError(
            f"{path}: a cell brings its limits (see README.md); none means "
            "nothing decides `correct`")
    return json.loads(path.read_text())["limits"]


def draw_sample(answers: list, n: int, seed: int, vocab: int) -> list:
    """``n`` answers drawn from the seed, the longest prompt among them."""
    if len(answers) <= n:
        return list(answers)
    longest = max(range(len(answers)), key=lambda i: len(
        tokenizer.encode(answers[i].binary_prompt, vocab)))
    rng = np.random.default_rng([int(seed), 7])
    rest = [i for i in rng.permutation(len(answers)) if i != longest]
    return [answers[i] for i in [longest] + rest[:n - 1]]


def pack(sample: list, vocab: int) -> tuple:
    """Sequences for the reference: per answer its binary and its
    confidence prompt, each followed by its served tokens. Returns
    (tokens (2n, T) right-padded, prompt lengths, served id lists)."""
    seqs, lens, served = [], [], []
    for a in sample:
        for text, resp in ((a.binary_prompt, a.response),
                           (a.confidence_prompt, a.confidence_response)):
            ids = tokenizer.encode(text, vocab)
            out = tokenizer.served_ids(resp)
            seqs.append(ids + out)
            lens.append(len(ids))
            served.append(out)
    width = max(len(s) for s in seqs)
    tokens = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    return tokens, lens, served


def answer_positions(lens: list, served: list) -> np.ndarray:
    """Per sequence the positions whose logits predict a served token
    (at least the first answer position), padded by repeating the last."""
    width = max(max(len(s) for s in served), 1)
    pos = np.zeros((len(lens), width), np.int32)
    for i, (n, s) in enumerate(zip(lens, served)):
        for j in range(width):
            pos[i, j] = n - 1 + min(j, max(len(s) - 1, 0))
    return pos


def reference_inputs(spec, seed: int, answers: list, n_sample: int) -> tuple:
    """(sample, tokens, served, positions): what the reference is run on."""
    sample = draw_sample(answers, n_sample, seed, spec.vocab)
    tokens, lens, served = pack(sample, spec.vocab)
    return sample, tokens, served, answer_positions(lens, served)


def gaps(sample: list, served: list, logits: np.ndarray, vocab: int) -> dict:
    """The two numbers, from reference logits (2n, P, vocab) at
    ``answer_positions``."""
    logits = np.asarray(logits, np.float64)
    first = logits[:, 0, :]
    logp = first - np.logaddexp.reduce(first, axis=-1, keepdims=True)
    worst_lp = 0.0
    for i, a in enumerate(sample):
        ref = logp[2 * i]
        pairs = [(tokenizer.word_id(t, vocab), math.log(p)) for t, p in
                 zip(a.targets, (a.token_1_prob, a.token_2_prob))]
        pairs += [(int(k), float(v)) for k, v in
                  json.loads(a.log_probabilities).items()]
        worst_lp = max([worst_lp] + [abs(v - ref[k]) for k, v in pairs])
    worst_tok, n_tokens = 0.0, 0
    for s, ids in enumerate(served):
        for j, t in enumerate(ids):
            row = logits[s, j]
            worst_tok = max(worst_tok, float(row.max() - row[t]))
            n_tokens += 1
    return {"logprob_gap": float(worst_lp), "token_gap": worst_tok,
            "served_tokens": n_tokens}


def control_gaps(logits_ref: np.ndarray, logits_low: np.ndarray,
                 served: list) -> dict:
    """The same two numbers for a control that does not decode: at every
    answer position, the log-probabilities the lower precision gives and
    the gap of the token it puts first."""
    ref = np.asarray(logits_ref, np.float64)
    low = np.asarray(logits_low, np.float64)
    lp_ref = ref[:, 0] - np.logaddexp.reduce(ref[:, 0], -1, keepdims=True)
    lp_low = low[:, 0] - np.logaddexp.reduce(low[:, 0], -1, keepdims=True)
    worst_lp = 0.0
    for s in range(0, ref.shape[0], 2):          # binary branches
        top = np.argsort(lp_low[s])[-20:]
        worst_lp = max(worst_lp, float(np.abs(lp_low[s, top]
                                              - lp_ref[s, top]).max()))
    worst_tok = 0.0
    for s, ids in enumerate(served):
        for j in range(max(len(ids), 1)):
            row = ref[s, j]
            worst_tok = max(worst_tok,
                            float(row.max() - row[low[s, j].argmax()]))
    return {"logprob_gap": worst_lp, "token_gap": worst_tok}


def readings(spec, ref, seed: int, answers: list, n_sample: int,
             controls: tuple = ()) -> dict:
    """The program's two numbers and, for each control precision, the
    control's, over ONE sample: what limits are set from (never run by
    the benchmark's own runs)."""
    sample, tokens, served, positions = reference_inputs(
        spec, seed, answers, n_sample)
    logits = np.asarray(ref.logits_at(spec, seed, tokens, positions))
    out = {"program": gaps(sample, served, logits, spec.vocab)}
    for precision in controls:
        low = np.asarray(ref.logits_at(spec, seed, tokens, positions,
                                       precision=precision))
        out[precision] = control_gaps(logits, low, served)
    return out


def check(spec, ref, seed: int, answers: list, n_sample: int, limits: dict,
          attempted: int, failed: int) -> tuple:
    """(correct, numbers) where numbers is ``{name: {"value", "limit"}}``
    in the order they are printed."""
    numbers = {"answers_missing": {"value": float(failed), "limit": 0.0}}
    if answers:
        sample, tokens, served, positions = reference_inputs(
            spec, seed, answers, n_sample)
        logits = ref.logits_at(spec, seed, tokens, positions)
        got = gaps(sample, served, np.asarray(logits), spec.vocab)
        for name in ("logprob_gap", "token_gap"):
            numbers[name] = {"value": got[name],
                             "limit": float(limits[name])}
        numbers["served_tokens_compared"] = {
            "value": float(got["served_tokens"]),
            "limit": float(limits["min_served_tokens"])}
    correct = bool(
        answers and attempted > 0
        and numbers["answers_missing"]["value"] <= 0.0
        and all(numbers[k]["value"] <= numbers[k]["limit"]
                for k in ("logprob_gap", "token_gap"))
        and numbers["served_tokens_compared"]["value"]
        >= numbers["served_tokens_compared"]["limit"])
    return correct, numbers
