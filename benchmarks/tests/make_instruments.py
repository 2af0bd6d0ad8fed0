#!/usr/bin/env python3
"""Write ``traffic/legal_instruments.json``: the five legal prompts of
``traffic/legal_prompts.json``, each ``main`` an instrument of 16,000 words
(the policy or agreement the question is about, attached whole) followed by
the prompt's own question. Run once; the file is committed.

    python3 benchmarks/tests/make_instruments.py

The instruments are made from a seed, from a vocabulary of 2,400 distinct
words (240 legal words and 2,160 pronounceable made-up terms, as a long
instrument's defined terms and names are), in numbered sections of
sentences, each instrument on another stream of the seed: pooled keys
differ from block to block and no two instruments share a passage.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]          # benchmarks/
SEED = 31
WORDS = 16000

LEGAL = """agreement policy insured insurer coverage exclusion endorsement
premium deductible claim loss damage peril flood water surface overflow
levee failure negligence liability indemnify indemnity warranty covenant
condition precedent subsequent breach remedy damages liquidated consequential
incidental notice written party parties hereto herein hereof thereof
whereas therefore provided however notwithstanding foregoing pursuant
subject section clause schedule exhibit annex appendix term termination
renewal effective date period limit sublimit aggregate occurrence accident
property premises building contents inventory equipment vehicle tenant
landlord lessee lessor lease rent deposit assignment sublease consent
approval waiver amendment entire severability governing law jurisdiction
venue arbitration mediation dispute resolution confidential disclosure
proprietary license grant royalty intellectual trademark copyright patent
employee employer contractor agent principal fiduciary duty care good faith
reasonable material adverse change force majeure act god war terrorism
riot strike pandemic government order regulation statute ordinance code
compliance audit inspection records books accounts payment invoice interest
late fee tax withholding currency dollars sum amount value replacement cost
actual cash depreciation appraisal umpire salvage subrogation contribution
other insurance excess primary pro rata cancellation nonrenewal misstatement
concealment fraud representation application declarations named additional
mortgagee payee beneficiary trustee estate heirs successors assigns
affiliate subsidiary parent control ownership merger acquisition sale
transfer purchase price closing escrow title deed lien encumbrance easement
survey zoning permit occupancy repair maintain restore rebuild debris
removal pollution contamination mold fungus wear tear deterioration latent
defect vermin settling cracking earth movement earthquake windstorm hail
fire lightning explosion smoke theft vandalism collapse weight ice snow""".split()

ONSETS = "b c d f g h j k l m n p r s t v w z br cr dr fr gr pr st tr".split()
VOWELS = "a e i o u ai ea ou".split()
CODAS = ["", "n", "r", "l", "s", "t", "m", "x"]


def vocabulary(rng) -> list:
    made = set()
    while len(made) < 2160:
        n = int(rng.integers(2, 4))
        made.add("".join(rng.choice(ONSETS) + rng.choice(VOWELS)
                         + rng.choice(CODAS) for _ in range(n)))
    words = sorted(set(LEGAL)) + sorted(made)
    assert len(set(words)) >= 2000, len(set(words))
    return words


def instrument(rng, words: list, n_words: int) -> list:
    out, section = [], 0
    # Each section leans on its own few dozen terms, as a definitions
    # clause or a schedule does.
    while len(out) < n_words:
        section += 1
        terms = list(rng.choice(words, 48, replace=False))
        out += ["Section", f"{section}."]
        for _ in range(int(rng.integers(6, 14))):
            length = int(rng.integers(9, 28))
            sent = [str(rng.choice(terms)) if rng.random() < 0.6
                    else str(rng.choice(words)) for _ in range(length)]
            sent[0] = sent[0].capitalize()
            sent[-1] += rng.choice([".", ".", ";", ":"])
            out += sent
    return out[:n_words]


def main() -> None:
    prompts = json.loads((ROOT / "traffic" / "legal_prompts.json").read_text())
    words = vocabulary(np.random.default_rng([SEED, 0]))
    rows = []
    for i, p in enumerate(prompts):
        body = instrument(np.random.default_rng([SEED, 1 + i]), words, WORDS)
        assert len(body) == WORDS
        rows.append(dict(p, main=" ".join(body) + " " + p["main"]))
    text = json.dumps(rows, indent=0, ensure_ascii=True)
    (ROOT / "traffic" / "legal_instruments.json").write_text(text + "\n")
    distinct = len({w for r in rows for w in r["main"].split()})
    print(f"{len(rows)} instruments of {WORDS} words, {distinct} distinct "
          f"words, {len(text)} bytes")


if __name__ == "__main__":
    main()
