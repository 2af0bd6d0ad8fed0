"""Fake inference backend for hermetic tests (SURVEY.md §4).

The reference has no test suite; its committed CSVs double as golden outputs.
Our upgrade: a deterministic tokenizer + tiny-model stand-in so the engine
(L2) and stats (L4) layers are testable with zero network, zero weights, and
zero TPU time. The FakeTokenizer implements exactly the slice of the HF
tokenizer protocol the engine touches (``__call__ -> .input_ids``,
``decode``, ``pad_token_id``).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import List, Sequence


@dataclasses.dataclass
class _Encoding:
    input_ids: List[int]


class FakeTokenizer:
    """Whitespace word tokenizer with a stable hashed vocab.

    Ids are stable across runs/processes (md5, not Python hash). ' Yes' and
    ' No' map to dedicated reserved ids so yes/no readout tests are exact.

    ``vocab`` MUST cover the model config it is paired with
    (``vocab <= cfg.vocab_size``): an out-of-vocab id reads an
    out-of-range embedding row, whose NaN readouts the numerics guard
    quarantines as error:numerics (the historical
    __graft_entry__.dryrun_multichip harness bug — default 1000 vs the
    tiny flagship's 512). Pass ``vocab=cfg.vocab_size`` whenever the
    model's vocab is smaller than the default.
    """

    VOCAB = 1000
    PAD, YES, NO = 0, 1, 2
    _RESERVED = 3

    pad_token_id = PAD
    eos_token_id = PAD

    def __init__(self, vocab: int = VOCAB):
        if vocab <= self._RESERVED:
            raise ValueError(f"FakeTokenizer vocab {vocab} leaves no room "
                             f"past the {self._RESERVED} reserved ids")
        self.VOCAB = int(vocab)   # instance override; class default kept
        # word -> id, filled as words are met: a sweep tokenizes the same
        # few thousand words half a million times, and the md5 below was
        # two thirds of its plan stage (PERF.md §6, PR 26).
        self._ids = {"Yes": self.YES, "No": self.NO}

    def _word_id(self, w: str) -> int:
        wid = self._ids.get(w)
        if wid is None:
            h = int(hashlib.md5(w.encode()).hexdigest(), 16)
            wid = self._ids[w] = (self._RESERVED
                                  + h % (self.VOCAB - self._RESERVED))
        return wid

    def __call__(self, text: str, add_special_tokens: bool = True) -> _Encoding:
        return _Encoding([self._word_id(w) for w in text.split()])

    def decode(self, ids: Sequence[int], skip_special_tokens: bool = True) -> str:
        out = []
        for i in ids:
            i = int(i)
            if i == self.YES:
                out.append("Yes")
            elif i == self.NO:
                out.append("No")
            elif i != self.PAD or not skip_special_tokens:
                out.append(f"<{i}>")
        return " ".join(out)

    def __len__(self) -> int:
        return self.VOCAB
