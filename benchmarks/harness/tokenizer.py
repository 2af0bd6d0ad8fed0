"""The word tokenizer's rule, restated for the reference.

The program scores with ``backends.fake.FakeTokenizer(vocab)``: split on
whitespace, "Yes" -> 1, "No" -> 2, any other word ->
``3 + md5(word) % (vocab - 3)``; id 0 is padding and end of text, and it
prints id ``i`` as ``<i>``. The reference may import nothing of the
program, so the rule is written down again here; the comparison fails at
once if the two ever part (every logit would differ).
"""

from __future__ import annotations

import functools
import hashlib

PAD, YES, NO, RESERVED = 0, 1, 2, 3


@functools.lru_cache(maxsize=1 << 16)
def word_id(word: str, vocab: int) -> int:
    """A word's id; kept, since a 16,000-word row repeats a few thousand
    words and the window's rows are encoded again after it closes."""
    if word == "Yes":
        return YES
    if word == "No":
        return NO
    return RESERVED + int(hashlib.md5(word.encode()).hexdigest(), 16) % (
        vocab - RESERVED)


def encode(text: str, vocab: int) -> list:
    return [word_id(w, vocab) for w in text.split()]


def encode_pair(prompt, main: str, vocab: int) -> tuple:
    """(binary ids, confidence ids, tokens the two share at their head) of
    one grid cell or request."""
    b = encode(prompt.binary(main), vocab)
    c = encode(prompt.confidence(main), vocab)
    shared = 0
    while shared < min(len(b), len(c)) and b[shared] == c[shared]:
        shared += 1
    return b, c, shared


def served_ids(text: str) -> list:
    """The ids a decoded response stands for (it was cut at the first
    end-of-text id, and padding prints as nothing)."""
    out = []
    for piece in text.split():
        if piece == "Yes":
            out.append(YES)
        elif piece == "No":
            out.append(NO)
        else:
            out.append(int(piece[1:-1]))
    return out
