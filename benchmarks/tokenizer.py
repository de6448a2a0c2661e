"""The benchmark's own one-byte-one-token tokenizer.

A copy of the idea of the program's ``IdTokenizer`` (list the original
under Open questions): every byte of the prompt is one token, every
generated id renders as visible text so that a random-weights model
streams a token step per id.  ``eos_id`` lies outside the vocabulary, so no
request stops early and every completed request returns its whole budget.
"""

from __future__ import annotations

import re

_TOKEN = re.compile(r" t\d+")


class BenchTokenizer:
    pad_id, bos_id = 0, 1

    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size
        self.eos_id = vocab_size  # never an argmax: nothing stops early

    def encode(self, text: str) -> list[int]:
        return [3 + (b % 250) for b in text.encode("utf-8")]

    def decode(self, ids: list[int]) -> str:
        return "".join(f" t{i}" for i in ids)


def count_tokens(text: str) -> int:
    """How many generated ids a streamed text delta carries."""
    return len(_TOKEN.findall(text))
