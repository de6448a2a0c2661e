"""Tokenizer seam: HF tokenizers when a checkpoint directory is given, a
dependency-free byte tokenizer otherwise (tests / zero-weights smoke runs).
"""

from __future__ import annotations

from typing import Protocol


class Tokenizer(Protocol):
    bos_id: int
    eos_id: int
    pad_id: int

    def encode(self, text: str) -> list[int]: ...

    def decode(self, ids: list[int]) -> str: ...

    @property
    def vocab_size(self) -> int: ...


class ByteTokenizer:
    """UTF-8 bytes + 3 specials: deterministic, vocab 259, no deps."""

    pad_id = 0
    bos_id = 1
    eos_id = 2
    _OFFSET = 3

    @property
    def vocab_size(self) -> int:
        return 256 + self._OFFSET

    def encode(self, text: str) -> list[int]:
        return [b + self._OFFSET for b in text.encode("utf-8")]

    def decode(self, ids: list[int]) -> str:
        # ids beyond the byte range (a model vocab can be larger) are dropped
        data = bytes(
            i - self._OFFSET
            for i in ids
            if self._OFFSET <= i < 256 + self._OFFSET
        )
        return data.decode("utf-8", errors="replace")


class IdTokenizer:
    """Renders EVERY generated id as visible text — for random-weights runs
    (``chip_smoke.py``, ``benchmarks/run.py``): such a model emits mostly ids the
    byte tokenizer drops, the decoded text comes out empty, and no token
    step is ever streamed."""

    pad_id, bos_id, eos_id = 0, 1, 2

    def __init__(self, vocab_size: int = 32000):
        self.vocab_size = vocab_size

    def encode(self, text: str) -> list[int]:
        return [3 + (b % 250) for b in text.encode("utf-8")]

    def decode(self, ids: list[int]) -> str:
        return "".join(f" t{i}" for i in ids)


class HFTokenizer:
    """transformers AutoTokenizer over a LOCAL directory (zero egress)."""

    def __init__(self, path: str):
        from transformers import AutoTokenizer  # lazy: heavyweight import

        self._tok = AutoTokenizer.from_pretrained(path, local_files_only=True)
        # id 0 is a legitimate special-token id — never `or` these
        def _id(value: int | None, default: int) -> int:
            return value if value is not None else default

        self.bos_id = _id(self._tok.bos_token_id, 1)
        self.eos_id = _id(self._tok.eos_token_id, 2)
        self.pad_id = _id(self._tok.pad_token_id, 0)

    @property
    def vocab_size(self) -> int:
        return len(self._tok)

    def encode(self, text: str) -> list[int]:
        return self._tok.encode(text, add_special_tokens=False)

    def decode(self, ids: list[int]) -> str:
        return self._tok.decode(ids, skip_special_tokens=True)
