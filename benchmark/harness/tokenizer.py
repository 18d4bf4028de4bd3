"""The yardstick's tokenizer: every token id is visible text.

A copy of ``prime_tpu/loadgen/backends.py`` ``NumericTokenizer`` (ids travel as
decimal words, so SSE deltas carry the ids the engine emitted and the client can
count and return them), plus a log of what it encoded: the benchmark, not the
program, then knows the exact prompt ids of every request, role words included.
"""

from __future__ import annotations

import threading


class NumericTokenizer:
    eos_id = 2
    pad_id = 0

    def __init__(self, eos_id: int = 2, pad_id: int = 0) -> None:
        self.eos_id, self.pad_id = eos_id, pad_id
        self._lock = threading.Lock()
        self.encoded: dict[tuple[int, ...], list[int]] = {}

    def encode(self, text: str, add_special_tokens: bool = True) -> list[int]:
        words = text.split()
        ids = [int(w) if w.isdigit() else (sum(w.encode()) % 97) + 3 for w in words]
        key = tuple(int(w) for w in words if w.isdigit())
        with self._lock:
            self.encoded[key] = ids
        return ids

    def decode(self, ids: list[int]) -> str:
        return " ".join(str(i) for i in ids)

    def prompt_ids_for(self, numeric_ids) -> list[int] | None:
        """The full prompt (role words included) whose numeric words were
        ``numeric_ids``, as the server rendered and this tokenizer encoded it."""
        with self._lock:
            return self.encoded.get(tuple(numeric_ids))
