"""Generated tables as host arrays: what the engine loads and what the plain
references read."""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Data:
    """``tables[table][column]``: a numpy array (int32 codes for strings);
    ``vocab[table][column]``: the sorted vocabulary of a string column, so
    that code order is string order."""

    tables: dict = dataclasses.field(default_factory=dict)
    vocab: dict = dataclasses.field(default_factory=dict)

    def add(self, table: str, **cols) -> None:
        """Add a table.  A column given as ``(codes, vocabulary)`` is a
        string column; any other is stored as int32 or float32."""
        out, voc = {}, {}
        for name, c in cols.items():
            if isinstance(c, tuple):
                codes, words = c
                if list(words) != sorted(words):
                    raise ValueError(f"{table}.{name}: vocabulary not sorted")
                out[name] = np.asarray(codes, np.int32)
                voc[name] = tuple(words)
                continue
            a = np.asarray(c)
            out[name] = a.astype(np.float32 if a.dtype.kind == "f"
                                 else np.int32)
        self.tables[table] = out
        self.vocab[table] = voc

    def rows(self, table: str) -> int:
        return len(next(iter(self.tables[table].values())))

    def row(self, table: str, key: str, values) -> np.ndarray:
        """The row of each of ``values`` in ``table``, whose ``key`` column
        is sorted."""
        return np.searchsorted(self.tables[table][key], values)

    def code(self, table: str, col: str, word: str) -> int:
        """The code of ``word`` in a string column (-1 where absent)."""
        words = self.vocab[table][col]
        return words.index(word) if word in words else -1

    def codes(self, table: str, col: str, words) -> np.ndarray:
        return np.array([self.code(table, col, w) for w in words], np.int32)

    def words(self, table: str, col: str) -> np.ndarray:
        """The column decoded to strings."""
        return np.asarray(self.vocab[table][col], dtype=object)[
            self.tables[table][col]]


def pick(rng, words, n: int):
    """``n`` strings drawn uniformly from ``words``, as (codes, sorted
    vocabulary)."""
    vocab = sorted(words)
    return rng.integers(0, len(vocab), n, dtype=np.int32), tuple(vocab)
