"""Plain references and the comparison that decides ``correct``.

A reference is straightforward numpy over the benchmark's own generated
host arrays; it imports nothing of the engine.  Every statement's
reference returns an :class:`Answer`: the selected rows of each output
column, their NULL flags and, for float columns, the magnitude of the
terms behind each value (the sum of their absolute values), against which
a float gap is measured.  The engine's output is brought into the same
form by :func:`from_output`, and a :class:`Tally` reads two numbers off
pairs of answers:

* ``gap``: the widest ``|got - ref| / magnitude`` over all float values;
* ``mismatches``: rows, keys, integer or string values, NULL flags or
  row order that differ exactly.
"""
from __future__ import annotations

import dataclasses
import math

import ml_dtypes
import numpy as np


@dataclasses.dataclass(frozen=True)
class Prec:
    """The precision a reference computes float columns in: their storage
    and every element-wise operation.  Sums accumulate in float64 in both
    precisions and their results are rounded to ``dtype``, so the control
    (``BF16``) loses only what bfloat16 values and results lose."""

    name: str
    dtype: np.dtype

    def f(self, x) -> np.ndarray:
        """An input (array or scalar) in this precision."""
        return np.asarray(x).astype(self.dtype)

    def out(self, x) -> np.ndarray:
        """A result rounded to this precision, as float64."""
        return np.asarray(x, np.float64).astype(self.dtype).astype(np.float64)


F64 = Prec("float64", np.dtype(np.float64))
BF16 = Prec("bfloat16", np.dtype(ml_dtypes.bfloat16))


@dataclasses.dataclass
class Answer:
    """One call's answer: selected rows only, strings decoded."""

    cols: dict
    valid: dict
    mag: dict = dataclasses.field(default_factory=dict)
    keys: tuple = ()
    order: tuple = ()   # ((column, ascending), ...) the rows must follow
    limit: int | None = None  # rows kept of ``cols`` (a reference holds all)

    @property
    def n(self) -> int:
        return len(next(iter(self.cols.values()))) if self.cols else 0


def answer(cols: dict, mag: dict | None = None, valid: dict | None = None,
           **kw) -> Answer:
    cols = {k: np.asarray(v) for k, v in cols.items()}
    valid = {k: np.asarray(valid[k], bool) if valid and k in valid
             else np.ones(len(v), bool) for k, v in cols.items()}
    return Answer(cols, valid, {k: np.asarray(v, np.float64)
                                for k, v in (mag or {}).items()}, **kw)


def from_output(mask, cols: dict, vocabs: dict) -> Answer:
    """The engine's fetched output (``mask``, ``{name: (data, valid)}``,
    ``{name: vocabulary or None}``) as an :class:`Answer`."""
    m = np.asarray(mask, bool)
    out, valid = {}, {}
    for name, (data, v) in cols.items():
        d = np.asarray(data)[m]
        if vocabs.get(name) is not None:
            d = np.asarray(vocabs[name], dtype=object)[d]
        out[name] = d
        valid[name] = np.asarray(v, bool)[m]
    return Answer(out, valid)


def _cell(a: Answer, name: str, i: int):
    return a.cols[name][i] if a.valid[name][i] else None


def _row_key(a: Answer, i: int, names) -> tuple:
    return tuple((v is None, v if v is not None else 0)
                 for v in (_cell(a, n, i) for n in names))


def _out_of_order(a: Answer, i: int, order) -> bool:
    """Whether row ``i`` must come after row ``i + 1`` (NULLs sort last)."""
    for name, asc in order:
        x, y = _cell(a, name, i), _cell(a, name, i + 1)
        if x == y:
            continue
        if x is None or y is None:
            return x is None
        return bool(x > y) if asc else bool(x < y)
    return False


def _order_violations(a: Answer, order) -> int:
    return sum(_out_of_order(a, i, order) for i in range(a.n - 1))


def served(a: Answer) -> Answer:
    """A reference answer as a query serves it: rows in its order, cut to
    its limit (how the control stands in for the engine)."""
    def rank(i):
        return tuple((v is None, 0 if v is None else (v if asc else -v))
                     for v, asc in ((_cell(a, n, i), asc)
                                    for n, asc in a.order))

    rows = sorted(range(a.n), key=rank)[:a.limit]
    pick = np.asarray(rows, dtype=np.int64)
    return Answer({k: v[pick] for k, v in a.cols.items()},
                  {k: v[pick] for k, v in a.valid.items()},
                  {k: v[pick] for k, v in a.mag.items()}, a.keys)


class Tally:
    """Running ``gap`` and ``mismatches`` over many compared answers."""

    def __init__(self):
        self.gap = 0.0
        self.mismatches = 0
        self.compared = 0
        self.by_label: dict = {}   # label -> (widest gap, mismatches)

    def value(self, got, ref, mag) -> None:
        if not np.isfinite(got):
            self.mismatches += 1
            return
        err = abs(float(got) - float(ref))
        if err:
            self.gap = max(self.gap, err / max(float(mag), 1e-30))

    def row(self, got: Answer, i: int, ref: Answer, j: int) -> None:
        for name in ref.cols:
            gv, rv = got.valid[name][i], ref.valid[name][j]
            if gv != rv:
                self.mismatches += 1
            elif not gv:
                continue
            elif name in ref.mag:
                self.value(got.cols[name][i], ref.cols[name][j],
                           ref.mag[name][j])
            elif got.cols[name][i] != ref.cols[name][j]:
                self.mismatches += 1

    def add(self, got: Answer, ref: Answer, label: str = "") -> None:
        """Compare one answer with its reference; ``label`` (a statement's
        name) keeps its own widest gap and mismatch count."""
        gap, bad = self.gap, self.mismatches
        self.gap, self.mismatches = 0.0, 0
        try:
            self._add(got, ref)
        finally:
            g, b = self.by_label.get(label, (0.0, 0))
            self.by_label[label] = (max(g, self.gap), b + self.mismatches)
            self.gap, self.mismatches = max(gap, self.gap), bad + self.mismatches

    def _add(self, got: Answer, ref: Answer) -> None:
        self.compared += 1
        if set(got.cols) != set(ref.cols):
            self.mismatches += 1
            return
        self.mismatches += _order_violations(got, ref.order)
        want = ref.n if ref.limit is None else min(ref.limit, ref.n)
        if got.n != want:
            self.mismatches += abs(got.n - want)
            return
        if ref.limit is not None:
            self._add_limited(got, ref)
            return
        # same multiset of rows: sort both by the keys, then by the values
        names = list(ref.keys) + [c for c in ref.cols if c not in ref.keys]
        gi = sorted(range(got.n), key=lambda i: _row_key(got, i, names))
        ri = sorted(range(ref.n), key=lambda i: _row_key(ref, i, names))
        for i, j in zip(gi, ri):
            self.row(got, i, ref, j)

    def _add_limited(self, got: Answer, ref: Answer) -> None:
        """Top-``limit`` rows by one sort key: each row kept must be a
        reference row with the same values, and a row left out may beat
        the least row kept only by a float gap, which counts as one."""
        index = {_row_key(ref, j, ref.keys): j for j in range(ref.n)}
        kept = []
        for i in range(got.n):
            j = index.get(_row_key(got, i, ref.keys))
            if j is None:
                self.mismatches += 1
                continue
            kept.append(j)
            self.row(got, i, ref, j)
        (col, asc), = ref.order
        if not kept or col not in ref.mag:
            return
        vals = ref.cols[col]
        sign = 1.0 if asc else -1.0
        worst = max(sign * vals[j] for j in kept)
        out = np.ones(ref.n, bool)
        out[kept] = False
        beat = out & (sign * vals < worst)
        for j in np.flatnonzero(beat):
            self.gap = max(self.gap, (worst - sign * vals[j])
                           / max(ref.mag[col][j], 1e-30))

    def checks(self, limits: dict) -> dict:
        """Each number compared beside its limit."""
        return {"gap": {"value": self.gap, "limit": limits["gap"]},
                "mismatches": {"value": self.mismatches,
                               "limit": limits["mismatches"]}}


def within(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] and not math.isnan(c["value"])
               for c in checks.values())


def gsum(values, groups, n: int) -> np.ndarray:
    """Grouped sum, accumulated in float64."""
    return np.bincount(groups, weights=np.asarray(values, np.float64),
                       minlength=n)
