"""Columnar tables over JAX arrays.

TPU-native layout: one contiguous ``jnp`` array per column plus an optional
validity bitmap (SQL NULL semantics).  This mirrors a column store
(paper §8.2.6) — set-oriented plans stream whole columns through the VPU/MXU
instead of interpreting rows.

Strings are dictionary-encoded (int32 codes into a host-side vocabulary),
which is both what real column stores do and the only sane representation on
a tensor machine.  Dates are int32 days since 1970-01-01 (civil-day math is
implemented in pure integer jnp so date intrinsics vectorize).
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Dictionary encoding for string columns
# ---------------------------------------------------------------------------


class DictEncoding:
    """A host-side vocabulary assigning int32 codes to strings."""

    def __init__(self, values: Sequence[str] = ()):
        self._to_code: dict[str, int] = {}
        self._from_code: list[str] = []
        for v in values:
            self.code(v)

    def code(self, value: str) -> int:
        c = self._to_code.get(value)
        if c is None:
            c = len(self._from_code)
            self._to_code[value] = c
            self._from_code.append(value)
        return c

    def lookup(self, value: str) -> int:
        """Code for ``value`` or -1 if absent (compares false against all)."""
        return self._to_code.get(value, -1)

    def decode(self, code: int) -> str:
        return self._from_code[int(code)]

    @property
    def vocab(self) -> tuple[str, ...]:
        """The code -> string table, in code order (round-trips the
        encoding: ``DictEncoding(enc.vocab)`` assigns identical codes)."""
        return tuple(self._from_code)

    def __len__(self) -> int:
        return len(self._from_code)

    def like_mask(self, pattern: str) -> np.ndarray:
        """Bool mask over the vocabulary for a SQL LIKE pattern.

        Supports ``%`` wildcards (prefix/suffix/contains).  Evaluated host-
        side once per query; on device LIKE becomes a gather into this mask
        (the TPU adaptation of string predicates).
        """
        import fnmatch

        pat = pattern.replace("%", "*")
        return np.array(
            [fnmatch.fnmatchcase(v, pat) for v in self._from_code], dtype=bool
        )


# ---------------------------------------------------------------------------
# Civil-date <-> day-number conversions (Howard Hinnant's algorithms),
# pure int32 arithmetic so they vectorize on the VPU.
# ---------------------------------------------------------------------------


def days_from_civil(y, m, d):
    """days since 1970-01-01 from (year, month, day); jnp-vectorized."""
    y = jnp.asarray(y, jnp.int32)
    m = jnp.asarray(m, jnp.int32)
    d = jnp.asarray(d, jnp.int32)
    y = y - (m <= 2).astype(jnp.int32)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = (m + 9) % 12
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def civil_from_days(z):
    """(year, month, day) from days since epoch; jnp-vectorized."""
    z = jnp.asarray(z, jnp.int32) + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = y + (m <= 2).astype(jnp.int32)
    return y, m, d


def date_add(part: str, n, days):
    """SQL DATEADD on day-number dates.  part in {dd, mm, yy}."""
    days = jnp.asarray(days, jnp.int32)
    n = jnp.asarray(n, jnp.int32)
    if part in ("dd", "day"):
        return days + n
    y, m, d = civil_from_days(days)
    if part in ("yy", "year"):
        return days_from_civil(y + n, m, d)
    if part in ("mm", "month"):
        tot = (y * 12 + (m - 1)) + n
        return days_from_civil(tot // 12, tot % 12 + 1, d)
    raise ValueError(f"unsupported DATEADD part {part!r}")


def date_part(part: str, days):
    """SQL DATEPART on day-number dates.  part in {yy, mm, dd, dw}."""
    y, m, d = civil_from_days(days)
    if part in ("yy", "year"):
        return y
    if part in ("mm", "month"):
        return m
    if part in ("dd", "day"):
        return d
    if part == "dw":  # 1=Sunday..7=Saturday (1970-01-01 was a Thursday)
        return (jnp.asarray(days, jnp.int32) + 4) % 7 + 1
    raise ValueError(f"unsupported DATEPART part {part!r}")


# ---------------------------------------------------------------------------
# Columns and Tables
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Column:
    """One column: data array + optional validity (True == non-NULL) +
    optional dictionary for string columns."""

    data: jnp.ndarray
    valid: jnp.ndarray | None = None  # None means all-valid
    dictionary: DictEncoding | None = None

    @property
    def dtype(self):
        return self.data.dtype

    def validity(self) -> jnp.ndarray:
        if self.valid is None:
            return jnp.ones(self.data.shape, dtype=bool)
        return self.valid


class Table:
    """An ordered mapping name -> Column with uniform row count."""

    def __init__(self, columns: Mapping[str, Column] | None = None):
        self.columns: dict[str, Column] = dict(columns or {})
        # per-column statistics (n_distinct, min, max) — populated by
        # compute_stats(); drives capacity hints in the query optimizer
        self.stats: dict[str, tuple[int, int, int]] = {}
        # names of the columns that hold a NULL, by compute_stats()
        self.nullable: set[str] = set()
        if self.columns:
            n = {int(c.data.shape[0]) for c in self.columns.values()}
            if len(n) != 1:
                raise ValueError(f"ragged table: row counts {n}")

    def compute_stats(self) -> "Table":
        """Host-side column statistics for integer/dictionary columns
        (the costing input the paper notes UDFs used to hide, §2.3), and
        which columns hold a NULL (their stats bound the values, not the
        NULLs)."""
        self.nullable = {name for name, c in self.columns.items()
                         if c.valid is not None
                         and not bool(np.asarray(c.valid).all())}
        for name, c in self.columns.items():
            if c.dictionary is not None:
                self.stats[name] = (len(c.dictionary), 0, len(c.dictionary) - 1)
            elif jnp.issubdtype(c.data.dtype, jnp.integer) and c.data.size:
                arr = np.asarray(c.data)
                self.stats[name] = (
                    int(len(np.unique(arr))), int(arr.min()), int(arr.max())
                )
        return self

    # -- constructors ------------------------------------------------------
    @staticmethod
    def from_arrays(**arrays) -> "Table":
        cols = {}
        for name, arr in arrays.items():
            if isinstance(arr, Column):
                cols[name] = arr
                continue
            a = np.asarray(arr)
            if a.dtype.kind in ("U", "S", "O"):  # strings -> dict encode
                enc = DictEncoding()
                codes = np.array([enc.code(str(v)) for v in a], dtype=np.int32)
                cols[name] = Column(jnp.asarray(codes), dictionary=enc)
            else:
                if a.dtype == np.float64:
                    a = a.astype(np.float32)
                if a.dtype == np.int64:
                    a = a.astype(np.int32)
                cols[name] = Column(jnp.asarray(a))
        return Table(cols)

    # -- basic ops ---------------------------------------------------------
    @property
    def num_rows(self) -> int:
        if not self.columns:
            return 1  # ConstantScan semantics: one row, no columns
        return int(next(iter(self.columns.values())).data.shape[0])

    def names(self) -> list[str]:
        return list(self.columns.keys())

    def column(self, name: str) -> Column:
        return self.columns[name]

    def with_column(self, name: str, col: Column) -> "Table":
        cols = dict(self.columns)
        cols[name] = col
        return Table(cols)

    def project(self, names: Sequence[str]) -> "Table":
        return Table({n: self.columns[n] for n in names})

    def rename(self, mapping: Mapping[str, str]) -> "Table":
        return Table({mapping.get(n, n): c for n, c in self.columns.items()})

    def gather(self, idx: jnp.ndarray, valid: jnp.ndarray | None = None) -> "Table":
        """Row gather; optionally invalidates rows where ``valid`` is False
        (used for outer-join null padding)."""
        cols = {}
        for n, c in self.columns.items():
            data = jnp.take(c.data, idx, axis=0, mode="clip")
            v = jnp.take(c.validity(), idx, axis=0, mode="clip")
            if valid is not None:
                v = v & valid
            cols[n] = Column(data, v, c.dictionary)
        return Table(cols)

    def to_numpy(self) -> dict[str, np.ndarray]:
        """Materialize to host, decoding dictionaries and masking NULLs
        (NULL floats become nan; NULL ints become the raw value — use
        ``valids``)."""
        out = {}
        for n, c in self.columns.items():
            arr = np.asarray(c.data)
            if c.dictionary is not None:
                arr = np.array([c.dictionary.decode(v) for v in arr], dtype=object)
            out[n] = arr
        return out

    def valids(self) -> dict[str, np.ndarray]:
        return {n: np.asarray(c.validity()) for n, c in self.columns.items()}

    def nbytes(self) -> int:
        tot = 0
        for c in self.columns.values():
            tot += c.data.size * c.data.dtype.itemsize
            if c.valid is not None:
                tot += c.valid.size
        return tot

    def __repr__(self) -> str:
        cols = ", ".join(f"{n}:{c.dtype}" for n, c in self.columns.items())
        return f"Table[{self.num_rows} rows]({cols})"
