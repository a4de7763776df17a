"""Correlated subqueries: the per-row apply vs the decorrelation rewrite.

The PR-10 optimizer pass's set-oriented argument: a correlated scalar
aggregate naively re-runs its body once per outer row — N scans of the
fact table per call — while the rewritten plan materializes ONE keyed
``GroupAgg`` build (one fact scan, d = distinct-binding rows out) and
left-joins it back.  The margin is algorithmic (N × body vs body + join),
like the cursor-loop gate, not parallelism-bound.

    PYTHONPATH=src python -m benchmarks.bench_decorrelate [--quick]

Rows:
    decorr/perrow_interp/<N>  — per-row apply through the interpreter
                                Executor (the oracle's reference arm)
    decorr/perrow/<N>         — per-row apply COMPILED (decorrelation
                                rules disabled, everything else identical:
                                same session path, same vmapped program) —
                                the strongest honest baseline
    decorr/decorrelated/<N>   — the rewritten keyed-build plan, FROID

``derived`` on the decorrelated rows carries speedup vs the compiled
per-row arm plus the rewrite evidence (builds/joins in the plan, the
distinct-binding pool size d) — the CI decorr gate reads the N=1024 row
and requires >= 10x.  Element-wise parity across all three arms —
including a parameter set that empties every group (NULL semantics) — is
asserted before timing.
"""
from __future__ import annotations

import os
import time
from contextlib import contextmanager

import numpy as np

from benchmarks.common import emit
from repro.core import (FROID, Session, col, param, scalar_subquery, scan,
                        sum_)
from repro.core import optimizer as O
from repro.core import relalg as R
from repro.core import scalar as S
from repro.core.executor import Executor
from repro.core.session import _param_value

M_FACTS = 16384
DOMAIN = 7          # the small distinct-binding pool: d = 7 groups
SWEEP = (32, 1024)  # outer-key cardinalities; the CI gate reads 1024
#: parameter sets for parity: mid cut, empty cut (qty < 9 everywhere, so
#: minq=9 empties every group -> NULL totals), permissive cut
PARITY_PARAMS = ({"minq": 4}, {"minq": 9}, {"minq": 0})

#: the optimizer stack with ONLY the decorrelation rules removed — the
#: honest per-row arm (what every call paid before the rewrite existed)
PER_ROW_RULES = tuple(r for r in O.DEFAULT_RULES
                      if r not in (O.decorrelate_in_computes,
                                   O.decorrelate_filters))


@contextmanager
def per_row_optimizer():
    """Compile through the Session with decorrelation disabled."""
    orig = O.optimize

    def patched(plan, catalog=None, required=None, rules=None,
                max_passes=12):
        return orig(plan, catalog, required=required,
                    rules=PER_ROW_RULES, max_passes=max_passes)

    O.optimize = patched
    try:
        yield
    finally:
        O.optimize = orig


def create_tables(db, n_keys: int, facts: str = "facts",
                  keys: str = "keys", seed: int = 0) -> None:
    """The fact table (M_FACTS rows over DOMAIN keys) and the ``n_keys``
    outer rows, under the given table names."""
    rng = np.random.default_rng(seed)
    db.create_table(
        facts,
        fk=rng.integers(0, DOMAIN, M_FACTS),
        val=rng.normal(size=M_FACTS).astype(np.float32),
        qty=rng.integers(0, 9, M_FACTS),
    )
    db.create_table(keys, k=np.arange(n_keys) % DOMAIN)


def _setup(n_keys: int) -> Session:
    db = Session()
    create_tables(db, n_keys)
    return db


def query(facts: str = "facts", keys: str = "keys"):
    """Per outer key: SUM(val) of its facts with qty >= @minq."""
    body = (scan(facts)
            .filter((col("fk") == S.Outer("k"))
                    & (col("qty") >= param("minq")))
            .agg(total=sum_(col("val"))))
    return (scan(keys).compute(total=scalar_subquery(body, "total"))
            .project("k", "total"))


def _has_corr(plan) -> bool:
    for n in R.walk_plan_deep(plan):
        for e in n.exprs():
            for s in S.walk(e):
                if isinstance(s, (S.ScalarSubquery, S.Exists)):
                    from repro.core.executor import _plan_outer_refs
                    if _plan_outer_refs(s.plan):
                        return True
    return False


def _col(mt, name):
    c = mt.table.columns[name]
    return (np.asarray(c.data),
            np.asarray(c.valid) & np.asarray(mt.mask))


def _check_parity(dec_stmt, row_stmt, interp_plan, catalog):
    ex = Executor(catalog)
    for p in PARITY_PARAMS:
        dv, dm = _col(dec_stmt.execute(params=dict(p)).masked, "total")
        rv, rm = _col(row_stmt.execute(params=dict(p)).masked, "total")
        iv, im = _col(ex.execute(
            interp_plan,
            params={n: _param_value(v) for n, v in p.items()}), "total")
        np.testing.assert_array_equal(dm, rm)
        np.testing.assert_array_equal(dm, im)
        np.testing.assert_allclose(np.where(dm, dv, 0.0),
                                   np.where(rm, rv, 0.0),
                                   rtol=2e-3, atol=1e-3)
        np.testing.assert_allclose(np.where(dm, dv, 0.0),
                                   np.where(im, iv, 0.0),
                                   rtol=2e-3, atol=1e-3)


def _time_calls(stmt, iters: int) -> float:
    """Warm median us/call cycling the parity parameter sets."""
    stmt.execute(params=dict(PARITY_PARAMS[0]))  # pay compile per bucket
    stmt.execute(params=dict(PARITY_PARAMS[1]))
    samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(iters):
            stmt.execute(params=dict(PARITY_PARAMS[i % 2]))
        samples.append((time.perf_counter() - t0) / iters)
    return float(np.median(samples)) * 1e6


def run(quick: bool = False):
    iters = 3 if quick else 10
    cpus = os.cpu_count() or 1

    for n in SWEEP:
        db = _setup(n)
        q = query()
        dec_stmt = db.prepare(q, FROID)
        assert not _has_corr(dec_stmt.plan), "rewrite did not fire"
        builds = sum(1 for nd in R.walk_plan(dec_stmt.plan)
                     if isinstance(nd, R.GroupAgg) and nd.keys)

        with per_row_optimizer():
            db_row = _setup(n)
            row_stmt = db_row.prepare(query(), FROID)
        assert _has_corr(row_stmt.plan), "per-row arm was rewritten"

        node = q.node
        wanted = set(R.output_columns(node, db.catalog))
        interp_plan = O.optimize(node, db.catalog, required=wanted,
                                 rules=PER_ROW_RULES)

        # parity across all three arms first (also pays every warm-up)
        _check_parity(dec_stmt, row_stmt, interp_plan, db.catalog)

        pv = {k: _param_value(v) for k, v in PARITY_PARAMS[0].items()}
        ex = Executor(db.catalog)
        ex.execute(interp_plan, params=dict(pv))
        t0 = time.perf_counter()
        ex.execute(interp_plan, params=dict(pv))
        t_interp = (time.perf_counter() - t0) * 1e6
        emit(f"decorr/perrow_interp/{n}", t_interp,
             f"interpreter per-row apply, {M_FACTS}-row body")

        t_row = _time_calls(row_stmt, iters)
        emit(f"decorr/perrow/{n}", t_row,
             f"compiled per-row apply ({n}x{M_FACTS} work)")

        t_dec = _time_calls(dec_stmt, iters)
        emit(
            f"decorr/decorrelated/{n}", t_dec,
            f"speedup={t_row / t_dec:.1f}x interp_speedup="
            f"{t_interp / t_dec:.1f}x builds={builds} d={DOMAIN} "
            f"host_cpus={cpus} decorrelated=True parity=ok",
        )


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    run(quick=args.quick)
