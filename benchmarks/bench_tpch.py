"""Figure 9: TPC-H queries rewritten with scalar UDFs (paper §8.2.4/§11).

For each query: (a) original (no UDFs), (b) rewritten with UDFs, froid OFF
(natively-compiled iterative — the *faster* baseline), (c) froid ON.
Correctness cross-check: (a) == (c) within float tolerance.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, time_run
from benchmarks.tpch_udfs import QUERIES, register_udfs
from repro.core import FROID, HEKATON, INTERPRETED, Session
from repro.data.tpch import generate_tpch

SF = 0.02  # 120k lineitems (CPU-scale)


def _results_match(db, qa, qb) -> bool:
    """Whether two queries' results agree; a failure while comparing
    raises instead of reading as a mismatch."""
    ra = db.execute(qa, FROID).table
    rb = db.execute(qb, FROID).table
    for name in ra.names():
        if name not in rb.columns:
            continue
        a = np.asarray(ra.columns[name].data, np.float64)
        b = np.asarray(rb.columns[name].data, np.float64)
        if a.shape != b.shape or not np.allclose(a, b, rtol=2e-3, atol=1e-2):
            return False
    return True


def run(quick: bool = False, sf: float = SF):
    db = Session()
    generate_tpch(db, sf=sf)
    register_udfs(db)
    names = list(QUERIES)[:3] if quick else list(QUERIES)
    for name in names:
        q_udf, q_orig = QUERIES[name]
        qu, qo = q_udf(), q_orig()

        fn_orig = db.prepare(qo, FROID)
        t_orig = time_run(fn_orig)
        emit(f"fig9/{name}/original", t_orig * 1e6, "")

        fn_on = db.prepare(qu, FROID)
        t_on = time_run(fn_on)
        ok = _results_match(db, qu, qo)
        emit(f"fig9/{name}/udf_froid_on", t_on * 1e6,
             f"vs_orig={t_on/t_orig:.2f}x match={ok}")

        fn_off = db.prepare(qu, HEKATON)
        t_off = time_run(fn_off, warmup=1, iters=1)
        emit(f"fig9/{name}/udf_froid_off_native", t_off * 1e6,
             f"slowdown_vs_on={t_off/t_on:.1f}x")

        # interpreted mode (the paper's actual baseline): measure per-row
        # cost on a subset, extrapolate to the full cardinality
        sub = _subset_db(db, rows=300)
        register_udfs(sub)
        r = sub.execute(qu, INTERPRETED)
        n_sub = sub.catalog["lineitem"].num_rows
        n_full = db.catalog["lineitem"].num_rows
        t_interp = r.elapsed_s * n_full / n_sub
        emit(f"fig9/{name}/udf_froid_off_interpreted", t_interp * 1e6,
             f"extrapolated_from_{n_sub}_rows slowdown_vs_on={t_interp/t_on:.0f}x")


def _subset_db(db: Session, rows: int) -> Session:
    """Copy of the db with lineitem truncated (for interpreted-mode cost)."""
    import jax.numpy as jnp

    from repro.tables.table import Column, Table

    sub = Session()
    for name, t in db.catalog.items():
        if name == "lineitem":
            cols = {
                n: Column(c.data[:rows], None if c.valid is None else c.valid[:rows],
                          c.dictionary)
                for n, c in t.columns.items()
            }
            sub.catalog[name] = Table(cols)
        else:
            sub.catalog[name] = t
    return sub


if __name__ == "__main__":
    run()
