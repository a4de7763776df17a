"""Cursor-loop UDFs: per-row interpreted loops vs the Aggify rewrite.

The loop-to-scan rewrite's set-oriented argument (ISSUE-6): a cursor loop
interpreted per invocation walks its cursor relation row by row on the
host — one Python-dispatched step per row per invocation — while the
rewritten plan runs the whole loop as ONE relational operator
(:class:`repro.core.relalg.LoopScan`, a predicated ``lax.scan`` over the
cursor relation) inside the inlined, vmapped, batched device program.

    PYTHONPATH=src python -m benchmarks.bench_cursor_loops [--quick]

Rows:
    cursorloop/interp/<I>         — INTERPRETED serial loop (per-row host
                                    interpretation of the cursor loop)
    cursorloop/rewrite/32         — FROID execute_many, 32 tickets
    cursorloop/rewrite_many/1024  — FROID execute_many, 1024 tickets

``derived`` on the rewrite rows carries speedup vs the interpreted arm
(us/call over us/ticket) plus the verdict kind and host CPU count — the
CI cursorloop gate reads the N=1024 row and requires >= 20x.  The margin
is algorithmic (per-row host stepping vs one device scan), not
parallelism, so the bar holds on small hosts too.  Element-wise identity
between the interpreted and rewritten arms is asserted before timing.
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchmarks.common import emit
from repro.core import (
    FROID,
    INTERPRETED,
    CursorLoop,
    Session,
    UdfBuilder,
    col,
    lit,
    param,
    scan,
    udf,
    var,
)
from repro.loops import classify

M_FACTS = 256
M_FACTS_QUICK = 96
N_KEYS = 4
#: interpreted serial calls (each call interprets N_KEYS cursor loops)
INTERP_N = 8
INTERP_N_QUICK = 4
# quick mode keeps the full ticket sweep — the CI gate reads the 1024 row
SWEEP = (32, 1024)


def create_tables(db, m: int = M_FACTS, facts: str = "facts",
                  keys: str = "keys", fn: str = "floop", seed: int = 0) -> None:
    """The cursor's fact table (``m`` rows), the N_KEYS outer rows and the
    cursor-loop UDF ``fn`` over ``facts``, under the given names."""
    rng = np.random.default_rng(seed)
    db.create_table(
        facts,
        fk=rng.integers(0, 8, m),
        val=np.round(rng.uniform(-10, 10, m), 2).astype(np.float32),
        qty=rng.integers(0, 9, m),
    )
    db.create_table(keys, k=np.arange(N_KEYS))
    # order-dependent running fold with an early-exit BREAK: scan-kind
    # lowering (a predicated lax.scan), the rewrite's hardest shape
    u = UdfBuilder(fn, [("x", "float32")], "float32")
    u.declare("t", "float32", lit(0.0))
    u.declare("v", "float32", None)
    with u.cursor_loop({"v": "val"}, scan(facts),
                       where=col("fk") <= param("x")):
        u.set("t", var("t") * 0.5 + var("v"))
        with u.if_(var("t") > lit(75.0)):
            u.break_()
    u.return_(var("t"))
    f = u.build()
    loop = next(s for s in f.body if isinstance(s, CursorLoop))
    assert classify(loop).kind == "scan"
    db.create_function(f)


def _setup(quick: bool) -> Session:
    db = Session()
    create_tables(db, M_FACTS_QUICK if quick else M_FACTS)
    return db


def query(keys: str = "keys", fn: str = "floop"):
    """Per outer key below @cut: the cursor loop's fold at k + @shift."""
    return (
        scan(keys)
        .filter(col("k") < param("cut"))
        .compute(out=udf(fn, col("k") * 1.0 + param("shift")))
        .project("k", "out")
    )


def _params(n: int, seed: int = 7):
    rng = np.random.default_rng(seed)
    return [{"cut": int(c), "shift": float(round(s, 2))}
            for c, s in zip(rng.integers(1, N_KEYS + 1, n),
                            rng.uniform(-1, 2, n))]


def _check_identical(expected, got):
    for s, b in zip(expected, got):
        m = np.asarray(s.masked.mask)
        np.testing.assert_array_equal(m, np.asarray(b.masked.mask))
        np.testing.assert_allclose(
            np.asarray(b.masked.table.columns["out"].data)[m],
            np.asarray(s.masked.table.columns["out"].data)[m],
            rtol=2e-3, atol=1e-3,
        )


def run(quick: bool = False):
    db = _setup(quick)
    interp_n = INTERP_N_QUICK if quick else INTERP_N
    cpus = os.cpu_count() or 1
    s_interp = db.prepare(query(), INTERPRETED)
    s_froid = db.prepare(query(), FROID)

    # parity first (also pays both arms' warm-up): the rewritten LoopScan
    # plan must reproduce the per-row interpreted loop bit-for-bit on
    # masks/validity and within float tolerance on values
    pwarm = _params(interp_n)
    interp_r = [s_interp.execute(params=p) for p in pwarm]
    _check_identical(interp_r, [s_froid.execute(params=p) for p in pwarm])
    _check_identical(interp_r, s_froid.execute_many(pwarm))

    t0 = time.perf_counter()
    for p in pwarm:
        s_interp.execute(params=p)
    t_interp = (time.perf_counter() - t0) / interp_n
    emit(f"cursorloop/interp/{interp_n}", t_interp * 1e6,
         f"{interp_n} per-row interpreted cursor loops")

    for n in SWEEP:
        plist = _params(n)
        s_froid.execute_many(plist)  # pay the per-bucket vmapped jit
        t0 = time.perf_counter()
        rs = s_froid.execute_many(plist)
        t_many = (time.perf_counter() - t0) / n
        st = rs[0].stats
        tag = "rewrite" if n == SWEEP[0] else "rewrite_many"
        emit(
            f"cursorloop/{tag}/{n}", t_many * 1e6,
            f"speedup={t_interp / t_many:.1f}x kind=scan "
            f"bucket={st.get('batch_bucket')} host_cpus={cpus} "
            f"rewritten=True",
        )


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    run(quick=args.quick)
