"""Optimizer rewrite rules preserve semantics."""
import numpy as np

from repro.core import Database, col, count_, lit, scan, sum_
from repro.core import optimizer as O
from repro.core import relalg as R
from repro.core import scalar as S


def _db(rng):
    db = Database()
    db.create_table(
        "t",
        k=rng.integers(0, 20, 300),
        v=rng.uniform(-5, 5, 300).astype(np.float32),
    )
    return db


def _run_plan(db, plan):
    from repro.core.executor import Executor

    out = Executor(db.catalog).execute(plan)
    return (
        {n: np.asarray(c.data) for n, c in out.table.columns.items()},
        np.asarray(out.mask),
    )


def _equal(db, p1, p2, cols):
    a, ma = _run_plan(db, p1)
    b, mb = _run_plan(db, p2)
    np.testing.assert_array_equal(ma, mb)
    for c in cols:
        np.testing.assert_allclose(a[c][ma], b[c][mb], rtol=1e-5)


def test_rule_remove_applies_preserves_semantics(rng):
    db = _db(rng)
    region = R.Compute(R.ConstantScan(), {"y": S.Outer("v") * S.Const(2.0)})
    plan = R.Apply(R.Scan("t"), region, kind="outer")
    rewritten, changed = O.remove_applies(plan, db.catalog)
    assert changed
    assert not any(isinstance(n, R.Apply) for n in R.walk_plan(rewritten))
    _equal(db, plan, rewritten, ["y"])


def test_rule_fold_constants_dynamic_slicing(rng):
    db = _db(rng)
    expr = S.Case([(S.Const(5) > S.Const(3), S.ColRef("v"))], S.Const(0.0))
    plan = R.Compute(R.Scan("t"), {"o": expr + (S.Const(2) * S.Const(3))})
    rewritten, changed = O.fold_constants(plan, db.catalog)
    assert changed
    # the CASE folded away; the 2*3 folded to 6
    comp = next(n for n in R.walk_plan(rewritten) if isinstance(n, R.Compute))
    reprs = repr(list(comp.computed.values()))
    assert "Case" not in reprs and "Const(6)" in reprs
    _equal(db, plan, rewritten, ["o"])


def test_rule_decorrelate_matches_vmap_fallback(rng):
    db = _db(rng)
    sub = R.GroupAgg(
        R.Filter(R.Scan("t"), S.ColRef("k") == S.Outer("k")),
        [],
        {"s": R.AggSpec("sum", S.ColRef("v"))},
    )
    plan = R.Compute(R.Scan("t"), {"tot": S.ScalarSubquery(sub, "s")})
    rewritten, changed = O.decorrelate_in_computes(plan, db.catalog)
    assert changed
    assert any(isinstance(n, R.Join) for n in R.walk_plan(rewritten))
    _equal(db, plan, rewritten, ["tot"])


def test_rule_dense_group_stats_matches_sort_path(rng):
    db = _db(rng)
    plan = R.GroupAgg(R.Scan("t"), ["k"], {"s": R.AggSpec("sum", S.ColRef("v")),
                                           "c": R.AggSpec("count_star", None)})
    annotated, changed = O.annotate_group_stats(plan, db.catalog)
    assert changed
    ga = next(n for n in R.walk_plan(annotated) if isinstance(n, R.GroupAgg))
    assert ga.dense_range is not None
    a, ma = _run_plan(db, plan)
    b, mb = _run_plan(db, annotated)
    key_a = {int(k): i for i, k in enumerate(a["k"][ma])}
    key_b = {int(k): i for i, k in enumerate(b["k"][mb])}
    assert set(key_a) == set(key_b)
    for k in key_a:
        np.testing.assert_allclose(
            a["s"][ma][key_a[k]], b["s"][mb][key_b[k]], rtol=1e-5
        )
        assert a["c"][ma][key_a[k]] == b["c"][mb][key_b[k]]


def test_rule_prune_removes_dead_compute(rng):
    db = _db(rng)
    plan = R.Compute(R.Scan("t"), {"dead": S.ColRef("v") * S.Const(3.0),
                                   "live": S.ColRef("v") + S.Const(1.0)})
    pruned, changed = O.prune_columns(plan, db.catalog, required={"live", "k"})
    assert changed
    comp = next(n for n in R.walk_plan(pruned) if isinstance(n, R.Compute))
    assert "dead" not in comp.computed and "live" in comp.computed
