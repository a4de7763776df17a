"""Pinned-key pushdown: a filter that pins a join key to a parameter or a
literal filters the join's build side on that value too, and a GroupAgg
whose every key is pinned becomes a keyless aggregate.

Oracle: the rewritten plans answer as ``PLAIN`` does (the INTERPRETED
policy with the optimizer off: per-row UDF evaluation, correlated
subqueries evaluated row by row, no rewrite at all), element-wise, for keys with rows
and keys with none, through ``execute`` and through one ``execute_many``
wave of different keys.  Shape: what fires, what is counted, and what is
left exactly as it was (non-pinning filters, Figure 1 over every customer,
the seven TPC-H UDF queries).
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

from conformance_util import assert_rows_equal
from repro.core import (
    FROID,
    INTERPRETED,
    Session,
    UdfBuilder,
    avg_,
    col,
    count_,
    lit,
    param,
    scan,
    sum_,
    udf,
    var,
)
from repro.core import optimizer as O
from repro.core import relalg as R
from repro.core import scalar as S
from repro.core.binder import Binder
from repro.core.fingerprint import plan_fingerprint
from repro.core.frontend import exists, not_exists

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:   # the benchmark's functions and statements
    sys.path.insert(0, str(ROOT))

#: keys 0..6 have facts, 7..9 have none
N_KEYS, N_FACT_KEYS = 10, 7
KEYS = range(N_KEYS)
PLAIN = dataclasses.replace(INTERPRETED, name="plain", optimize=False)
PLAIN_RULES = tuple(r for r in O.DEFAULT_RULES
                    if r not in (O.push_pinned_keys,
                                 O.collapse_pinned_groupaggs))


def _db(seed: int = 4) -> Session:
    rng = np.random.default_rng(seed)
    db = Session()
    db.create_table("keys", k=np.arange(N_KEYS))
    db.create_table("facts", fk=rng.integers(0, N_FACT_KEYS, 31),
                    val=np.round(rng.uniform(-10, 10, 31), 2)
                    .astype(np.float32))
    for body in BODIES:
        db.create_function(_udf(body))
    return db


BODIES = ("sum", "avg", "count", "exists", "not_exists")


def _udf(body: str):
    """``f_<body>(@key)``: an aggregate of the key's facts (0 where NULL),
    or 1/0 on whether the key has facts."""
    u = UdfBuilder(f"f_{body}", [("key", "int32")], "float32")
    rows = scan("facts").filter(col("fk") == param("key"))
    if body in ("exists", "not_exists"):
        test = exists(rows) if body == "exists" else not_exists(rows)
        with u.if_(test):
            u.return_(lit(1.0))
        u.return_(lit(0.0))
        return u.build()
    agg = {"sum": sum_, "avg": avg_, "count": count_}[body](col("val"))
    u.declare("v", "float32")
    u.select({"v": agg}, frm=scan("facts"),
             where=col("fk") == param("key"))
    with u.if_(var("v").is_null()):
        u.return_(lit(0.0))
    u.return_(var("v"))
    return u.build()


def _query(body: str, pin):
    return (scan("keys").filter(col("k") == pin)
            .compute(out=udf(f"f_{body}", col("k"))).project("k", "out"))


def _keyed_groupaggs(plan) -> int:
    return sum(isinstance(n, R.GroupAgg) and bool(n.keys)
               for n in R.walk_plan_deep(plan))


# ---------------------------------------------------------------------------
# oracle: the collapsed plans against the unoptimized per-row interpreter
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("body", BODIES)
def test_param_pin_matches_interpreter_serial_and_in_one_wave(body):
    db = _db()
    q = _query(body, param("key"))
    stmt = db.prepare(q, FROID)
    assert db.timing_stats["pinned_groupaggs"] == 1
    assert _keyed_groupaggs(stmt.plan) == 0, stmt.explain()
    params = [{"key": k} for k in KEYS]
    expected = [db.execute(q, PLAIN, params=p) for p in params]
    for p, want in zip(params, expected):
        assert_rows_equal(want, stmt.execute(params=p), f"{body} {p}")
    for p, want, got in zip(params, expected, stmt.execute_many(params)):
        assert_rows_equal(want, got, f"{body} wave {p}")


@pytest.mark.parametrize("body", BODIES)
@pytest.mark.parametrize("key", [2, 8])
def test_const_pin_matches_interpreter(body, key):
    db = _db()
    q = _query(body, lit(key))
    stmt = db.prepare(q, FROID)
    assert db.timing_stats["pinned_groupaggs"] == 1
    assert_rows_equal(db.execute(q, PLAIN), stmt.execute(),
                      f"{body} const {key}")


def test_empty_keys_give_zero_sum_and_count():
    """A key with no facts: the collapsed build has no row, the left join
    misses, and the UDF's NULL branch answers 0; COUNT is 0 as well."""
    db = _db()
    for body in ("sum", "count"):
        stmt = db.prepare(_query(body, param("key")), FROID)
        for k in range(N_FACT_KEYS, N_KEYS):
            m = stmt.execute(params={"k": 0, "key": k}).masked
            live = np.asarray(m.mask)
            assert live.sum() == 1
            out = m.table.columns["out"]
            assert bool(np.asarray(out.validity())[live][0])
            assert float(np.asarray(out.data)[live][0]) == 0.0


def _join_query(kind: str):
    keys = scan("keys").filter(col("k") == param("key"))
    if kind in ("inner", "left"):
        grouped = scan("facts").group_by("fk", s=sum_(col("val")),
                                         n=count_())
        return keys.join(grouped, ("k", "fk"), kind)
    rows = scan("facts").filter(col("fk") == S.Outer("k"))
    return keys.filter(exists(rows) if kind == "semi" else not_exists(rows))


@pytest.mark.parametrize("kind", ["inner", "left", "semi", "anti"])
def test_join_kinds_take_the_pin_and_match_interpreter(kind):
    db = _db()
    q = _join_query(kind)
    stmt = db.prepare(q, FROID)
    joins = [n for n in R.walk_plan(stmt.plan) if isinstance(n, R.Join)]
    assert len(joins) == 1 and joins[0].kind == kind, stmt.explain()
    pinned = [n for n in R.walk_plan(joins[0].right)
              if isinstance(n, R.Filter) and "Param(key)" in repr(n.pred)]
    assert pinned, stmt.explain()
    grouped = kind in ("inner", "left")
    assert db.timing_stats["pinned_groupaggs"] == int(grouped)
    params = [{"key": k} for k in KEYS]
    for p, got in zip(params, stmt.execute_many(params)):
        want = db.execute(q, PLAIN, params=p)
        assert_rows_equal(want, got, f"{kind} {p}")
        assert_rows_equal(want, stmt.execute(params=p), f"{kind} {p}")


def test_pinned_groupagg_keeps_its_key_dtype_and_output():
    """A user GroupAgg on a pinned key: one row, its key the column's own
    dtype even when the parameter is a float."""
    db = _db()
    q = (scan("facts").filter(col("fk") == param("key"))
         .group_by("fk", s=sum_(col("val")), n=count_()))
    stmt = db.prepare(q, FROID)
    assert "GroupAgg keys=[]" in stmt.explain()
    assert O.PINNED_COUNT in stmt.explain()
    for key in (3, 3.0, 9):
        m = stmt.execute(params={"key": key}).masked
        want = db.execute(q, PLAIN, params={"key": key}).masked
        assert m.table.columns["fk"].data.dtype == np.int32
        assert _live_rows(m) == _live_rows(want), key
        assert len(_live_rows(m)) == (key != 9)


def _live_rows(masked) -> list[tuple]:
    """The surviving rows as sorted (column, value-or-None) tuples."""
    live = np.asarray(masked.mask)
    cols = {n: (np.asarray(c.data)[live], np.asarray(c.validity())[live])
            for n, c in masked.table.columns.items()}
    return sorted(
        tuple((n, round(float(d[i]), 3) if v[i] else None)
              for n, (d, v) in sorted(cols.items()))
        for i in range(int(live.sum())))


# ---------------------------------------------------------------------------
# shape: what fires, what is counted, what stays as it was
# ---------------------------------------------------------------------------


def _figure1_db() -> Session:
    rng = np.random.default_rng(1)
    db = Session()
    db.create_table("customer", c_custkey=np.arange(1, 41),
                    c_other=np.arange(1, 41) % 7)
    db.create_table("orders", o_custkey=rng.integers(1, 28, 200),
                    o_totalprice=rng.uniform(10, 1000, 200)
                    .astype(np.float32))
    from bench.functions import total_price

    total_price.register(db)
    return db


def _figure1(pred=None, table="customer"):
    q = scan(table)
    if pred is not None:
        q = q.filter(pred)
    return (q.compute(total=udf("total_price", col("c_custkey")))
            .project("c_custkey", "total"))


def test_total_price_pinned_has_no_keyed_groupagg_and_counts_one():
    db = _figure1_db()
    stmt = db.prepare(_figure1(col("c_custkey") == param("key")), FROID)
    txt = stmt.explain()
    assert _keyed_groupaggs(stmt.plan) == 0, txt
    assert "GroupAgg keys=[]" in txt and "Join[left]" in txt, txt
    assert db.timing_stats["pinned_groupaggs"] == 1
    # a second prepare of the same statement builds no plan
    db.prepare(_figure1(col("c_custkey") == param("key")), FROID)
    assert db.timing_stats["pinned_groupaggs"] == 1
    for key in (1, 5, 33):   # 33: no orders
        want = db.execute(_figure1(col("c_custkey") == param("key")),
                          PLAIN, params={"key": key})
        assert_rows_equal(want, stmt.execute(params={"key": key}),
                          f"key {key}")


def test_figure1_over_all_customers_keeps_join_and_keyed_groupagg():
    db = _figure1_db()
    stmt = db.prepare(_figure1(), FROID)
    kinds = {type(n).__name__ for n in R.walk_plan(stmt.plan)}
    assert "Join" in kinds and _keyed_groupaggs(stmt.plan) == 1
    assert db.timing_stats["pinned_groupaggs"] == 0


def _optimized(db, node, rules):
    wanted = R.output_columns(node, db.catalog)
    bound = Binder(db.registry, db.constraints).bind(node)
    return O.optimize(bound, db.catalog, required=set(wanted), rules=rules)


#: filters that pin no key: a column against a column, a range, an
#: inequality, and an equality on a float column
NON_PINNING = {
    "col_eq_col": ("customer", lambda: col("c_custkey") == col("c_other")),
    "col_lt_param": ("customer", lambda: col("c_custkey") < param("key")),
    "col_ne_param": ("customer", lambda: col("c_custkey") != param("key")),
    "float_key": ("fcustomer", lambda: col("c_custkey") == param("key")),
}


@pytest.mark.parametrize("pred", sorted(NON_PINNING))
def test_non_pinning_filters_leave_the_plan_unchanged(pred):
    db = _figure1_db()
    db.create_table("fcustomer", c_custkey=np.arange(1, 41) * 1.0)
    table, p = NON_PINNING[pred]
    node = _figure1(p(), table).node
    with_rules = _optimized(db, node, O.DEFAULT_RULES)
    assert O.pinned_groupaggs(with_rules) == 0
    assert (plan_fingerprint(with_rules)
            == plan_fingerprint(_optimized(db, node, PLAIN_RULES)))


def test_huge_integer_keys_are_not_pinned():
    """Past 2**24 a float parameter can equal two int32 keys: no pin."""
    db = Session()
    base = 1 << 24
    db.create_table("keys", k=np.arange(base, base + 4))
    db.create_table("facts", fk=np.array([base, base + 1, base + 1]),
                    val=np.array([1.0, 2.0, 3.0], np.float32))
    q = (scan("facts").filter(col("fk") == param("key"))
         .group_by("fk", s=sum_(col("val"))))
    assert "GroupAgg keys=['fk']" in db.prepare(q, FROID).explain()
    assert db.timing_stats["pinned_groupaggs"] == 0


def test_power_queries_plans_do_not_move():
    """The seven TPC-H UDF queries hold no filter that pins a join or
    grouping key: their optimized plans fingerprint the same with and
    without the pinned-key rules.  ``total_price`` is the control: its
    plan does move."""
    from bench import harness

    cfg = {**harness.cell("udf_queries.power").config,
           "scale_factor": 0.001}
    data = harness.generate(cfg, np.random.SeedSequence(7))
    db = Session()
    harness.load(db, data)
    for f in cfg["functions"]:
        harness.load_named("functions", f).register(db)
    harness.load_named("functions", "total_price").register(db)
    assert len(cfg["statements"]) == 7
    for name in cfg["statements"]:
        node = harness.load_named("statements", name).build().node
        assert (plan_fingerprint(_optimized(db, node, O.DEFAULT_RULES))
                == plan_fingerprint(_optimized(db, node, PLAIN_RULES))), name
    node = harness.load_named("statements", "total_price").build().node
    assert (plan_fingerprint(_optimized(db, node, O.DEFAULT_RULES))
            != plan_fingerprint(_optimized(db, node, PLAIN_RULES)))
