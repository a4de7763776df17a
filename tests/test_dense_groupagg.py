"""Direct-address grouping: a GroupAgg whose keys catalog stats bound to a
small domain segments by the mixed-radix code of its keys instead of
sorting and gathering every row.

Oracles, on the live rows in order (both lowerings give groups in key
order; the dense one leaves unoccupied slots between them): the same
optimized plan with the annotation stripped (the sort lowering), and
``PLAIN`` (the INTERPRETED policy with the optimizer off, so nothing is
annotated).  The values are multiples of 1/4 and small, so every float32
sum is exact in any order and the comparison is bit for bit.  Shape: which
GroupAggs engage (``Session.timing_stats["dense_groupaggs"]``,
``O.dense_groupaggs``), which keep the sort path, the benchmark's
statements, and a join whose build key is read through an inner join.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:0] = [str(ROOT), str(ROOT / "src"), str(ROOT / "tests")]

from conformance_util import assert_rows_equal  # noqa: E402
from repro.core import (  # noqa: E402
    FROID,
    INTERPRETED,
    Session,
    avg_,
    col,
    count_,
    lit,
    max_,
    min_,
    param,
    resolve_policy,
    scan,
    sum_,
)
from repro.core import optimizer as O  # noqa: E402
from repro.core import relalg as R  # noqa: E402
from repro.core import scalar as S  # noqa: E402
from repro.core.executor import SMALL_GROUP_DOMAIN, Executor  # noqa: E402
from repro.core.fingerprint import plan_fingerprint  # noqa: E402
from repro.tables.table import Column, DictEncoding, Table  # noqa: E402

PLAIN = dataclasses.replace(INTERPRETED, name="plain", optimize=False)
N = 240
#: five dimension tables joined in a chain, each keyed 1..K with a gap
CHAIN = ("d1", "d2", "d3", "d4", "d5")


def _session(fact_rows: int = N, nullable_flag: bool = False) -> Session:
    """``fact`` (two dictionary keys as Q1's, an integer key, a foreign
    key with misses, values with NULLs) and a chain of five dimensions,
    the last but one carrying a dictionary name as Q5's ``n_name``."""
    rng = np.random.default_rng(18)
    db = Session()
    n = fact_rows
    flag = np.array(["A", "N", "R"])[rng.integers(0, 3, n)]
    status = np.array(["F", "O"])[rng.integers(0, 2, n)]
    xv = np.arange(n) % 7 != 3
    fact = Table.from_arrays(
        flag=flag, status=status,
        mode=np.array(["AIR", "MAIL", "RAIL", "SHIP", "TRUCK"])[
            rng.integers(0, 5, n)],
        g=rng.integers(0, 9, n).astype(np.int32),
        fk=rng.integers(0, 14, n).astype(np.int32),     # 0 and 13 miss
        x=Column(jnp.asarray(rng.integers(-40, 40, n) / 4, jnp.float32),
                 jnp.asarray(xv)),
        y=(rng.integers(0, 64, n) / 4).astype(np.float32))
    if nullable_flag:
        c = fact.columns["flag"]
        fact.columns["flag"] = Column(c.data, jnp.asarray(np.arange(n) % 9
                                                          != 4), c.dictionary)
    if fact_rows == 0:    # an empty table whose keys still have vocabularies
        fact = Table({
            name: Column(c.data, c.valid,
                         DictEncoding(("A", "N", "R") if name == "flag" else
                                      ("F", "O") if name == "status" else
                                      ("AIR", "MAIL")) if c.dictionary
                         is not None else None)
            for name, c in fact.columns.items()})
    db.create_table("fact", fact)
    for i, t in enumerate(CHAIN):
        k = np.arange(1, 13, dtype=np.int32)
        cols = {f"k{i + 1}": k,
                f"a{i + 1}": ((k * (i + 3)) % 12 + 1).astype(np.int32)}
        if t == "d4":
            cols["name"] = np.array([f"NATION{j % 5}" for j in range(12)])
        db.create_table(t, Table.from_arrays(**cols))
    return db


def _chain(depth: int):
    """``fact`` joined inner through ``depth`` dimensions of the chain."""
    q = scan("fact").join(scan("d1"), ("fk", "k1"))
    for i in range(1, depth):
        q = q.join(scan(CHAIN[i]), (f"a{i}", f"k{i + 1}"))
    return q


AGGS = dict(s=sum_(col("x")), a=avg_(col("y")), n=count_(col("x")),
            c=count_(), lo=min_(col("x")), hi=max_(col("y")))

#: engaged shapes -> (session, query, bindings or None)
ENGAGED = {
    # Q1's: two dictionary keys above a filter
    "two_dict_keys": lambda: (_session(), scan("fact").filter(
        col("y") < lit(12.0)).group_by("flag", "status", **AGGS), None),
    # Q12's: a probe-side dictionary key read through one inner join
    "one_join": lambda: (_session(), _chain(1).group_by("mode", **AGGS),
                         None),
    # Q5's: a build-side dictionary key read through five inner joins
    "five_joins": lambda: (_session(), _chain(5).filter(
        col("a5") > lit(2)).group_by("name", **AGGS), None),
    # an integer key whose groups a filter empties in part
    "masked_rows": lambda: (_session(), scan("fact").filter(
        (col("g") % lit(3)) != lit(1)).group_by("g", "status", **AGGS),
        None),
    # every row filtered out, and a table of no rows
    "empty_filter": lambda: (_session(), scan("fact").filter(
        col("y") < lit(-1.0)).group_by("flag", "status", **AGGS), None),
    "empty_table": lambda: (_session(0), scan("fact").group_by(
        "flag", "status", **AGGS), None),
    # an execute_many wave of three bindings over a dense GroupAgg
    "wave": lambda: (_session(), _chain(2).filter(
        col("y") >= param("cut")).group_by("flag", "mode", **AGGS),
        [{"cut": 0.0}, {"cut": 7.5}, {"cut": 99.0}]),
}

#: shapes that keep the sort path -> (session, query)
DECLINED = {
    # a left join's build columns can come out NULL
    "left_join_key": lambda: (_session(), scan("fact").join(
        scan("d4"), ("fk", "k4"), "left").group_by("name", **AGGS)),
    # a base column that holds a NULL
    "nullable_key": lambda: (_session(nullable_flag=True),
                             scan("fact").group_by("flag", **AGGS)),
    # a composite domain past the limit: 9 x (limit // 9 + 1) slots
    "over_the_limit": lambda: (
        _with_wide(_session(), SMALL_GROUP_DOMAIN // 9 + 1),
        scan("fact").join(scan("wide"), ("fk", "w")).group_by(
            "g", "v", **AGGS)),
    # a computed key: its values are not the column's
    "computed_key": lambda: (_session(), scan("fact").compute(
        g2=col("g") + lit(1)).group_by("g2", **AGGS)),
}


def _with_wide(db: Session, span: int) -> Session:
    """``db`` with a dimension ``wide`` keyed 0..13 whose integer ``v``
    spans ``span`` values."""
    w = np.arange(14, dtype=np.int32)
    v = (np.arange(14) * 7 % span).astype(np.int32)
    v[0], v[1] = 0, span - 1
    db.create_table("wide", Table.from_arrays(w=w, v=v))
    return db


def _sort_path(plan: R.RelNode) -> R.RelNode:
    """``plan`` with every GroupAgg's key ranges and capacity stripped."""
    return R.transform_plan(plan, lambda n: (
        R.GroupAgg(n.child, n.keys, dict(n.aggs))
        if isinstance(n, R.GroupAgg) and n.dense_range is not None
        else None))


def _live(masked) -> dict:
    """Per column, the live rows' (validity, data where valid), in order."""
    m = np.asarray(masked.mask)
    out = {}
    for name, c in masked.table.columns.items():
        valid = np.asarray(c.validity())[m]
        out[name] = (valid, np.asarray(c.data)[m][valid])
    return out


def _assert_same_groups(want, got, label, unspecified=()):
    """The same live rows; ``unspecified`` names columns whose data is
    left out of the comparison (their validity is still compared)."""
    a, b = _live(want), _live(got)
    assert a.keys() == b.keys(), label
    for name in a:
        np.testing.assert_array_equal(a[name][0], b[name][0],
                                      err_msg=f"{label}: validity({name})")
        if name in unspecified:
            continue
        np.testing.assert_array_equal(a[name][1], b[name][1],
                                      err_msg=f"{label}: data({name})")


def _prepare(db, q, dense: int):
    before = db.timing_stats["dense_groupaggs"]
    stmt = db.prepare(q, FROID)
    assert db.timing_stats["dense_groupaggs"] - before == dense, \
        stmt.explain()
    assert O.dense_groupaggs(stmt.plan) == dense
    return stmt


# ---------------------------------------------------------------------------
# engaged: the dense lowering against the sort lowering and the interpreter
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", sorted(ENGAGED))
def test_dense_groupagg_equals_sort_path_and_interpreter(case):
    db, q, waves = ENGAGED[case]()
    stmt = _prepare(db, q, 1)
    (ga,) = [n for n in R.walk_plan(stmt.plan) if isinstance(n, R.GroupAgg)]
    assert ga.capacity == np.prod([hi - lo + 1 for lo, hi in ga.dense_range])
    ex = Executor(db.catalog)
    for p in waves or [None]:
        values = {k: S.Value(jnp.asarray(v, jnp.float32))
                  for k, v in (p or {}).items()}
        dense = ex.execute(stmt.plan, params=values)
        assert dense.num_rows == ga.capacity
        _assert_same_groups(ex.execute(_sort_path(stmt.plan), params=values),
                            dense, f"{case} {p}: dense vs sort path")
        _assert_same_groups(db.execute(q, PLAIN, params=p).masked, dense,
                            f"{case} {p}: dense vs PLAIN")
    if case.startswith("empty"):
        assert not np.asarray(dense.mask).any()
    if waves:
        serial = [stmt.execute(params=p) for p in waves]
        for p, want, got in zip(waves, serial, stmt.execute_many(waves)):
            assert_rows_equal(want, got, f"wave {p}")
            assert got.stats.get("batch_size") == len(waves), got.stats


def test_keys_decode_from_the_slot_and_groups_come_in_key_order():
    db, q, _ = ENGAGED["two_dict_keys"]()
    got = _prepare(db, q, 1).execute().masked
    flags = np.asarray(got.table.columns["flag"].data)
    status = np.asarray(got.table.columns["status"].data)
    np.testing.assert_array_equal(flags, [0, 0, 1, 1, 2, 2])
    np.testing.assert_array_equal(status, [0, 1, 0, 1, 0, 1])
    assert np.asarray(got.mask).all()


# ---------------------------------------------------------------------------
# declined: keys whose NULLs or values the rule cannot bound keep sorting
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", sorted(DECLINED))
def test_declined_shapes_keep_the_sort_path(shape):
    db, q = DECLINED[shape]()
    stmt = _prepare(db, q, 0)
    gas = [n for n in R.walk_plan(stmt.plan) if isinstance(n, R.GroupAgg)]
    assert [g.dense_range for g in gas] == [None]
    # the sort lowering gives the group of NULL keys a key marked valid
    # whose data is unspecified (its rows' raw data); that group's
    # aggregates are compared, its key's data is not
    nulls = {"left_join_key": ("name",), "nullable_key": ("flag",)}
    _assert_same_groups(db.execute(q, PLAIN).masked, stmt.execute().masked,
                        f"{shape}: compiled vs PLAIN", nulls.get(shape, ()))


def test_a_domain_at_the_limit_engages():
    span = SMALL_GROUP_DOMAIN // 9
    db = _with_wide(_session(), span)
    q = scan("fact").join(scan("wide"), ("fk", "w")).group_by("g", "v",
                                                             **AGGS)
    stmt = _prepare(db, q, 1)
    (ga,) = [n for n in R.walk_plan(stmt.plan) if isinstance(n, R.GroupAgg)]
    assert ga.dense_range == ((0, 8), (0, span - 1))
    assert ga.capacity == 9 * span <= SMALL_GROUP_DOMAIN
    _assert_same_groups(db.execute(q, PLAIN).masked, stmt.execute().masked,
                        "at the limit: compiled vs PLAIN")


# ---------------------------------------------------------------------------
# the field: one pair per key; a bare pair is one key's, as before
# ---------------------------------------------------------------------------


def test_a_bare_pair_is_one_keys_range_and_explain_shows_the_ranges():
    child = R.Scan("fact")
    aggs = {"s": R.AggSpec("sum", col("x"))}
    old = R.GroupAgg(child, ["g"], aggs, 9, (0, 8))
    new = R.GroupAgg(child, ["g"], aggs, 9, [(0, 8)])
    assert old.dense_range == new.dense_range == ((0, 8),)
    assert plan_fingerprint(old) == plan_fingerprint(new)
    assert plan_fingerprint(old) == plan_fingerprint(old.with_children([child]))
    assert plan_fingerprint(old) != plan_fingerprint(R.GroupAgg(child, ["g"],
                                                                aggs, 9))
    db, q, _ = ENGAGED["two_dict_keys"]()
    assert "dense=[(0, 2), (0, 1)]" in _prepare(db, q, 1).explain()


# ---------------------------------------------------------------------------
# joins share the trace: a build key read through an inner join engages
# ---------------------------------------------------------------------------


def test_join_build_key_read_through_an_inner_join():
    db = _session()
    build = scan("d1").join(scan("d2"), ("a1", "k2"))
    q = scan("fact").join(build, ("fk", "k1"))
    before = db.timing_stats["dense_joins"]
    stmt = db.prepare(q, FROID)
    joins = [n for n in R.walk_plan(stmt.plan) if isinstance(n, R.Join)]
    assert [j.dense_range for j in joins] == [(1, 12), (1, 12)]
    assert db.timing_stats["dense_joins"] - before == 2
    ex = Executor(db.catalog)
    stripped = R.transform_plan(stmt.plan, lambda n: (
        R.Join(n.left, n.right, n.on, n.kind)
        if isinstance(n, R.Join) and n.dense_range is not None else None))
    assert_rows_equal(db.execute(q, PLAIN), stmt.execute(), "vs PLAIN")
    want, got = ex.execute(stripped), ex.execute(stmt.plan)
    np.testing.assert_array_equal(np.asarray(want.mask), np.asarray(got.mask))
    # through a left join the build key is not traced
    build = scan("d1").join(scan("d2"), ("a1", "k2"), "left")
    q = scan("fact").join(build.project(kk="k2", a1="a1"), ("fk", "kk"))
    joins = [n for n in R.walk_plan(db.prepare(q, FROID).plan)
             if isinstance(n, R.Join)]
    assert [j.dense_range for j in joins] == [None, (1, 12)]


# ---------------------------------------------------------------------------
# the benchmark's statements: Q1, Q5 and Q12 engage; nothing else does
# ---------------------------------------------------------------------------

BENCH = {"Q1": 1, "Q5": 1, "Q12": 1, "Q3": 0, "Q6": 0, "Q14": 0, "Q19": 0,
         "q1_revenue": 0, "total_price": 0}


@pytest.fixture(scope="module")
def bench_sessions():
    from bench import harness

    out = {}
    for workload in ("udf_queries.power", "udf_calls.serial"):
        cfg = {**harness.cell(workload).config, "scale_factor": 0.001}
        data = harness.generate(cfg, np.random.SeedSequence(16))
        db = Session()
        harness.load(db, data)
        for f in cfg["functions"]:
            harness.load_named("functions", f).register(db)
        for name in cfg["statements"]:
            out[name] = (db, resolve_policy(cfg["policy"]),
                         harness.load_named("statements", name).build())
    return out


@pytest.mark.parametrize("name", list(BENCH))
def test_benchmark_statements_dense_groupaggs(bench_sessions, name):
    db, policy, q = bench_sessions[name]
    before = db.timing_stats["dense_groupaggs"]
    stmt = db.prepare(q, policy)
    assert db.timing_stats["dense_groupaggs"] - before == BENCH[name], \
        stmt.explain()
    if name == "Q3":
        (ga,) = [n for n in R.walk_plan(stmt.plan)
                 if isinstance(n, R.GroupAgg)]
        assert ga.keys == ["l_orderkey", "o_orderdate", "o_shippriority"]
        assert ga.dense_range is None and ga.capacity is None
