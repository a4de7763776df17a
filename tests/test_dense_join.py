"""Direct-address joins: a join whose build key an integer base column's
stats bound to a dense range probes a slot table over that range instead
of sorting the build side and binary-searching every probe key.

Oracles, element-wise: the same optimized plan with the annotation
stripped (the sort + ``searchsorted`` lowering), bit for bit, and ``PLAIN``
(the INTERPRETED policy with the optimizer off, so nothing is annotated).
Shape: which joins engage (``Session.timing_stats["dense_joins"]``,
``O.dense_joins``), which decline, and that a replaced build table
re-plans.  Run as a script, the module is the child of the four-device
check (``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
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
    col,
    lit,
    param,
    resolve_policy,
    scan,
    sum_,
)
from repro.core import optimizer as O  # noqa: E402
from repro.core import relalg as R  # noqa: E402
from repro.core.executor import Executor  # noqa: E402
from repro.core.fingerprint import plan_fingerprint  # noqa: E402
from repro.tables.table import Column, Table  # noqa: E402

PLAIN = dataclasses.replace(INTERPRETED, name="plain", optimize=False)
KINDS = ("inner", "left", "semi", "anti")

#: TPC-H's order keys: runs of 8 in every 32, so the range spans 4x the
#: distinct keys (less 24); here 1..8, 33..40, 65..72
DIM_KEYS = np.arange(24) // 8 * 32 + np.arange(24) % 8 + 1
#: probe keys: hits, a gap inside the range, below lo, above hi
FACT_KEYS = np.array([1, 5, 8, 33, 40, 65, 72, 20, 9, 0, -7, 73, 500,
                      2**31 - 1, -2**31, 36, 36, 1])


def _session(dim: dict, fact_keys=FACT_KEYS, fact_valid=None) -> Session:
    db = Session()
    n = len(dim["k"])
    rng = np.random.default_rng(n)
    db.create_table("dim", Table.from_arrays(
        k=Column(jnp.asarray(dim["k"], jnp.int32),
                 None if dim.get("valid") is None
                 else jnp.asarray(dim["valid"])),
        v=np.arange(n, dtype=np.float32) + 0.5,
        flag=dim.get("flag", np.ones(n, np.int32)),
        tag=np.array([f"t{i % 3}" for i in range(n)]),
        fv=rng.uniform(0, 10, n).astype(np.float32)))
    m = len(fact_keys)
    db.create_table("fact", Table.from_arrays(
        fk=Column(jnp.asarray(fact_keys, jnp.int32),
                  None if fact_valid is None else jnp.asarray(fact_valid)),
        x=np.arange(m, dtype=np.float32) * 2.0,
        keep=np.arange(m) % 4 != 3))
    return db


#: build tables that engage: masked build rows (a literal filter), NULL
#: keys on either side, duplicate keys (the lowest-numbered row wins)
CASES = {
    "plain": lambda: _session({"k": DIM_KEYS}),
    "masked_build": lambda: _session(
        {"k": DIM_KEYS, "flag": (np.arange(24) % 3 != 1).astype(np.int32)}),
    "null_keys": lambda: _session(
        {"k": DIM_KEYS, "valid": np.arange(24) % 5 != 2},
        fact_valid=np.arange(len(FACT_KEYS)) % 4 != 1),
    "duplicates": lambda: _session(
        {"k": np.array([1, 2, 2, 3, 5, 5, 5, 7, 2, 8, 1, 4])}),
    "masked_probe": lambda: _session({"k": DIM_KEYS}),
}


def _query(case: str, kind: str):
    probe = scan("fact")
    if case == "masked_probe":
        probe = probe.filter(col("keep"))
    build = scan("dim")
    if case == "masked_build":
        build = build.filter(col("flag") == lit(1))
    return probe.join(build, ("fk", "k"), kind)


def _sorted(plan: R.RelNode) -> R.RelNode:
    """``plan`` with every join's dense range stripped."""
    return R.transform_plan(plan, lambda n: (
        R.Join(n.left, n.right, n.on, n.kind)
        if isinstance(n, R.Join) and n.dense_range is not None else None))


def _assert_same(a, b, label):
    """Bit for bit: the same mask, and on every live row the same data
    and validity."""
    m = np.asarray(a.mask)
    np.testing.assert_array_equal(m, np.asarray(b.mask), err_msg=label)
    assert a.table.names() == b.table.names(), label
    for name, c in a.table.columns.items():
        d = b.table.columns[name]
        np.testing.assert_array_equal(np.asarray(c.validity())[m],
                                      np.asarray(d.validity())[m],
                                      err_msg=f"{label}: validity({name})")
        live = m & np.asarray(c.validity())
        np.testing.assert_array_equal(np.asarray(c.data)[live],
                                      np.asarray(d.data)[live],
                                      err_msg=f"{label}: data({name})")


def _prepare(db, q, dense: int):
    before = db.timing_stats["dense_joins"]
    stmt = db.prepare(q, FROID)
    assert db.timing_stats["dense_joins"] - before == dense, stmt.explain()
    assert O.dense_joins(stmt.plan) == dense
    return stmt


# ---------------------------------------------------------------------------
# engaged: the dense lowering against the sorted one and the interpreter
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_join_equals_sorted_join_and_interpreter(case, kind):
    db = CASES[case]()
    q = _query(case, kind)
    stmt = _prepare(db, q, 1)
    ex = Executor(db.catalog)
    _assert_same(ex.execute(_sorted(stmt.plan)), ex.execute(stmt.plan),
                 f"{case} {kind}: dense vs sorted")
    assert_rows_equal(db.execute(q, PLAIN), stmt.execute(),
                      f"{case} {kind}: compiled vs PLAIN")


def test_duplicate_keys_take_the_lowest_row():
    db = CASES["duplicates"]()
    got = _prepare(db, _query("duplicates", "inner"), 1).execute().masked
    live = np.asarray(got.mask)
    fk = np.asarray(got.table.columns["fk"].data)[live]
    v = np.asarray(got.table.columns["v"].data)[live]
    keys = CASES["duplicates"]().catalog["dim"].columns["k"].data
    first = {int(k): i + 0.5 for i, k in reversed(list(enumerate(keys)))}
    assert dict(zip(fk.tolist(), v.tolist())) == {
        k: first[k] for k in fk.tolist()}
    assert sorted(set(fk.tolist())) == [1, 5, 8]


def test_explain_shows_the_range_and_fingerprints_differ():
    db = CASES["plain"]()
    stmt = _prepare(db, _query("plain", "inner"), 1)
    assert "dense=(1, 72)" in stmt.explain(), stmt.explain()
    assert plan_fingerprint(stmt.plan) != plan_fingerprint(_sorted(stmt.plan))


# ---------------------------------------------------------------------------
# the density rule: a span of 4x the distinct keys engages, past it declines
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("last,dense", [(4 * 16 - 1, 1), (4 * 16, 0)])
def test_span_of_four_times_distinct_engages_and_past_it_declines(
        last, dense):
    keys = np.arange(16) * 4
    keys[-1] = last   # span = last + 1 against 16 distinct keys
    db = _session({"k": keys}, fact_keys=np.array([0, 4, 8, 60, 63, 64, 3]))
    q = _query("plain", "inner")
    stmt = _prepare(db, q, dense)
    assert_rows_equal(db.execute(q, PLAIN), stmt.execute(), f"last {last}")


# ---------------------------------------------------------------------------
# declined: builds that would not give one slot table per program, and keys
# the rule cannot bound
# ---------------------------------------------------------------------------

DECLINED = {
    # total_price's shape: the build's key comes out of a GroupAgg
    "grouped_build": lambda: scan("fact").join(
        scan("dim").group_by("k", s=sum_(col("v"))), ("fk", "k"), "left"),
    # a computed key: its values are not the column's
    "computed_key": lambda: scan("fact").join(
        scan("dim").compute(k2=col("k") + lit(0)), ("fk", "k2")),
    # a parameter reaches the build: one slot table per binding
    "param_build": lambda: scan("fact").join(
        scan("dim").filter(col("v") > param("cut")), ("fk", "k")),
    "composite_key": lambda: scan("fact").compute(t=col("fk") % lit(3)).join(
        scan("dim").compute(tk=col("k") % lit(3)), [("fk", "k"), ("t", "tk")]),
    "float_key": lambda: scan("fact").compute(f=col("fk") * lit(1.0)).join(
        scan("dim"), ("f", "fv")),
}


@pytest.mark.parametrize("shape", sorted(DECLINED))
def test_declined_shapes_keep_the_sorted_join(shape):
    db = CASES["plain"]()
    q = DECLINED[shape]()
    stmt = _prepare(db, q, 0)
    params = {"cut": 7.0} if shape == "param_build" else None
    assert_rows_equal(db.execute(q, PLAIN, params=params),
                      stmt.execute(params=params), shape)


# ---------------------------------------------------------------------------
# waves: one execute_many wave, and a sharded wave on four devices
# ---------------------------------------------------------------------------


def _wave_query():
    return (scan("fact").filter(col("fk") >= param("a"))
            .join(scan("dim"), ("fk", "k")))


WAVE = [{"a": a} for a in (-10, 0, 5, 33, 41, 100, 7)]


def test_execute_many_wave_equals_serial_loop():
    db = CASES["null_keys"]()
    stmt = _prepare(db, _wave_query(), 1)
    serial = [stmt.execute(params=p) for p in WAVE]
    for p, want, got in zip(WAVE, serial, stmt.execute_many(WAVE)):
        assert_rows_equal(want, got, f"wave {p}")
        assert_rows_equal(db.execute(_wave_query(), PLAIN, params=p), got,
                          f"PLAIN {p}")


def _four_device_child() -> dict:
    db = CASES["null_keys"]()
    policy = resolve_policy("FROID+data4")
    stmt = db.prepare(_wave_query(), policy)
    rs = stmt.execute_many(WAVE)
    serial = [stmt.execute(params=p) for p in WAVE]
    same = True
    for s, w in zip(serial, rs):
        try:
            assert_rows_equal(s, w, "sharded")
        except AssertionError:
            same = False
    return {"devices": policy.shard_devices(), "same": same,
            "sharded": bool(rs[0].stats.get("sharded")),
            "dense_joins": db.timing_stats["dense_joins"]}


def test_sharded_wave_on_four_devices_equals_serial_loop():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, __file__], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    found = json.loads(out.stdout.strip().splitlines()[-1])
    assert found == {"devices": 4, "same": True, "sharded": True,
                     "dense_joins": 1}, found


# ---------------------------------------------------------------------------
# DDL: a replaced build table re-plans from its new stats
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("new_keys,dense_range", [
    (DIM_KEYS + 200, (201, 272)),    # outside the old range: re-ranged
    (DIM_KEYS * 10, None),           # sparse: the join declines
])
def test_replaced_build_table_replans(new_keys, dense_range):
    db = CASES["plain"]()
    fact = np.concatenate([FACT_KEYS, new_keys[::3]])
    db.create_table("fact", Table.from_arrays(
        fk=fact.astype(np.int32), x=np.arange(len(fact), dtype=np.float32),
        keep=np.ones(len(fact), bool)))
    q = _query("plain", "inner")
    stmt = _prepare(db, q, 1)
    before = int(np.asarray(stmt.execute().masked.mask).sum())
    n = len(new_keys)
    db.create_table("dim", Table.from_arrays(
        k=new_keys.astype(np.int32), v=np.arange(n, dtype=np.float32) + 0.5,
        flag=np.ones(n, np.int32),
        tag=np.array([f"t{i % 3}" for i in range(n)]),
        fv=np.zeros(n, np.float32)))
    joins = [j for j in R.walk_plan(stmt.plan) if isinstance(j, R.Join)]
    assert [j.dense_range for j in joins] == [dense_range]
    assert db.timing_stats["dense_joins"] == 1 + (dense_range is not None)
    got = stmt.execute()
    assert_rows_equal(db.execute(q, PLAIN), got, "after DDL")
    hits = int(np.isin(fact, new_keys).sum())
    assert int(np.asarray(got.masked.mask).sum()) == hits
    assert before == int(np.isin(fact, DIM_KEYS).sum()) != hits


# ---------------------------------------------------------------------------
# the benchmark's statements: every power join engages, no call join does
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workload,dense", [("udf_queries.power", 10),
                                            ("udf_calls.serial", 0)])
def test_benchmark_statements_dense_joins(workload, dense):
    from bench import harness

    cfg = {**harness.cell(workload).config, "scale_factor": 0.001}
    data = harness.generate(cfg, np.random.SeedSequence(16))
    db = Session()
    harness.load(db, data)
    for f in cfg["functions"]:
        harness.load_named("functions", f).register(db)
    policy = resolve_policy(cfg["policy"])
    for name in cfg["statements"]:
        db.prepare(harness.load_named("statements", name).build(), policy)
    assert db.timing_stats["dense_joins"] == dense


if __name__ == "__main__":
    print(json.dumps(_four_device_child()), flush=True)
