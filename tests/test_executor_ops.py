"""Unit tests: relational operators vs numpy oracles, NULL semantics,
date intrinsics, Apply probe/pass-through."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Database, avg_, col, count_, lit, max_, min_, scan, sum_
from repro.core import relalg as R
from repro.core import scalar as S
from repro.core.executor import Executor
from repro.kernels.relagg.relagg import MAX_GROUPS
from repro.tables.table import Table, civil_from_days, date_add, date_part, days_from_civil


def _db(rng, n=200, k=13):
    db = Database()
    db.create_table(
        "t",
        k=rng.integers(0, k, n),
        v=rng.uniform(-5, 5, n).astype(np.float32),
        q=rng.integers(0, 100, n),
    )
    db.create_table("d", dk=np.arange(k), w=rng.uniform(0, 1, k).astype(np.float32))
    return db


def test_filter_and_groupby_vs_numpy(rng):
    db = _db(rng)
    q = (
        scan("t")
        .filter(col("q") > 50)
        .group_by("k", s=sum_(col("v")), c=count_(), m=min_(col("v")),
                  x=max_(col("v")), a=avg_(col("v")))
    )
    r = db.run(q).table
    kk = np.asarray(db.catalog["t"].columns["k"].data)
    vv = np.asarray(db.catalog["t"].columns["v"].data)
    qq = np.asarray(db.catalog["t"].columns["q"].data)
    sel = qq > 50
    got = {int(k): i for i, k in enumerate(np.asarray(r.columns["k"].data))}
    for key in np.unique(kk[sel]):
        rows = vv[sel & (kk == key)]
        i = got[int(key)]
        np.testing.assert_allclose(r.columns["s"].data[i], rows.sum(), rtol=1e-5)
        assert int(r.columns["c"].data[i]) == len(rows)
        np.testing.assert_allclose(r.columns["m"].data[i], rows.min(), rtol=1e-5)
        np.testing.assert_allclose(r.columns["x"].data[i], rows.max(), rtol=1e-5)
        np.testing.assert_allclose(r.columns["a"].data[i], rows.mean(), rtol=1e-4)


def test_join_left_and_inner(rng):
    db = _db(rng)
    q = scan("t").join(scan("d"), on=("k", "dk"), kind="inner").compute(
        wv=col("v") * col("w")
    )
    r = db.run(q).table
    assert r.num_rows == db.catalog["t"].num_rows  # all keys exist in d
    vv = np.asarray(db.catalog["t"].columns["v"].data)
    kk = np.asarray(db.catalog["t"].columns["k"].data)
    ww = np.asarray(db.catalog["d"].columns["w"].data)
    # result preserves probe order
    np.testing.assert_allclose(
        np.asarray(r.columns["wv"].data), vv * ww[kk], rtol=1e-5
    )


def test_left_join_null_padding(rng):
    db = Database()
    db.create_table("a", x=np.array([0, 1, 2, 3]))
    db.create_table("b", y=np.array([1, 3]), z=np.array([10.0, 30.0], dtype=np.float32))
    q = scan("a").join(scan("b"), on=("x", "y"), kind="left")
    r = db.run(q)
    z = r.table.columns["z"]
    valid = np.asarray(z.validity())
    assert valid.tolist() == [False, True, False, True]
    assert np.asarray(z.data)[1] == 10.0 and np.asarray(z.data)[3] == 30.0


def test_semi_anti_join(rng):
    db = Database()
    db.create_table("a", x=np.array([0, 1, 2, 3, 4]))
    db.create_table("b", y=np.array([1, 3]))
    semi = db.run(scan("a").join(scan("b"), on=("x", "y"), kind="semi")).table
    anti = db.run(scan("a").join(scan("b"), on=("x", "y"), kind="anti")).table
    assert sorted(np.asarray(semi.columns["x"].data).tolist()) == [1, 3]
    assert sorted(np.asarray(anti.columns["x"].data).tolist()) == [0, 2, 4]


def test_sort_limit(rng):
    db = _db(rng)
    q = scan("t").sort(("v", False), limit=5)
    r = db.run(q).table
    vv = np.sort(np.asarray(db.catalog["t"].columns["v"].data))[::-1][:5]
    np.testing.assert_allclose(np.asarray(r.columns["v"].data), vv, rtol=1e-6)


def test_null_three_valued_logic():
    n = S.Const(None)
    t = S.Const(True)
    f = S.Const(False)
    ctx = S.EvalContext()

    def ev(e):
        v = S.eval_scalar(e, {}, ctx)
        return (bool(np.asarray(v.data)), bool(np.asarray(v.validity())))

    # Kleene: NULL or TRUE == TRUE; NULL and FALSE == FALSE; NULL and TRUE == NULL
    assert ev(S.BoolOp("or", [n, t])) == (True, True)
    assert ev(S.BoolOp("and", [n, f]))[1] is True and ev(S.BoolOp("and", [n, f]))[0] is False
    assert ev(S.BoolOp("and", [n, t]))[1] is False
    assert ev(S.BoolOp("not", [n]))[1] is False
    # arithmetic propagates NULL
    assert ev(S.Const(1) + n)[1] is False
    # IS NULL / COALESCE
    assert ev(S.IsNull(n)) == (True, True)
    v = S.eval_scalar(S.Coalesce([n, S.Const(3)]), {}, ctx)
    assert int(np.asarray(v.data)) == 3 and bool(np.asarray(v.validity()))


def test_division_by_zero_is_null():
    ctx = S.EvalContext()
    v = S.eval_scalar(S.Const(1.0) / S.Const(0.0), {}, ctx)
    assert not bool(np.asarray(v.validity()))


def test_date_roundtrip_and_arith():
    days = jnp.asarray([0, 1, 365, 10957, 19000, -1], jnp.int32)
    y, m, d = civil_from_days(days)
    back = days_from_civil(y, m, d)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(days))
    assert np.asarray(y).tolist() == [1970, 1970, 1971, 2000, 2022, 1969]
    # 1970-01-01 + 1 month = 1970-02-01
    feb = date_add("mm", 1, jnp.asarray(0))
    assert int(np.asarray(feb)) == 31
    assert int(np.asarray(date_part("yy", date_add("yy", 5, jnp.asarray(0))))) == 1975
    # dw: 1970-01-01 was a Thursday (dw=5 with Sunday=1)
    assert int(np.asarray(date_part("dw", jnp.asarray(0)))) == 5


def test_apply_probe_passthrough(rng):
    """Apply.passthrough: rows where the predicate is true bypass the right
    side (their right-side columns are NULL) — paper §4.2.1."""
    db = Database()
    db.create_table("a", x=np.array([1.0, 2.0, 3.0, 4.0], dtype=np.float32))
    right = R.Compute(R.ConstantScan(), {"y": S.Outer("x") * S.Const(10.0)})
    plan = R.Apply(R.Scan("a"), right, kind="outer", passthrough=S.ColRef("x") > S.Const(2.5))
    ex = Executor(db.catalog)
    out = ex.execute(plan)
    valid = np.asarray(out.table.columns["y"].validity())
    data = np.asarray(out.table.columns["y"].data)
    assert valid.tolist() == [True, True, False, False]
    np.testing.assert_allclose(data[:2], [10.0, 20.0])


def test_uncorrelated_subquery_hoisted(rng):
    db = _db(rng)
    q = scan("t").compute(
        rel=col("v")
        - S.ScalarSubquery(
            R.GroupAgg(R.Scan("t"), [], {"m": R.AggSpec("avg", S.ColRef("v"))}), "m"
        )
    )
    r = db.run(q).table
    vv = np.asarray(db.catalog["t"].columns["v"].data)
    np.testing.assert_allclose(
        np.asarray(r.columns["rel"].data), vv - vv.mean(), rtol=1e-4, atol=1e-5
    )


def test_string_like_and_in(rng):
    db = Database()
    db.create_table(
        "p",
        pname=np.array(["PROMO A", "STANDARD B", "PROMO C", "ECO D"]),
        v=np.array([1.0, 2.0, 3.0, 4.0], dtype=np.float32),
    )
    from repro.core import like, in_list

    r = db.run(scan("p").filter(like(col("pname"), "PROMO%"))).table
    assert r.num_rows == 2
    r2 = db.run(scan("p").filter(in_list(col("pname"), ["ECO D", "PROMO A"]))).table
    assert r2.num_rows == 2


def test_groupagg_capacity_overflow_guard(rng):
    db = _db(rng, n=50, k=10)
    q = scan("t").group_by("k", capacity=10, s=sum_(col("v")))
    r = db.run(q).table
    assert r.num_rows == len(np.unique(np.asarray(db.catalog["t"].columns["k"].data)))


@pytest.mark.parametrize("n_keys", [3, MAX_GROUPS + 5])
def test_relagg_batchmode_matches_sort_path(rng, n_keys):
    """GroupAgg via the fused Pallas relagg kernel (batch mode, §8.2.6)
    equals the sort-based path on a dictionary key; a vocabulary past the
    kernel's group bound declines the kernel and still matches."""
    db = Database()
    n = n_keys + 500
    # every key occurs, so the vocabulary size is exactly n_keys
    flags = np.array([f"F{i}" for i in range(n_keys)])[
        np.concatenate([np.arange(n_keys), rng.integers(0, n_keys, 500)])]
    db.create_table(
        "li",
        flag=flags,
        price=rng.uniform(1, 100, n).astype(np.float32),
        qty=rng.integers(1, 10, n),
    )
    q = scan("li").filter(col("qty") > 3).group_by(
        "flag", s=sum_(col("price")), c=count_(), a=avg_(col("price"))
    )
    r_sort = db.run(q, pallas_agg=False).table
    r_pal = db.run(q, pallas_agg=True).table
    key_sort = {db.catalog["li"].columns["flag"].dictionary.decode(k): i
                for i, k in enumerate(np.asarray(r_sort.columns["flag"].data))}
    key_pal = {db.catalog["li"].columns["flag"].dictionary.decode(k): i
               for i, k in enumerate(np.asarray(r_pal.columns["flag"].data))}
    assert set(key_sort) == set(key_pal)
    for key in key_sort:
        i, j = key_sort[key], key_pal[key]
        for colname in ("s", "c", "a"):
            np.testing.assert_allclose(
                np.asarray(r_sort.columns[colname].data)[i],
                np.asarray(r_pal.columns[colname].data)[j],
                rtol=1e-4,
                err_msg=f"{key}:{colname}",
            )
