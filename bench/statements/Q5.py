"""TPC-H Q5 with the paper's UDFs: revenue per nation of ASIA in 1994
from suppliers and customers of the same nation."""
from __future__ import annotations

import numpy as np

from bench.datasets.tpch import D
from bench.reference import F64, answer, gsum

COLUMNS = {"lineitem": ("l_orderkey", "l_suppkey", "l_extendedprice",
                        "l_discount"),
           "orders": ("o_orderkey", "o_custkey", "o_orderdate"),
           "customer": ("c_custkey", "c_nationkey"),
           "supplier": ("s_suppkey", "s_nationkey"),
           "nation": ("n_nationkey", "n_name", "n_regionkey"),
           "region": ("r_regionkey", "r_name")}


def build():
    from repro.core import col, scan, sum_, udf

    return (
        scan("lineitem")
        .join(scan("orders"), on=("l_orderkey", "o_orderkey"))
        .join(scan("customer"), on=("o_custkey", "c_custkey"))
        .join(scan("supplier"), on=("l_suppkey", "s_suppkey"))
        .join(scan("nation"), on=("s_nationkey", "n_nationkey"))
        .join(scan("region"), on=("n_regionkey", "r_regionkey"))
        .filter(col("c_nationkey") == col("s_nationkey"))
        .filter(udf("q5conditions", col("r_name"), col("o_orderdate")) == 1)
        .group_by("n_name",
                  revenue=sum_(udf("discount_price", col("l_extendedprice"),
                                   col("l_discount"))))
        .sort(("revenue", False))
    )


def reference(data, params, p, cache):
    t = data.tables
    li, o = t["lineitem"], t["orders"]
    ok = data.row("orders", "o_orderkey", li["l_orderkey"])
    odate = o["o_orderdate"][ok]
    cnat = t["customer"]["c_nationkey"][
        data.row("customer", "c_custkey", o["o_custkey"][ok])]
    snat = t["supplier"]["s_nationkey"][
        data.row("supplier", "s_suppkey", li["l_suppkey"])]
    rname = t["region"]["r_name"][t["nation"]["n_regionkey"][snat]]
    sel = ((cnat == snat) & (rname == data.code("region", "r_name", "ASIA"))
           & (odate >= D["1994-01-01"]) & (odate < D["1995-01-01"]))
    g = t["nation"]["n_name"][snat[sel]]
    n = len(data.vocab["nation"]["n_name"])
    rev = {}
    for q in (p, F64):
        x = q.f(li["l_extendedprice"][sel]) * (q.f(1.0)
                                               - q.f(li["l_discount"][sel]))
        rev[q] = gsum(x, g, n)
    live = np.flatnonzero(np.bincount(g, minlength=n))
    return answer({"n_name": np.asarray(data.vocab["nation"]["n_name"],
                                        object)[live],
                   "revenue": p.out(rev[p][live])},
                  {"revenue": rev[F64][live]},
                  keys=("n_name",), order=(("revenue", False),))
