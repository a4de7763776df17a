"""TPC-H Q3 with the paper's UDFs: the ten unshipped orders of the
BUILDING segment with the highest revenue as of 1995-03-15."""
from __future__ import annotations

import numpy as np

from bench.datasets.tpch import D
from bench.reference import F64, answer, gsum

COLUMNS = {"lineitem": ("l_orderkey", "l_shipdate", "l_extendedprice",
                        "l_discount"),
           "orders": ("o_orderkey", "o_custkey", "o_orderdate",
                      "o_shippriority"),
           "customer": ("c_custkey", "c_mktsegment")}


def build():
    from repro.core import col, scan, sum_, udf

    return (
        scan("lineitem")
        .join(scan("orders"), on=("l_orderkey", "o_orderkey"))
        .join(scan("customer"), on=("o_custkey", "c_custkey"))
        .filter(udf("q3conditions", col("c_mktsegment"), col("o_orderdate"),
                    col("l_shipdate")) == 1)
        .group_by(
            "l_orderkey", "o_orderdate", "o_shippriority",
            revenue=sum_(udf("discount_price", col("l_extendedprice"),
                             col("l_discount"))),
        )
        .sort(("revenue", False), limit=10)
    )


def reference(data, params, p, cache):
    li, o = data.tables["lineitem"], data.tables["orders"]
    c = data.tables["customer"]
    ok = data.row("orders", "o_orderkey", li["l_orderkey"])
    odate = o["o_orderdate"][ok]
    seg = c["c_mktsegment"][data.row("customer", "c_custkey",
                                     o["o_custkey"][ok])]
    ship = li["l_shipdate"]
    d = D["1995-03-15"]
    sel = ((seg == data.code("customer", "c_mktsegment", "BUILDING"))
           & (odate < d) & (ship > d) & (ship <= d + 122))
    g, no = ok[sel], data.rows("orders")
    rev = {}
    for q in (p, F64):
        x = q.f(li["l_extendedprice"][sel]) * (q.f(1.0)
                                               - q.f(li["l_discount"][sel]))
        rev[q] = gsum(x, g, no)
    live = np.flatnonzero(np.bincount(g, minlength=no))
    return answer({"l_orderkey": o["o_orderkey"][live],
                   "o_orderdate": o["o_orderdate"][live],
                   "o_shippriority": o["o_shippriority"][live],
                   "revenue": p.out(rev[p][live])},
                  {"revenue": rev[F64][live]},
                  keys=("l_orderkey", "o_orderdate", "o_shippriority"),
                  order=(("revenue", False),), limit=10)
