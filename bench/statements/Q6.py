"""TPC-H Q6 with the paper's UDFs: revenue from discounts of 0.06 +- 0.01
on small-quantity lines shipped in 1994 (a scan, no sort or join)."""
from __future__ import annotations

import numpy as np

from bench.datasets.tpch import D
from bench.reference import F64, answer

COLUMNS = {"lineitem": ("l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice")}


def build():
    from repro.core import col, scan, sum_, udf

    return (
        scan("lineitem")
        .filter(udf("q6conditions", col("l_shipdate"), col("l_discount"),
                    col("l_quantity")) == 1)
        .agg(revenue=sum_(col("l_extendedprice") * col("l_discount")))
    )


def reference(data, params, p, cache):
    li = data.tables["lineitem"]
    # the UDF's bounds are float32 variables: 0.06 -+ 0.01 in float32
    f32 = np.float32
    lo, hi = f32(f32(0.06) - f32(0.01)), f32(f32(0.06) + f32(0.01))
    d, ship = li["l_discount"], li["l_shipdate"]
    sel = ((ship >= D["1994-01-01"]) & (ship < D["1995-01-01"])
           & (li["l_quantity"] < 24) & (d >= lo) & (d <= hi))
    rev = {q: np.sum(np.asarray(q.f(li["l_extendedprice"][sel])
                                * q.f(d[sel]), np.float64))
           for q in (p, F64)}
    return answer({"revenue": [p.out(rev[p])]}, {"revenue": [rev[F64]]},
                  valid={"revenue": [sel.any()]})
