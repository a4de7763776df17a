"""TPC-H Q1 with the paper's UDFs: pricing summary per (returnflag,
linestatus) of the lines shipped by 90 days before 1998-12-01."""
from __future__ import annotations

import numpy as np

from bench.datasets.tpch import D
from bench.reference import F64, answer, gsum

COLUMNS = {"lineitem": ("l_shipdate", "l_returnflag", "l_linestatus",
                        "l_quantity", "l_extendedprice", "l_discount",
                        "l_tax")}


def build():
    from repro.core import avg_, col, count_, lit, scan, sum_, udf

    return (
        scan("lineitem")
        .filter(udf("isShippedBefore", col("l_shipdate"), lit(-90),
                    lit(D["1998-12-01"])) == 1)
        .group_by(
            "l_returnflag", "l_linestatus",
            sum_qty=sum_(col("l_quantity")),
            sum_base=sum_(col("l_extendedprice")),
            sum_disc_price=sum_(udf("discount_price", col("l_extendedprice"),
                                    col("l_discount"))),
            sum_charge=sum_(udf("discount_taxprice", col("l_extendedprice"),
                                col("l_discount"), col("l_tax"))),
            avg_qty=avg_(col("l_quantity")),
            avg_price=avg_(col("l_extendedprice")),
            count_order=count_(),
        )
    )


def reference(data, params, p, cache):
    li = data.tables["lineitem"]
    sel = li["l_shipdate"] <= D["1998-12-01"] - 90
    nls = len(data.vocab["lineitem"]["l_linestatus"])
    n = len(data.vocab["lineitem"]["l_returnflag"]) * nls
    g = li["l_returnflag"][sel] * nls + li["l_linestatus"][sel]
    cnt = np.bincount(g, minlength=n)
    def terms(q):
        price = q.f(li["l_extendedprice"][sel])
        disc_price = price * (q.f(1.0) - q.f(li["l_discount"][sel]))
        return {"sum_qty": q.f(li["l_quantity"][sel]), "sum_base": price,
                "sum_disc_price": disc_price,
                "sum_charge": disc_price * (q.f(1.0)
                                            + q.f(li["l_tax"][sel]))}

    sums = {k: gsum(x, g, n) for k, x in terms(p).items()}
    mags = {k: gsum(np.abs(np.asarray(x, np.float64)), g, n)
            for k, x in terms(F64).items()}
    live = np.flatnonzero(cnt)
    cols = {
        "l_returnflag": np.asarray(data.vocab["lineitem"]["l_returnflag"],
                                   object)[live // nls],
        "l_linestatus": np.asarray(data.vocab["lineitem"]["l_linestatus"],
                                   object)[live % nls],
        "count_order": cnt[live],
    }
    mag = {}
    for name, s in sums.items():
        cols[name], mag[name] = p.out(s[live]), mags[name][live]
    for avg, of in (("avg_qty", "sum_qty"), ("avg_price", "sum_base")):
        cols[avg] = p.out(sums[of][live] / cnt[live])
        mag[avg] = mags[of][live] / cnt[live]
    return answer(cols, mag, keys=("l_returnflag", "l_linestatus"))
