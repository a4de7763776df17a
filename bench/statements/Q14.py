"""TPC-H Q14 with the paper's UDFs: the promotion share of revenue from
lines shipped in 1995-09."""
from __future__ import annotations

import numpy as np

from bench.datasets.tpch import D
from bench.reference import F64, answer

COLUMNS = {"lineitem": ("l_partkey", "l_shipdate", "l_extendedprice",
                        "l_discount"),
           "part": ("p_partkey", "p_type")}


def build():
    from repro.core import col, dateadd, lit, scan, sum_, udf

    lo = lit(D["1995-09-01"])
    return (
        scan("lineitem")
        .join(scan("part"), on=("l_partkey", "p_partkey"))
        .filter((col("l_shipdate") >= lo)
                & (col("l_shipdate") < dateadd("mm", 1, lo)))
        .agg(
            promo=sum_(udf("promo_disc", col("p_type"), col("l_extendedprice"),
                           col("l_discount"))),
            total=sum_(udf("discount_price", col("l_extendedprice"),
                           col("l_discount"))),
        )
        .compute(promo_revenue=col("promo") * 100.0 / col("total"))
        .project("promo_revenue")
    )


def reference(data, params, p, cache):
    li = data.tables["lineitem"]
    ship = li["l_shipdate"]
    sel = (ship >= D["1995-09-01"]) & (ship < D["1995-10-01"])
    vocab = data.vocab["part"]["p_type"]
    promo_codes = [i for i, w in enumerate(vocab) if w.startswith("PROMO")]
    promo = np.isin(data.tables["part"]["p_type"][
        data.row("part", "p_partkey", li["l_partkey"][sel])], promo_codes)
    share = {}
    for q in (p, F64):
        x = np.asarray(q.f(li["l_extendedprice"][sel])
                       * (q.f(1.0) - q.f(li["l_discount"][sel])), np.float64)
        share[q] = (q.f(q.out(x[promo].sum())) * q.f(100.0)
                    / q.f(q.out(x.sum())))
    return answer({"promo_revenue": [p.out(share[p])]},
                  {"promo_revenue": [abs(float(share[F64]))]},
                  valid={"promo_revenue": [sel.any()]})
