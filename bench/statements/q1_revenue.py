"""``q1_revenue(@cutoff)``: TPC-H Q1's discounted revenue and line count
of the lines shipped by ``1998-12-01 - DELTA`` days, through two of the
paper's UDFs (a full lineitem scan per call), without Q1's grouping.
``DELTA`` is drawn as TPC-H's Q1 substitution rule says: uniformly from 60
to 120."""
from __future__ import annotations

import numpy as np

from bench.datasets.tpch import D
from bench.reference import F64, answer

COLUMNS = {"lineitem": ("l_shipdate", "l_extendedprice", "l_discount")}


def build():
    from repro.core import col, count_, lit, param, scan, sum_, udf

    return (scan("lineitem")
            .filter(udf("isShippedBefore", col("l_shipdate"), lit(0),
                        param("cutoff")) == 1)
            .agg(revenue=sum_(udf("discount_price", col("l_extendedprice"),
                                  col("l_discount"))),
                 n=count_()))


def bindings(rng, n, data):
    return [{"cutoff": int(D["1998-12-01"] - delta)}
            for delta in rng.integers(60, 121, n)]


def _prefix_sums(data, p, cache):
    """Lines by ship date, with running sums of their discounted prices in
    ``p`` and of their magnitudes."""
    key = ("q1_revenue", p.name)
    if key not in cache:
        li = data.tables["lineitem"]
        order = np.argsort(li["l_shipdate"], kind="stable")

        def terms(q):
            return np.asarray(q.f(li["l_extendedprice"][order])
                              * (q.f(1.0) - q.f(li["l_discount"][order])),
                              np.float64)

        cache[key] = (li["l_shipdate"][order],
                      np.concatenate([[0.0], np.cumsum(terms(p))]),
                      np.concatenate([[0.0], np.cumsum(np.abs(terms(F64)))]))
    return cache[key]


def reference(data, params, p, cache):
    ship, total, mag = _prefix_sums(data, p, cache)
    k = int(np.searchsorted(ship, params["cutoff"], side="right"))
    return answer({"revenue": [p.out(total[k])], "n": [k]},
                  {"revenue": [mag[k]]}, valid={"revenue": [k > 0]})
