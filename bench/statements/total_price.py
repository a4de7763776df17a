"""``total_price(@key)``: one customer's row with the paper's Figure 1 UDF
summing that customer's order prices.  Keys are drawn uniformly over the
customers, as TPC-H draws its substitution parameters; a third of them
have no orders."""
from __future__ import annotations

import numpy as np

from bench.reference import F64, answer

COLUMNS = {"customer": ("c_custkey",),
           "orders": ("o_custkey", "o_totalprice")}


def build():
    from repro.core import col, param, scan, udf

    return (scan("customer").filter(col("c_custkey") == param("key"))
            .compute(total=udf("total_price", col("c_custkey")))
            .project("c_custkey", "total"))


def bindings(rng, n, data):
    return [{"key": int(k)}
            for k in rng.integers(1, data.rows("customer") + 1, n)]


def _per_customer(data, p, cache):
    key = ("total_price", p.name)
    if key not in cache:
        o = data.tables["orders"]
        nc = data.rows("customer") + 1   # keys run from 1
        cache[key] = (
            np.bincount(o["o_custkey"], weights=np.asarray(
                p.f(o["o_totalprice"]), np.float64), minlength=nc),
            np.bincount(o["o_custkey"], weights=np.abs(np.asarray(
                F64.f(o["o_totalprice"]))), minlength=nc))
    return cache[key]


def reference(data, params, p, cache):
    total, mag = _per_customer(data, p, cache)
    k = params["key"]
    # no order at all sums to NULL, which the UDF returns as 0
    return answer({"c_custkey": [k], "total": [p.out(total[k])]},
                  {"total": [mag[k]]}, keys=("c_custkey",))
