"""TPC-H Q12 with the paper's UDFs: high- and low-priority line counts per
ship mode (MAIL, SHIP) of lines received late in 1995-09."""
from __future__ import annotations

import numpy as np

from bench.datasets.tpch import D
from bench.reference import answer

COLUMNS = {"lineitem": ("l_orderkey", "l_shipmode", "l_commitdate",
                        "l_receiptdate", "l_shipdate"),
           "orders": ("o_orderkey", "o_orderpriority")}


def build():
    from repro.core import col, lit, scan, sum_, udf

    return (
        scan("lineitem")
        .join(scan("orders"), on=("l_orderkey", "o_orderkey"))
        .filter(udf("q12conditions", col("l_shipmode"), col("l_commitdate"),
                    col("l_receiptdate"), col("l_shipdate")) == 1)
        .group_by(
            "l_shipmode",
            high=sum_(udf("line_count", col("o_orderpriority"), lit("high"))),
            low=sum_(udf("line_count", col("o_orderpriority"), lit("low"))),
        )
        .sort("l_shipmode")
    )


def reference(data, params, p, cache):
    li = data.tables["lineitem"]
    mode = li["l_shipmode"]
    commit, receipt = li["l_commitdate"], li["l_receiptdate"]
    sel = (np.isin(mode, data.codes("lineitem", "l_shipmode",
                                    ["MAIL", "SHIP"]))
           & (receipt >= D["1994-01-01"]) & (commit < receipt)
           & (li["l_shipdate"] < commit) & (receipt < D["1995-10-01"]))
    prio = data.tables["orders"]["o_orderpriority"][
        data.row("orders", "o_orderkey", li["l_orderkey"][sel])]
    high = np.isin(prio, data.codes("orders", "o_orderpriority",
                                    ["1-URGENT", "2-HIGH"]))
    g, n = mode[sel], len(data.vocab["lineitem"]["l_shipmode"])
    nhigh = np.bincount(g, weights=high, minlength=n)
    nall = np.bincount(g, minlength=n)
    live = np.flatnonzero(nall)
    counts = {"high": nhigh[live], "low": (nall - nhigh)[live]}
    return answer({"l_shipmode": np.asarray(
                       data.vocab["lineitem"]["l_shipmode"], object)[live],
                   **{k: p.out(v) for k, v in counts.items()}},
                  counts, keys=("l_shipmode",),
                  order=(("l_shipmode", True),))
