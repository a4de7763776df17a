"""TPC-H Q19 with the paper's UDFs: discounted revenue of three brand,
container, quantity and size combinations shipped by air in person."""
from __future__ import annotations

import numpy as np

from bench.reference import F64, answer

COLUMNS = {"lineitem": ("l_partkey", "l_quantity", "l_shipmode",
                        "l_shipinstruct", "l_extendedprice", "l_discount"),
           "part": ("p_partkey", "p_container", "p_size", "p_brand")}

#: (brand, containers, quantity range, largest size) of the three branches
BRANCHES = (
    ("Brand#12", ["SM CASE", "SM BOX", "SM PACK", "SM PKG"], (1, 11), 5),
    ("Brand#23", ["MED BAG", "MED BOX", "MED PKG", "MED PACK"], (10, 20), 10),
    ("Brand#34", ["LG CASE", "LG BOX", "LG PACK", "LG PKG"], (20, 30), 15),
)


def build():
    from repro.core import col, scan, sum_, udf

    return (
        scan("lineitem")
        .join(scan("part"), on=("l_partkey", "p_partkey"))
        .filter(udf("q19conditions", col("p_container"), col("l_quantity"),
                    col("p_size"), col("l_shipmode"), col("l_shipinstruct"),
                    col("p_brand")) == 1)
        .agg(revenue=sum_(udf("discount_price", col("l_extendedprice"),
                              col("l_discount"))))
    )


def reference(data, params, p, cache):
    li, part = data.tables["lineitem"], data.tables["part"]
    pk = data.row("part", "p_partkey", li["l_partkey"])
    qty = li["l_quantity"]
    brand, cont = part["p_brand"][pk], part["p_container"][pk]
    size = part["p_size"][pk]
    any_branch = np.zeros(len(pk), bool)
    for b, containers, (qlo, qhi), shi in BRANCHES:
        any_branch |= ((brand == data.code("part", "p_brand", b))
                       & np.isin(cont, data.codes("part", "p_container",
                                                  containers))
                       & (qty >= qlo) & (qty <= qhi)
                       & (size >= 1) & (size <= shi))
    sel = (np.isin(li["l_shipmode"], data.codes("lineitem", "l_shipmode",
                                                ["AIR", "AIR REG"]))
           & (li["l_shipinstruct"] == data.code("lineitem", "l_shipinstruct",
                                                "DELIVER IN PERSON"))
           & any_branch)
    rev = {q: np.sum(np.asarray(q.f(li["l_extendedprice"][sel])
                                * (q.f(1.0) - q.f(li["l_discount"][sel])),
                                np.float64))
           for q in (p, F64)}
    return answer({"revenue": [p.out(rev[p])]}, {"revenue": [rev[F64]]},
                  valid={"revenue": [sel.any()]})
