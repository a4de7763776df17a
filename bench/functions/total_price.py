"""Figure 1's ``total_price(@key)`` of the Froid paper, pointed at TPC-H's
orders: the sum of a customer's order prices, 0 where there is none."""
from __future__ import annotations


def register(db) -> None:
    from repro.core import UdfBuilder, col, lit, param, scan, sum_, var

    u = UdfBuilder("total_price", [("key", "int32")], "float32")
    u.declare("price", "float32")
    u.select({"price": sum_(col("o_totalprice"))}, frm=scan("orders"),
             where=col("o_custkey") == param("key"))
    with u.if_(var("price").is_null()):
        u.return_(lit(0.0))
    u.return_(var("price"))
    db.create_function(u.build())
