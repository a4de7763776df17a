"""The paper's section 11 scalar UDFs that the seven TPC-H queries call,
ported from their T-SQL definitions (Froid, VLDB 2018)."""
from __future__ import annotations

from bench.datasets.tpch import D


def register(db) -> None:
    from repro.core import UdfBuilder, between, dateadd, in_list, like, lit, \
        param, udf, var

    def define(u):
        db.create_function(u.build())

    # discount_price(extprice, disc) = extprice * (1 - disc)
    u = UdfBuilder("discount_price",
                   [("extprice", "float32"), ("disc", "float32")], "float32")
    u.return_(param("extprice") * (1.0 - param("disc")))
    define(u)

    # discount_taxprice = discount_price(...) * (1 + tax), a nested call
    u = UdfBuilder("discount_taxprice", [("extprice", "float32"),
                                         ("disc", "float32"),
                                         ("tax", "float32")], "float32")
    u.return_(udf("discount_price", param("extprice"), param("disc"))
              * (1.0 + param("tax")))
    define(u)

    u = UdfBuilder("isShippedBefore", [("shipdate", "date"),
                                       ("duration", "int32"),
                                       ("stdate", "date")], "int32")
    u.declare("newdate", "date")
    u.set("newdate", dateadd("dd", param("duration"), param("stdate")))
    with u.if_(param("shipdate") > var("newdate")):
        u.return_(lit(0))
    u.return_(lit(1))
    define(u)

    u = UdfBuilder("checkDate", [("d", "date"), ("odate", "date"),
                                 ("shipdate", "date")], "int32")
    with u.if_((param("odate") < param("d"))
               & (param("shipdate") > param("d"))):
        u.return_(lit(1))
    u.return_(lit(0))
    define(u)

    u = UdfBuilder("q3conditions", [("cmkt", "str"), ("odate", "date"),
                                    ("shipdate", "date")], "int32")
    u.declare("thedate", "date", lit(D["1995-03-15"]))
    with u.if_(param("cmkt") != lit("BUILDING")):
        u.return_(lit(0))
    with u.if_(udf("checkDate", var("thedate"), param("odate"),
                   param("shipdate")) == 0):
        u.return_(lit(0))
    with u.if_(udf("isShippedBefore", param("shipdate"), lit(122),
                   var("thedate")) == 0):
        u.return_(lit(0))
    u.return_(lit(1))
    define(u)

    u = UdfBuilder("q5conditions", [("rname", "str"), ("odate", "date")],
                   "int32")
    u.declare("beginDate", "date", lit(D["1994-01-01"]))
    u.declare("newdate", "date")
    with u.if_(param("rname") != lit("ASIA")):
        u.return_(lit(0))
    with u.if_(param("odate") < var("beginDate")):
        u.return_(lit(0))
    u.set("newdate", dateadd("yy", 1, var("beginDate")))
    with u.if_(param("odate") >= var("newdate")):
        u.return_(lit(0))
    u.return_(lit(1))
    define(u)

    u = UdfBuilder("q6conditions", [("shipdate", "date"),
                                    ("discount", "float32"),
                                    ("qty", "int32")], "int32")
    u.declare("stdate", "date", lit(D["1994-01-01"]))
    u.declare("newdate", "date")
    u.set("newdate", dateadd("yy", 1, var("stdate")))
    with u.if_(param("shipdate") < var("stdate")):
        u.return_(lit(0))
    with u.if_(param("shipdate") >= var("newdate")):
        u.return_(lit(0))
    with u.if_(param("qty") >= 24):
        u.return_(lit(0))
    u.declare("val", "float32", lit(0.06))
    u.declare("epsilon", "float32", lit(0.01))
    u.declare("lowerbound", "float32")
    u.declare("upperbound", "float32")
    u.set("lowerbound", var("val") - var("epsilon"))
    u.set("upperbound", var("val") + var("epsilon"))
    with u.if_((param("discount") >= var("lowerbound"))
               & (param("discount") <= var("upperbound"))):
        u.return_(lit(1))
    u.return_(lit(0))
    define(u)

    u = UdfBuilder("q12conditions", [("shipmode", "str"),
                                     ("commitdate", "date"),
                                     ("receiptdate", "date"),
                                     ("shipdate", "date")], "int32")
    with u.if_(in_list(param("shipmode"), ["MAIL", "SHIP"])):
        u.declare("stdate", "date", lit(D["1995-09-01"]))
        u.declare("newdate", "date")
        u.set("newdate", dateadd("mm", 1, var("stdate")))
        with u.if_(param("receiptdate") < lit(D["1994-01-01"])):
            u.return_(lit(0))
        with u.if_((param("commitdate") < param("receiptdate"))
                   & (param("shipdate") < param("commitdate"))
                   & (param("receiptdate") < var("newdate"))):
            u.return_(lit(1))
    u.return_(lit(0))
    define(u)

    # line_count(oprio, mode), the paper's Q12 helper
    u = UdfBuilder("line_count", [("oprio", "str"), ("mode", "str")], "int32")
    u.declare("val", "int32", lit(0))
    with u.if_(param("mode") == lit("high")):
        with u.if_(in_list(param("oprio"), ["1-URGENT", "2-HIGH"])):
            u.set("val", lit(1))
    with u.else_():
        with u.if_(~in_list(param("oprio"), ["1-URGENT", "2-HIGH"])):
            u.set("val", lit(1))
    u.return_(var("val"))
    define(u)

    u = UdfBuilder("promo_disc", [("ptype", "str"), ("extprice", "float32"),
                                  ("disc", "float32")], "float32")
    u.declare("val", "float32")
    with u.if_(like(param("ptype"), "PROMO%")):
        u.set("val", udf("discount_price", param("extprice"), param("disc")))
    with u.else_():
        u.set("val", lit(0.0))
    u.return_(var("val"))
    define(u)

    u = UdfBuilder("q19conditions", [("pcontainer", "str"), ("lqty", "int32"),
                                     ("psize", "int32"), ("shipmode", "str"),
                                     ("shipinst", "str"), ("pbrand", "str")],
                   "int32")
    u.declare("val", "int32", lit(0))
    with u.if_(in_list(param("shipmode"), ["AIR", "AIR REG"])
               & (param("shipinst") == lit("DELIVER IN PERSON"))):
        for brand, size, (qlo, qhi), shi in (
                ("Brand#12", "SM", (1, 11), 5),
                ("Brand#23", "MED", (10, 20), 10),
                ("Brand#34", "LG", (20, 30), 15)):
            kinds = (["CASE", "BOX", "PACK", "PKG"] if size != "MED"
                     else ["BAG", "BOX", "PKG", "PACK"])
            with u.if_((param("pbrand") == lit(brand))
                       & in_list(param("pcontainer"),
                                 [f"{size} {k}" for k in kinds])
                       & between(param("lqty"), qlo, qhi)
                       & between(param("psize"), 1, shi)):
                u.set("val", lit(1))
    u.return_(var("val"))
    define(u)
