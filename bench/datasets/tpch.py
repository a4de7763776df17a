"""TPC-H tables, generated in bulk from a seed by the rules of the TPC-H
specification's section 4.2.3 (what dbgen does), with vectorised numpy.

Every column of the eight tables is there.  Keys are dbgen's: 1-based,
orders' keys sparse (the first 8 of every 32), customers whose key is a
multiple of 3 place no orders, each order has 1 to 7 lines stored together,
a line's supplier is one of its part's four, its price is its quantity
times the part's retail price, and the return flag, the line status, the
order status and the order's total price follow from the lines as the
specification says.  Each seed gets the same row counts: the orders' line
counts are one fixed, exactly uniform set of 1 to 7 in the seed's order.

String columns are dictionary codes over sorted vocabularies.  Free-text
columns (comments, addresses) draw their codes from a pool of texts built
from dbgen's word lists at the specification's lengths.  Dates are day
numbers since 1970-01-01.
"""
from __future__ import annotations

import datetime

import numpy as np

from bench.data import Data, pick


def day(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


#: the literal dates the queries use, and the DATEADDs of them they compute
D = {
    "1994-01-01": day(1994, 1, 1),
    "1995-01-01": day(1995, 1, 1),
    "1995-03-15": day(1995, 3, 15),
    "1995-09-01": day(1995, 9, 1),
    "1995-10-01": day(1995, 10, 1),
    "1998-12-01": day(1998, 12, 1),
}
STARTDATE, CURRENTDATE, ENDDATE = day(1992, 1, 1), day(1995, 6, 17), \
    day(1998, 12, 31)

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
SHIPINSTR = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
TYPES = [f"{a} {b} {c}"
         for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush "
    "brown burlywood burnished chartreuse chiffon chocolate coral cornflower "
    "cornsilk cream cyan dark deep dim dodger drab firebrick floral forest "
    "frosted gainsboro ghost goldenrod green grey honeydew hot indian ivory "
    "khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid "
    "pale papaya peach peru pink plum powder puff purple red rose rosy royal "
    "saddle salmon sandy seashell sienna sky slate smoke snow spring steel "
    "tan thistle tomato turquoise violet wheat white yellow").split()
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
#: dbgen's text grammar, shortened: sentences of these parts of speech
WORDS = {
    "noun": "foxes ideas theodolites pinto beans instructions dependencies "
            "excuses platelets asymptotes courts dolphins multipliers "
            "sauternes warthogs frets dinos attainments somas patterns "
            "forges braids frays warhorses dugouts notornis epitaphs pearls "
            "tithes waters orbits gifts sheaves depths sentiments decoys "
            "realms pains grouches escapades".split(),
    "verb": "sleep wake are cajole haggle nag use boost affix detect "
            "integrate maintain nod was lose sublate solve thrash promise "
            "engage hinder print x-ray breach eat grow impress mold poach "
            "serve run dazzle snooze doze unwind kindle play hang believe "
            "doubt".split(),
    "adjective": "furious sly careful blithe quick fluffy slow quiet "
                 "ruthless thin close dogged daring brave stealthy permanent "
                 "enticing idle busy regular final ironic even bold "
                 "silent".split(),
    "adverb": "sometimes always never furiously slyly carefully blithely "
              "quickly fluffily slowly quietly ruthlessly thinly closely "
              "doggedly daringly bravely stealthily permanently enticingly "
              "idly busily regularly finally ironically evenly boldly "
              "silently".split(),
    "preposition": "about above across after against along among around at "
                   "atop before behind beneath beside besides between beyond "
                   "by despite during except for from inside into near of on "
                   "outside over past since through to toward under until up "
                   "upon without with within".split(),
    "terminator": list(".;:?!-"),
}
SENTENCE = ("adjective", "noun", "verb", "adverb", "preposition", "adjective",
            "noun", "terminator")
ALNUM = np.frombuffer(b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
                      b"0123456789,.", np.uint8)
#: distinct texts a free-text column draws from
TEXT_POOL = 4096


def rows(scale_factor: float) -> dict:
    """Rows per table at a scale factor; lineitem's follows from the orders
    (about 4 a order: 5,999,995 at scale factor 1)."""
    sf = scale_factor
    part = max(int(200_000 * sf), 50)
    orders = max(int(1_500_000 * sf), 100)
    return {"region": len(REGIONS), "nation": len(NATIONS),
            "supplier": max(int(10_000 * sf), 20),
            "customer": max(int(150_000 * sf), 60),
            "part": part, "partsupp": 4 * part, "orders": orders,
            "lineitem": int(line_counts(orders).sum()),
            "clerks": max(int(1_000 * sf), 1)}


def line_counts(orders: int) -> np.ndarray:
    """Lines per order, 1 to 7, exactly uniform, in order of count."""
    return np.sort(np.arange(orders) % 7 + 1)


def _cents(rng, lo: int, hi: int, n: int) -> np.ndarray:
    """Money drawn uniformly in whole cents from ``lo`` to ``hi`` cents."""
    return rng.integers(lo, hi + 1, n) / 100.0


def _vocab(strings) -> tuple:
    """(codes, sorted vocabulary) of an array of strings."""
    words, codes = np.unique(np.asarray(strings), return_inverse=True)
    return codes.astype(np.int32), tuple(words.tolist())


def _numbered(prefix: str, keys) -> tuple:
    """``prefix`` and the key in nine digits; the vocabulary is every such
    name up to the largest key, so that code order is key order."""
    top = int(np.max(keys))
    return np.asarray(keys, np.int32) - 1, tuple(
        f"{prefix}{k:09d}" for k in range(1, top + 1))


def _text(rng, n: int, lo: int, hi: int) -> tuple:
    """Codes of ``n`` free texts of ``lo`` to ``hi`` characters, drawn
    from a pool of sentences of dbgen's words."""
    pool, sentences = min(n, TEXT_POOL), 4
    words = np.stack([rng.choice(WORDS[p], pool) for _ in range(sentences)
                      for p in SENTENCE], axis=1)
    lengths = rng.integers(lo, hi + 1, pool).tolist()
    texts = [" ".join(w)[:k].strip()
             for w, k in zip(words.tolist(), lengths)]
    codes, vocab = _vocab(texts)
    return codes[rng.integers(0, pool, n)], vocab


def _vstring(rng, n: int, lo: int, hi: int) -> tuple:
    """``n`` random alphanumeric strings of ``lo`` to ``hi`` characters
    (dbgen's addresses)."""
    chars = ALNUM[rng.integers(0, len(ALNUM), (n, hi))]
    chars[np.arange(hi) >= rng.integers(lo, hi + 1, n)[:, None]] = 0
    return _vocab(np.char.decode(chars.view(f"S{hi}").ravel(), "ascii"))


def _phones(rng, nation) -> tuple:
    n = len(nation)
    num = np.stack([np.asarray(nation) + 10, rng.integers(100, 1000, n),
                    rng.integers(100, 1000, n), rng.integers(1000, 10000, n)],
                   axis=1)
    return _vocab([f"{a:02d}-{b}-{c}-{d}" for a, b, c, d in num.tolist()])


def supplier_of(partkey, i, suppliers: int):
    """The ``i``-th (0 to 3) of a part's four suppliers."""
    s = suppliers
    return (partkey + i * (s // 4 + (partkey - 1) // s)) % s + 1


def retail_price(partkey):
    return (90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)) \
        / 100.0


def generate(data: Data, rng, scale_factor: float) -> None:
    n = rows(scale_factor)
    names = sorted(name for name, _ in NATIONS)
    data.add("region", r_regionkey=np.arange(n["region"]),
             r_name=(np.arange(n["region"]), tuple(REGIONS)),
             r_comment=_text(rng, n["region"], 31, 115))
    data.add("nation", n_nationkey=np.arange(n["nation"]),
             n_name=(np.array([names.index(m) for m, _ in NATIONS]),
                     tuple(names)),
             n_regionkey=np.array([r for _, r in NATIONS]),
             n_comment=_text(rng, n["nation"], 31, 114))

    ns = n["supplier"]
    suppkey = np.arange(1, ns + 1)
    s_nation = rng.integers(0, n["nation"], ns)
    data.add("supplier", s_suppkey=suppkey,
             s_name=_numbered("Supplier#", suppkey),
             s_address=_vstring(rng, ns, 10, 40),
             s_nationkey=s_nation, s_phone=_phones(rng, s_nation),
             s_acctbal=_cents(rng, -99_999, 999_999, ns),
             s_comment=_text(rng, ns, 25, 100))

    nc = n["customer"]
    custkey = np.arange(1, nc + 1)
    c_nation = rng.integers(0, n["nation"], nc)
    data.add("customer", c_custkey=custkey,
             c_name=_numbered("Customer#", custkey),
             c_address=_vstring(rng, nc, 10, 40),
             c_nationkey=c_nation, c_phone=_phones(rng, c_nation),
             c_acctbal=_cents(rng, -99_999, 999_999, nc),
             c_mktsegment=pick(rng, SEGMENTS, nc),
             c_comment=_text(rng, nc, 29, 116))

    npart = n["part"]
    partkey = np.arange(1, npart + 1)
    words = np.asarray(COLORS, dtype=object)[np.argpartition(
        rng.random((npart, len(COLORS))), 5, axis=1)[:, :5]]
    mfgr = rng.integers(1, 6, npart)
    data.add("part", p_partkey=partkey,
             p_name=_vocab([" ".join(w) for w in words.tolist()]),
             p_mfgr=_vocab(np.char.add("Manufacturer#", mfgr.astype(str))),
             p_brand=_vocab(np.char.add(np.char.add(
                 "Brand#", mfgr.astype(str)),
                 rng.integers(1, 6, npart).astype(str))),
             p_type=pick(rng, TYPES, npart),
             p_size=rng.integers(1, 51, npart),
             p_container=pick(rng, CONTAINERS, npart),
             p_retailprice=retail_price(partkey),
             p_comment=_text(rng, npart, 5, 22))

    nps = n["partsupp"]
    ps_part = np.repeat(partkey, 4)
    data.add("partsupp", ps_partkey=ps_part,
             ps_suppkey=supplier_of(ps_part, np.tile(np.arange(4), npart), ns),
             ps_availqty=rng.integers(1, 10_000, nps),
             ps_supplycost=_cents(rng, 100, 100_000, nps),
             ps_comment=_text(rng, nps, 49, 198))

    no = n["orders"]
    orderkey = np.arange(no) // 8 * 32 + np.arange(no) % 8 + 1
    # customers with a key that is a multiple of 3 place no orders
    pickc = rng.integers(0, nc - nc // 3, no)
    o_cust = 3 * (pickc // 2) + pickc % 2 + 1
    odate = rng.integers(STARTDATE, ENDDATE - 151 + 1, no)
    counts = rng.permutation(line_counts(no))
    nl = int(counts.sum())
    row = np.repeat(np.arange(no), counts)
    first = np.cumsum(counts) - counts
    l_part = rng.integers(1, npart + 1, nl)
    qty = rng.integers(1, 51, nl)
    price = qty * retail_price(l_part)
    disc = rng.integers(0, 11, nl) / 100.0
    tax = rng.integers(0, 9, nl) / 100.0
    ship = odate[row] + rng.integers(1, 122, nl)
    receipt = ship + rng.integers(1, 31, nl)
    # received by the current date: returned (R) or accepted (A), else N
    flag = np.where(receipt <= CURRENTDATE, 2 * rng.integers(0, 2, nl), 1)
    # shipped after the current date: open (O), else finished (F)
    status = (ship > CURRENTDATE).astype(np.int32)
    open_lines = np.bincount(row, weights=status, minlength=no)
    o_status = np.where(open_lines == counts, 1,
                        np.where(open_lines == 0, 0, 2))
    data.add("orders", o_orderkey=orderkey, o_custkey=o_cust,
             o_orderstatus=(o_status, ("F", "O", "P")),
             o_totalprice=np.round(np.bincount(
                 row, weights=price * (1 + tax) * (1 - disc), minlength=no),
                 2),
             o_orderdate=odate,
             o_orderpriority=pick(rng, PRIORITIES, no),
             o_clerk=_numbered("Clerk#", rng.integers(1, n["clerks"] + 1, no)),
             o_shippriority=np.zeros(no, np.int32),
             o_comment=_text(rng, no, 19, 78))
    data.add("lineitem", l_orderkey=orderkey[row], l_partkey=l_part,
             l_suppkey=supplier_of(l_part, rng.integers(0, 4, nl), ns),
             l_linenumber=np.arange(nl) - first[row] + 1,
             l_quantity=qty, l_extendedprice=price,
             l_discount=disc, l_tax=tax,
             l_returnflag=(flag, ("A", "N", "R")),
             l_linestatus=(status, ("F", "O")),
             l_shipdate=ship,
             l_commitdate=odate[row] + rng.integers(30, 91, nl),
             l_receiptdate=receipt,
             l_shipinstruct=pick(rng, SHIPINSTR, nl),
             l_shipmode=pick(rng, SHIPMODES, nl),
             l_comment=_text(rng, nl, 10, 43))
