"""TPC-H tables from a seed: the benchmark's own copy of the generator.

Started as a copy of the engine's mini-dbgen (``repro.data.tpch.generate``),
kept here so that no change to the program changes the data the
benchmark measures.  Row counts, value domains and date ranges are
those of TPC-H v3.0.1 clause 4.2.3, and so are the columns that clause
derives from others: sparse order keys, the customers that place
orders, the four suppliers of each part, ``p_retailprice``,
``l_extendedprice``, ``l_linestatus`` and ``l_returnflag`` from the
dates, ``o_orderstatus`` and ``o_totalprice`` from the order's lines.
Text columns are word salad, not dbgen's grammar, built by whole-array
concatenation of object arrays (one Python string at a time made the
original take about 30 s at SF 1).

Same seed, same tables.  Imports numpy only.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

Tables = Dict[str, Dict[str, np.ndarray]]

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
# clause 4.2.3: lines shipped after it are open, lines received by it may be returned
CURRENTDATE = np.datetime64("1995-06-17", "D")
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINER_S1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINER_S2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
COLORS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished", "chartreuse",
    "chiffon", "chocolate", "coral", "cornflower", "cornsilk", "cream", "cyan",
    "dark", "deep", "dim", "dodger", "drab", "firebrick", "floral", "forest",
    "frosted", "gainsboro", "ghost", "goldenrod", "green", "grey", "honeydew",
    "hot", "indian", "ivory", "khaki", "lace", "lavender", "lawn", "lemon",
    "light", "lime", "linen", "magenta", "maroon", "medium", "metallic", "midnight",
    "mint", "misty", "moccasin", "navajo", "navy", "olive", "orange", "orchid",
    "pale", "papaya", "peach", "peru", "pink", "plum", "powder", "puff", "purple",
    "red", "rose", "rosy", "royal", "saddle", "salmon", "sandy", "seashell",
    "sienna", "sky", "slate", "smoke", "snow", "spring", "steel", "tan",
    "thistle", "tomato", "turquoise", "violet", "wheat", "white", "yellow",
]
WORDS = [
    "the", "quickly", "slyly", "carefully", "furiously", "express", "regular",
    "final", "ironic", "pending", "bold", "even", "silent", "blithely",
    "deposits", "accounts", "packages", "foxes", "theodolites", "pinto",
    "beans", "instructions", "dependencies", "platelets", "requests", "ideas",
    "asymptotes", "somas", "sheaves", "sauternes", "waters", "dugouts",
    "sleep", "wake", "nag", "haggle", "boost", "detect", "integrate", "among",
    "above", "according", "against", "along", "alongside",
]


def _s(values) -> np.ndarray:
    return np.array(values, dtype=object)


def _cat(*parts) -> np.ndarray:
    """Element-wise concatenation of object arrays of str and scalars."""
    out = parts[0]
    for p in parts[1:]:
        out = out + p
    return out


def _num(values, width: int = 0) -> np.ndarray:
    out = np.asarray(values).astype(str)
    return (np.strings.zfill(out, width) if width else out).astype(object)


def _rand_words(rng, n, k_lo=4, k_hi=9, vocab=WORDS) -> np.ndarray:
    """Random word-salad sentences of k_lo..k_hi words."""
    vocab = _s(vocab)
    picks = rng.integers(0, len(vocab), size=(n, k_hi))
    lens = rng.integers(k_lo, k_hi + 1, size=n)
    out = vocab[picks[:, 0]]
    for j in range(1, k_hi):
        longer = lens > j
        out[longer] = out[longer] + " " + vocab[picks[longer, j]]
    return out


def _inject_pattern(rng, comments: np.ndarray, first: str, second: str,
                    prob: float) -> np.ndarray:
    """Put '<first> ... <second>' into a fraction of the comments (the
    dbgen trick that makes q13/q16 predicates selective)."""
    hit = rng.random(comments.shape[0]) < prob
    mids = ["packages", "ironic", "", "pending accounts"]
    for i in np.nonzero(hit)[0]:
        mid = mids[rng.integers(0, len(mids))]
        sep = f" {mid} " if mid else " "
        c = comments[i]
        comments[i] = f"{c[:20]} {first}{sep}{second} {c[20:40]}"
    return comments


def _dates(rng, n, lo, hi):
    base = np.datetime64(lo, "D")
    span = int((np.datetime64(hi, "D") - base).astype(int))
    return base + rng.integers(0, span + 1, n).astype("timedelta64[D]")


def _phones(rng, nationkeys: np.ndarray) -> np.ndarray:
    n = nationkeys.shape[0]
    a = rng.integers(100, 1000, n)
    b = rng.integers(100, 1000, n)
    c = rng.integers(1000, 10000, n)
    return (_cat(_num(10 + nationkeys), "-", _num(a), "-", _num(b), "-", _num(c)))


def _line_numbers(per_order: np.ndarray) -> np.ndarray:
    total = int(per_order.sum())
    starts = np.cumsum(per_order) - per_order
    return (np.arange(total) - np.repeat(starts, per_order) + 1).astype(np.int64)


def _supplier_of(partkey: np.ndarray, i: np.ndarray, n_supp: int) -> np.ndarray:
    """The ``i``-th (0..3) supplier of each part (clause 4.2.3, PS_SUPPKEY)."""
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) % n_supp + 1


def generate(sf: float, seed: int) -> Tables:
    """All eight TPC-H tables at scale factor ``sf``; text columns are
    object arrays of str, dates ``datetime64[D]``."""
    rng = np.random.default_rng(seed)
    n_supp = max(3, int(10_000 * sf))
    n_part = max(5, int(200_000 * sf))
    n_cust = max(5, int(150_000 * sf))
    n_ord = max(10, int(1_500_000 * sf))

    region = {
        "r_regionkey": np.arange(5, dtype=np.int64),
        "r_name": np.array(REGIONS, dtype=object),
        "r_comment": (_rand_words(rng, 5)),
    }
    nation = {
        "n_nationkey": np.arange(25, dtype=np.int64),
        "n_name": np.array([n for n, _ in NATIONS], dtype=object),
        "n_regionkey": np.array([r for _, r in NATIONS], dtype=np.int64),
        "n_comment": (_rand_words(rng, 25)),
    }

    s_nat = rng.integers(0, 25, n_supp)
    supp_ids = np.arange(1, n_supp + 1)
    supplier = {
        "s_suppkey": supp_ids.astype(np.int64),
        "s_name": (_cat("Supplier#", _num(supp_ids, 9))),
        "s_address": (_rand_words(rng, n_supp, 2, 4)),
        "s_nationkey": s_nat,
        "s_phone": _phones(rng, s_nat),
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_supp), 2),
        "s_comment": _inject_pattern(
            rng, _rand_words(rng, n_supp), "Customer", "Complaints", 0.003
        ),
    }

    mfgr = rng.integers(1, 6, n_part)
    brand = mfgr * 10 + rng.integers(1, 6, n_part)
    t1 = _s(TYPE_S1)[rng.integers(0, 6, n_part)]
    t2 = _s(TYPE_S2)[rng.integers(0, 5, n_part)]
    t3 = _s(TYPE_S3)[rng.integers(0, 5, n_part)]
    colors = _s(COLORS)[rng.integers(0, len(COLORS), size=(n_part, 5))]
    part_ids = np.arange(1, n_part + 1)
    part = {
        "p_partkey": part_ids.astype(np.int64),
        "p_name": (_cat(colors[:, 0], " ", colors[:, 1], " ", colors[:, 2], " ",
                            colors[:, 3], " ", colors[:, 4])),
        "p_mfgr": (_cat("Manufacturer#", _num(mfgr))),
        "p_brand": (_cat("Brand#", _num(brand))),
        "p_type": (_cat(t1, " ", t2, " ", t3)),
        "p_size": rng.integers(1, 51, n_part),
        "p_container": (_cat(
            _s(CONTAINER_S1)[rng.integers(0, 5, n_part)], " ",
            _s(CONTAINER_S2)[rng.integers(0, 8, n_part)],
        )),
        "p_retailprice": (90_000 + (part_ids // 10) % 20_001 + 100 * (part_ids % 1000)) / 100,
        "p_comment": (_rand_words(rng, n_part, 2, 5)),
    }

    # partsupp: 4 suppliers per part
    ps_part = np.repeat(part_ids.astype(np.int64), 4)
    n_ps = ps_part.shape[0]
    ps_supp = _supplier_of(ps_part, np.tile(np.arange(4), n_part), n_supp)
    partsupp = {
        "ps_partkey": ps_part,
        "ps_suppkey": ps_supp.astype(np.int64),
        "ps_availqty": rng.integers(1, 10_000, n_ps),
        "ps_supplycost": np.round(rng.uniform(1.0, 1000.0, n_ps), 2),
        "ps_comment": (_rand_words(rng, n_ps, 3, 8)),
    }

    c_nat = rng.integers(0, 25, n_cust)
    cust_ids = np.arange(1, n_cust + 1)
    customer = {
        "c_custkey": cust_ids.astype(np.int64),
        "c_name": (_cat("Customer#", _num(cust_ids, 9))),
        "c_address": (_rand_words(rng, n_cust, 2, 4)),
        "c_nationkey": c_nat,
        "c_phone": _phones(rng, c_nat),
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_cust), 2),
        "c_mktsegment": np.array(SEGMENTS, dtype=object)[rng.integers(0, 5, n_cust)],
        "c_comment": (_rand_words(rng, n_cust)),
    }

    # orders: customers whose key is a multiple of 3 place none; of
    # every 32 order keys only the first 8 are used
    with_orders = cust_ids[cust_ids % 3 != 0]
    o_cust = with_orders[rng.integers(0, len(with_orders), n_ord)]
    o_date = _dates(rng, n_ord, "1992-01-01", "1998-08-02")
    k = np.arange(n_ord, dtype=np.int64)
    orders = {
        "o_orderkey": (k // 8) * 32 + k % 8 + 1,
        "o_custkey": o_cust.astype(np.int64),
        "o_orderdate": o_date,
        "o_orderpriority": np.array(PRIORITIES, dtype=object)[rng.integers(0, 5, n_ord)],
        "o_clerk": (_cat("Clerk#", _num(rng.integers(1, max(1, int(1000 * sf)) + 1, n_ord), 9))),
        "o_shippriority": np.zeros(n_ord, dtype=np.int64),
        "o_comment": _inject_pattern(
            rng, _rand_words(rng, n_ord), "special", "requests", 0.01
        ),
    }

    # lineitem: 1..7 lines per order
    per_order = rng.integers(1, 8, n_ord)
    l_ordkey = np.repeat(orders["o_orderkey"], per_order)
    n_li = l_ordkey.shape[0]
    l_odate = np.repeat(o_date, per_order)
    l_part = rng.integers(1, n_part + 1, n_li)
    # the supplier is one of the part's 4 in partsupp
    l_supp = _supplier_of(l_part, rng.integers(0, 4, n_li), n_supp)
    quantity = rng.integers(1, 51, n_li).astype(np.float64)
    extended = np.round(quantity * part["p_retailprice"][l_part - 1], 2)
    discount = rng.integers(0, 11, n_li) / 100
    tax = rng.integers(0, 9, n_li) / 100
    ship_lag = rng.integers(1, 122, n_li).astype("timedelta64[D]")
    commit_lag = rng.integers(30, 91, n_li).astype("timedelta64[D]")
    receipt_lag = rng.integers(1, 31, n_li).astype("timedelta64[D]")
    l_ship = l_odate + ship_lag
    l_receipt = l_ship + receipt_lag
    returned = np.array(["R", "A"], dtype=object)[rng.integers(0, 2, n_li)]
    shipped = l_ship <= CURRENTDATE
    lineitem = {
        "l_orderkey": l_ordkey.astype(np.int64),
        "l_partkey": l_part.astype(np.int64),
        "l_suppkey": l_supp.astype(np.int64),
        "l_linenumber": _line_numbers(per_order),
        "l_quantity": quantity,
        "l_extendedprice": extended,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": np.where(l_receipt <= CURRENTDATE, returned, "N").astype(object),
        "l_linestatus": np.where(shipped, "F", "O").astype(object),
        "l_shipdate": l_ship,
        "l_commitdate": l_odate + commit_lag,
        "l_receiptdate": l_receipt,
        "l_shipinstruct": np.array(INSTRUCTS, dtype=object)[rng.integers(0, 4, n_li)],
        "l_shipmode": np.array(SHIPMODES, dtype=object)[rng.integers(0, 7, n_li)],
        "l_comment": (_rand_words(rng, n_li, 2, 5)),
    }

    # an order is F when all its lines are, O when none is, P otherwise
    starts = np.cumsum(per_order) - per_order
    n_shipped = np.add.reduceat(shipped.astype(np.int64), starts)
    status = np.where(n_shipped == per_order, "F", np.where(n_shipped == 0, "O", "P"))
    totals = np.round(np.add.reduceat(extended * (1 + tax) * (1 - discount), starts), 2)
    orders = {
        "o_orderkey": orders["o_orderkey"],
        "o_custkey": orders["o_custkey"],
        "o_orderstatus": status.astype(object),
        "o_totalprice": totals,
        **{c: v for c, v in orders.items() if c not in ("o_orderkey", "o_custkey")},
    }

    return {
        "region": region,
        "nation": nation,
        "supplier": supplier,
        "part": part,
        "partsupp": partsupp,
        "customer": customer,
        "orders": orders,
        "lineitem": lineitem,
    }
