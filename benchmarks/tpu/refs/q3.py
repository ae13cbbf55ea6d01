"""TPC-H Q3, shipping priority, in vectorized numpy.

    SELECT l_orderkey, o_orderdate, o_shippriority,
           SUM(l_extendedprice * (1 - l_discount)) AS revenue
    FROM customer, orders, lineitem
    WHERE c_mktsegment = '[SEGMENT]' AND c_custkey = o_custkey
      AND l_orderkey = o_orderkey
      AND o_orderdate < DATE '[DATE]' AND l_shipdate > DATE '[DATE]'
    GROUP BY l_orderkey, o_orderdate, o_shippriority
    ORDER BY revenue DESC, o_orderdate

(The engine's text has no LIMIT, so every group is compared.)
"""
import numpy as np

ORDER = [("revenue", "desc"), ("o_orderdate", "asc")]


def answer(T, segment="BUILDING", date="1995-03-15"):
    day = np.datetime64(date, "D")
    cust, orders, li = T["customer"], T["orders"], T["lineitem"]
    custkeys = cust["c_custkey"][cust["c_mktsegment"] == segment]
    om = (orders["o_orderdate"].astype("datetime64[D]") < day) & np.isin(
        orders["o_custkey"], custkeys
    )
    okey = orders["o_orderkey"][om]
    odate = orders["o_orderdate"][om].astype("datetime64[D]")
    oprio = orders["o_shippriority"][om]
    lm = li["l_shipdate"].astype("datetime64[D]") > day
    lkey = li["l_orderkey"][lm]
    rev = li["l_extendedprice"][lm] * (1 - li["l_discount"][lm])
    order = np.argsort(okey)
    okey, odate, oprio = okey[order], odate[order], oprio[order]
    pos = np.searchsorted(okey, lkey)
    pos_c = np.minimum(pos, max(len(okey) - 1, 0))
    hit = (pos < len(okey)) & (okey[pos_c] == lkey) if len(okey) else np.zeros(len(lkey), bool)
    keys, inv = np.unique(lkey[hit], return_inverse=True)
    revenue = np.bincount(inv, weights=rev[hit], minlength=len(keys))
    at = np.searchsorted(okey, keys)
    date_of, prio_of = odate[at], oprio[at]
    # ORDER BY revenue DESC, o_orderdate (l_orderkey breaks exact ties)
    rank = np.lexsort((keys, date_of, -revenue))
    return {
        "l_orderkey": keys[rank].astype(np.int64),
        "o_orderdate": date_of[rank],
        "o_shippriority": prio_of[rank].astype(np.int64),
        "revenue": revenue[rank],
    }
