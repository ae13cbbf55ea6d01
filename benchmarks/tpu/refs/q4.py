"""TPC-H Q4, order priority checking, in vectorized numpy.

    SELECT o_orderpriority, COUNT(*) AS order_count FROM orders
    WHERE o_orderdate >= DATE '[DATE]'
      AND o_orderdate < DATE '[DATE]' + INTERVAL '3' MONTH
      AND EXISTS (SELECT * FROM lineitem
                  WHERE l_orderkey = o_orderkey AND l_commitdate < l_receiptdate)
    GROUP BY o_orderpriority ORDER BY o_orderpriority

``date_lo``/``date_hi`` are the two ends of the three months.
"""
import numpy as np

ORDER = [("o_orderpriority", "asc")]


def answer(T, date_lo="1993-07-01", date_hi="1993-10-01"):
    orders, li = T["orders"], T["lineitem"]
    od = orders["o_orderdate"].astype("datetime64[D]")
    om = (od >= np.datetime64(date_lo, "D")) & (od < np.datetime64(date_hi, "D"))
    late = li["l_orderkey"][
        li["l_commitdate"].astype("datetime64[D]") < li["l_receiptdate"].astype("datetime64[D]")
    ]
    om &= np.isin(orders["o_orderkey"], late)
    prio, count = np.unique(orders["o_orderpriority"][om].astype(str), return_counts=True)
    return {
        "o_orderpriority": prio.astype(object),
        "order_count": count.astype(np.int64),
    }
