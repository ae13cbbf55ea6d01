"""TPC-H Q1, pricing summary report, in vectorized numpy.

    SELECT l_returnflag, l_linestatus, SUM(l_quantity), SUM(l_extendedprice),
           SUM(l_extendedprice * (1 - l_discount)),
           SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
           AVG(l_quantity), AVG(l_extendedprice), AVG(l_discount), COUNT(*)
    FROM lineitem WHERE l_shipdate <= DATE '1998-12-01' - INTERVAL '[DELTA]' DAY
    GROUP BY l_returnflag, l_linestatus ORDER BY l_returnflag, l_linestatus
"""
import numpy as np

ORDER = [("l_returnflag", "asc"), ("l_linestatus", "asc")]


def answer(T, delta="90"):
    li = T["lineitem"]
    cutoff = np.datetime64("1998-12-01", "D") - int(delta)
    m = li["l_shipdate"].astype("datetime64[D]") <= cutoff
    rf = li["l_returnflag"][m].astype(str)
    ls = li["l_linestatus"][m].astype(str)
    groups, inv = np.unique(np.char.add(rf, ls), return_inverse=True)
    qty, price = li["l_quantity"][m], li["l_extendedprice"][m]
    disc, tax = li["l_discount"][m], li["l_tax"][m]
    disc_price = price * (1 - disc)

    def gsum(w):
        return np.bincount(inv, weights=w, minlength=len(groups))

    count = np.bincount(inv, minlength=len(groups))
    return {
        "l_returnflag": np.array([g[0] for g in groups], dtype=object),
        "l_linestatus": np.array([g[1] for g in groups], dtype=object),
        "sum_qty": gsum(qty),
        "sum_base_price": gsum(price),
        "sum_disc_price": gsum(disc_price),
        "sum_charge": gsum(disc_price * (1 + tax)),
        "avg_qty": gsum(qty) / count,
        "avg_price": gsum(price) / count,
        "avg_disc": gsum(disc) / count,
        "count_order": count.astype(np.int64),
    }
