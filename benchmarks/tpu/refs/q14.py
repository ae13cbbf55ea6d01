"""TPC-H Q14, promotion effect, in vectorized numpy.

    SELECT 100.00 * SUM(CASE WHEN p_type LIKE 'PROMO%'
                             THEN l_extendedprice * (1 - l_discount) ELSE 0 END)
           / SUM(l_extendedprice * (1 - l_discount)) AS promo_revenue
    FROM lineitem, part
    WHERE l_partkey = p_partkey
      AND l_shipdate >= DATE '[DATE]' AND l_shipdate < DATE '[DATE]' + INTERVAL '1' MONTH

``date_lo``/``date_hi`` are the month's ends.
"""
import numpy as np

ORDER = []


def answer(T, date_lo="1995-09-01", date_hi="1995-10-01"):
    li, part = T["lineitem"], T["part"]
    ship = li["l_shipdate"].astype("datetime64[D]")
    m = (ship >= np.datetime64(date_lo, "D")) & (ship < np.datetime64(date_hi, "D"))
    order = np.argsort(part["p_partkey"])
    keys = part["p_partkey"][order]
    lpk = li["l_partkey"][m]
    pos = np.minimum(np.searchsorted(keys, lpk), len(keys) - 1)
    hit = keys[pos] == lpk
    promo = np.char.startswith(part["p_type"][order][pos[hit]].astype(str), "PROMO")
    rev = (li["l_extendedprice"][m] * (1 - li["l_discount"][m]))[hit]
    return {"promo_revenue": np.array([100.0 * rev[promo].sum() / rev.sum()])}
