"""TPC-H Q6, forecasting revenue change, in vectorized numpy.

    SELECT SUM(l_extendedprice * l_discount) AS revenue FROM lineitem
    WHERE l_shipdate >= DATE '[DATE]' AND l_shipdate < DATE '[DATE]' + INTERVAL '1' YEAR
      AND l_discount BETWEEN [DISCOUNT] - 0.01 AND [DISCOUNT] + 0.01
      AND l_quantity < [QUANTITY]

The parameters are the literals as the SQL text spells them:
``date_lo``/``date_hi`` the year's ends, ``discount_lo``/``discount_hi``
the BETWEEN bounds.
"""
import numpy as np

ORDER = []


def answer(T, date_lo="1994-01-01", date_hi="1995-01-01", discount_lo="0.05",
           discount_hi="0.07", quantity="24"):
    li = T["lineitem"]
    ship = li["l_shipdate"].astype("datetime64[D]")
    disc, qty = li["l_discount"], li["l_quantity"]
    m = (
        (ship >= np.datetime64(date_lo, "D"))
        & (ship < np.datetime64(date_hi, "D"))
        & (disc >= float(discount_lo))
        & (disc <= float(discount_hi))
        & (qty < float(quantity))
    )
    return {"revenue": np.array([np.sum(li["l_extendedprice"][m] * disc[m])])}
