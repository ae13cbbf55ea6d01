"""The comparison that decides ``correct``: one answer against its reference.

An answer is a dict of column name to host numpy array, as
``TensorFrame.to_dict()`` returns it and as the references in ``refs/``
build it.  Two things are compared:

- the exact part: the column names, the row count, every non-float
  cell (keys, strings, dates, counts), and the order of the rows under
  the query's ORDER BY.  Any difference makes the answer wrong.
- the float cells: the widest relative gap
  ``|got - want| / max(|got|, |want|, 1)``, which the caller holds
  against the configuration's limit.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

# Two ORDER BY keys whose relative gap is under this count as a tie, so
# that summation order cannot flip rows of (nearly) equal revenue.  A
# float64 answer's own gap is some 1e-13; a misordering that matters is
# far wider.
ORDER_TIE = 1e-6


def _canon(a) -> np.ndarray:
    a = np.asarray(a)
    if np.issubdtype(a.dtype, np.datetime64):
        return a.astype("datetime64[D]").astype(np.int64)
    if a.dtype.kind == "f":
        return a.astype(np.float64)
    if a.dtype.kind in "iub":
        return a.astype(np.int64)
    return np.array(["\0null" if v is None else str(v) for v in a.tolist()])


def _order_ok(cols: Dict[str, np.ndarray], order: List[Tuple[str, str]]) -> bool:
    """True when consecutive rows are sorted under ``order``.  Two float
    keys within ``ORDER_TIE`` of each other may come in either order,
    and then decide the pair: the engine orders by its own float value,
    so the next key only orders rows whose float keys are equal."""
    n = len(next(iter(cols.values()))) if cols else 0
    if n < 2 or not order:
        return True
    undecided = np.ones(n - 1, dtype=bool)
    for name, direction in order:
        a, b = cols[name][:-1], cols[name][1:]
        close = a == b
        if a.dtype.kind == "f":
            close = np.abs(a - b) <= ORDER_TIE * np.maximum(np.maximum(np.abs(a), np.abs(b)), 1.0)
        wrong = (a > b) if direction == "asc" else (a < b)
        if np.any(undecided & ~close & wrong):
            return False
        undecided &= a == b
    return True


def compare(got: Dict, want: Dict, order: List[Tuple[str, str]]) -> Tuple[Optional[str], float]:
    """Returns ``(what is wrong or None, widest relative float gap)``."""
    if set(got) != set(want):
        return f"columns {sorted(got)} != {sorted(want)}", float("inf")
    g = {k: _canon(v) for k, v in got.items()}
    w = {k: _canon(v) for k, v in want.items()}
    n = len(next(iter(w.values()))) if w else 0
    if any(len(v) != n for v in g.values()):
        return f"{len(next(iter(g.values())))} rows, want {n}", float("inf")
    if not _order_ok(g, order):
        return f"rows not in ORDER BY {order}", float("inf")
    exact = sorted(k for k, v in w.items() if v.dtype.kind != "f")
    floats = sorted(k for k, v in w.items() if v.dtype.kind == "f")
    if exact:
        # match rows by their exact cells, then floats for duplicate keys
        keys = exact + floats
        gi = np.lexsort([g[k] for k in reversed(keys)])
        wi = np.lexsort([w[k] for k in reversed(keys)])
        g = {k: v[gi] for k, v in g.items()}
        w = {k: v[wi] for k, v in w.items()}
        for k in exact:
            if g[k].dtype != w[k].dtype or not np.array_equal(g[k], w[k]):
                return f"column {k} differs", float("inf")
    gap = 0.0
    for k in floats:
        a, b = g[k], w[k]
        nan_a, nan_b = np.isnan(a), np.isnan(b)
        if not np.array_equal(nan_a, nan_b):
            return f"column {k}: null or NaN where the reference has none", float("inf")
        a, b = a[~nan_a], b[~nan_b]
        if a.size:
            rel = np.abs(a - b) / np.maximum(np.maximum(np.abs(a), np.abs(b)), 1.0)
            gap = max(gap, float(rel.max()))
    return None, gap
