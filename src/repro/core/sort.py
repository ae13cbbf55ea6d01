"""Multi-key sort.

Dictionary codes are order-isomorphic to string order (sorted
dictionaries — encoding.py), so sorting by a string column is an
integer sort on its codes: cardinality-awareness pays again.

Every key becomes a non-negative order code (its offset from the
column's bound, or for floats its rank among the column's values), the
codes pack by Horner's rule into one integer, and one stable integer
argsort orders the rows — no multi-operand lexsort, which compiles
slowly for the TPU.
"""
from __future__ import annotations

from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from . import hashing, prims
from .frame import INT, TensorFrame

_PACK_LIMIT = 1 << 62


def _order_code(frame: TensorFrame, name: str, asc: bool) -> Tuple[jax.Array, int]:
    """(codes in ``[0, card)`` ordered like the key, card)."""
    # view-aware: gathers only the sort-key column from a lazy frame
    v = frame.col_values(name)
    if frame.meta(name).kind == "float":
        return prims.float_rank(v if asc else -v), frame.nrows + 1
    lo, hi = prims.bounds(v)
    return (v - lo if asc else hi - v).astype(INT), hi - lo + 1


def sort_values(
    frame: TensorFrame,
    by: Union[str, Sequence[str]],
    ascending: Union[bool, Sequence[bool]] = True,
) -> TensorFrame:
    """Stable multi-key sort: equal-key rows keep their input order.
    That makes ``head``/``LIMIT`` after a sort deterministic and matches
    any stable reference implementation (e.g. Python's ``sorted``)."""
    by = [by] if isinstance(by, str) else list(by)
    if isinstance(ascending, bool):
        ascending = [ascending] * len(by)
    if len(ascending) != len(by):
        raise ValueError("ascending must match by")
    if frame.nrows <= 1:
        return frame.take(jnp.arange(frame.nrows, dtype=INT), stats="permutation")
    acc, card = None, 1
    for name, asc in zip(by, ascending):
        code, c = _order_code(frame, name, asc)
        if acc is None:
            acc, card = code, c
            continue
        if card * c > _PACK_LIMIT:
            # densify the codes so far: ranks keep their order
            _, acc, card = hashing.distinct(acc, lohi=(0, card - 1))
            if card * c > _PACK_LIMIT:
                _, code, c = hashing.distinct(code, lohi=(0, c - 1))
        acc, card = acc * c + code, card * c
    order = prims.argsort(acc, (0, card - 1))
    return frame.take(order, stats="permutation")
