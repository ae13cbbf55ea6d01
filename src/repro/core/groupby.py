"""Group-by aggregation (paper §IV-B).

Main path = Alg. 2 (MojoFrame Transposed Group-By), TPU-adapted:

1. gather the k key columns into row-major layout ("transpose"),
2. build one immutable composite key per row in a single pass
   (exact int64 packing; hash fallback) — ``hashing.composite_key``,
3. find distinct keys by sort + run boundaries (Mojo Dict replacement),
4. aggregate with segment ops.

Baselines for the Fig. 11 ablation:
- ``incremental_group_ids``: Alg. 1 (Pandas column-order incremental
  sparse-to-dense), vectorized in numpy, and
- ``pydict_group_ids``: the "PandasMojo" pathology — a Python dict of
  per-row tuples built row-by-row (the mutable-key deep-copy analog).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from . import hashing
from .agg import normalize_specs, segment_agg
from .frame import INT, TensorFrame
from .prims import to_host


class GroupBy:
    def __init__(self, frame: TensorFrame, keys: List[str]):
        self.frame = frame
        self.keys = keys
        packed, self.exact, dense_domain = hashing.composite_key(frame, keys)
        self.uniques, self.gids, self.m = hashing.distinct(packed, dense_domain)
        # representative (first-occurrence) row per group
        if frame.nrows:
            self.rep = jax.ops.segment_min(
                jnp.arange(frame.nrows, dtype=INT), self.gids, self.m
            )
        else:
            self.rep = jnp.zeros((0,), dtype=INT)

    @property
    def ngroups(self) -> int:
        return self.m

    def agg(self, specs) -> TensorFrame:
        specs = normalize_specs(specs)
        # key columns come from the representative rows, preserving
        # original values (and dictionaries) exactly
        out = self.frame.take(self.rep, stats="subset").select(self.keys)
        for out_name, fn, colname in specs:
            vals = segment_agg(self.frame, self.gids, self.m, fn, colname)
            if fn == "first":
                meta = self.frame.meta(colname)
                if meta.kind == "dict":
                    out = out._append_int_column(out_name, vals, "dict", meta.dictionary)
                    continue
                if meta.kind in ("date", "bool"):
                    out = out._append_int_column(out_name, vals, meta.kind)
                    continue
            if jnp.issubdtype(vals.dtype, jnp.floating):
                out = out._append_float_column(out_name, vals)
            else:
                out = out._append_int_column(out_name, vals)
        # the grouped output is unique by construction on its key
        # combination: seed the stats cache so a downstream
        # join(algorithm='auto') direct-addresses without a sort test.
        # Not when an aggregate output overwrote a key column — its
        # values are no longer the group keys.
        if not (set(self.keys) & {name for name, _, _ in specs}):
            out.set_stats(self.keys, unique=True, distinct=self.m)
            if len(self.keys) == 1:
                out.set_stats(self.keys[0], unique=True, distinct=self.m)
        return out

    def size(self, name: str = "size") -> TensorFrame:
        return self.agg([(name, "size", "")])

    def group_ids(self) -> jax.Array:
        return self.gids


def unique_rows(frame: TensorFrame, keys: List[str]) -> TensorFrame:
    gb = GroupBy(frame, keys)
    out = frame.take(gb.rep, stats="subset").select(keys)
    out.set_stats(keys, unique=True, distinct=gb.m)
    if len(keys) == 1:
        out.set_stats(keys[0], unique=True, distinct=gb.m)
    return out


def nunique_column(frame: TensorFrame, name: str) -> int:
    codes, _ = hashing.key_codes(frame, name)
    _, _, m = hashing.distinct(codes)
    # cache on the source frame: a later join build against this column
    # skips its uniqueness sort test
    if not frame.has_nulls(name):
        frame.set_stats(name, unique=(m == frame.nrows), distinct=m)
    return m


# ----------------------------------------------------------------------
# Fig. 11 ablation baselines (benchmark-only)
# ----------------------------------------------------------------------
def incremental_group_ids(cols: Sequence[np.ndarray]) -> np.ndarray:
    """Alg. 1: per-column incremental sparse-to-dense composite building
    (the Pandas strategy).  n re-densifications of the running key."""
    n = cols[0].shape[0]
    ids = np.zeros(n, dtype=np.int64)
    for c in cols:
        _, cc = np.unique(c, return_inverse=True)
        card = cc.max() + 1 if n else 1
        pairs = ids * np.int64(card) + cc
        _, ids = np.unique(pairs, return_inverse=True)
    return ids


def pydict_group_ids(cols: Sequence[np.ndarray]) -> np.ndarray:
    """'PandasMojo' analog: row-by-row tuple keys through a Python dict
    (what Mojo's deep-copying Dict forces; Fig. 11 right)."""
    n = cols[0].shape[0]
    ids = np.empty(n, dtype=np.int64)
    seen: Dict[tuple, int] = {}
    host = [np.asarray(c) for c in cols]
    for i in range(n):
        key = tuple(c[i] for c in host)
        gid = seen.get(key)
        if gid is None:
            gid = len(seen)
            seen[key] = gid
        ids[i] = gid
    return ids


def transposed_group_ids(cols: Sequence[np.ndarray]) -> np.ndarray:
    """Alg. 2 key-building in isolation (for the operator benchmark):
    one-pass packed composite + sort-based distinct."""
    arrs = [jnp.asarray(np.asarray(c).astype(np.int64)) for c in cols]
    packed = jnp.zeros(arrs[0].shape, dtype=INT)
    if arrs[0].shape[0]:
        # all k cardinalities in ONE device fetch (was: one int(a.max())
        # host sync per key column)
        cards = to_host(jnp.stack([a.max() for a in arrs]), "groupby.transposed") + 1
    else:
        cards = np.ones((len(arrs),), dtype=np.int64)
    for a, card in zip(arrs, cards):
        packed = packed * np.int64(max(1, int(card))) + a
    _, gids, _ = hashing.distinct(packed)
    return to_host(gids, "groupby.transposed")
