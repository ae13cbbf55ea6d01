"""TensorFrame: the paper's tensor-backed dataframe, adapted to JAX.

Representation (paper §III, Fig. 3), TPU-adapted per DESIGN.md §2:

- ``itensor``: one 2-D int64 device tensor holding ALL integer-like
  columns — raw ints, dates (days since epoch), bools (0/1) and the
  dense dictionary codes of low-cardinality non-numeric columns.
- ``ftensor``: one 2-D float device tensor holding all measures.
- high-cardinality non-numeric columns are *offloaded*
  (``OffloadedColumn``): the physical host array never moves; a device
  row indexer maps logical rows to physical positions, so relational
  ops only update the indexer (paper §III-f).
- ``columns``: the column indexer — an ordered map from logical column
  name to its physical (tensor, slot) location.  Logical column order is
  decoupled from physical slot order.

Late materialization (ISSUE 5): row-subsetting ops do not copy the
tensors.  A frame may instead carry a ``RowView`` — a list of
``ViewBlock`` s, each pairing base (itensor, ftensor) payloads with a
row-index *selection vector* — and every ``ColumnMeta`` names the block
it lives in.  ``take``/``mask_rows``/``filter``/``sort`` compose the
selection vectors (an int64 gather per block, never a payload copy) and
joins stack blocks from both sides, so a multi-join chain accumulates
indices and performs **one** payload gather per base table when
``materialize()`` fires at a pipeline exit (column decode, tensor
append, vconcat).  Column accessors (``col_values``/``col_codes``/
``valid_array``/``column``) gather single columns through the view
without materializing the rest.

Null semantics: nullable columns carry a hidden companion column
``__v__<name>`` (0/1 in the int tensor) that flows through every
relational op like any other column.

Stats cache: each frame carries ``_stats`` — per-column(-combination)
distinct counts and provable-uniqueness flags (``ColStats``), populated
by ``TensorFrame.from_store`` (zone maps), ``GroupBy`` and ``distinct``,
and consulted by ``join(algorithm="auto")`` so proving build-key
uniqueness no longer costs a full sort of the build side.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs as _obs

from .config import CONFIG
from . import encoding
from .prims import bounds, compact, to_host

INT = jnp.int64
# Sentinel stored in int/code slots of null cells (the hidden validity
# column is authoritative; the sentinel just keeps gathers in-range).
INT_NULL = np.int64(-1)

VALID_PREFIX = "__v__"


#: Bytes of the host arrays handed to the device by ``_assemble_frame``
#: (every frame built from host columns: store scans, ``from_arrays``).
UPLOADS = {"bytes_uploaded": 0}


def _uploads_reset() -> None:
    UPLOADS["bytes_uploaded"] = 0


_obs.metrics.register_group("frame", lambda: dict(UPLOADS), _uploads_reset)


def _valid_name(name: str) -> str:
    return VALID_PREFIX + name


def _is_hidden(name: str) -> bool:
    return name.startswith(VALID_PREFIX)


def float_dtype():
    return jnp.dtype(CONFIG.float_dtype)


@dataclasses.dataclass
class ColumnMeta:
    """Logical column → physical storage mapping (the column indexer).

    ``block`` names the ``RowView`` block holding the column's payload
    (always 0 for materialized frames).
    """

    name: str
    kind: str  # 'int' | 'float' | 'bool' | 'date' | 'dict' | 'obj'
    slot: int  # slot in itensor (int-like kinds) or ftensor ('float'); -1 for 'obj'
    dictionary: Optional[np.ndarray] = None  # sorted uniques for 'dict'
    block: int = 0  # RowView block index (0 when the frame is eager)

    def is_int_like(self) -> bool:
        return self.kind in ("int", "bool", "date", "dict")


@dataclasses.dataclass
class ColStats:
    """Cached per-column(-combination) statistics for algorithm picks.

    ``unique=True`` is a correctness-grade guarantee (the values — as a
    tuple, for multi-column keys — are pairwise distinct over the
    frame's rows); ``unique=False`` is a perf hint only (duplicates were
    observed in a superset of the rows — a row subset may have become
    unique, but treating it as non-unique is always safe).  ``distinct``
    is the exact non-null distinct count when known.

    ``vmin``/``vmax`` bound an int-like column's values (possibly over a
    *superset* of the rows — row subsetting and join gathers can only
    shrink the true range, so stale bounds stay valid for range
    compression).  Seeded from store zone maps, computed once (one
    fused device fetch) otherwise — they make repeat joins sync-free.
    """

    unique: Optional[bool] = None
    distinct: Optional[int] = None
    vmin: Optional[int] = None
    vmax: Optional[int] = None


class ViewBlock:
    """One late-materialization source: base payload tensors + the id
    of its selection vector in the owning view's row matrix.
    ``row_id=None`` means identity (the base tensors are already
    aligned with the frame's logical rows)."""

    __slots__ = ("itensor", "ftensor", "row_id")

    def __init__(self, itensor, ftensor, row_id: Optional[int]):
        self.itensor = itensor
        self.ftensor = ftensor
        self.row_id = row_id


class RowView:
    """The selection-vector representation.

    ``blocks`` are the payload sources; ``rowmat`` is ONE stacked
    ``(R, nrows)`` int64 matrix holding every block's selection vector
    as a row.  Keeping the vectors stacked means ``take`` composes the
    whole view with a single 2-D gather (``rowmat[:, rows]``) no
    matter how many base tables a join chain has accumulated.
    """

    __slots__ = ("blocks", "rowmat", "_row_cache")

    def __init__(self, blocks: List[ViewBlock], rowmat: Optional[jax.Array]):
        self.blocks = blocks
        self.rowmat = rowmat
        self._row_cache: Dict[int, jax.Array] = {}

    def rows_of(self, block: ViewBlock) -> Optional[jax.Array]:
        rid = block.row_id
        if rid is None:
            return None
        if rid not in self._row_cache:
            self._row_cache[rid] = self.rowmat[rid]
        return self._row_cache[rid]


class OffloadedColumn:
    """High-cardinality non-numeric column, offloaded from the tensor.

    ``values`` is the immutable physical host array; ``idx`` is a device
    int64 row indexer (logical row -> physical position).  Factorized
    codes and packed byte tensors are cached on the *physical* array so
    filtered/joined views share them.
    """

    def __init__(self, values: np.ndarray, idx: Optional[jax.Array] = None,
                 _cache: Optional[dict] = None):
        self.values = values
        if idx is None:
            idx = jnp.arange(values.shape[0], dtype=INT)
        self.idx = idx
        # cache shared across views of the same physical array
        self._cache = _cache if _cache is not None else {}

    @property
    def nrows(self) -> int:
        return int(self.idx.shape[0])

    def take(self, rows: jax.Array) -> "OffloadedColumn":
        return OffloadedColumn(self.values, self.idx[rows], self._cache)

    def materialize(self) -> np.ndarray:
        return self.values[to_host(self.idx, "frame.column")]

    def phys_factorize(self) -> Tuple[np.ndarray, np.ndarray]:
        """(codes over physical rows, sorted dictionary), cached."""
        if "fact" not in self._cache:
            self._cache["fact"] = encoding.factorize(self.values)
        return self._cache["fact"]

    def codes(self) -> Tuple[jax.Array, np.ndarray]:
        """Dense codes aligned with *logical* rows + dictionary."""
        phys_codes, dictionary = self.phys_factorize()
        if "dev_codes" not in self._cache:
            self._cache["dev_codes"] = jnp.asarray(phys_codes, dtype=INT)
        return self._cache["dev_codes"][self.idx], dictionary

    def packed(self, max_len: Optional[int] = None):
        """Packed (n_phys, L) uint8 byte tensor + lengths, cached."""
        from . import strings  # local import to avoid cycle

        key = ("packed", max_len)
        if key not in self._cache:
            self._cache[key] = strings.pack_strings(self.values, max_len)
        return self._cache[key]


def _empty_tensor(n: int, dtype) -> jax.Array:
    return jnp.zeros((n, 0), dtype=dtype)


def _assemble_frame(
    int_cols: List[Tuple[str, np.ndarray, str, Optional[np.ndarray]]],
    float_cols: List[Tuple[str, np.ndarray]],
    offloaded: Dict[str, "OffloadedColumn"],
    order: List[str],
    n: int,
) -> "TensorFrame":
    """Stack encoded host columns into the two device tensors (the
    ``frame.upload`` span; ``frame.bytes_uploaded`` counts the bytes)."""
    columns: Dict[str, ColumnMeta] = {}
    islots: Dict[str, int] = {}
    fslots: Dict[str, int] = {}
    for i, (name, _, _, _) in enumerate(int_cols):
        islots[name] = i
    for i, (name, _) in enumerate(float_cols):
        fslots[name] = i
    with _obs.span("frame.upload", rows=n) as sp:
        nbytes = 0
        if int_cols:
            host = np.column_stack([c[1] for c in int_cols]).astype(np.int64)
            nbytes += host.nbytes
            itensor = jnp.asarray(host)
        else:
            itensor = _empty_tensor(n, INT)
        if float_cols:
            host = np.column_stack([c[1] for c in float_cols]).astype(
                np.dtype(CONFIG.float_dtype)
            )
            nbytes += host.nbytes
            ftensor = jnp.asarray(host)
        else:
            ftensor = _empty_tensor(n, float_dtype())
        UPLOADS["bytes_uploaded"] += nbytes
        sp.set(bytes=nbytes)
    imeta = {name: (kind, dic) for name, _, kind, dic in int_cols}
    for name in order:
        if name in islots:
            kind, dic = imeta[name]
            columns[name] = ColumnMeta(name, kind, islots[name], dic)
        elif name in fslots:
            columns[name] = ColumnMeta(name, "float", fslots[name])
        else:
            columns[name] = ColumnMeta(name, "obj", -1)
    return TensorFrame(itensor, ftensor, columns, offloaded, n)


class TensorFrame:
    def __init__(
        self,
        itensor: Optional[jax.Array],
        ftensor: Optional[jax.Array],
        columns: Dict[str, ColumnMeta],
        offloaded: Dict[str, OffloadedColumn],
        nrows: int,
        view: Optional[RowView] = None,
    ):
        self._itensor = itensor
        self._ftensor = ftensor
        self.columns = columns
        self.offloaded = offloaded
        self.nrows = int(nrows)
        self._view = view
        self._stats: Dict[Tuple[str, ...], ColStats] = {}

    @classmethod
    def _from_view(
        cls,
        columns: Dict[str, ColumnMeta],
        offloaded: Dict[str, OffloadedColumn],
        nrows: int,
        blocks: List[ViewBlock],
        rowmat: Optional[jax.Array],
    ) -> "TensorFrame":
        return cls(None, None, columns, offloaded, nrows, RowView(blocks, rowmat))

    # ------------------------------------------------------------------
    # late materialization
    # ------------------------------------------------------------------
    @property
    def is_view(self) -> bool:
        return self._view is not None

    def _view_parts(self) -> Tuple[List[ViewBlock], Optional[jax.Array]]:
        """(blocks, rowmat) — identity block for an eager frame; the
        join's zero-copy stacking input."""
        if self._view is not None:
            return self._view.blocks, self._view.rowmat
        return [ViewBlock(self._itensor, self._ftensor, None)], None

    def materialize(self) -> "TensorFrame":
        """Resolve the view: ONE fused 2-D gather per (block, tensor)
        of exactly the live slots, then a single horizontal concat —
        this is the "one payload gather per base table" a join chain
        deferred to.  In-place (caching) — logical content is
        unchanged.  Returns ``self`` for chaining."""
        if self._view is None:
            return self
        blocks = self._view.blocks
        # live slots per block, in column order (dead columns from
        # select()/projection pruning are never gathered)
        per_int: List[List[int]] = [[] for _ in blocks]
        per_float: List[List[int]] = [[] for _ in blocks]
        within: Dict[str, int] = {}
        for name, m in self.columns.items():
            if m.kind == "obj":
                continue
            lst = per_float[m.block] if m.kind == "float" else per_int[m.block]
            if m.slot in lst:  # two logical columns sharing a payload
                within[name] = lst.index(m.slot)
            else:
                within[name] = len(lst)
                lst.append(m.slot)

        def _gather(tensor: jax.Array, slots: List[int], rows) -> jax.Array:
            # whole-tensor row gather when every slot is live in order
            # (the common join-chain case) — XLA's fast contiguous-row
            # path; otherwise slice the live columns, then gather rows
            full = slots == list(range(tensor.shape[1]))
            sub = tensor if full else tensor[:, jnp.asarray(slots, dtype=INT)]
            return sub if rows is None else sub[rows]

        iparts: List[jax.Array] = []
        fparts: List[jax.Array] = []
        ioffs: List[int] = []
        foffs: List[int] = []
        ioff = foff = 0
        for b, isl, fsl in zip(blocks, per_int, per_float):
            ioffs.append(ioff)
            foffs.append(foff)
            rows = self._view.rows_of(b)
            if isl:
                iparts.append(_gather(b.itensor, isl, rows))
                ioff += len(isl)
            if fsl:
                fparts.append(_gather(b.ftensor, fsl, rows))
                foff += len(fsl)
        self._itensor = (
            jnp.concatenate(iparts, axis=1)
            if len(iparts) > 1
            else (iparts[0] if iparts else _empty_tensor(self.nrows, INT))
        )
        self._ftensor = (
            jnp.concatenate(fparts, axis=1)
            if len(fparts) > 1
            else (fparts[0] if fparts else _empty_tensor(self.nrows, float_dtype()))
        )
        newcols: Dict[str, ColumnMeta] = {}
        for name, m in self.columns.items():  # original order preserved
            if m.kind == "obj":
                newcols[name] = dataclasses.replace(m, block=0)
                continue
            base = foffs[m.block] if m.kind == "float" else ioffs[m.block]
            newcols[name] = dataclasses.replace(
                m, slot=base + within[name], block=0
            )
        self.columns = newcols
        self._view = None
        return self

    @property
    def itensor(self) -> jax.Array:
        if self._view is not None:
            self.materialize()
        return self._itensor

    @property
    def ftensor(self) -> jax.Array:
        if self._view is not None:
            self.materialize()
        return self._ftensor

    # ------------------------------------------------------------------
    # stats cache
    # ------------------------------------------------------------------
    @staticmethod
    def _stats_key(cols: Sequence[str]) -> Tuple[str, ...]:
        return tuple(sorted(cols))

    def set_stats(
        self,
        cols: Union[str, Sequence[str]],
        *,
        unique: Optional[bool] = None,
        distinct: Optional[int] = None,
        vmin: Optional[int] = None,
        vmax: Optional[int] = None,
    ) -> None:
        key = self._stats_key([cols] if isinstance(cols, str) else cols)
        st = self._stats.setdefault(key, ColStats())
        if unique is not None:
            st.unique = unique
        if distinct is not None:
            st.distinct = distinct
        if vmin is not None:
            st.vmin = vmin
        if vmax is not None:
            st.vmax = vmax

    def col_stats(self, cols: Union[str, Sequence[str]]) -> Optional[ColStats]:
        key = self._stats_key([cols] if isinstance(cols, str) else cols)
        return self._stats.get(key)

    def int_bounds(self, name: str) -> Tuple[int, int]:
        """(lo, hi) bounds of a (non-empty) int-like column.

        Answered from the stats cache when possible — store zone maps
        seed it, joins/filters propagate it — else computed with ONE
        fused device fetch and cached on this frame.  Bounds may cover
        a superset of the rows; callers use them for range compression,
        where a wider span is still correct.
        """
        st = self.col_stats(name)
        if st is not None and st.vmin is not None:
            return int(st.vmin), int(st.vmax)
        lo, hi = bounds(self.col_values(name))
        self.set_stats(name, vmin=lo, vmax=hi)
        return lo, hi

    def unique_hint(self, cols: Sequence[str]) -> Optional[bool]:
        """Is the column combination provably unique (/ non-unique)?

        ``True`` is correctness-grade (safe to direct-address a join
        build side); ``False`` is a perf hint; ``None`` means unknown.
        Any single member column being unique makes the combination
        unique.
        """
        st = self._stats.get(self._stats_key(cols))
        if st is not None and st.unique is not None:
            return st.unique
        for c in cols:
            s1 = self._stats.get((c,))
            if s1 is not None and s1.unique:
                return True
        return None

    def _drop_stats_mentioning(self, name: str) -> None:
        """Invalidate every cached stat involving ``name`` — a column
        replacement voids single-column AND combination entries (a
        stale combo uniqueness would mis-drive the join pick)."""
        self._stats = {k: v for k, v in self._stats.items() if name not in k}

    def _inherit_stats(
        self,
        child: "TensorFrame",
        mode: str,
        mapping: Optional[Dict[str, str]] = None,
    ) -> None:
        """Propagate stats onto ``child``.

        ``mode``: 'permutation' keeps everything (same row multiset),
        'subset' keeps unique flags and value bounds but drops distinct
        counts (rows were removed), 'bounds' keeps only value bounds
        (rows may repeat — join gathers), 'columns' keeps everything
        (row set unchanged).  Entries whose columns do not all survive
        in ``child`` are dropped; ``mapping`` renames columns.
        """
        for key, st in self._stats.items():
            cols = [mapping.get(c, c) for c in key] if mapping else list(key)
            if not all(c in child.columns for c in cols):
                continue
            if mode == "permutation" or mode == "columns":
                new = dataclasses.replace(st)
            elif mode == "subset":
                if st.unique is None and st.vmin is None:
                    continue
                new = ColStats(unique=st.unique, vmin=st.vmin, vmax=st.vmax)
            elif mode == "bounds":
                if st.vmin is None:
                    continue
                new = ColStats(vmin=st.vmin, vmax=st.vmax)
            else:
                raise ValueError(mode)
            child._stats[child._stats_key(cols)] = new

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @staticmethod
    def from_arrays(
        data: Dict[str, np.ndarray],
        *,
        card_threshold: Optional[float] = None,
        encode: Optional[Dict[str, str]] = None,
    ) -> "TensorFrame":
        """Build a frame from host numpy arrays.

        ``encode`` optionally forces 'dict' or 'obj' per column name,
        overriding the cardinality policy (paper lets users set the
        threshold; default 50%).
        """
        threshold = CONFIG.card_threshold if card_threshold is None else card_threshold
        encode = encode or {}
        int_cols: List[Tuple[str, np.ndarray, str, Optional[np.ndarray]]] = []
        float_cols: List[Tuple[str, np.ndarray]] = []
        offloaded: Dict[str, OffloadedColumn] = {}
        order: List[str] = []
        n = None
        for name, arr in data.items():
            arr = np.asarray(arr)
            if n is None:
                n = arr.shape[0]
            elif arr.shape[0] != n:
                raise ValueError(f"column {name}: length {arr.shape[0]} != {n}")
            order.append(name)
            if np.issubdtype(arr.dtype, np.datetime64):
                days = arr.astype("datetime64[D]").astype(np.int64)
                int_cols.append((name, days, "date", None))
            elif arr.dtype == np.bool_:
                int_cols.append((name, arr.astype(np.int64), "bool", None))
            elif np.issubdtype(arr.dtype, np.integer):
                int_cols.append((name, arr.astype(np.int64), "int", None))
            elif np.issubdtype(arr.dtype, np.floating):
                float_cols.append((name, arr))
            elif encoding.is_string_like(arr):
                forced = encode.get(name)
                if forced == "obj":
                    offloaded[name] = OffloadedColumn(arr)
                    continue
                codes, dictionary = encoding.factorize(arr)
                if forced == "dict" or dictionary.shape[0] <= threshold * max(1, n):
                    int_cols.append((name, codes, "dict", dictionary))
                else:
                    offloaded[name] = OffloadedColumn(arr)
            else:
                raise TypeError(f"column {name}: unsupported dtype {arr.dtype}")
        n = 0 if n is None else n
        return _assemble_frame(int_cols, float_cols, offloaded, order, n)

    @staticmethod
    def from_store(
        table,
        columns: Optional[Sequence[str]] = None,
        predicates: Sequence = (),
        *,
        card_threshold: Optional[float] = None,
        encode: Optional[Dict[str, str]] = None,
        result=None,
    ) -> "TensorFrame":
        """Materialize a frame from a ``repro.store`` chunked table.

        ``predicates`` are sargable store conjuncts
        (``repro.store.Pred``): zone maps skip whole chunks and the
        survivors are row-filtered host-side, so only matching rows
        ever reach the device tensors (scan pushdown).  Dictionary
        columns keep the store's *interned* dictionary — no
        re-factorization, and frames built from the same store share
        dictionary objects, making join-time dictionary merges
        identity operations.

        Zone-map statistics thread through: columns the chunk stats
        prove unique (or duplicate-bearing) seed the frame's stats
        cache, so downstream ``join(algorithm="auto")`` picks its
        build strategy without sorting the build side.

        ``result`` short-circuits the scan with a precomputed
        ``store.ScanResult`` for exactly these columns/predicates —
        the serving layer's shared-scan path (``store.shared_scan``)
        answers many concurrent scans in one pass and materializes
        each frame from its own result here.
        """
        from repro import store as _store

        if result is None:
            result = _store.scan(table, columns, list(predicates))
        threshold = (
            CONFIG.card_threshold if card_threshold is None else card_threshold
        )
        encode = encode or {}
        n = result.nrows
        int_cols: List[Tuple[str, np.ndarray, str, Optional[np.ndarray]]] = []
        float_cols: List[Tuple[str, np.ndarray]] = []
        offloaded: Dict[str, OffloadedColumn] = {}
        order: List[str] = []
        for name, mc in result.columns.items():
            order.append(name)
            forced = encode.get(name)
            if mc.dictionary is not None:
                if forced == "obj":
                    safe = np.clip(
                        mc.values, 0, max(0, mc.dictionary.shape[0] - 1)
                    )
                    offloaded[name] = OffloadedColumn(mc.dictionary[safe])
                else:
                    int_cols.append((name, mc.values, "dict", mc.dictionary))
            elif mc.ctype == "float":
                float_cols.append((name, mc.values))
            elif mc.ctype == "str":
                # plain (high-cardinality) strings: same policy as
                # from_arrays — dict-encode below the threshold, offload
                # above it
                if forced == "obj":
                    offloaded[name] = OffloadedColumn(mc.values)
                    continue
                codes, dictionary = encoding.factorize(mc.values)
                if forced == "dict" or dictionary.shape[0] <= threshold * max(1, n):
                    int_cols.append((name, codes, "dict", dictionary))
                else:
                    offloaded[name] = OffloadedColumn(mc.values)
            else:  # int / date / bool days already in physical form
                int_cols.append((name, mc.values, mc.ctype, None))
        # explicit store validity bitmaps become the engine's hidden
        # __v__ companion columns (float nulls stay NaN-encoded)
        for name, mc in result.columns.items():
            if mc.validity is not None and not bool(mc.validity.all()):
                vname = _valid_name(name)
                order.append(vname)
                int_cols.append(
                    (vname, mc.validity.astype(np.int64), "bool", None)
                )
        out = _assemble_frame(int_cols, float_cols, offloaded, order, n)
        # thread zone-map uniqueness/distinct/bounds stats into the
        # frame so joins and group-bys skip their probing work
        unfiltered = not predicates and n == table.nrows
        for name in order:
            if name not in table.columns:
                continue
            col = table.columns[name]
            if col.ctype == "float":
                continue
            unique, distinct = col.uniqueness_from_stats()
            if unique is True:
                # uniqueness survives any row filtering (subset)
                out.set_stats(
                    name,
                    unique=True,
                    distinct=distinct if unfiltered else None,
                )
            elif unique is False and unfiltered:
                out.set_stats(name, unique=False, distinct=distinct)
            if n and col.ctype in ("int", "date", "bool") and col.encoding != "dict":
                mins, maxs, exact = col.zone_bounds()
                if exact and not np.isnan(mins).all():
                    out.set_stats(
                        name,
                        vmin=int(np.nanmin(mins)),
                        vmax=int(np.nanmax(maxs)),
                    )
        return out

    # ------------------------------------------------------------------
    # basic introspection
    # ------------------------------------------------------------------
    @property
    def column_names(self) -> List[str]:
        return [c for c in self.columns if not _is_hidden(c)]

    def meta(self, name: str) -> ColumnMeta:
        try:
            return self.columns[name]
        except KeyError:
            raise KeyError(f"no column {name!r}; have {self.column_names}") from None

    def has_column(self, name: str) -> bool:
        return name in self.columns

    def has_nulls(self, name: str) -> bool:
        return _valid_name(name) in self.columns

    def valid_array(self, name: str) -> Optional[jax.Array]:
        vn = _valid_name(name)
        if vn in self.columns:
            return self.col_values(vn) != 0
        return None

    # ------------------------------------------------------------------
    # column access
    # ------------------------------------------------------------------
    def _raw_values(self, m: ColumnMeta) -> jax.Array:
        """Device payload of one non-obj column, gathered through the
        view when lazy (a single-column gather — no materialization)."""
        if self._view is not None:
            b = self._view.blocks[m.block]
            arr = (b.ftensor if m.kind == "float" else b.itensor)[:, m.slot]
            rows = self._view.rows_of(b)
            return arr if rows is None else arr[rows]
        t = self._ftensor if m.kind == "float" else self._itensor
        return t[:, m.slot]

    def col_values(self, name: str) -> jax.Array:
        """Device numeric representation (codes for dict columns)."""
        m = self.meta(name)
        if m.kind == "obj":
            codes, _ = self.offloaded[name].codes()
            return codes
        return self._raw_values(m)

    def col_codes(self, name: str) -> Tuple[jax.Array, np.ndarray]:
        """(codes, dictionary) for any string-typed column."""
        m = self.meta(name)
        if m.kind == "dict":
            return self._raw_values(m), m.dictionary
        if m.kind == "obj":
            return self.offloaded[name].codes()
        raise TypeError(f"column {name} is not string-typed (kind={m.kind})")

    def column(self, name: str) -> np.ndarray:
        """Decode a column back to host numpy (for users/tests).

        A pipeline exit: gathers exactly this column through the view.
        """
        m = self.meta(name)
        valid = self.valid_array(name)
        if valid is not None:
            valid = to_host(valid, "frame.column")
        if m.kind == "obj":
            out = self.offloaded[name].materialize()
            if valid is not None:
                out = out.astype(object)
                out[~valid] = None
            return out
        if m.kind == "float":
            out = to_host(self._raw_values(m), "frame.column")
            if valid is not None:
                out = out.copy()
                out[~valid] = np.nan
            return out
        raw = to_host(self._raw_values(m), "frame.column")
        if m.kind == "dict":
            if m.dictionary.shape[0] == 0:
                # empty dictionary (e.g. null rows stitched against an
                # empty build side): every cell is null
                return np.full(raw.shape, None, dtype=object)
            safe = np.clip(raw, 0, m.dictionary.shape[0] - 1)
            out = m.dictionary[safe].astype(object)
            if valid is not None:
                out[~valid] = None
            elif (raw < 0).any():
                out[raw < 0] = None
            return out
        if m.kind == "date":
            out = raw.astype("datetime64[D]")
            return out
        if m.kind == "bool":
            return raw != 0
        return raw

    def to_dict(self) -> Dict[str, np.ndarray]:
        return {name: self.column(name) for name in self.column_names}

    def __len__(self) -> int:
        return self.nrows

    # ------------------------------------------------------------------
    # row ops
    # ------------------------------------------------------------------
    def take(
        self,
        rows: Union[jax.Array, np.ndarray],
        *,
        stats: str = "none",
    ) -> "TensorFrame":
        """Select rows by index.

        Late-materializing by default: the result is a ``RowView``
        frame whose blocks compose ``rows`` into their selection
        vectors — no payload is copied until ``materialize()``.

        ``stats`` declares what the caller knows about ``rows`` for
        stats propagation: 'permutation' (every row exactly once),
        'subset' (no row more than once), 'none' (may repeat rows —
        drop all cached stats).
        """
        rows = jnp.asarray(rows, dtype=INT)
        n = int(rows.shape[0])
        off = {k: v.take(rows) for k, v in self.offloaded.items()}
        if CONFIG.late_materialization:
            v = self._view
            if v is None:
                blocks = [ViewBlock(self._itensor, self._ftensor, 0)]
                rowmat = rows[None, :]
            else:
                # compose EVERY block's selection vector in one 2-D
                # gather; identity blocks share one new vector (= rows)
                mats = []
                ident_id: Optional[int] = None
                if v.rowmat is not None:
                    mats.append(v.rowmat[:, rows])
                if any(b.row_id is None for b in v.blocks):
                    ident_id = 0 if v.rowmat is None else int(v.rowmat.shape[0])
                    mats.append(rows[None, :])
                rowmat = mats[0] if len(mats) == 1 else jnp.concatenate(mats, axis=0)
                blocks = [
                    ViewBlock(
                        b.itensor,
                        b.ftensor,
                        ident_id if b.row_id is None else b.row_id,
                    )
                    for b in v.blocks
                ]
            out = TensorFrame._from_view(dict(self.columns), off, n, blocks, rowmat)
        else:
            it = (
                self.itensor[rows]
                if self.itensor.shape[1]
                else _empty_tensor(n, INT)
            )
            ft = (
                self.ftensor[rows]
                if self.ftensor.shape[1]
                else _empty_tensor(n, float_dtype())
            )
            out = TensorFrame(it, ft, dict(self.columns), off, n)
        if stats in ("permutation", "subset"):
            self._inherit_stats(out, stats)
        return out

    def head(self, n: int) -> "TensorFrame":
        n = min(n, self.nrows)
        return self.take(jnp.arange(n, dtype=INT), stats="subset")

    def mask_rows(self, mask: jax.Array) -> "TensorFrame":
        """Compact rows where mask is True (one host sync for the
        count; the payload gather is deferred behind the view)."""
        mask = jnp.asarray(mask)
        count = int(to_host(mask.sum(), "frame.mask_rows"))
        return self.take(compact(mask, count), stats="subset")

    def filter(self, expr) -> "TensorFrame":
        from .expr import Expr

        if isinstance(expr, Expr):
            mask = expr.eval_bool(self)
        else:
            mask = jnp.asarray(expr)
        return self.mask_rows(mask)

    # ------------------------------------------------------------------
    # column ops
    # ------------------------------------------------------------------
    def select(self, names: Sequence[str]) -> "TensorFrame":
        cols: Dict[str, ColumnMeta] = {}
        off: Dict[str, OffloadedColumn] = {}
        for name in names:
            m = self.meta(name)
            cols[name] = m
            if m.kind == "obj":
                off[name] = self.offloaded[name]
            vn = _valid_name(name)
            if vn in self.columns:
                cols[vn] = self.columns[vn]
        out = TensorFrame(
            self._itensor, self._ftensor, cols, off, self.nrows, self._view
        )
        self._inherit_stats(out, "columns")
        return out

    def drop(self, names: Sequence[str]) -> "TensorFrame":
        keep = [c for c in self.column_names if c not in set(names)]
        return self.select(keep)

    def rename(self, mapping: Dict[str, str]) -> "TensorFrame":
        cols: Dict[str, ColumnMeta] = {}
        off: Dict[str, OffloadedColumn] = {}
        for name, m in self.columns.items():
            if _is_hidden(name):
                base = name[len(VALID_PREFIX):]
                new = _valid_name(mapping.get(base, base))
            else:
                new = mapping.get(name, name)
            cols[new] = dataclasses.replace(m, name=new)
            if m.kind == "obj":
                off[new] = self.offloaded[name]
        out = TensorFrame(
            self._itensor, self._ftensor, cols, off, self.nrows, self._view
        )
        self._inherit_stats(out, "columns", mapping=mapping)
        return out

    def _append_int_column(
        self,
        name: str,
        values: jax.Array,
        kind: str = "int",
        dictionary: Optional[np.ndarray] = None,
    ) -> "TensorFrame":
        values = jnp.asarray(values, dtype=INT).reshape(self.nrows, 1)
        cols = dict(self.columns)
        cols.pop(name, None)
        off = dict(self.offloaded)
        off.pop(name, None)
        if self._view is not None:
            # Lazy append: the computed column is already aligned with
            # the logical rows, so it rides along as its own identity
            # block — the view's deferred gathers survive (a
            # filter -> with_column -> join chain stays one gather per
            # base table at materialize time).
            blocks = list(self._view.blocks)
            blocks.append(
                ViewBlock(values, _empty_tensor(self.nrows, float_dtype()), None)
            )
            cols[name] = ColumnMeta(name, kind, 0, dictionary, len(blocks) - 1)
            out = TensorFrame._from_view(
                cols, off, self.nrows, blocks, self._view.rowmat
            )
        else:
            it = jnp.concatenate([self._itensor, values], axis=1)
            cols[name] = ColumnMeta(name, kind, self._itensor.shape[1], dictionary)
            out = TensorFrame(it, self._ftensor, cols, off, self.nrows)
        self._inherit_stats(out, "columns")
        out._drop_stats_mentioning(name)  # the name may have been replaced
        return out

    def _append_float_column(self, name: str, values: jax.Array) -> "TensorFrame":
        values = jnp.asarray(values, dtype=float_dtype()).reshape(self.nrows, 1)
        cols = dict(self.columns)
        cols.pop(name, None)
        off = dict(self.offloaded)
        off.pop(name, None)
        if self._view is not None:
            blocks = list(self._view.blocks)
            blocks.append(
                ViewBlock(_empty_tensor(self.nrows, INT), values, None)
            )
            cols[name] = ColumnMeta(name, "float", 0, None, len(blocks) - 1)
            out = TensorFrame._from_view(
                cols, off, self.nrows, blocks, self._view.rowmat
            )
        else:
            ft = jnp.concatenate([self._ftensor, values], axis=1)
            cols[name] = ColumnMeta(name, "float", self._ftensor.shape[1])
            out = TensorFrame(self._itensor, ft, cols, off, self.nrows)
        self._inherit_stats(out, "columns")
        out._drop_stats_mentioning(name)
        return out

    def _append_offloaded(self, name: str, col: OffloadedColumn) -> "TensorFrame":
        cols = dict(self.columns)
        cols[name] = ColumnMeta(name, "obj", -1)
        off = dict(self.offloaded)
        off[name] = col
        out = TensorFrame(
            self._itensor, self._ftensor, cols, off, self.nrows, self._view
        )
        self._inherit_stats(out, "columns")
        out._drop_stats_mentioning(name)
        return out

    def with_column(self, name: str, expr) -> "TensorFrame":
        from .expr import Expr, Value

        if isinstance(expr, Expr):
            val = expr.eval(self)
        elif isinstance(expr, Value):
            val = expr
        else:  # raw array
            arr = jnp.asarray(expr)
            if jnp.issubdtype(arr.dtype, jnp.floating):
                return self._append_float_column(name, arr)
            return self._append_int_column(name, arr)
        if val.kind == "str":
            return self._append_int_column(name, val.arr, "dict", val.dictionary)
        if val.kind == "bool":
            return self._append_int_column(name, val.arr.astype(INT), "bool")
        if val.kind == "date":
            return self._append_int_column(name, val.arr, "date")
        arr = val.arr
        if jnp.issubdtype(arr.dtype, jnp.floating):
            out = self._append_float_column(name, arr)
        else:
            out = self._append_int_column(name, arr)
        if val.valid is not None:
            out = out._append_int_column(_valid_name(name), val.valid.astype(INT), "bool")
        return out

    # ------------------------------------------------------------------
    # relational ops (implemented in sibling modules)
    # ------------------------------------------------------------------
    def groupby(self, keys: Union[str, Sequence[str]]):
        from .groupby import GroupBy

        if isinstance(keys, str):
            keys = [keys]
        return GroupBy(self, list(keys))

    def agg(self, specs) -> Dict[str, float]:
        from .agg import frame_agg

        return frame_agg(self, specs)

    def sort_values(self, by, ascending=True) -> "TensorFrame":
        from .sort import sort_values

        return sort_values(self, by, ascending)

    def join(self, other: "TensorFrame", **kwargs) -> "TensorFrame":
        from .join import join

        return join(self, other, **kwargs)

    def nunique(self, name: str) -> int:
        from .groupby import nunique_column

        return nunique_column(self, name)

    def unique_rows(self, names: Sequence[str]) -> "TensorFrame":
        from .groupby import unique_rows

        return unique_rows(self, list(names))

    def scalar(self, name: str):
        arr = self.column(name)
        if arr.shape[0] != 1:
            raise ValueError(f"scalar() on column with {arr.shape[0]} rows")
        return arr[0]

    # ------------------------------------------------------------------
    # memory accounting (paper §VI-H)
    # ------------------------------------------------------------------
    def memory_bytes(self) -> Dict[str, int]:
        dict_bytes = 0
        for m in self.columns.values():
            if m.kind == "dict" and m.dictionary is not None:
                dict_bytes += sum(len(str(s).encode()) + 8 for s in m.dictionary)
        offload_bytes = 0
        for oc in self.offloaded.values():
            # physical payload + per-string overhead (Mojo strings carry
            # ~20B of metadata per the paper; we report our own measured
            # layout in the benchmark, this is the payload estimate)
            offload_bytes += sum(len(str(s).encode()) + 20 for s in oc.values)
            offload_bytes += oc.idx.size * 8
        return {
            "itensor": int(np.prod(self.itensor.shape)) * self.itensor.dtype.itemsize,
            "ftensor": int(np.prod(self.ftensor.shape)) * self.ftensor.dtype.itemsize,
            "dicts": dict_bytes,
            "offloaded": offload_bytes,
        }

    def total_bytes(self) -> int:
        return sum(self.memory_bytes().values())

    # ------------------------------------------------------------------
    def __repr__(self) -> str:
        cols = ", ".join(
            f"{name}:{self.columns[name].kind}" for name in self.column_names
        )
        tag = " view" if self._view is not None else ""
        return f"TensorFrame({self.nrows} rows{tag}; {cols})"

    def show(self, n: int = 8) -> str:
        names = self.column_names
        data = {name: self.column(name)[: min(n, self.nrows)] for name in names}
        widths = {
            name: max(len(name), *(len(str(v)) for v in data[name])) if self.nrows else len(name)
            for name in names
        }
        lines = [" | ".join(name.ljust(widths[name]) for name in names)]
        lines.append("-+-".join("-" * widths[name] for name in names))
        for i in range(min(n, self.nrows)):
            lines.append(
                " | ".join(str(data[name][i]).ljust(widths[name]) for name in names)
            )
        if self.nrows > n:
            lines.append(f"... ({self.nrows} rows)")
        return "\n".join(lines)


def concat_rows(frames: Sequence[TensorFrame]) -> TensorFrame:
    """Vertical concatenation (schemas must match by name & kind)."""
    if not frames:
        raise ValueError("concat of zero frames")
    base = frames[0]
    names = list(base.columns.keys())
    for f in frames[1:]:
        if list(f.columns.keys()) != names:
            raise ValueError("concat: schema mismatch")
    import numpy as _np

    data: Dict[str, np.ndarray] = {}
    # Decode through host; concat is rare in the workloads (correctness
    # over speed here).
    for name in base.column_names:
        data[name] = _np.concatenate([f.column(name) for f in frames])
    return TensorFrame.from_arrays(data)
