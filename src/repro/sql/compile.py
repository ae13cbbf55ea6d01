"""Whole-plan compilation: one jitted XLA program per query plan.

The op-by-op engine dispatches each plan node separately and host-syncs
between several of them (join uniqueness probes, group counts, scalar
subqueries).  This module instead lowers the *whole* optimized logical
plan into a single traced function over the base-table tensors and
``jax.jit``-compiles it, so a repeated query is one device launch.

Tracing needs static shapes, so every relation inside the program is a
fixed-capacity ``CTable``: payload tensors padded to a power-of-two row
capacity plus a traced valid-row count ``n`` (rows ``[0, n)`` are live,
in their original order).  Host-computed base-table value bounds travel
with each relation as trace-time constants, so composite keys pack into
single int64 codes with *static* spans: joins direct-address a dense
table when the code space fits (sort + ``searchsorted`` otherwise),
small group-by key spaces segment without sorting at all, and ORDER BY
scatters a rank bijection instead of lexsorting — the argsort/lexsort
primitives are several times slower than plain ``sort`` on the CPU XLA
backend, so the whole module is built to avoid them.

Compiled executables are cached keyed by a fingerprint of (plan
structure with literals replaced by parameter markers, per-table schema
+ dtypes + bucketed capacities + key-uniqueness verdicts), so repeated
parameterized queries — same shape, different literals — reuse the
executable with zero retraces.  Anything the tracer cannot express
(non-unique-side inner joins, float group keys, store-backed scans)
raises ``Unsupported`` and falls back to the op-by-op engine; the
verdict is negative-cached.  ``CONFIG.compiled`` picks the route:
``off`` | ``auto`` (size-gated) | ``force``.

Observability: ``STATS`` counts cache hits/misses/evictions/fallbacks
and records per-plan trace / compile / execute timings.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
import warnings
import weakref
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core import prims
from repro.core.config import CONFIG
from repro.core.expr import Expr, Value
from repro.core.frame import (
    INT,
    ColumnMeta,
    TensorFrame,
    _empty_tensor,
    _valid_name,
    float_dtype,
)

from .parser import (
    Boxed,
    SBin,
    SCol,
    SDate,
    SExtract,
    SFunc,
    SIn,
    SLike,
    SLit,
    SqlError,
    transform,
)
from .plan import (
    Aggregate,
    AttachScalar,
    Distinct,
    Filter,
    Join,
    Limit,
    Project,
    Scan,
    Shared,
    Sort,
    node_columns,
    walk_scans,
)
from .lower import to_expr
from .udf import active_udfs, plan_uses_udf

__all__ = [
    "STATS",
    "Unsupported",
    "clear_cache",
    "maybe_execute_compiled",
    "negative_plans",
    "reset_stats",
]

CACHE_CAPACITY = 32

_BIG = np.int64(np.iinfo(np.int64).max // 4)


class Unsupported(Exception):
    """Plan construct the traced path cannot express; fall back to the
    op-by-op engine."""


# ----------------------------------------------------------------------
# observability
# ----------------------------------------------------------------------
def _fresh_stats() -> Dict:
    return {
        "hits": 0,  # executable reused from the plan cache
        "misses": 0,  # fingerprint not cached -> trace+compile attempt
        "evictions": 0,  # LRU capacity evictions
        "compiles": 0,  # successful trace+compile
        "fallbacks": 0,  # unsupported plan -> op-by-op engine
        "fallback_reasons": {},  # reason -> fallback count
        "compile_failures": 0,  # unexpected trace/compile crashes
        "skipped_small": 0,  # auto mode: input under compiled_min_rows
        "plans": {},  # digest -> per-plan timing/shape record
    }


STATS = _fresh_stats()

_CACHE: "OrderedDict[str, _Entry]" = OrderedDict()
_NEGATIVE: Dict[str, str] = {}  # fingerprint -> unsupported reason

# Concurrency (the serving layer calls in from many threads): _LOCK
# guards every shared structure here — _CACHE / _NEGATIVE / STATS /
# _PREP — while _TRACE_LOCKS holds one lock per in-flight fingerprint
# so two threads first-compiling the *same* plan serialize (one traces,
# the other reuses the entry) without blocking compiles of *different*
# plans.  XLA executables are safe to invoke concurrently.
_LOCK = threading.RLock()
_TRACE_LOCKS: Dict[str, threading.Lock] = {}


def reset_stats() -> None:
    with _LOCK:
        STATS.clear()
        STATS.update(_fresh_stats())


def _stats_snapshot() -> Dict:
    with _LOCK:
        out = {k: v for k, v in STATS.items() if k != "plans"}
        out["plans"] = {d: dict(r) for d, r in STATS["plans"].items()}
        out["fallback_reasons"] = dict(STATS["fallback_reasons"])
    return out


def negative_plans() -> Dict[str, str]:
    """``{plan digest: reason}`` of every plan negative-cached to op-by-op
    dispatch: the plans the compiled path refused, and why."""
    with _LOCK:
        return {_digest(f): r for f, r in _NEGATIVE.items()}


def _digest(fpr: str) -> str:
    return hashlib.sha1(fpr.encode()).hexdigest()[:12]


def _fallback(reason: str) -> None:
    """Count one dispatch to the op-by-op engine under ``reason``
    (``_LOCK`` is re-entrant, so callers may hold it)."""
    with _LOCK:
        STATS["fallbacks"] += 1
        reasons = STATS["fallback_reasons"]
        reasons[reason] = reasons.get(reason, 0) + 1


obs.metrics.register_group("sql.compile", _stats_snapshot, reset_stats)


def clear_cache() -> None:
    with _LOCK:
        _CACHE.clear()
        _NEGATIVE.clear()
        _TRACE_LOCKS.clear()


def _pow2(n: int) -> int:
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


# ----------------------------------------------------------------------
# literal parameterization
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SParam:
    """Placeholder for a numeric/date literal in a parameterized plan.

    The plan fingerprint is computed over the *parameterized* tree, so
    two runs of the same query shape with different literals share one
    compiled executable; the literal values travel as runtime inputs."""

    index: int
    kind: str  # 'int' | 'float' | 'date'

    def render(self) -> str:
        return f"?{self.index}:{self.kind}"


@dataclasses.dataclass(eq=False)
class _ParamLit(Expr):
    """Core expression broadcasting one traced parameter scalar."""

    scalar: object
    kind: str

    def eval(self, frame: TensorFrame) -> Value:
        n = frame.nrows
        if self.kind == "float":
            return Value("num", jnp.full((n,), self.scalar, dtype=float_dtype()))
        if self.kind == "date":
            return Value("date", jnp.full((n,), self.scalar, dtype=INT))
        return Value("num", jnp.full((n,), self.scalar, dtype=INT))


class _BoundParam:
    """SQL-AST-side wrapper binding an SParam to a traced scalar;
    ``lower.to_expr`` dispatches on the ``to_core_expr`` hook."""

    __slots__ = ("scalar", "kind")

    def __init__(self, scalar, kind: str):
        self.scalar = scalar
        self.kind = kind

    def to_core_expr(self) -> Expr:
        return _ParamLit(self.scalar, self.kind)

    def render(self) -> str:
        return f"?bound:{self.kind}"


def _param_item(v, out):
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return _param_expr(v, out)
    if isinstance(v, tuple):
        return tuple(_param_item(x, out) for x in v)
    return v


def _param_expr(e, out: List[Tuple[str, object]]):
    """Replace numeric/date literals with SParam markers, collecting
    their values.  IN lists, LIKE patterns, and SUBSTRING bounds stay
    literal — the engine needs them static (LUTs, slices)."""
    if isinstance(e, SLit):
        v = e.value
        if isinstance(v, bool) or not isinstance(
            v, (int, float, np.integer, np.floating)
        ):
            return e
        kind = "float" if isinstance(v, (float, np.floating)) else "int"
        out.append((kind, v))
        return SParam(len(out) - 1, kind)
    if isinstance(e, SDate):
        out.append(("date", int(e.days)))
        return SParam(len(out) - 1, "date")
    if isinstance(e, (SIn, SLike)) or (
        isinstance(e, SFunc) and e.name == "substring"
    ):
        return e
    if not dataclasses.is_dataclass(e):
        return e
    changes = {}
    for f in dataclasses.fields(e):
        v = getattr(e, f.name)
        nv = _param_item(v, out)
        if nv != v:
            changes[f.name] = nv
    return dataclasses.replace(e, **changes) if changes else e


def parameterize(node):
    """plan -> (plan with SParam markers, [(kind, value), ...]).

    Traversal order is deterministic, so re-running on a fresh plan of
    the same shape yields values aligned with the cached executable's
    parameter slots."""
    out: List[Tuple[str, object]] = []
    shared: Dict[Shared, Shared] = {}
    return _param_plan(node, out, shared), out


def _param_plan(node, out, shared):
    if isinstance(node, Scan):
        return node
    if isinstance(node, Shared):
        # equal copies must stay equal (and collect their literals
        # once), so parameterize the subtree a single time
        got = shared.get(node)
        if got is None:
            got = Shared(_param_plan(node.child, out, shared))
            shared[node] = got
        return got
    if isinstance(node, Filter):
        return Filter(
            _param_plan(node.child, out, shared), _param_expr(node.pred, out)
        )
    if isinstance(node, Project):
        return Project(
            _param_plan(node.child, out, shared),
            tuple((n, _param_expr(e, out)) for n, e in node.outputs),
        )
    if isinstance(node, Aggregate):
        return Aggregate(
            _param_plan(node.child, out, shared),
            tuple((n, _param_expr(e, out)) for n, e in node.keys),
            tuple(
                (n, fn, None if e is None else _param_expr(e, out))
                for n, fn, e in node.aggs
            ),
        )
    if isinstance(node, Join):
        return dataclasses.replace(
            node,
            left=_param_plan(node.left, out, shared),
            right=_param_plan(node.right, out, shared),
        )
    if isinstance(node, (Sort, Limit, Distinct)):
        return dataclasses.replace(
            node, child=_param_plan(node.child, out, shared)
        )
    if isinstance(node, AttachScalar):
        return dataclasses.replace(
            node,
            child=_param_plan(node.child, out, shared),
            sub=Boxed(_param_plan(node.sub.v, out, shared)),
        )
    raise Unsupported(f"plan node {type(node).__name__}")


# ----------------------------------------------------------------------
# base-table preparation (host side, cached per frame)
# ----------------------------------------------------------------------
class _PrepTable:
    __slots__ = ("frame", "cap", "combos", "bounds", "pads")

    def __init__(self, frame: TensorFrame):
        self.frame = frame
        self.cap = _pow2(frame.nrows)
        # (int slots, float slots) -> cached padded (itensor, ftensor,
        # n) args — only used when the backend ignores donation (CPU),
        # where reuse is safe
        self.pads: Dict[Tuple[Tuple[int, ...], Tuple[int, ...]], Tuple] = {}
        # tuple(sorted cols) -> bool uniqueness verdict (host-computed
        # once; part of the fingerprint since it drives join strategy)
        self.combos: Dict[Tuple[str, ...], bool] = {}
        # name -> (lo, hi) static value bounds for int/date columns,
        # span rounded up to a power of two so nearby datasets share a
        # fingerprint.  These are trace-time constants: they turn join
        # builds into direct addressing and group codes into dense
        # segment ids (dict/bool columns get bounds from their metadata
        # instead).  Part of the fingerprint — they shape the program.
        self.bounds: Dict[str, Tuple[int, int]] = {}
        if frame.nrows:
            for name, m in frame.columns.items():
                if m.kind not in ("int", "date") or name.startswith(
                    _valid_name("")
                ):
                    continue
                lo, hi = frame.int_bounds(name)
                self.bounds[name] = (lo, lo + _pow2(hi - lo + 1) - 1)


_PREP: "weakref.WeakKeyDictionary[TensorFrame, _PrepTable]" = (
    weakref.WeakKeyDictionary()
)


def _prep_table(src: TensorFrame) -> _PrepTable:
    with _LOCK:
        return _prep_table_locked(src)


def _prep_table_locked(src: TensorFrame) -> _PrepTable:
    got = _PREP.get(src)
    if got is not None:
        return got
    f = src.materialize()
    for name in list(f.offloaded):
        # offloaded strings become dictionary-code int columns so the
        # traced program never touches host arrays; codes/dictionary
        # are cached on the physical column, so this is cheap to redo
        codes, dictionary = f.offloaded[name].codes()
        f = f._append_int_column(name, codes, "dict", dictionary)
    f.materialize()
    got = _PrepTable(f)
    _PREP[src] = got
    return got


def _ensure_unique(prep: _PrepTable, cols: Tuple[str, ...]) -> bool:
    with _LOCK:
        return _ensure_unique_locked(prep, cols)


def _ensure_unique_locked(prep: _PrepTable, cols: Tuple[str, ...]) -> bool:
    key = tuple(sorted(cols))
    if key in prep.combos:
        return prep.combos[key]
    f = prep.frame
    ok = all(
        c in f.columns
        and f.columns[c].is_int_like()
        and _valid_name(c) not in f.columns
        for c in key
    )
    verdict = False
    if ok:
        hint = f.unique_hint(list(key))
        if hint is None:
            if f.nrows == 0:
                hint = True
            else:
                arrs = [prims.to_host(f.col_values(c), "compile.unique_test") for c in key]
                if len(arrs) == 1:
                    hint = int(np.unique(arrs[0]).size) == f.nrows
                else:
                    hint = (
                        np.unique(np.stack(arrs, axis=1), axis=0).shape[0]
                        == f.nrows
                    )
            f.set_stats(list(key), unique=bool(hint))
        verdict = bool(hint)
    prep.combos[key] = verdict
    return verdict


class _Narrow:
    """The columns a plan reads from one base table, renumbered into the
    narrow tensors ``_build_args`` copies (only these slots are padded
    and passed: on the TPU every copied column costs HBM and time)."""

    __slots__ = ("columns", "islots", "fslots")

    def __init__(self, f: TensorFrame, names) -> None:
        self.columns: Dict[str, ColumnMeta] = {}
        islots: List[int] = []
        fslots: List[int] = []
        hidden = _valid_name("")
        for name, m in f.columns.items():
            base = name[len(hidden):] if name.startswith(hidden) else name
            if base not in names:
                continue
            slots = fslots if m.kind == "float" else islots
            self.columns[name] = dataclasses.replace(m, slot=len(slots), block=0)
            slots.append(m.slot)
        self.islots, self.fslots = tuple(islots), tuple(fslots)


def _table_sig(name: str, prep: _PrepTable, narrow: _Narrow) -> str:
    cols = ",".join(
        f"{n}:{m.kind}:{m.slot}:"
        f"{0 if m.dictionary is None else id(m.dictionary)}"
        for n, m in narrow.columns.items()
    )
    combos = ";".join(
        f"{'+'.join(k)}={int(v)}" for k, v in sorted(prep.combos.items())
    )
    bounds = ";".join(
        f"{n}={lo}:{hi}" for n, (lo, hi) in sorted(prep.bounds.items())
    )
    return (
        f"{name}[cap={prep.cap},iw={len(narrow.islots)},"
        f"fw={len(narrow.fslots)}]({cols})u({combos})b({bounds})"
    )


# ----------------------------------------------------------------------
# uniqueness requests: which base-column combos drive join strategy
# ----------------------------------------------------------------------
def _base_cols(node, names: List[str]):
    """Map qualified column names through rename-only chains back to
    one Scan's (table, base columns); None when not resolvable."""
    if isinstance(node, Scan):
        strip = node.alias + "."
        out = []
        for n in names:
            if not n.startswith(strip):
                return None
            out.append(n[len(strip):])
        return node.table, tuple(out)
    if isinstance(node, (Filter, Sort, Limit, Distinct, Shared)):
        return _base_cols(node.child, names)
    if isinstance(node, AttachScalar):
        if node.name in names:
            return None
        return _base_cols(node.child, names)
    if isinstance(node, Project):
        m = {n: e for n, e in node.outputs}
        mapped = []
        for n in names:
            e = m.get(n)
            if not isinstance(e, SCol):
                return None
            mapped.append(e.internal)
        return _base_cols(node.child, mapped)
    if isinstance(node, Join):
        want = set(names)
        if want <= node_columns(node.left):
            return _base_cols(node.left, names)
        if node.how not in ("semi", "anti") and want <= node_columns(
            node.right
        ):
            return _base_cols(node.right, names)
        return None
    return None


def _collect_unique_requests(node, reqs: Dict[str, set]):
    if isinstance(node, Join):
        if node.how in ("inner", "left"):
            for side, keys in (
                (node.left, node.left_keys),
                (node.right, node.right_keys),
            ):
                got = _base_cols(side, list(keys))
                if got is not None:
                    reqs.setdefault(got[0], set()).add(got[1])
        _collect_unique_requests(node.left, reqs)
        _collect_unique_requests(node.right, reqs)
        return
    if isinstance(node, AttachScalar):
        _collect_unique_requests(node.child, reqs)
        _collect_unique_requests(node.sub.v, reqs)
        return
    child = getattr(node, "child", None)
    if child is not None:
        _collect_unique_requests(child, reqs)


# ----------------------------------------------------------------------
# traced relations
# ----------------------------------------------------------------------
class CTable:
    """Fixed-capacity traced relation: an eager in-trace TensorFrame
    whose first ``n`` rows (traced count) are live, in original order.

    ``unique`` holds column combos known unique over the live rows;
    ``bounds`` holds *static* per-column (lo, hi) value bounds seeded
    from host-computed base-table stats — they make key spans known at
    trace time, which turns sort-based joins into direct addressing
    and multi-key group codes into single packed integers."""

    __slots__ = ("frame", "n", "unique", "bounds", "mask", "fdeps", "dbound")

    def __init__(
        self, frame: TensorFrame, n, unique=(), bounds=None, mask=None,
        fdeps=None, dbound=None,
    ):
        self.frame = frame
        self.n = n  # traced live-row count (== sum(mask) when masked)
        self.unique = set(unique)
        self.bounds = dict(bounds or {})
        # None: rows [0, n) are live (contiguous).  Otherwise a traced
        # bool mask: live rows sit at their original positions and the
        # compaction (nonzero + full-width gather, the most expensive
        # shape-preserving ops on this backend) is deferred until an
        # operator truly needs contiguity (sort / limit / final output)
        self.mask = mask
        # functional dependencies: column -> the probe-key columns that
        # determine it (a unique-build join makes every build column a
        # function of the probe keys).  GROUP BY / DISTINCT drop
        # determined columns from their packed code, which is what lets
        # e.g. q3's 3-key grouping collapse to one bounded key
        self.fdeps: Dict[str, frozenset] = dict(fdeps or {})
        # static upper bound on the column's distinct live values (only
        # where tighter than cap): an inner probe against a unique build
        # side bounds the surviving probe keys by the build capacity.
        # GROUP BY shrinks its output capacity to the product of its
        # keys' bounds — which shrinks every operator downstream
        self.dbound: Dict[str, int] = dict(dbound or {})

    @property
    def cap(self) -> int:
        return self.frame.nrows

    @property
    def row_valid(self):
        if self.mask is not None:
            return self.mask
        return jnp.arange(self.cap, dtype=INT) < self.n


def _compact(ct: CTable) -> CTable:
    """Gather the live rows into [0, n) (no-op when already there)."""
    if ct.mask is None:
        return ct
    idx = prims.compact(ct.mask, ct.cap)
    return _gather_rows(ct, idx, ct.n)


def _is_unique(ct: CTable, keys) -> bool:
    ks = set(keys)
    return any(u <= ks for u in ct.unique)


def _rows(t, idx):
    """``t[idx]`` for an (n, k) payload, gathered column-major: on the TPU
    a row-major (n, k) temporary pads k up to 128 lanes (a 4 GB copy at
    n = 2^23 for any k), a (k, n) one only pads k up to 8 sublanes."""
    return t.T[:, idx].T


def _hcat(a, b):
    """Column-major ``concatenate([a, b], axis=1)`` (see ``_rows``)."""
    return jnp.concatenate([a.T, b.T], axis=0).T


def _gather_rows(ct: CTable, idx, n, unique=None) -> CTable:
    f = ct.frame
    out = TensorFrame(
        _rows(f.itensor, idx), _rows(f.ftensor, idx), dict(f.columns), {},
        int(idx.shape[0]),
    )
    # row subsets keep value bounds (padding rows are masked everywhere)
    return CTable(
        out, n, ct.unique if unique is None else unique, ct.bounds,
        fdeps=ct.fdeps, dbound=ct.dbound,
    )


def _effective_keys(ct: CTable, names) -> List[str]:
    """Drop grouping columns functionally determined by other kept
    grouping columns — equality of the determinants already implies
    equality of the determined values row-to-row."""
    kept = set(names)
    out: List[str] = []
    for k in names:
        dep = ct.fdeps.get(k)
        if dep and dep <= (kept - {k}):
            kept.discard(k)
            continue
        out.append(k)
    return out or list(names)[:1]


def _masked_min(v, ok):
    return jnp.min(jnp.where(ok, v, _BIG))


def _masked_max(v, ok):
    return jnp.max(jnp.where(ok, v, -_BIG))


def _sort(v):
    """Ascending one-operand sort (stability means nothing for one
    operand, and the stable form compiles three times slower on TPU)."""
    return jax.lax.sort(v, is_stable=False)


def _rank(v, n: int):
    """Equality-preserving codes in ``[0, n]``: each value's first
    position in its own sorted order.  A one-operand sort plus a
    binary search is several times cheaper than argsort/lexsort, to run
    on the CPU and to compile for the TPU, which is why every operator
    here range-compresses through this instead of sorting composite
    keys directly."""
    return jnp.searchsorted(_sort(v), v)


def _expr_bounds(ct: CTable, e) -> Optional[Tuple[int, int]]:
    """Sound static (lo, hi) value bounds for an integer-valued scalar
    plan expression, or None.  Interval arithmetic over column bounds
    lets *computed* group / sort keys (q7-q9's EXTRACT(YEAR ...), price
    buckets, ...) keep trace-time spans, so they pack densely instead
    of forcing the rank path and a full-capacity aggregate output."""
    if isinstance(e, SCol):
        m = ct.frame.meta(e.internal) if ct.frame.has_column(e.internal) else None
        if m is not None and m.kind == "bool":
            return 0, 1
        return ct.bounds.get(e.internal)
    if isinstance(e, SLit):
        if isinstance(e.value, bool):
            return int(e.value), int(e.value)
        if isinstance(e.value, int):
            return e.value, e.value
        return None
    if isinstance(e, SDate):
        return e.days, e.days
    if isinstance(e, SExtract):
        if e.field == "month":
            return 1, 12
        if e.field == "day":
            return 1, 31
        b = _expr_bounds(ct, e.e)
        if b is None:
            return None
        # calendar year is monotone in epoch days
        def _year(days: int) -> int:
            return int(
                np.datetime64(int(days), "D").astype("datetime64[Y]").astype(int)
            ) + 1970
        return _year(b[0]), _year(b[1])
    if isinstance(e, SBin) and e.op in ("+", "-", "*"):
        a = _expr_bounds(ct, e.a)
        b = _expr_bounds(ct, e.b)
        if a is None or b is None:
            return None
        if e.op == "+":
            lo, hi = a[0] + b[0], a[1] + b[1]
        elif e.op == "-":
            lo, hi = a[0] - b[1], a[1] - b[0]
        else:
            cands = [a[0] * b[0], a[0] * b[1], a[1] * b[0], a[1] * b[1]]
            lo, hi = min(cands), max(cands)
        if abs(lo) > 1 << 62 or abs(hi) > 1 << 62:
            return None  # the real computation could overflow int64
        return lo, hi
    return None


def _static_span(ct: CTable, name: str) -> Optional[Tuple[int, int]]:
    """(lo, span) known at trace time, or None.  Dict codes span the
    dictionary, bools span {0,1}, int/date columns use the bucketed
    base-table bounds propagated through row-preserving operators."""
    m = ct.frame.meta(name)
    if m.kind == "dict" and m.dictionary is not None:
        return 0, max(int(m.dictionary.shape[0]), 1)
    if m.kind == "bool":
        return 0, 2
    b = ct.bounds.get(name)
    if b is not None:
        return b[0], max(b[1] - b[0] + 1, 1)
    return None


# packed composite codes must stay well inside int64 (and strictly
# below the _BIG padding sentinel)
_PACK_LIMIT = 1 << 59
# largest direct-address table a join build will scatter into
_DENSE_JOIN_LIMIT = 1 << 22
# largest dense group-id space (sort-free group-by)
_DENSE_GROUP_LIMIT = 1 << 20


# ----------------------------------------------------------------------
# traced operators
# ----------------------------------------------------------------------
def _c_filter(node: Filter, ct: CTable, ctx) -> CTable:
    expr = to_expr(ctx.bind(node.pred))
    mask = expr.eval_bool(ct.frame) & ct.row_valid
    # no compaction: rows keep their positions under a narrowed mask
    return CTable(
        ct.frame, jnp.sum(mask, dtype=INT), ct.unique, ct.bounds, mask,
        ct.fdeps, ct.dbound,
    )


def _int_key(ct: CTable, name: str):
    m = ct.frame.meta(name)
    if not m.is_int_like():
        raise Unsupported(f"non-integer join key {name} (kind={m.kind})")
    v = ct.frame.col_values(name)
    val = ct.frame.valid_array(name)
    ok = ct.row_valid if val is None else (ct.row_valid & val)
    return v, ok


def _joint_codes(lct: CTable, lkeys, rct: CTable, rkeys):
    """Composite key codes comparable across sides, plus a *static*
    bound ``S`` on the code space when every key has trace-time bounds
    (dict keys re-coded onto a merged dictionary when the two sides'
    dictionaries differ).  ``S`` is None when any key needed traced
    bounds; a static ``S`` small enough lets the join direct-address."""
    lcode = jnp.zeros((lct.cap,), dtype=INT)
    rcode = jnp.zeros((rct.cap,), dtype=INT)
    lok = lct.row_valid
    rok = rct.row_valid
    S: Optional[int] = 1
    for i, (lk, rk) in enumerate(zip(lkeys, rkeys)):
        lv, lo_ok = _int_key(lct, lk)
        rv, ro_ok = _int_key(rct, rk)
        lok = lok & lo_ok
        rok = rok & ro_ok
        lm, rm = lct.frame.meta(lk), rct.frame.meta(rk)
        if (lm.kind == "dict") != (rm.kind == "dict"):
            raise Unsupported(f"join key {lk}={rk} mixes dict and non-dict")
        lo = span = None
        if lm.kind == "dict" and lm.dictionary is not rm.dictionary:
            merged = np.union1d(
                lm.dictionary.astype("U"), rm.dictionary.astype("U")
            )
            llut = jnp.asarray(
                np.searchsorted(merged, lm.dictionary.astype("U")), dtype=INT
            )
            rlut = jnp.asarray(
                np.searchsorted(merged, rm.dictionary.astype("U")), dtype=INT
            )
            lv = llut[jnp.clip(lv, 0, lm.dictionary.shape[0] - 1)]
            rv = rlut[jnp.clip(rv, 0, rm.dictionary.shape[0] - 1)]
            lo, span = 0, max(int(merged.shape[0]), 1)
        else:
            ls = _static_span(lct, lk)
            rs = _static_span(rct, rk)
            if ls is not None and rs is not None:
                lo = min(ls[0], rs[0])
                span = max(ls[0] + ls[1], rs[0] + rs[1]) - lo
        if i and (S is None or (span is not None and S * span > _PACK_LIMIT)):
            # packing could overflow int64 (previous key had only
            # traced bounds, or the static product got too wide):
            # rank-compress the running codes *jointly* so both sides
            # stay comparable.  Rank output is statically bounded by
            # the total capacity, so S recovers a static value.
            cat = jnp.concatenate([lcode, rcode])
            rr = _rank(cat, lct.cap + rct.cap)
            lcode, rcode = rr[: lct.cap], rr[lct.cap:]
            S = lct.cap + rct.cap + 1
        if span is None:
            tlo = jnp.minimum(_masked_min(lv, lok), _masked_min(rv, rok))
            thi = jnp.maximum(_masked_max(lv, lok), _masked_max(rv, rok))
            tspan = jnp.maximum(thi - tlo + 1, 1)
            lcode = lcode * tspan + jnp.clip(lv - tlo, 0, tspan - 1)
            rcode = rcode * tspan + jnp.clip(rv - tlo, 0, tspan - 1)
            S = None
        else:
            lcode = lcode * span + jnp.clip(lv - lo, 0, span - 1)
            rcode = rcode * span + jnp.clip(rv - lo, 0, span - 1)
            S = None if S is None else S * span
    return lcode, lok, rcode, rok, S


def _dense_lookup(code, ok, cap: int, S: int):
    """Direct-address table: slot ``c`` holds the (last) row whose key
    code is ``c``, or -1.  Exact — no post-probe code comparison."""
    return (
        jnp.full((S,), -1, dtype=INT)
        .at[jnp.where(ok, code, S)]
        .set(jnp.arange(cap, dtype=INT), mode="drop")
    )


def _stack_sides(lf: TensorFrame, l_idx, rf: TensorFrame, r_idx, cap: int):
    """Horizontal stack of gathered left and right payloads (left
    columns first, like the engine's join output).  A ``None`` index
    keeps that side's rows in place — no gather at all."""
    if set(lf.columns) & set(rf.columns):
        raise Unsupported("join sides share column names")
    lit_ = lf.itensor if l_idx is None else _rows(lf.itensor, l_idx)
    lft_ = lf.ftensor if l_idx is None else _rows(lf.ftensor, l_idx)
    rit_ = rf.itensor if r_idx is None else _rows(rf.itensor, r_idx)
    rft_ = rf.ftensor if r_idx is None else _rows(rf.ftensor, r_idx)
    it = _hcat(lit_, rit_)
    ft = _hcat(lft_, rft_)
    iw, fw = lf.itensor.shape[1], lf.ftensor.shape[1]
    cols: Dict[str, ColumnMeta] = {}
    for name, m in lf.columns.items():
        cols[name] = dataclasses.replace(m)
    for name, m in rf.columns.items():
        off = fw if m.kind == "float" else iw
        cols[name] = dataclasses.replace(m, slot=m.slot + off)
    return TensorFrame(it, ft, cols, {}, cap)


def _probe_build(build: CTable, bcode, bok, pcode, pok, S):
    """(matched, brow): for each probe row, whether a build row with an
    equal key exists and (any) one such row's index.  Direct addressing
    when the static code space fits; else sort + binary search."""
    if S is not None and S <= _DENSE_JOIN_LIMIT:
        tbl = _dense_lookup(bcode, bok, build.cap, S)
        brow = tbl[jnp.clip(pcode, 0, S - 1)]
        matched = (brow >= 0) & pok
        return matched, jnp.clip(brow, 0, build.cap - 1)
    key = jnp.where(bok, bcode, _BIG)
    s = _sort(key)
    pos = jnp.searchsorted(s, pcode)
    posc = jnp.clip(pos, 0, build.cap - 1)
    matched = (pos < build.cap) & (s[posc] == pcode) & pok
    # recover the row index behind sorted slot ``posc``: rank the same
    # codes against the sort and scatter the row ids (live build codes
    # are unique, so ranks are collision-free where it matters)
    rank = jnp.searchsorted(s, key)
    perm = (
        jnp.zeros((build.cap,), dtype=INT)
        .at[rank]
        .set(jnp.arange(build.cap, dtype=INT))
    )
    return matched, perm[posc]


def _c_join(node: Join, lct: CTable, rct: CTable) -> CTable:
    lcode, lok, rcode, rok, S = _joint_codes(
        lct, node.left_keys, rct, node.right_keys
    )
    if node.how in ("semi", "anti"):
        # membership only
        if S is not None and S <= _DENSE_JOIN_LIMIT:
            tbl = _dense_lookup(rcode, rok, rct.cap, S)
            present = (tbl[jnp.clip(lcode, 0, S - 1)] >= 0) & lok
        else:
            s = _sort(jnp.where(rok, rcode, _BIG))
            pos = jnp.searchsorted(s, lcode)
            posc = jnp.clip(pos, 0, rct.cap - 1)
            present = (pos < rct.cap) & (s[posc] == lcode) & lok
        keep = present if node.how == "semi" else (lct.row_valid & ~present)
        return CTable(
            lct.frame, jnp.sum(keep, dtype=INT), lct.unique, lct.bounds,
            keep, lct.fdeps, lct.dbound,
        )
    if node.how not in ("inner", "left"):
        raise Unsupported(f"join type {node.how}")

    right_build = _is_unique(rct, node.right_keys)
    if node.how == "left":
        if not right_build:
            if _is_unique(lct, node.left_keys):
                # one-to-many: expand matches instead of probing
                return _c_left_expand(
                    node, lct, lcode, lok, rct, rcode, rok, S
                )
            raise Unsupported("left join with no provably-unique side")
    elif not right_build and not _is_unique(lct, node.left_keys):
        raise Unsupported("inner join with no provably-unique side")
    if right_build:
        build, probe = rct, lct
        bcode, bok, pcode, pok = rcode, rok, lcode, lok
        pkeys = node.left_keys
    else:  # swapped: build on the unique left side, probe the right
        build, probe = lct, rct
        bcode, bok, pcode, pok = lcode, lok, rcode, rok
        pkeys = node.right_keys

    matched, brow = _probe_build(build, bcode, bok, pcode, pok, S)

    unique = set(probe.unique)
    if _is_unique(probe, pkeys):
        unique |= build.unique
    bounds = {**lct.bounds, **rct.bounds}
    # rows agreeing on the probe keys read the same (unique) build row,
    # so every build column is now a function of the probe keys
    fdeps = {**lct.fdeps, **rct.fdeps}
    dep = frozenset(pkeys)
    dbound = {**lct.dbound, **rct.dbound}
    for cname in build.frame.columns:
        fdeps[cname] = dep
        # build payloads are gathered from <= build.cap rows
        dbound[cname] = min(dbound.get(cname, build.cap), build.cap)

    if node.how == "inner":
        # surviving probe keys are a subset of the build side's live
        # key tuples, of which there are at most build.cap
        for pk in pkeys:
            dbound[pk] = min(dbound.get(pk, probe.cap), build.cap)
        # probe rows stay in place (the match flag becomes the mask);
        # only the build side pays a gather
        n_out = jnp.sum(matched, dtype=INT)
        if right_build:
            out = _stack_sides(probe.frame, None, build.frame, brow, probe.cap)
        else:
            out = _stack_sides(build.frame, brow, probe.frame, None, probe.cap)
        return CTable(out, n_out, unique, bounds, matched, fdeps, dbound)

    # left join: keep every probe (=left) row; unmatched rows take
    # clamped build payloads masked off by fresh validity columns
    out = _stack_sides(probe.frame, None, build.frame, brow, probe.cap)
    out = _mask_right(out, build.frame, matched)
    return CTable(out, probe.n, unique, bounds, probe.mask, fdeps, dbound)


def _mask_right(out: TensorFrame, rf: TensorFrame, matched) -> TensorFrame:
    """Append/merge validity for every right-side output column so
    unmatched left rows read as NULL (mirrors the engine's left-outer
    ``need_valid`` append)."""
    for name in list(rf.columns):
        if name.startswith(_valid_name("")):
            continue
        vn = _valid_name(name)
        if vn in out.columns:
            flag = (out.col_values(vn) != 0) & matched
        else:
            flag = matched
        out = out._append_int_column(vn, flag.astype(INT), "bool")
    return out


def _c_left_expand(node, lct, lcode, lok, rct, rcode, rok, S) -> CTable:
    """One-to-many left join with a provably-unique LEFT side: each
    right row finds its single left owner, producing the matched pairs;
    left rows no right row claimed are appended with NULL right
    payloads.  Output capacity = cap_right + cap_left."""
    matched_r, lrow_r = _probe_build(lct, lcode, lok, rcode, rok, S)

    # matched pairs, compacted over the right capacity
    idx_r = prims.compact(matched_r, rct.cap)
    n1 = jnp.sum(matched_r, dtype=INT)
    # left rows never claimed (null-key left rows stay too: engine left
    # joins keep them with NULL right payloads)
    hit = (
        jnp.zeros((lct.cap,), dtype=INT)
        .at[lrow_r]
        .max(matched_r.astype(INT))
    )
    keep_l = lct.row_valid & (hit == 0)
    idx_l = prims.compact(keep_l, lct.cap)
    n2 = jnp.sum(keep_l, dtype=INT)

    live = jnp.concatenate(
        [
            jnp.arange(rct.cap, dtype=INT) < n1,
            jnp.arange(lct.cap, dtype=INT) < n2,
        ]
    )
    # compact both parts into contiguous [0, n1+n2)
    sel = prims.compact(live, rct.cap + lct.cap)
    l_all = jnp.concatenate([lrow_r[idx_r], idx_l])[sel]
    r_all = jnp.concatenate([idx_r, jnp.zeros((lct.cap,), dtype=INT)])[sel]
    matched_all = jnp.concatenate(
        [jnp.ones((rct.cap,), dtype=bool), jnp.zeros((lct.cap,), dtype=bool)]
    )[sel]
    out = _stack_sides(lct.frame, l_all, rct.frame, r_all, rct.cap + lct.cap)
    out = _mask_right(out, rct.frame, matched_all)
    return CTable(
        out, n1 + n2, set(), {**lct.bounds, **rct.bounds},
        dbound={**lct.dbound, **rct.dbound},
    )


def _check_group_cols(f: TensorFrame, names) -> None:
    for k in names:
        if f.valid_array(k) is not None:
            raise Unsupported(f"nullable group key {k}")
        if f.meta(k).kind == "obj":
            raise Unsupported(f"group key {k} is offloaded")


def _pack_group_code(ct: CTable, f: TensorFrame, names) -> Tuple:
    """(code, S): one int64 composite code per row whose equality
    matches tuple-equality of the named columns, and a static bound on
    the code space.  Keys with trace-time spans pack directly; float or
    unbounded keys are rank-compressed first (rank preserves equality),
    so S always stays static."""
    _check_group_cols(f, names)
    cap = ct.cap
    code = jnp.zeros((cap,), dtype=INT)
    S = 1
    for k in names:
        m = f.meta(k)
        v = f.col_values(k)
        sp = None if m.kind == "float" else _static_span(ct, k)
        if sp is None:
            if m.kind == "float":
                # collapse -0.0 onto +0.0 so equal keys share a rank
                v = jnp.where(v == 0, jnp.zeros((), dtype=v.dtype), v)
            v = _rank(v, cap)
            span = cap + 1
        else:
            lo, span = sp
            v = jnp.clip(v - lo, 0, span - 1)
        if S * span > _PACK_LIMIT:
            # re-rank the running code (injective on present values)
            code = _rank(code, cap)
            S = cap + 1
        code = code * span + v
        S = S * span
    return code, S


def _dbound_product(ct: CTable, names) -> int:
    """Static upper bound on the number of distinct live key tuples:
    the product of the per-key distinct bounds, saturating at cap."""
    db = 1
    for k in names:
        db *= ct.dbound.get(k, ct.cap)
        if db >= ct.cap:
            return ct.cap
    return db


def _group_ids(code, S: int, rv, cap: int, dmax: Optional[int] = None):
    """(gids, n_groups, cap_out): dense group ids in first-seen-code
    order for live rows; padding maps to ``cap_out`` so segment
    scatters drop it.  A small static code space counts occupancy
    directly (no sort at all); otherwise sort the codes once and rank
    against the distinct values.  ``dmax`` (a sound static bound on the
    distinct key count) shrinks the output capacity below the code
    space — the whole plan downstream of the aggregate narrows with
    it."""
    cap_out = min(cap, _pow2(S))
    if dmax is not None:
        cap_out = min(cap_out, _pow2(max(dmax, 1)))
    if S <= _DENSE_GROUP_LIMIT:
        ids = jnp.where(rv, code, S)
        cnt = jax.ops.segment_sum(
            jnp.ones((cap,), dtype=INT), ids, num_segments=S
        )
        present = cnt > 0
        dense = prims.prefix_sum(present.astype(INT)) - 1
        n_groups = jnp.sum(present, dtype=INT)
        gids = jnp.where(rv, dense[jnp.clip(code, 0, S - 1)], cap_out)
        return gids, n_groups, cap_out
    scode = jnp.where(rv, code, _BIG)
    s = _sort(scode)
    live = s < _BIG
    first = jnp.concatenate([live[:1], (s[1:] != s[:-1]) & live[1:]])
    n_groups = jnp.sum(first, dtype=INT)
    # dense id per sorted slot; a live row reads it back through the
    # first-occurrence slot of its own code
    did = prims.prefix_sum(first.astype(INT)) - 1
    gids = jnp.where(rv, did[jnp.searchsorted(s, scode)], cap_out)
    return gids, n_groups, cap_out


def _c_aggregate(node: Aggregate, ct: CTable, ctx) -> CTable:
    f = ct.frame
    key_names: List[str] = []
    kbounds = dict(ct.bounds)
    for name, e in node.keys:
        if not (
            isinstance(e, SCol) and e.internal == name and f.has_column(name)
        ):
            f = f.with_column(name, to_expr(ctx.bind(e)))
            kb = _expr_bounds(ct, e)
            if kb is not None:
                kbounds[name] = kb
        key_names.append(name)
    specs: List[Tuple[str, str, Optional[str]]] = []
    for name, fn, e in node.aggs:
        if fn == "size":
            specs.append((name, fn, None))
            continue
        if isinstance(e, SCol) and f.has_column(e.internal):
            specs.append((name, fn, e.internal))
        else:
            cn = f"__in.{name}"
            f = f.with_column(cn, to_expr(ctx.bind(e)))
            specs.append((name, fn, cn))
    ct = CTable(f, ct.n, ct.unique, kbounds, ct.mask, ct.fdeps, ct.dbound)
    cap, rv = ct.cap, ct.row_valid
    fd = float_dtype()

    if key_names:
        _check_group_cols(f, key_names)
        eff = _effective_keys(ct, key_names)
        code, S = _pack_group_code(ct, f, eff)
        gids, n_groups, cap_out = _group_ids(
            code, S, rv, cap, _dbound_product(ct, eff)
        )
        rep = jax.ops.segment_min(
            jnp.where(rv, jnp.arange(cap, dtype=INT), _BIG),
            gids,
            num_segments=cap_out,
        )
        repc = jnp.clip(rep, 0, cap - 1)
    else:
        cap_out = 1
        n_groups = jnp.asarray(1, dtype=INT)
        gids = jnp.where(rv, 0, 1)
        repc = None

    icols: List = []
    fcols: List = []
    cols: Dict[str, ColumnMeta] = {}

    def add(name: str, kind: str, arr, dictionary=None):
        if kind == "float":
            cols[name] = ColumnMeta(name, "float", len(fcols))
            fcols.append(arr.astype(fd))
        else:
            cols[name] = ColumnMeta(name, kind, len(icols), dictionary)
            icols.append(arr.astype(INT))

    for k in key_names:
        m = f.meta(k)
        add(k, m.kind, f.col_values(k)[repc], m.dictionary)

    for name, fn, cn in specs:
        if fn == "size":
            add(name, "int", _seg_sum(rv.astype(INT), gids, cap_out))
            continue
        m = f.meta(cn)
        if m.kind == "obj":
            raise Unsupported(f"aggregate over offloaded column {cn}")
        v = f.col_values(cn)
        val = f.valid_array(cn)
        ok = rv if val is None else (rv & val)
        isf = m.kind == "float"
        if fn == "count":
            add(name, "int", _seg_sum(ok.astype(INT), gids, cap_out))
        elif fn == "sum":
            zero = jnp.zeros((), dtype=v.dtype)
            arr = _seg_sum(jnp.where(ok, v, zero), gids, cap_out)
            add(name, "float" if isf else "int", arr)
        elif fn == "mean":
            s_ = _seg_sum(jnp.where(ok, v, 0).astype(fd), gids, cap_out)
            c_ = _seg_sum(ok.astype(INT), gids, cap_out)
            # engine formula (agg.segment_agg): sum / max(count, 1)
            add(name, "float", s_ / jnp.maximum(c_, 1).astype(fd))
        elif fn in ("min", "max"):
            if fn == "min":
                sent = jnp.asarray(np.inf if isf else _BIG, dtype=v.dtype)
                arr = jax.ops.segment_min(
                    jnp.where(ok, v, sent), gids, num_segments=cap_out
                )
            else:
                sent = jnp.asarray(-np.inf if isf else -_BIG, dtype=v.dtype)
                arr = jax.ops.segment_max(
                    jnp.where(ok, v, sent), gids, num_segments=cap_out
                )
            add(name, m.kind, arr, m.dictionary)
        elif fn == "nunique":
            if isf:
                raise Unsupported("nunique over float column")
            add(name, "int", _seg_nunique(v, ok, gids, cap_out, cap))
        else:
            raise Unsupported(f"aggregate fn {fn}")

    it = jnp.stack(icols, axis=1) if icols else _empty_tensor(cap_out, INT)
    ft = jnp.stack(fcols, axis=1) if fcols else _empty_tensor(cap_out, fd)
    out = TensorFrame(it, ft, cols, {}, cap_out)
    unique = {frozenset(key_names)} if key_names else set()
    bounds = {k: ct.bounds[k] for k in key_names if k in ct.bounds}
    dbound = {k: ct.dbound[k] for k in key_names if k in ct.dbound}
    for name, fn, cn in specs:
        if fn in ("min", "max") and cn in ct.bounds:
            bounds[name] = ct.bounds[cn]  # output values c input values
    return CTable(out, n_groups, unique, bounds, dbound=dbound)


def _seg_sum(vals, gids, m: int):
    return jax.ops.segment_sum(vals, gids, num_segments=m)


def _seg_nunique(v, ok, gids, cap_out: int, cap: int):
    """COUNT(DISTINCT col) per group: pack (gid, rank(value)) into one
    code, sort it once, count first occurrences per gid — the traced
    twin of agg._segment_nunique, minus the host sync and the lexsort."""
    M = 2 * cap  # rank(v) <= cap < M, so the packing is collision-free
    pair = gids * M + _rank(v, cap)
    s = _sort(jnp.where(ok, pair, _BIG))
    live = s < _BIG
    first = jnp.concatenate([live[:1], (s[1:] != s[:-1]) & live[1:]])
    seg = jnp.where(live, s // M, cap_out)
    return _seg_sum(first.astype(INT), seg, cap_out)


def _c_project(node: Project, ct: CTable, ctx) -> CTable:
    f = ct.frame
    srcs: List[str] = []
    mapping: Dict[str, str] = {}
    used = set()
    ebounds: Dict[str, Tuple[int, int]] = {}
    for i, (name, e) in enumerate(node.outputs):
        if (
            isinstance(e, SCol)
            and f.has_column(e.internal)
            and e.internal not in used
        ):
            src = e.internal
        else:
            src = f"__o.{i}.{name}"
            f = f.with_column(src, to_expr(ctx.bind(e)))
            eb = _expr_bounds(ct, e)
            if eb is not None:
                ebounds[name] = eb
        used.add(src)
        srcs.append(src)
        mapping[src] = name
    out = f.select(srcs).rename(mapping)
    unique = set()
    for combo in ct.unique:
        if all(c in mapping for c in combo):
            unique.add(frozenset(mapping[c] for c in combo))
    bounds = {
        name: ct.bounds[src]
        for src, name in mapping.items()
        if src in ct.bounds
    }
    bounds.update(ebounds)
    fdeps = {}
    for src, name in mapping.items():
        dep = ct.fdeps.get(src)
        if dep is not None and dep <= set(mapping):
            fdeps[name] = frozenset(mapping[d] for d in dep)
    dbound = {
        name: ct.dbound[src]
        for src, name in mapping.items()
        if src in ct.dbound
    }
    return CTable(out, ct.n, unique, bounds, ct.mask, fdeps, dbound)


def _order_code(node: Sort, ct: CTable):
    """One int64 per row whose ascending order IS the requested sort:
    keys pack least-significant first (static spans multiply in; float
    or unbounded keys enter through their order-preserving rank),
    seeded with the row index so codes are *distinct* per row and ties
    break stably; dead rows land after every live row."""
    f = ct.frame
    cap = ct.cap
    acc = jnp.arange(cap, dtype=INT)  # stable tiebreak, keeps acc distinct
    S = cap
    for name, asc in reversed(node.keys):  # first key most significant
        m = f.meta(name)
        v = f.col_values(name)
        if not asc:
            v = -v
        sp = None if m.kind == "float" else _static_span(ct, name)
        if sp is None:
            r = _rank(v, cap)  # order-preserving (ties collapse: fine)
            lo, span = 0, cap + 1
        else:
            lo, span = sp
            if not asc:
                lo = -(lo + span - 1)  # negation flips the window
            r = jnp.clip(v - lo, 0, span - 1)
        if S * span > _PACK_LIMIT:
            acc = _rank(acc, cap)  # bijective on a distinct array
            S = cap
        acc = r * S + acc
        S = S * span
    return jnp.where(ct.row_valid, acc, acc + S)  # padding rows last


def _c_sort(node: Sort, ct: CTable) -> CTable:
    """ORDER BY without a lexsort: ranking the distinct packed order
    codes is a bijection, so scattering the ranks yields the sort
    permutation from two cheap sorts."""
    cap = ct.cap
    acc = _order_code(node, ct)
    pos = _rank(acc, cap)  # bijection: every acc value is distinct
    order = jnp.zeros((cap,), dtype=INT).at[pos].set(jnp.arange(cap, dtype=INT))
    return _gather_rows(ct, order, ct.n)


def _c_topk(sort_node: Sort, k: int, ct: CTable) -> CTable:
    """Fused ORDER BY + LIMIT k: ``top_k`` over the negated order
    codes finds the k smallest (ties to the lower row index, matching
    the stable sort), so only k rows are ever gathered."""
    kk = min(ct.cap, _pow2(k))
    _, idx = jax.lax.top_k(-_order_code(sort_node, ct), kk)
    out = _gather_rows(ct, idx, jnp.minimum(ct.n, k))
    return out


def _c_limit(node: Limit, ct: CTable) -> CTable:
    k = int(node.n)
    ct = _compact(ct)  # LIMIT slices, so rows must sit in [0, n)
    new_cap = min(ct.cap, _pow2(k))
    f = ct.frame
    out = TensorFrame(
        f.itensor[:new_cap], f.ftensor[:new_cap], dict(f.columns), {}, new_cap
    )
    return CTable(
        out, jnp.minimum(ct.n, k), ct.unique, ct.bounds, fdeps=ct.fdeps,
        dbound=ct.dbound,
    )


def _c_distinct(ct: CTable) -> CTable:
    f = ct.frame
    names = f.column_names
    for c in names:
        if not f.meta(c).is_int_like():
            raise Unsupported(
                f"DISTINCT over kind {f.meta(c).kind} column {c}"
            )
    rv = ct.row_valid
    _check_group_cols(f, names)
    eff = _effective_keys(ct, names)
    code, S = _pack_group_code(ct, f, eff)
    gids, n_out, cap_out = _group_ids(
        code, S, rv, ct.cap, _dbound_product(ct, eff)
    )
    # each group's first row index; sorting puts the kept rows in
    # original order (matches the engine) with empty slots pushed last
    rep = jax.ops.segment_min(
        jnp.where(rv, jnp.arange(ct.cap, dtype=INT), _BIG),
        gids,
        num_segments=cap_out,
    )
    idx = jnp.clip(_sort(rep), 0, ct.cap - 1)
    return _gather_rows(
        ct, idx, n_out, unique=ct.unique | {frozenset(names)}
    )


def _c_attach_scalar(node: AttachScalar, ct: CTable, sub: CTable) -> CTable:
    q = node.sub.v
    while isinstance(q, Project):
        q = q.child
    if not (isinstance(q, Aggregate) and not q.keys):
        raise Unsupported("scalar subquery not provably single-row")
    m = sub.frame.meta(node.output)
    if _valid_name(node.output) in sub.frame.columns:
        raise Unsupported("nullable scalar subquery output")
    v = sub.frame.col_values(node.output)[0]
    f = ct.frame
    if m.kind == "float":
        out = f._append_float_column(
            node.name, jnp.full((ct.cap,), v, dtype=float_dtype())
        )
    else:
        out = f._append_int_column(
            node.name, jnp.full((ct.cap,), v, dtype=INT), m.kind, m.dictionary
        )
    return CTable(
        out, ct.n, ct.unique, ct.bounds, ct.mask, ct.fdeps, ct.dbound
    )


def _c_scan(node: Scan, ctx) -> CTable:
    if node.predicates:
        raise Unsupported("scan with pushed predicates")
    base = ctx.base_table(node.table)
    f = base.frame.select(list(node.columns))
    f = f.rename({c: f"{node.alias}.{c}" for c in node.columns})
    prep = ctx.preps[node.table]
    uniq = set()
    have = set(node.columns)
    for combo, verdict in prep.combos.items():
        if verdict and set(combo) <= have:
            uniq.add(frozenset(f"{node.alias}.{c}" for c in combo))
    bounds = {
        f"{node.alias}.{c}": prep.bounds[c]
        for c in node.columns
        if c in prep.bounds
    }
    return CTable(f, base.n, uniq, bounds)


def _c_lower(node, ctx, memo: Dict) -> CTable:
    if isinstance(node, Shared):
        if node not in memo:
            memo[node] = _c_lower(node.child, ctx, memo)
        return memo[node]
    if isinstance(node, Scan):
        return _c_scan(node, ctx)
    if isinstance(node, Filter):
        return _c_filter(node, _c_lower(node.child, ctx, memo), ctx)
    if isinstance(node, Join):
        return _c_join(
            node,
            _c_lower(node.left, ctx, memo),
            _c_lower(node.right, ctx, memo),
        )
    if isinstance(node, Aggregate):
        return _c_aggregate(node, _c_lower(node.child, ctx, memo), ctx)
    if isinstance(node, Project):
        return _c_project(node, _c_lower(node.child, ctx, memo), ctx)
    if isinstance(node, Sort):
        return _c_sort(node, _c_lower(node.child, ctx, memo))
    if isinstance(node, Limit):
        if isinstance(node.child, Sort) and int(node.n) <= 1 << 12:
            return _c_topk(
                node.child,
                int(node.n),
                _c_lower(node.child.child, ctx, memo),
            )
        return _c_limit(node, _c_lower(node.child, ctx, memo))
    if isinstance(node, Distinct):
        return _c_distinct(_c_lower(node.child, ctx, memo))
    if isinstance(node, AttachScalar):
        return _c_attach_scalar(
            node,
            _c_lower(node.child, ctx, memo),
            _c_lower(node.sub.v, ctx, memo),
        )
    raise Unsupported(f"plan node {type(node).__name__}")


def _finalize(ct: CTable) -> CTable:
    """Compact the result to fresh, dead-slot-free payload tensors so
    the program returns exactly what the caller slices."""
    ct = _compact(ct)
    f = ct.frame
    islots: List[int] = []
    fslots: List[int] = []
    cols: Dict[str, ColumnMeta] = {}
    for name, m in f.columns.items():
        if m.kind == "float":
            cols[name] = dataclasses.replace(m, slot=len(fslots), block=0)
            fslots.append(m.slot)
        else:
            cols[name] = dataclasses.replace(m, slot=len(islots), block=0)
            islots.append(m.slot)
    it = (
        f.itensor[:, jnp.asarray(islots, dtype=INT)]
        if islots
        else _empty_tensor(f.nrows, INT)
    )
    ft = (
        f.ftensor[:, jnp.asarray(fslots, dtype=INT)]
        if fslots
        else _empty_tensor(f.nrows, float_dtype())
    )
    out = TensorFrame(it, ft, cols, {}, f.nrows)
    return CTable(out, jnp.asarray(ct.n, dtype=INT), ct.unique)


# ----------------------------------------------------------------------
# trace context + compiled-program construction
# ----------------------------------------------------------------------
class _Ctx:
    def __init__(self, bases, preps, narrow, params_i, params_f, slots):
        self.bases = bases  # table -> (itensor, ftensor, n) traced
        self.preps = preps
        self.narrow = narrow
        self.params_i = params_i
        self.params_f = params_f
        self.slots = slots  # global param index -> ('i'|'f', position)
        self._base_memo: Dict[str, CTable] = {}

    def base_table(self, name: str) -> CTable:
        got = self._base_memo.get(name)
        if got is None:
            it, ft, n = self.bases[name]
            cols = {
                k: dataclasses.replace(m)
                for k, m in self.narrow[name].columns.items()
            }
            got = CTable(TensorFrame(it, ft, cols, {}, self.preps[name].cap), n)
            self._base_memo[name] = got
        return got

    def bind(self, e):
        if not self.slots:
            return e

        def fn(n):
            if isinstance(n, SParam):
                tag, j = self.slots[n.index]
                arr = self.params_f if tag == "f" else self.params_i
                return _BoundParam(arr[j], n.kind)
            return n

        return transform(e, fn)


def _param_slots(kinds: List[str]):
    slots = []
    ni = nf = 0
    for k in kinds:
        if k == "float":
            slots.append(("f", nf))
            nf += 1
        else:
            slots.append(("i", ni))
            ni += 1
    return slots, ni, nf


class _Entry:
    __slots__ = (
        "compiled",
        "columns",
        "cap",
        "order",
        "digest",
        "trace_s",
        "compile_s",
    )

    def __init__(self, compiled, columns, cap, order, digest, trace_s, compile_s):
        self.compiled = compiled
        self.columns = columns
        self.cap = cap
        self.order = order
        self.digest = digest
        self.trace_s = trace_s
        self.compile_s = compile_s


def _donating() -> bool:
    # donation is a no-op on the CPU backend, so there the padded
    # inputs can be built once per base table and reused every call;
    # accelerators really consume donated buffers and need fresh ones
    return jax.default_backend() != "cpu"


def _build_args(preps, narrow, order, values, slots, n_i, n_f):
    args = []
    fresh = _donating()
    for name in order:
        prep, nw = preps[name], narrow[name]
        f = prep.frame
        key = (nw.islots, nw.fslots)
        pads = None if fresh else prep.pads.get(key)
        if pads is None:
            pads = (
                _pad_rows(f.itensor, nw.islots, prep.cap),
                _pad_rows(f.ftensor, nw.fslots, prep.cap),
                jnp.asarray(f.nrows, dtype=INT),
            )
            if not fresh:
                prep.pads[key] = pads
        args.extend(pads)
    vi = np.zeros((n_i,), dtype=np.int64)
    vf = np.zeros((n_f,), dtype=np.float64)
    for (kind, v), (tag, j) in zip(values, slots):
        if tag == "f":
            vf[j] = float(v)
        else:
            vi[j] = int(v)
    args.append(jnp.asarray(vi, dtype=INT))
    args.append(jnp.asarray(vf, dtype=float_dtype()))
    return args


def _pad_rows(t, slots: Tuple[int, ...], cap: int):
    # always a FRESH buffer (never the base tensor itself): the padded
    # inputs are donated to the executable, and donating a shared
    # buffer would invalidate the caller's base table
    n = t.shape[0]
    out = jnp.zeros((cap, len(slots)), dtype=t.dtype)
    if not slots:
        return out
    return out.at[:n].set(t[:, jnp.asarray(slots, dtype=INT)])


def _compile_entry(fpr, pplan, preps, narrow, order, kinds, args):
    from repro.resilience.faults import fault_point

    fault_point("compile")
    slots, _, _ = _param_slots(kinds)
    captured: Dict = {}

    def run(*flat):
        i = 0
        bases = {}
        for name in order:
            bases[name] = (flat[i], flat[i + 1], flat[i + 2])
            i += 3
        ctx = _Ctx(bases, preps, narrow, flat[i], flat[i + 1], slots)
        out = _finalize(_c_lower(pplan, ctx, {}))
        captured["columns"] = out.frame.columns
        captured["cap"] = out.cap
        return out.frame.itensor, out.frame.ftensor, out.n

    donate = (
        tuple(j for j in range(3 * len(order)) if j % 3 != 2)
        if _donating()
        else ()
    )
    fn = jax.jit(run, donate_argnums=donate)
    with warnings.catch_warnings():
        # CPU backends cannot honor every donation; that is fine
        warnings.simplefilter("ignore")
        t0 = time.perf_counter()
        with obs.span("sql.compile.trace", fingerprint=fpr[:80]):
            lowered = fn.lower(*args)
        t1 = time.perf_counter()
        with obs.span("sql.compile.compile"):
            compiled = lowered.compile()
        t2 = time.perf_counter()
    return _Entry(
        compiled, captured["columns"], captured["cap"], order, _digest(fpr),
        t1 - t0, t2 - t1,
    )


def _maybe_compile(fpr, pplan, preps, narrow, tables, kinds, args):
    """Resolve a cache miss: compile ``fpr``, or reuse the entry a
    racing thread produced while we waited on the trace lock.  The
    caller holds the per-fingerprint lock.  Returns None on fallback."""
    with _LOCK:
        entry = _CACHE.get(fpr)
        if entry is not None:
            STATS["hits"] += 1
            _CACHE.move_to_end(fpr)
            return entry
        if fpr in _NEGATIVE:
            _fallback(_NEGATIVE[fpr])
            return None
        STATS["misses"] += 1
    try:
        entry = _compile_entry(fpr, pplan, preps, narrow, tables, kinds, args)
    except _FALLBACK_ERRORS as e:
        with _LOCK:
            _NEGATIVE[fpr] = f"{type(e).__name__}: {e}"
            _TRACE_LOCKS.pop(fpr, None)
            _fallback(_NEGATIVE[fpr])
        return None
    except Exception as e:
        # an *unexpected* trace/compile crash (backend bug, injected
        # fault) must not poison serving: negative-cache the
        # fingerprint so the plan permanently dispatches op-by-op, and
        # release the trace lock so waiters aren't stuck behind it
        with _LOCK:
            _NEGATIVE[fpr] = f"compile failure {type(e).__name__}: {e}"
            _TRACE_LOCKS.pop(fpr, None)
            STATS["compile_failures"] += 1
            _fallback(_NEGATIVE[fpr])
        return None
    with _LOCK:
        STATS["compiles"] += 1
        _CACHE[fpr] = entry
        _TRACE_LOCKS.pop(fpr, None)
        while len(_CACHE) > CACHE_CAPACITY:
            _CACHE.popitem(last=False)
            STATS["evictions"] += 1
        rec = STATS["plans"].setdefault(
            entry.digest,
            {
                "tables": tables,
                "trace_s": 0.0,
                "compile_s": 0.0,
                "exec_s": 0.0,
                "calls": 0,
            },
        )
        rec["trace_s"] += entry.trace_s
        rec["compile_s"] += entry.compile_s
    return entry


_FALLBACK_ERRORS = (
    Unsupported,
    SqlError,
    jax.errors.ConcretizationTypeError,
    jax.errors.TracerArrayConversionError,
    jax.errors.TracerBoolConversionError,
    jax.errors.TracerIntegerConversionError,
)


def maybe_execute_compiled(plan, frames) -> Optional[TensorFrame]:
    """Run ``plan`` through the compiled path, or return None to let
    the caller dispatch op-by-op."""
    mode = CONFIG.compiled
    if mode == "off":
        return None
    scans: List[Scan] = list(walk_scans(plan))
    if not scans:
        return None
    tables = sorted({s.table for s in scans})
    if any(s.predicates for s in scans):
        return _fallback("scan with pushed predicates")
    if not all(isinstance(frames.get(t), TensorFrame) for t in tables):
        return _fallback("scan of a non-frame table")
    udfs = active_udfs()
    if udfs and plan_uses_udf(plan, frozenset(udfs)):
        # the fingerprint keys on plan structure; it cannot capture the
        # python closure behind a session UDF -> op-by-op dispatch
        return _fallback("session UDF")
    if mode != "force":
        total = sum(frames[t].nrows for t in tables)
        if total < CONFIG.compiled_min_rows:
            with _LOCK:
                STATS["skipped_small"] += 1
            return None

    preps = {t: _prep_table(frames[t]) for t in tables}
    read: Dict[str, set] = {t: set() for t in tables}
    for s in scans:
        read[s.table].update(s.columns)
    narrow = {t: _Narrow(preps[t].frame, read[t]) for t in tables}
    reqs: Dict[str, set] = {}
    _collect_unique_requests(plan, reqs)
    for t, combos in reqs.items():
        if t in preps:
            for combo in combos:
                _ensure_unique(preps[t], combo)

    try:
        pplan, values = parameterize(plan)
    except Unsupported as e:
        return _fallback(f"Unsupported: {e}")
    kinds = [k for k, _ in values]
    fpr = "|".join(
        [
            repr(pplan),
            f"fd={CONFIG.float_dtype}",
            *(_table_sig(t, preps[t], narrow[t]) for t in tables),
        ]
    )
    with _LOCK:
        if fpr in _NEGATIVE:
            return _fallback(_NEGATIVE[fpr])
        entry = _CACHE.get(fpr)
        if entry is not None:
            STATS["hits"] += 1
            _CACHE.move_to_end(fpr)
            tlock = None
        else:
            # one lock per in-flight fingerprint: concurrent first
            # compiles of the same plan serialize, distinct plans don't
            tlock = _TRACE_LOCKS.setdefault(fpr, threading.Lock())

    slots, n_i, n_f = _param_slots(kinds)
    args = _build_args(preps, narrow, tables, values, slots, n_i, n_f)

    cache_hit = entry is not None
    if entry is None:
        with tlock:
            entry = _maybe_compile(fpr, pplan, preps, narrow, tables, kinds, args)
        if entry is None:
            return None
        # tracing consumed (donated) the padded inputs; rebuild them
        args = _build_args(preps, narrow, tables, values, slots, n_i, n_f)

    t0 = time.perf_counter()
    with warnings.catch_warnings():
        # CPU backends cannot honor every donation; that is fine
        warnings.simplefilter("ignore")
        with obs.span(
            "sql.compile.execute", digest=entry.digest, cache_hit=cache_hit
        ) as sp:
            it, ft, n_out = entry.compiled(*args)
            n = int(prims.to_host(n_out, "compile.rows"))  # the program really ran
            sp.set(rows=n)
    t1 = time.perf_counter()
    with _LOCK:
        rec = STATS["plans"].get(entry.digest)
        if rec is not None:
            rec["exec_s"] += t1 - t0
            rec["calls"] += 1
    cols = {k: dataclasses.replace(m) for k, m in entry.columns.items()}
    return TensorFrame(it[:n], ft[:n], cols, {}, n)
