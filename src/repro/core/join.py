"""Joins (paper §IV-C, Alg. 3: factorize-then-join), TPU-adapted.

Pipeline:

1. **Shared factorization** of each key pair into one dense integer
   space (dictionary merge for strings, device-side range compression
   for ints — one bounds fetch, no host unique — with a combined-domain
   densify fallback for sparse domains) — Alg. 3 line 5.
2. **Composite packing** of multi-column keys (Horner over shared
   cardinalities, densifying between steps so the packed domain stays
   O(n) — always exact).
3. **Build/probe**: the Mojo hash table becomes a *direct-address
   table* (dense codes are a perfect hash): scatter build positions,
   gather probes — O(1) probes, no collisions, fully vectorized.  The
   build-side uniqueness decision is **stats-driven**: cached
   distinct/uniqueness stats (store zone maps, group-by outputs, prior
   joins) are consulted first, and only an unknown build side pays the
   sort-based test.  Non-unique build keys take sorted-probe
   (searchsorted + CSR expansion via the run-rank formulation shared
   with ``kernels/segment_reduce``).  ``sort_merge_join_rows`` is the
   paper's losing baseline (Fig. 12).
4. **Materialization**: *late* — matched row indices compose into the
   frames' ``RowView`` selection vectors and the two sides' payload
   blocks stack zero-copy, so a join chain gathers payloads once at the
   pipeline exit.  The probe compaction runs on-device (cumsum +
   scatter) behind a single deferred count fetch per join.

Supported: inner, left (outer), semi, anti — left/semi/anti go beyond
the paper (it defers them) but are required by TPC-H Q13/Q4/Q21/Q22.

Null keys never match (SQL semantics): left nulls are coded -1, right
nulls -2, and both build and probe paths reject negatives.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from . import encoding, prims
from .config import CONFIG
from .hashing import distinct
from .frame import (
    INT,
    ColumnMeta,
    OffloadedColumn,
    TensorFrame,
    ViewBlock,
    _empty_tensor,
    _is_hidden,
    _valid_name,
    float_dtype,
)

_DENSIFY_LIMIT_FACTOR = 4
VALID_PREFIX = "__v__"

#: Observable decision counters (tests / benchmarks): how often the
#: auto algorithm pick was answered by the stats cache vs. paying the
#: sort-based uniqueness test.
STATS = {
    "stats_unique_hits": 0,
    "stats_nonunique_hits": 0,
    "uniqueness_sort_tests": 0,
}


def reset_stats() -> None:
    for k in STATS:
        STATS[k] = 0


obs.metrics.register_group("core.join", lambda: dict(STATS), reset_stats)


def _as_list(x) -> List[str]:
    if x is None:
        return []
    return [x] if isinstance(x, str) else list(x)


# ----------------------------------------------------------------------
# shared factorization (Alg. 3 line 5)
# ----------------------------------------------------------------------
def shared_key_codes(
    left: TensorFrame, right: TensorFrame, lname: str, rname: str
) -> Tuple[jax.Array, jax.Array, int]:
    lm, rm = left.meta(lname), right.meta(rname)
    string_kinds = ("dict", "obj")
    if lm.kind in string_kinds or rm.kind in string_kinds:
        if lm.kind not in string_kinds or rm.kind not in string_kinds:
            raise TypeError(
                f"join key type mismatch: {lname}({lm.kind}) vs {rname}({rm.kind})"
            )
        lc, ld = left.col_codes(lname)
        rc, rd = right.col_codes(rname)
        if ld is rd:
            return lc, rc, int(ld.shape[0])
        merged, ra, rb = encoding.merge_dictionaries(ld, rd)
        return (
            jnp.asarray(ra, dtype=INT)[lc],
            jnp.asarray(rb, dtype=INT)[rc],
            int(merged.shape[0]),
        )
    if lm.kind == "float" or rm.kind == "float":
        raise TypeError("cannot join on float columns")
    la = left.col_values(lname)
    ra = right.col_values(rname)
    nl, nr = int(la.shape[0]), int(ra.shape[0])
    if nl == 0 and nr == 0:
        return la.astype(INT), ra.astype(INT), 1
    # range compression from cached bounds (store zone maps seed them,
    # joins/filters propagate them): after the first touch of a column
    # the join issues NO bounds sync — just the one count fetch
    los, his = [], []
    if nl:
        b = left.int_bounds(lname)
        los.append(b[0])
        his.append(b[1])
    if nr:
        b = right.int_bounds(rname)
        los.append(b[0])
        his.append(b[1])
    lo, hi = min(los), max(his)
    span = hi - lo + 1
    if span <= max(1 << 20, _DENSIFY_LIMIT_FACTOR * (nl + nr)):
        return (la - lo).astype(INT), (ra - lo).astype(INT), span
    # sparse domain fallback: densify over the combined key set
    ca, cb, domain = encoding.shared_codes_numeric(np.asarray(la), np.asarray(ra))
    return jnp.asarray(ca), jnp.asarray(cb), domain


def _densify_pair(lp: jax.Array, rp: jax.Array) -> Tuple[jax.Array, jax.Array, int]:
    nl = int(lp.shape[0])
    _, codes, m = distinct(jnp.concatenate([lp, rp]))
    return codes[:nl], codes[nl:], m


def composite_join_codes(
    left: TensorFrame,
    right: TensorFrame,
    left_on: Sequence[str],
    right_on: Sequence[str],
) -> Tuple[jax.Array, jax.Array, int]:
    """Pack multi-column join keys into one shared dense space (exact)."""
    nl, nr = left.nrows, right.nrows
    if len(left_on) == 1:  # single key: no Horner packing round
        lc, rc, card = shared_key_codes(left, right, left_on[0], right_on[0])
        return lc.astype(INT), rc.astype(INT), max(1, int(card))
    limit = max(1 << 20, _DENSIFY_LIMIT_FACTOR * (nl + nr))
    lp = jnp.zeros((nl,), dtype=INT)
    rp = jnp.zeros((nr,), dtype=INT)
    domain = 1
    for lk, rk in zip(left_on, right_on):
        lc, rc, card = shared_key_codes(left, right, lk, rk)
        card = max(1, card)
        if domain * card >= (1 << 62):
            lp, rp, domain = _densify_pair(lp, rp)
        lp = lp * np.int64(card) + lc.astype(INT)
        rp = rp * np.int64(card) + rc.astype(INT)
        domain = domain * card
        if domain > limit:
            lp, rp, domain = _densify_pair(lp, rp)
    return lp, rp, int(domain)


# ----------------------------------------------------------------------
# row-pair computation
# ----------------------------------------------------------------------
#: Inputs at least this long run the direct-address probe as two
#: jit-fused kernels (one per side of the single count sync).  Smaller
#: inputs stay op-by-op — compiling per (shape, domain) would cost more
#: than it saves on the many small unique shapes of a test suite.
_JIT_MIN_ROWS = 1 << 17


def _dar_probe(probe: jax.Array, build: jax.Array, domain: int):
    """Build + probe the direct-address table; everything up to (and
    fused behind) the one deferred match count."""
    nb = build.shape[0]
    # slot `domain` holds null build rows, slot `domain+1` is probed by
    # null probe rows and never written: matched is a single compare
    tbl = jnp.full((domain + 2,), np.int64(-1))
    build_idx = jnp.where(build >= 0, build, np.int64(domain))
    tbl = tbl.at[build_idx].set(jnp.arange(nb, dtype=INT))
    probe_idx = jnp.where(
        probe >= 0,
        jnp.minimum(probe, np.int64(max(0, domain - 1))),
        np.int64(domain + 1),
    )
    pos = tbl[probe_idx]
    matched = pos >= 0
    slots = prims.prefix_sum(matched.astype(INT))
    return pos, matched, slots


def _dar_compact(pos, matched, slots, cnt: int):
    """Stream-compact the matched probe rows into (probe_rows,
    build_rows) given the synced count."""
    npr = matched.shape[0]
    dest = jnp.where(matched, slots - 1, np.int64(cnt))
    probe_rows = (
        jnp.zeros((cnt + 1,), dtype=INT)
        .at[dest]
        .set(jnp.arange(npr, dtype=INT))[:cnt]
    )
    return probe_rows, pos[probe_rows]


_dar_probe_jit = jax.jit(_dar_probe, static_argnums=(2,))
_dar_compact_jit = jax.jit(_dar_compact, static_argnums=(3,))


def direct_address_rows(
    probe: jax.Array, build: jax.Array, domain: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Unique-build-key probe via perfect-hash (direct-address) table.

    Returns (matched mask over probe rows, probe_rows, build_rows).
    Negative codes (nulls) on either side never match; null build rows
    scatter into a trash slot that probes cannot reach.  The matched
    rows are compacted on-device (prefix sum + scatter) behind a single
    deferred count fetch — no ``nonzero`` host round-trip — and large
    probes run the whole thing as two fused kernels.
    """
    npr = int(probe.shape[0])
    use_jit = npr >= _JIT_MIN_ROWS
    probe_fn = _dar_probe_jit if use_jit else _dar_probe
    pos, matched, slots = probe_fn(probe, build, domain)
    if npr == 0:
        empty = jnp.zeros((0,), dtype=INT)
        return matched, empty, empty
    cnt = int(prims.to_host(slots[-1], "join.direct_probe"))  # the one sync
    compact_fn = _dar_compact_jit if use_jit else _dar_compact
    probe_rows, build_rows = compact_fn(pos, matched, slots, cnt)
    return matched, probe_rows, build_rows


def sorted_probe_rows(
    probe: jax.Array, build: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Many-to-many probe: sort build side once, binary-search ranges,
    expand via CSR arithmetic.  Returns (counts, probe_rows, build_rows).

    Output row ``j`` belongs to the probe row whose output run holds
    it: each probe row with matches writes its number at its run's
    first output slot, and a running max fills the run.  Its match is
    then ``j`` minus the run's first slot, counted from the probe's
    first match in the sorted build.
    """
    npr = int(probe.shape[0])
    order = prims.argsort(build)
    sb = build[order]
    starts = jnp.searchsorted(sb, probe, side="left")
    ends = jnp.searchsorted(sb, probe, side="right")
    counts = (ends - starts).astype(INT)
    total = int(prims.to_host(counts.sum(), "join.sorted_probe"))  # the one sync
    return (counts,) + _expand_runs(starts.astype(INT), counts, order, total)


@partial(jax.jit, static_argnums=3)
def _expand_runs(starts, counts, order, total: int):
    first = prims.prefix_sum(counts) - counts  # first output slot per probe
    marks = jnp.full((total,), -1, dtype=INT).at[
        jnp.where(counts > 0, first, total)
    ].max(jnp.arange(counts.shape[0], dtype=INT), mode="drop")
    probe_rows = prims.running_max(marks)
    within = jnp.arange(total, dtype=INT) - first[probe_rows]
    return probe_rows, order[starts[probe_rows] + within]


def sort_merge_join_rows(
    lcodes: jax.Array, rcodes: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Fig. 12 baseline: sort-merge join (sorts BOTH sides)."""
    lorder = prims.argsort(lcodes)
    ls = lcodes[lorder]
    _, li_sorted, ri = sorted_probe_rows(ls, rcodes)
    return lorder[li_sorted], ri


def membership(probe: jax.Array, build: jax.Array) -> jax.Array:
    """exists(probe value in build) — for semi/anti joins."""
    if int(build.shape[0]) == 0:
        return jnp.zeros(probe.shape, dtype=bool)
    return member_sorted(probe, prims.sort(build))


def member_sorted(probe: jax.Array, sb: jax.Array) -> jax.Array:
    """exists(probe value in the non-empty sorted build ``sb``);
    negative probe keys (nulls) never match.  Traceable."""
    pos = jnp.clip(jnp.searchsorted(sb, probe), 0, sb.shape[0] - 1)
    return (sb[pos] == probe) & (probe >= 0)


def _membership_routed(probe: jax.Array, build: jax.Array) -> jax.Array:
    """Membership with an optional sharded route: the probe side shards
    over a data mesh, the (small) build side broadcasts to every shard
    (repro.dist.dframe.dist_semi_join_mask)."""
    if CONFIG.distributed != "off" and int(build.shape[0]) > 0:
        from repro.dist import dframe

        if dframe.dist_enabled(int(probe.shape[0])):
            return dframe.dist_semi_join_mask(dframe.data_mesh(), probe, build)
    return membership(probe, build)


# ----------------------------------------------------------------------
# frame stitching
# ----------------------------------------------------------------------
def _right_name_map(
    left: TensorFrame, right: TensorFrame, drop_right: Sequence[str], suffix: str
) -> Dict[str, str]:
    """Mapping right-column -> output name (suffix on collision)."""
    out: Dict[str, str] = {}
    dropset = set(drop_right)
    for name in right.column_names:
        if name in dropset:
            continue
        out[name] = name + suffix if name in left.columns else name
    return out


def _hstack(
    left: TensorFrame,
    right: TensorFrame,
    name_map: Dict[str, str],
) -> TensorFrame:
    """Horizontal stack of two equal-length frames; right columns are
    renamed per ``name_map`` (absent = dropped).

    Late path: both sides' view blocks stack zero-copy — no payload
    moves; the output is a ``RowView`` frame over every source block.
    """
    n = left.nrows
    assert right.nrows == n, (right.nrows, n)
    if CONFIG.late_materialization:
        lblocks, lmat = left._view_parts()
        rblocks, rmat = right._view_parts()
        ofs = len(lblocks)
        rbase = 0 if lmat is None else int(lmat.shape[0])
        blocks = list(lblocks) + [
            ViewBlock(
                b.itensor,
                b.ftensor,
                None if b.row_id is None else rbase + b.row_id,
            )
            for b in rblocks
        ]
        if lmat is None:
            rowmat = rmat
        elif rmat is None:
            rowmat = lmat
        else:
            rowmat = jnp.concatenate([lmat, rmat], axis=0)
        cols: Dict[str, ColumnMeta] = dict(left.columns)
        off: Dict[str, OffloadedColumn] = dict(left.offloaded)
        for name, m in right.columns.items():
            if _is_hidden(name):
                base = name[len(VALID_PREFIX):]
                if base not in name_map:
                    continue
                new = _valid_name(name_map[base])
            else:
                if name not in name_map:
                    continue
                new = name_map[name]
            if m.kind == "obj":
                off[new] = right.offloaded[name]
                cols[new] = ColumnMeta(new, "obj", -1)
            else:
                cols[new] = dataclasses.replace(m, name=new, block=ofs + m.block)
        out = TensorFrame._from_view(cols, off, n, blocks, rowmat)
        # value bounds survive the gather (rows repeat, never widen)
        left._inherit_stats(out, "bounds")
        right._inherit_stats(out, "bounds", mapping=name_map)
        return out
    it = (
        jnp.concatenate([left.itensor, right.itensor], axis=1)
        if right.itensor.shape[1]
        else left.itensor
    )
    ft = (
        jnp.concatenate([left.ftensor, right.ftensor], axis=1)
        if right.ftensor.shape[1]
        else left.ftensor
    )
    iofs, fofs = left.itensor.shape[1], left.ftensor.shape[1]
    cols = dict(left.columns)
    off = dict(left.offloaded)
    for name, m in right.columns.items():
        if _is_hidden(name):
            base = name[len(VALID_PREFIX):]
            if base not in name_map:
                continue
            new = _valid_name(name_map[base])
        else:
            if name not in name_map:
                continue
            new = name_map[name]
        if m.kind == "obj":
            off[new] = right.offloaded[name]
            cols[new] = ColumnMeta(new, "obj", -1)
        elif m.kind == "float":
            cols[new] = ColumnMeta(new, "float", fofs + m.slot, None)
        else:
            cols[new] = ColumnMeta(new, m.kind, iofs + m.slot, m.dictionary)
    return TensorFrame(it, ft, cols, off, n)


def _vconcat_same_schema(a: TensorFrame, b: TensorFrame) -> TensorFrame:
    """Vertical concat of two frames with identical column dicts.

    Pipeline exit: both sides materialize (one fused gather per base
    tensor each) and concatenate tensor-to-tensor; mismatched slot
    layouts fall back to per-column stitching.
    """
    assert list(a.columns.keys()) == list(b.columns.keys())
    a.materialize()
    b.materialize()
    same_layout = all(
        (m.kind, m.slot) == (b.columns[name].kind, b.columns[name].slot)
        for name, m in a.columns.items()
    )
    if same_layout:
        it = jnp.concatenate([a.itensor, b.itensor], axis=0)
        ft = jnp.concatenate([a.ftensor, b.ftensor], axis=0)
        off: Dict[str, OffloadedColumn] = {}
        for name, oa in a.offloaded.items():
            ob = b.offloaded[name]
            assert oa.values is ob.values, "vconcat requires shared physical storage"
            off[name] = OffloadedColumn(
                oa.values, jnp.concatenate([oa.idx, ob.idx]), oa._cache
            )
        return TensorFrame(it, ft, dict(a.columns), off, a.nrows + b.nrows)
    n = a.nrows + b.nrows
    cols: Dict[str, ColumnMeta] = {}
    off = {}
    icols: List[jax.Array] = []
    fcols: List[jax.Array] = []
    for name, ma in a.columns.items():
        mb = b.columns[name]
        if ma.kind == "obj":
            oa, ob = a.offloaded[name], b.offloaded[name]
            assert oa.values is ob.values, "vconcat requires shared physical storage"
            off[name] = OffloadedColumn(
                oa.values, jnp.concatenate([oa.idx, ob.idx]), oa._cache
            )
            cols[name] = ColumnMeta(name, "obj", -1)
        elif ma.kind == "float":
            cols[name] = ColumnMeta(name, "float", len(fcols))
            fcols.append(jnp.concatenate([a._raw_values(ma), b._raw_values(mb)]))
        else:
            cols[name] = ColumnMeta(name, ma.kind, len(icols), ma.dictionary)
            icols.append(jnp.concatenate([a._raw_values(ma), b._raw_values(mb)]))
    it = jnp.stack(icols, axis=1) if icols else _empty_tensor(n, INT)
    ft = jnp.stack(fcols, axis=1) if fcols else _empty_tensor(n, float_dtype())
    return TensorFrame(it, ft, cols, off, n)


def _null_right_rows(right: TensorFrame, n: int) -> TensorFrame:
    """A frame with right's schema, n rows, all values null-ish.

    Existing validity columns land at 0 automatically (itensor zeros);
    offloaded indexers point at physical row 0 and are masked by
    validity downstream.  When ``right`` is a view the null frame
    mirrors its block structure so the metas stay valid.
    """
    off = {
        name: OffloadedColumn(oc.values, jnp.zeros((n,), dtype=INT), oc._cache)
        for name, oc in right.offloaded.items()
    }
    if right._view is not None:
        blocks = [
            ViewBlock(
                jnp.zeros((n, b.itensor.shape[1]), dtype=INT),
                jnp.full((n, b.ftensor.shape[1]), np.nan, dtype=b.ftensor.dtype),
                None,
            )
            for b in right._view.blocks
        ]
        return TensorFrame._from_view(dict(right.columns), off, n, blocks, None)
    it = jnp.zeros((n, right.itensor.shape[1]), dtype=INT)
    ft = jnp.full((n, right.ftensor.shape[1]), np.nan, dtype=right.ftensor.dtype)
    return TensorFrame(it, ft, dict(right.columns), off, n)


# ----------------------------------------------------------------------
# public join
# ----------------------------------------------------------------------
def join(
    left: TensorFrame,
    right: TensorFrame,
    on: Union[str, Sequence[str], None] = None,
    left_on: Union[str, Sequence[str], None] = None,
    right_on: Union[str, Sequence[str], None] = None,
    how: str = "inner",
    suffix: str = "_r",
    algorithm: str = "auto",  # 'auto' | 'direct' | 'sorted' | 'sortmerge'
) -> TensorFrame:
    if on is not None:
        left_on = right_on = _as_list(on)
    else:
        left_on, right_on = _as_list(left_on), _as_list(right_on)
    if not left_on or len(left_on) != len(right_on):
        raise ValueError("join requires matching key lists")
    lcodes, rcodes, domain = composite_join_codes(left, right, left_on, right_on)

    # null keys never match: -1 on the left, -2 on the right
    null_keys = False
    for lk in left_on:
        v = left.valid_array(lk)
        if v is not None:
            lcodes = jnp.where(v, lcodes, np.int64(-1))
    for rk in right_on:
        v = right.valid_array(rk)
        if v is not None:
            null_keys = True
            rcodes = jnp.where(v, rcodes, np.int64(-2))

    if how in ("semi", "anti"):
        with obs.span(
            "core.join",
            how=how,
            algorithm="membership",
            probe_rows=left.nrows,
            build_rows=right.nrows,
        ):
            exists = _membership_routed(lcodes, rcodes)
        return left.mask_rows(exists if how == "semi" else ~exists)
    if how not in ("inner", "left"):
        raise ValueError(f"unsupported join type {how!r}")

    drop_right = [rk for lk, rk in zip(left_on, right_on) if lk == rk]
    name_map = _right_name_map(left, right, drop_right, suffix)

    nb = right.nrows
    matched_counts = None
    if algorithm == "sortmerge":
        with obs.span(
            "core.join",
            how=how,
            algorithm="sort_merge",
            probe_rows=left.nrows,
            build_rows=nb,
        ):
            lrows, rrows = sort_merge_join_rows(lcodes, rcodes)
    else:
        unique_build = False
        if algorithm in ("auto", "direct") and nb > 0:
            hint = right.unique_hint(right_on)
            if hint is not None:
                unique_build = bool(hint)
                STATS[
                    "stats_unique_hits" if hint else "stats_nonunique_hits"
                ] += 1
            else:
                # unknown build side: pay the sort-based test once and
                # cache the verdict on the frame (exact distinct count
                # of the key combination — unless null keys collapsed
                # codes, which would under-count)
                STATS["uniqueness_sort_tests"] += 1
                steps = (jnp.diff(prims.sort(rcodes)) != 0).sum()
                m_build = int(prims.to_host(steps, "join.unique_test")) + 1
                unique_build = m_build == nb
                if not null_keys:
                    right.set_stats(
                        list(right_on), unique=unique_build, distinct=m_build
                    )
        algo = (
            "direct_address"
            if unique_build and algorithm != "sorted"
            else "sorted_probe"
        )
        with obs.span(
            "core.join",
            how=how,
            algorithm=algo,
            probe_rows=left.nrows,
            build_rows=nb,
        ):
            if algo == "direct_address":
                matched, lrows, rrows = direct_address_rows(
                    lcodes, rcodes, domain
                )
                matched_counts = matched.astype(INT)
            else:
                counts, lrows, rrows = sorted_probe_rows(lcodes, rcodes)
                matched_counts = counts

    inner = _hstack(left.take(lrows), right.take(rrows), name_map)
    if how == "inner":
        return inner

    # ---- left outer ----
    if matched_counts is None:  # sortmerge path
        matched_counts = jnp.zeros((left.nrows,), dtype=INT).at[lrows].add(1)
    unmatched = matched_counts == 0
    n_un = int(prims.to_host(unmatched.sum(), "join.left_outer"))
    outer_part = _hstack(left.mask_rows(unmatched), _null_right_rows(right, n_un), name_map)

    # every right output column must carry validity in both parts;
    # columns that already had a __v__ flow it through (zeros in the
    # null part); the rest get it appended here, in identical order
    need_valid = [
        out_name
        for rname, out_name in name_map.items()
        if not right.has_nulls(rname)
    ]
    for out_name in need_valid:
        inner = inner._append_int_column(
            _valid_name(out_name), jnp.ones((inner.nrows,), dtype=INT), "bool"
        )
        outer_part = outer_part._append_int_column(
            _valid_name(out_name), jnp.zeros((outer_part.nrows,), dtype=INT), "bool"
        )
    return _vconcat_same_schema(inner, outer_part)
