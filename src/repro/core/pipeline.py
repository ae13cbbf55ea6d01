"""Morsel-driven out-of-core execution over the store's chunk grid.

Instead of materializing a whole store scan into device tensors, the
pipeline streams it chunk by chunk:

- ``ChunkScan`` iterates the zone-map survivors of a planned store scan
  as small per-chunk ``TensorFrame`` s.  A prefetch thread
  (``CONFIG.ooc_prefetch`` deep) decodes chunk ``k+1`` host-side while
  the device processes chunk ``k`` — decode and compute overlap.  Each
  chunk frame is seeded with its own zone-map bounds (``ColStats``), so
  chunk-level pruning stays available *downstream* of filters and
  joins.
- ``HashBuild`` is the build-once/probe-per-chunk join side: the build
  frame's key is coded once (dictionary identity for interned store
  dictionaries, range compression from the build's own bounds for
  ints), and every probe chunk reuses it — a direct-address table for
  provably-unique inner builds, sorted membership codes for semi/anti.
  Probe chunks whose key bounds miss the build's range are skipped
  outright (inner/semi) or passed through unprobed (anti).
- ``StreamAgg`` folds each chunk into partial aggregates (mean
  decomposes into sum+count).  Where every group key is a non-null
  dictionary or bool column, the key domain is known on the host, and
  small, each chunk folds into device-resident ``(D,)`` accumulators
  with one jitted program and no fetch, at most ``CONFIG.ooc_prefetch``
  folds queued on the device; they come to the host once, at finalize.  Other keys (int, date, nullable, large domains) take
  per-chunk partial blocks re-aggregated every
  ``CONFIG.ooc_merge_every`` chunks.  Those blocks live under the
  spill manager (``repro.store.spill``), so a run under
  ``CONFIG.memory_budget_bytes`` keeps its working set bounded: cold
  partials go to ``.tfb`` chunk files and re-hydrate transparently.

``STATS`` makes the whole thing observable: chunks streamed/pruned,
rows streamed, chunks folded into a dense device accumulator
(``dense_agg_chunks``), pipeline and fallback counts, plus the spill
manager's bytes spilled/re-read, evictions and peak tracked bytes.
"""
from __future__ import annotations

import collections
import math
import queue
import threading
from functools import partial
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.resilience import checkpoint

from . import prims
from .agg import _num_values
from .config import CONFIG
from .frame import INT, TensorFrame, _assemble_frame, _valid_name
from .join import _hstack, _right_name_map, member_sorted

#: Observable pipeline counters.  The spill keys mirror
#: ``repro.store.spill.SPILL.counters`` (synced after every pipeline).
STATS = {
    "pipelines": 0,
    "chunks_streamed": 0,
    "chunks_pruned": 0,
    "rows_streamed": 0,
    "dense_agg_chunks": 0,
    "partial_merges": 0,
    "generic_probes": 0,
    "fallbacks": 0,
    "bytes_spilled": 0,
    "bytes_reread": 0,
    "evictions": 0,
    "peak_tracked_bytes": 0,
}


def reset_stats() -> None:
    from repro.store.spill import SPILL

    for k in STATS:
        STATS[k] = 0
    SPILL.reset_counters()


def sync_spill_stats() -> None:
    from repro.store.spill import SPILL

    STATS.update(SPILL.counters)


def _stats_snapshot() -> Dict:
    sync_spill_stats()
    return dict(STATS)


from repro import obs as _obs  # noqa: E402

_obs.metrics.register_group("core.pipeline", _stats_snapshot, reset_stats)


_INT_DOMAIN = ("int", "date", "bool")


# ----------------------------------------------------------------------
# chunk-pipelined store scan
# ----------------------------------------------------------------------
class ChunkScan:
    """Iterate a predicated store scan as per-chunk TensorFrames.

    Chunk decode (numpy: rle expansion, row masks, validity; the
    ``store.scan`` spans) runs on a prefetch thread up to
    ``CONFIG.ooc_prefetch`` chunks ahead; the consuming thread only does
    the host->device transfer and compute, and waits on the decode in
    ``pipeline.prefetch_wait`` spans.
    ``prefetch=0`` degrades to fully synchronous iteration.
    """

    def __init__(self, table, columns, predicates):
        from repro import store as _store

        self.table = table
        self.proj, self.phys_preds, self.survivors = _store.plan_scan(
            table, columns, predicates
        )
        STATS["chunks_pruned"] += table.n_chunks - len(self.survivors)

    def __len__(self) -> int:
        return len(self.survivors)

    def _results(self):
        from repro import store as _store

        depth = max(0, int(CONFIG.ooc_prefetch))
        if depth == 0 or len(self.survivors) <= 1:
            for i in self.survivors:
                res = _store.scan_chunk(
                    self.table, self.proj, self.phys_preds, int(i)
                )
                yield int(i), res
            return
        q: "queue.Queue" = queue.Queue(maxsize=depth)
        DONE = object()
        stop = threading.Event()

        def put(item) -> bool:
            # never block forever: an abandoned consumer (exception in
            # the pipeline body closes this generator early) sets
            # ``stop`` and the producer bails out instead of deadlocking
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

        def producer():
            try:
                for i in self.survivors:
                    if stop.is_set():
                        return
                    res = _store.scan_chunk(
                        self.table, self.proj, self.phys_preds, int(i)
                    )
                    if not put((int(i), res)):
                        return
            except BaseException as e:  # re-raised on the consumer side
                put(e)
            finally:
                put(DONE)

        # the producer's spans (``store.scan``) join the consumer's
        # query: same trace id, parented to the span open here
        t = threading.Thread(target=_obs.bind(producer), daemon=True)
        t.start()
        try:
            while True:
                with _obs.span("pipeline.prefetch_wait"):
                    item = q.get()
                if item is DONE:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()
            while True:  # unblock a producer stuck on a full queue
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join()

    def __iter__(self):
        for _, f in self.iter_indexed():
            yield f

    def iter_indexed(self):
        """Yield ``(chunk_index, frame)`` — the index lets callers build
        recompute closures that re-scan exactly this chunk."""
        for i, res in self._results():
            # deadline/cancel checkpoint: a streamed pipeline can abort
            # between chunks even when a single chunk's compute can't
            checkpoint("pipeline.chunk")
            STATS["chunks_streamed"] += 1
            STATS["rows_streamed"] += res.nrows
            f = TensorFrame.from_store(self.table, self.proj, [], result=res)
            # tighten the table-wide bounds from_store seeded down to
            # THIS chunk's zone map — downstream joins prune on them
            for name in self.proj:
                col = self.table.columns[name]
                if col.ctype in _INT_DOMAIN and col.encoding != "dict":
                    st = col.chunks[i].stats
                    if res.nrows and st.vmin is not None:
                        f.set_stats(
                            name, vmin=int(st.vmin), vmax=int(st.vmax)
                        )
            yield int(i), f


# ----------------------------------------------------------------------
# build-once / probe-per-chunk joins
# ----------------------------------------------------------------------
class HashBuild:
    """One join's build side, prepared once and probed per chunk.

    Single-column keys get a build-once fast path: int/date keys are
    range-compressed against the build's own bounds (out-of-range probe
    values can never match), interned dictionary keys share codes by
    identity.  Provably-unique inner builds scatter one direct-address
    table; semi/anti sort the build codes once.  Everything else —
    multi-column keys, non-unique inner builds, left outer, foreign
    dictionaries — probes through the engine's general join per chunk,
    which is still streaming-safe (each probe row is independent).
    """

    def __init__(
        self,
        probe_keys: Sequence[str],
        build: TensorFrame,
        build_keys: Sequence[str],
        how: str,
        suffix: str = "_r",
    ):
        self.probe_keys = list(probe_keys)
        self.build_keys = list(build_keys)
        self.build = build.materialize()
        self.how = how
        self.suffix = suffix
        self._fast = None  # (mode, kind, lo, hi, payload, domain)
        if len(self.build_keys) == 1 and how in ("inner", "semi", "anti"):
            self._prepare_fast()

    def _build_codes(self):
        """(codes, kind, lo, hi, domain) for the single build key, or
        None when the key shape has no build-once form."""
        bk = self.build_keys[0]
        m = self.build.meta(bk)
        if m.kind in _INT_DOMAIN:
            if self.build.nrows == 0:
                return None
            lo, hi = self.build.int_bounds(bk)
            codes = (self.build.col_values(bk) - np.int64(lo)).astype(INT)
            domain = hi - lo + 1
            kind = "intrange"
        elif m.kind == "dict":
            codes = self.build.col_values(bk).astype(INT)
            lo, hi = 0, max(0, int(m.dictionary.shape[0]) - 1)
            domain = int(m.dictionary.shape[0])
            kind = "dict"
        else:
            return None
        valid = self.build.valid_array(bk)
        if valid is not None:
            codes = jnp.where(valid, codes, np.int64(-1))
        return codes, kind, lo, hi, domain

    def _prepare_fast(self) -> None:
        got = self._build_codes()
        if got is None:
            return
        codes, kind, lo, hi, domain = got
        if self.how in ("semi", "anti"):
            self._fast = ("member", kind, lo, hi, prims.sort(codes), domain)
            return
        # inner: need a provably-unique build side for direct addressing
        nb = self.build.nrows
        if domain > max(1 << 22, 16 * max(1, nb)):
            return  # direct-address table would dwarf the build side
        hint = self.build.unique_hint(self.build_keys)
        if hint is None:
            # pay the sort test ONCE at build time (not per chunk)
            steps = (jnp.diff(prims.sort(codes)) != 0).sum()
            m_build = int(prims.to_host(steps, "pipeline.unique_test")) + 1
            hint = m_build == nb
            self.build.set_stats(
                self.build_keys[0], unique=bool(hint), distinct=m_build
            )
        if not hint:
            return
        tbl = jnp.full((domain + 2,), np.int64(-1))
        idx = jnp.where(codes >= 0, codes, np.int64(domain))
        tbl = tbl.at[idx].set(jnp.arange(nb, dtype=INT))
        self._fast = ("dar", kind, lo, hi, tbl, domain)

    # -- chunk-level pruning -------------------------------------------
    def disjoint(self, f: TensorFrame) -> bool:
        """Can the chunk's key bounds prove zero matches?  (Callers may
        then skip the chunk for inner/semi, or pass it through unprobed
        for anti — never drop rows on an anti join.)"""
        if self._fast is None or self._fast[1] != "intrange":
            return False
        st = f.col_stats(self.probe_keys[0])
        if st is None or st.vmin is None:
            return False
        _, _, lo, hi, _, _ = self._fast
        return st.vmax < lo or st.vmin > hi

    def _probe_codes(self, f: TensorFrame) -> Optional[jnp.ndarray]:
        pk = self.probe_keys[0]
        m = f.meta(pk)
        _, kind, lo, hi, _, _ = self._fast
        if kind == "intrange":
            if m.kind not in _INT_DOMAIN:
                return None
            pv = f.col_values(pk)
            codes = jnp.where(
                (pv < lo) | (pv > hi), np.int64(-1), pv - np.int64(lo)
            ).astype(INT)
        else:  # dict: codes are shared only by dictionary identity
            if m.kind != "dict" or m.dictionary is not self.build.meta(
                self.build_keys[0]
            ).dictionary:
                return None
            codes = f.col_values(pk).astype(INT)
        valid = f.valid_array(pk)
        if valid is not None:
            codes = jnp.where(valid, codes, np.int64(-1))
        return codes

    # -- the probe ------------------------------------------------------
    def apply(self, f: TensorFrame) -> TensorFrame:
        with _obs.detailed_span(
            "pipeline.probe_chunk", rows=f.nrows, how=self.how
        ):
            return self._apply(f)

    def _apply(self, f: TensorFrame) -> TensorFrame:
        if self._fast is not None:
            codes = self._probe_codes(f)
            if codes is not None:
                mode, _, _, _, payload, domain = self._fast
                if mode == "member":
                    sb = payload
                    if int(sb.shape[0]) == 0:
                        exists = jnp.zeros(codes.shape, dtype=bool)
                    else:
                        exists = member_sorted(codes, sb)
                    return f.mask_rows(
                        exists if self.how == "semi" else ~exists
                    )
                # direct-address inner probe against the prebuilt table
                probe_idx = jnp.where(
                    codes >= 0,
                    jnp.minimum(codes, np.int64(max(0, domain - 1))),
                    np.int64(domain + 1),
                )
                pos = payload[probe_idx]
                matched = pos >= 0
                cnt = int(prims.to_host(matched.sum(), "pipeline.probe_chunk"))
                lrows = prims.compact(matched, cnt)
                rrows = pos[lrows]
                name_map = _right_name_map(
                    f, self.build, self._drop_right(), self.suffix
                )
                return _hstack(
                    f.take(lrows), self.build.take(rrows), name_map
                )
        STATS["generic_probes"] += 1
        return f.join(
            self.build,
            left_on=self.probe_keys,
            right_on=self.build_keys,
            how=self.how,
            suffix=self.suffix,
        )

    def _drop_right(self) -> List[str]:
        return [
            rk
            for lk, rk in zip(self.probe_keys, self.build_keys)
            if lk == rk
        ]


# ----------------------------------------------------------------------
# dense device accumulators for small, host-known key domains
# ----------------------------------------------------------------------
#: Key domains (the product of the key cardinalities) up to this many
#: groups fold into device-resident accumulators.  A chunk folds as a
#: masked reduction over a ``(D, n)`` key compare, which grows with D:
#: on a v5e at n = 65,536 it took 1.1 ms at D = 6 to 256 and 3.1 ms at
#: 1,024 (``PERF.md``, Streamed path).  Larger domains keep the
#: per-chunk partial blocks.
DENSE_MAX_GROUPS = 1024


def _identity(op: str, dtype):
    """The value an empty group holds: 0 for sums and counts, the
    dtype's largest (smallest) value for min (max)."""
    if op in ("sum", "count"):
        return 0
    if jnp.issubdtype(dtype, jnp.floating):
        return np.inf if op == "min" else -np.inf
    info = np.iinfo(dtype)
    return info.max if op == "min" else info.min


@partial(jax.jit, static_argnames=("domain", "spec"))
def _dense_init(*, domain: int, spec: Tuple[Tuple[str, str], ...]):
    return tuple(
        jnp.full((domain,), _identity(op, jnp.dtype(dt)), dtype=dt)
        for op, dt in spec
    )


@partial(jax.jit, static_argnames=("keys", "cols", "valids", "cards", "ops"))
def _dense_fold(acc, itensor, ftensor, *, keys, cols, valids, cards, ops):
    """Fold one chunk, read straight from its two tensors, into the
    ``(D,)`` accumulators ``acc``: the row count of each group first,
    then one per partial.  ``keys`` are the key columns' slots in
    ``itensor``.  Per partial: ``ops`` is ``sum``/``count``/``min``/
    ``max``; ``cols`` its values' ``(is_float, slot)``, None for a
    count, which reads only validity; ``valids`` the slot of its
    validity column in ``itensor``, None when every row is valid."""
    n = itensor.shape[0]
    domain = math.prod(cards)
    packed = jnp.zeros((n,), dtype=INT)
    for slot, card in zip(keys, cards):  # Horner, as hashing.composite_key
        packed = packed * np.int64(card) + itensor[:, slot]
    hit = jnp.arange(domain, dtype=INT)[:, None] == packed[None, :]

    def reduce(v, op):  # a masked reduction: no scatter
        r = jnp.where(hit, v[None, :], _identity(op, v.dtype))
        if op == "min":
            return r.min(axis=1)
        return r.max(axis=1) if op == "max" else r.sum(axis=1)

    ones = jnp.ones((n,), dtype=INT)
    out = [acc[0] + reduce(ones, "count")]
    for a, src, vslot, op in zip(acc[1:], cols, valids, ops):
        valid = None if vslot is None else itensor[:, vslot] != 0
        if op == "count":
            v = ones if valid is None else valid.astype(INT)
        else:
            is_float, slot = src
            v = (ftensor if is_float else itensor)[:, slot]
            if valid is not None:
                v = jnp.where(valid, v, _identity(op, v.dtype))
        r = reduce(v, op)
        if op == "min":
            out.append(jnp.minimum(a, r))
        elif op == "max":
            out.append(jnp.maximum(a, r))
        else:
            out.append(a + r)
    return tuple(out)


class DenseAcc:
    """Device-resident ``(D,)`` accumulators of one keyed streamed
    aggregate.

    Engages when every group key is a dictionary or bool column with
    no validity: a group's packed code (Horner over the key codes)
    then indexes a domain whose size ``D``, the product of the
    dictionaries' lengths (2 for a bool), is known on the host without
    a fetch.  Each chunk folds in with one jitted program, with at most
    ``CONFIG.ooc_prefetch`` folds queued on the device (``_pace``); the
    accumulators come to the host once (``fetch``).  Groups no row
    reached are dropped there; the rest come out in ascending packed
    code, keys carrying the chunks' own dictionaries.
    """

    def __init__(self, keys, partials, kinds, dictionaries, cards, ops, acc):
        self.keys = keys
        self.partials = partials
        self.kinds = kinds
        self.dictionaries = dictionaries
        self.cards = cards
        self.ops = ops
        self.acc = acc
        self._inflight: Deque = collections.deque()  # see ``_pace``

    @classmethod
    def open(
        cls, f: TensorFrame, keys: List[str], partials
    ) -> Optional["DenseAcc"]:
        """Accumulators shaped by the first chunk ``f``, or None when
        its keys do not qualify."""
        kinds, dictionaries, cards = [], [], []
        for k in keys:
            m = f.meta(k)
            if m.kind not in ("dict", "bool") or f.has_nulls(k):
                return None
            kinds.append(m.kind)
            dictionaries.append(m.dictionary)
            cards.append(2 if m.kind == "bool" else int(m.dictionary.shape[0]))
        domain = math.prod(cards)
        if not 0 < domain <= DENSE_MAX_GROUPS:
            return None
        ops = tuple("count" if fn == "size" else fn for _, fn, _ in partials)
        spec = [("count", "int64")]
        for (_, fn, colname), op in zip(partials, ops):
            dt = "int64" if op == "count" else str(_num_values(f, colname).dtype)
            spec.append((op, dt))
        acc = _dense_init(domain=domain, spec=tuple(spec))
        return cls(keys, partials, kinds, dictionaries, tuple(cards), ops, acc)

    def accepts(self, f: TensorFrame) -> bool:
        """Do ``f``'s keys code into the same domain?  A dictionary key
        must carry the very same dictionary object."""
        for k, kind, dic in zip(self.keys, self.kinds, self.dictionaries):
            m = f.meta(k)
            if m.kind != kind or f.has_nulls(k):
                return False
            if kind == "dict" and m.dictionary is not dic:
                return False
        return True

    def fold(self, f: TensorFrame) -> None:
        f.materialize()  # one program reads every column of the chunk
        cols, valids = [], []
        for (_, _, colname), op in zip(self.partials, self.ops):
            if op == "count":
                cols.append(None)
            else:
                m = f.meta(colname)
                cols.append((m.kind == "float", m.slot))
            nullable = f.has_nulls(colname)  # never for a size ("")
            valids.append(f.meta(_valid_name(colname)).slot if nullable else None)
        self.acc = _dense_fold(
            self.acc,
            f.itensor,
            f.ftensor,
            keys=tuple(f.meta(k).slot for k in self.keys),
            cols=tuple(cols),
            valids=tuple(valids),
            cards=self.cards,
            ops=self.ops,
        )
        self._pace()

    def _pace(self) -> None:
        """Keep at most ``CONFIG.ooc_prefetch`` (at least one) folds
        queued on the device: block on the accumulators of the fold that
        many chunks back.  Nothing else in the loop waits for the
        device, and each queued fold holds its chunk's device buffers,
        so without this a host that outruns the device would pile up
        uploads for the whole stream."""
        self._inflight.append(self.acc)
        if len(self._inflight) > max(1, int(CONFIG.ooc_prefetch)):
            with _obs.span("pipeline.fold_wait"):
                jax.block_until_ready(self._inflight.popleft())

    def fetch(self) -> Tuple[List[np.ndarray], List[np.ndarray]]:
        """(key codes, partial values) of the groups some row reached,
        in ascending packed code: the one device->host fetch."""
        got = prims.to_host(self.acc, "pipeline.dense_agg")
        present = np.flatnonzero(got[0] > 0)
        codes, rest = [], present
        for card in reversed(self.cards):
            codes.append(rest % card)
            rest = rest // card
        return codes[::-1], [np.asarray(g)[present] for g in got[1:]]

    def frame(self, order: List[str]) -> TensorFrame:
        """The accumulated groups as a frame with columns ``order``
        (keys, then partials)."""
        codes, vals = self.fetch()
        int_cols, float_cols = [], []
        for k, kind, dic, c in zip(self.keys, self.kinds, self.dictionaries, codes):
            int_cols.append((k, c, kind, dic if kind == "dict" else None))
        for (pn, _, _), v in zip(self.partials, vals):
            if np.issubdtype(v.dtype, np.floating):
                float_cols.append((pn, v))
            else:
                int_cols.append((pn, v, "int", None))
        return _assemble_frame(int_cols, float_cols, {}, order, codes[0].shape[0])

    def block(self) -> Dict[str, np.ndarray]:
        """The accumulated groups as a host partial block, in the form
        ``StreamAgg`` spills (``TensorFrame.column`` values)."""
        codes, vals = self.fetch()
        out: Dict[str, np.ndarray] = {}
        for k, kind, dic, c in zip(self.keys, self.kinds, self.dictionaries, codes):
            out[k] = dic[c].astype(object) if kind == "dict" else c != 0
        for (pn, _, _), v in zip(self.partials, vals):
            out[pn] = v
        return out


# ----------------------------------------------------------------------
# streaming group-by aggregation
# ----------------------------------------------------------------------
_PARTIAL_MERGE = {"sum": "sum", "count": "sum", "size": "sum",
                  "min": "min", "max": "max"}

STREAMABLE_AGGS = frozenset(("sum", "count", "size", "min", "max", "mean"))


class StreamAgg:
    """Fold chunk frames into partial aggregates.

    ``specs`` are engine agg specs ``(out_name, fn, column)`` with
    ``fn`` in ``STREAMABLE_AGGS``.  Mean decomposes into sum+count
    partials and reassembles at finalize.  Keyless aggregates fold into
    python scalars.  Keys that ``DenseAcc`` takes fold on the device;
    should a later chunk's keys not fit its domain (a foreign
    dictionary), the accumulator becomes one partial block and the
    stream goes on as for other keys: per-chunk partial blocks
    registered with the spill manager — under a tight
    ``CONFIG.memory_budget_bytes`` they spill to ``.tfb`` and
    re-hydrate at each merge.
    """

    def __init__(self, key_names: List[str], specs):
        self.key_names = list(key_names)
        self.partials: List[Tuple[str, str, str]] = []
        self.finals: List[Tuple[str, str, Tuple[str, ...]]] = []
        for idx, (out_name, fn, colname) in enumerate(specs):
            if fn not in STREAMABLE_AGGS:
                raise ValueError(f"cannot stream aggregate {fn!r}")
            if fn == "mean":
                ps, pc = f"__p{idx}s", f"__p{idx}c"
                self.partials.append((ps, "sum", colname))
                self.partials.append((pc, "count", colname))
                self.finals.append((out_name, "mean", (ps, pc)))
            else:
                pn = f"__p{idx}"
                self.partials.append((pn, fn, colname))
                self.finals.append((out_name, fn, (pn,)))
        self._order = self.key_names + [p for p, _, _ in self.partials]
        self._merge_specs = [
            (pn, _PARTIAL_MERGE[fn], pn) for pn, fn, _ in self.partials
        ]
        self._first = True  # no keyed chunk seen yet
        self._dense: Optional[DenseAcc] = None
        self._dense_rebuilds: List = []  # recompute closures folded in
        self._pending: List = []  # Spillable partial blocks
        self._pending_rebuilds: List = []  # parallel recompute closures
        self._merged = None  # Spillable holding the running merge
        self._merged_rebuild = None
        # keyless accumulators
        self._scalars: Dict[str, object] = {}
        self._scalar_rows = 0

    # -- keyed path -----------------------------------------------------
    def _partial_block(self, part: TensorFrame) -> Dict[str, np.ndarray]:
        return {name: part.column(name) for name in self._order}

    def _combined_rebuild(self, rebuilds):
        """A recompute closure for a block merged from blocks with
        these closures (recompute each, re-merge), or None unless every
        one has a closure."""
        if not rebuilds or any(rb is None for rb in rebuilds):
            return None

        def rebuild(_rbs=tuple(rebuilds)):
            parts = [rb()[0] for rb in _rbs]
            return self._partial_block(self._merge_blocks(parts)), {}

        return rebuild

    def add(self, f: TensorFrame, rebuild=None) -> None:
        """Fold one chunk frame in.  ``rebuild`` (optional, zero-arg)
        re-produces the chunk frame from durable inputs; when given,
        the chunk's partial carries a recompute closure, so a corrupt
        spill block repairs itself instead of failing the query."""
        if f.nrows == 0:
            return
        from repro.store.spill import SPILL

        if not self.key_names:
            self._add_scalar(f)
            return
        block_rebuild = None
        if rebuild is not None:
            def block_rebuild(_rb=rebuild):
                p = _rb().groupby(self.key_names).agg(self.partials)
                return self._partial_block(p), {}
        if self._first:
            self._first = False
            self._dense = DenseAcc.open(f, self.key_names, self.partials)
        if self._dense is not None:
            if self._dense.accepts(f):
                self._dense.fold(f)
                self._dense_rebuilds.append(block_rebuild)
                STATS["dense_agg_chunks"] += 1
                return
            self._leave_dense()
        with _obs.detailed_span("pipeline.partial_agg", rows=f.nrows):
            part = f.groupby(self.key_names).agg(self.partials)
        self._pending.append(
            SPILL.register(self._partial_block(part), recompute=block_rebuild)
        )
        self._pending_rebuilds.append(block_rebuild)
        if len(self._pending) >= max(2, int(CONFIG.ooc_merge_every)):
            self._merge()

    def _leave_dense(self) -> None:
        """Hand the device accumulator to the partial path as one
        spill-managed block."""
        from repro.store.spill import SPILL

        block_rebuild = self._combined_rebuild(self._dense_rebuilds)
        self._pending.append(
            SPILL.register(self._dense.block(), recompute=block_rebuild)
        )
        self._pending_rebuilds.append(block_rebuild)
        self._dense = None
        self._dense_rebuilds = []

    def _merge_blocks(self, blocks) -> TensorFrame:
        if len(blocks) == 1:
            cat = blocks[0]
        else:
            cat = {
                name: np.concatenate([b[name] for b in blocks])
                for name in self._order
            }
        mf = TensorFrame.from_arrays(dict(cat))
        return mf.groupby(self.key_names).agg(self._merge_specs)

    def _merge(self) -> None:
        if not self._pending and self._merged is None:
            return
        with _obs.span("pipeline.merge_partials") as sp:
            blocks = []
            handles = list(self._pending)
            rebuilds = list(self._pending_rebuilds)
            if self._merged is not None:
                handles.append(self._merged)
                rebuilds.append(self._merged_rebuild)
            sp.set(partials=len(handles))
            for h in handles:
                data, _ = h.get()
                blocks.append(data)
                h.release()
            merged = self._merge_blocks(blocks)
            merged_rebuild = self._combined_rebuild(rebuilds)
            from repro.store.spill import SPILL

            self._merged = SPILL.register(
                self._partial_block(merged), recompute=merged_rebuild
            )
            self._merged_rebuild = merged_rebuild
            self._pending = []
            self._pending_rebuilds = []
            STATS["partial_merges"] += 1

    # -- keyless path ---------------------------------------------------
    def _add_scalar(self, f: TensorFrame) -> None:
        got = f.agg(self.partials)
        self._scalar_rows += f.nrows
        for pn, fn, _ in self.partials:
            v = got[pn]
            if pn not in self._scalars:
                self._scalars[pn] = v
            elif fn in ("sum", "count", "size"):
                self._scalars[pn] = self._scalars[pn] + v
            elif fn == "min":
                self._scalars[pn] = min(self._scalars[pn], v)
            else:  # max
                self._scalars[pn] = max(self._scalars[pn], v)

    # -- finalize -------------------------------------------------------
    def finalize(self) -> Optional[TensorFrame]:
        from .expr import col

        if not self.key_names:
            if self._scalar_rows == 0:
                return None  # caller falls back to the eager empty path
            out: Dict[str, np.ndarray] = {}
            for out_name, fn, pns in self.finals:
                if fn == "mean":
                    s, c = self._scalars[pns[0]], self._scalars[pns[1]]
                    v = float(s) / c if c else float("nan")
                else:
                    v = self._scalars[pns[0]]
                out[out_name] = np.asarray([v])
            return TensorFrame.from_arrays(out)
        if self._dense is not None:
            mf = self._dense.frame(self._order)
            self._dense = None
        else:
            self._merge()
            if self._merged is None:
                return None
            data, _ = self._merged.get()
            self._merged.release()
            self._merged = None
            mf = TensorFrame.from_arrays(dict(data))
        rename: Dict[str, str] = {}
        for out_name, fn, pns in self.finals:
            if fn == "mean":
                mf = mf.with_column(out_name, col(pns[0]) / col(pns[1]))
            else:
                rename[pns[0]] = out_name
        mf = mf.rename(rename)
        return mf.select(
            self.key_names + [out_name for out_name, _, _ in self.finals]
        )
