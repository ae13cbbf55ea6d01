"""Streamed aggregates whose keys fold into a dense device accumulator
(``core.pipeline.DenseAcc``): answers equal the eager path's, the
fallback to per-chunk partial blocks mid-stream, and one fetch per
stream whatever its number of chunks."""
import numpy as np
import pytest

from repro import sql, store
from repro.core import TensorFrame, pipeline, prims
from repro.core import oracle as orc
from repro.core.config import CONFIG
from repro.store.spill import SPILL


@pytest.fixture
def ooc(tmp_path):
    """Every eligible aggregate streams; partial blocks spill early."""
    saved = (
        CONFIG.out_of_core,
        CONFIG.memory_budget_bytes,
        CONFIG.spill_dir,
        CONFIG.ooc_merge_every,
    )
    CONFIG.out_of_core = "force"
    CONFIG.memory_budget_bytes = 1 << 14
    CONFIG.spill_dir = str(tmp_path)
    CONFIG.ooc_merge_every = 2
    yield tmp_path
    (
        CONFIG.out_of_core,
        CONFIG.memory_budget_bytes,
        CONFIG.spill_dir,
        CONFIG.ooc_merge_every,
    ) = saved


def _eager(query, scope):
    saved = CONFIG.out_of_core
    CONFIG.out_of_core = "off"
    try:
        return sql.execute(query, scope)
    finally:
        CONFIG.out_of_core = saved


def _assert_same(got, want):
    godf, wodf = orc.frame_to_odf(got), orc.frame_to_odf(want)
    assert set(godf) == set(wodf)
    orc.assert_odf_equal(godf, wodf, sort=True, rtol=1e-9)


def _labels(prefix, card):
    return np.array([f"{prefix}{i:03d}" for i in range(card)], dtype=object)


def _scope(card_a=3, card_b=2, nrows=2400, chunk_rows=256, seed=0):
    """Table ``t``: dictionary keys ``a`` (``card_a`` labels) and ``b``
    (``card_b``), bool ``flag``, int ``k``, ``v`` and ``d`` (zero-row
    chunks: ``d`` is 0 from row 1,024 to 1,535), float ``w``.  Label
    ``a000`` appears in the last chunk only, so most chunks miss it.
    Table ``dim``: ``k``'s values but those of ``a000``'s rows, with
    float ``x`` and int ``y``; every ``b`` label of ``a000``'s rows also
    appears in rows ``dim`` matches."""
    rng = np.random.default_rng(seed)
    every = np.arange(nrows)  # every label appears
    a = _labels("a", card_a)[1 + rng.permutation(every % (card_a - 1))]
    a[-chunk_rows // 2:] = "a000"
    k = rng.integers(0, 20, nrows)
    k[a == "a000"] = 20 + np.arange(chunk_rows // 2) % 20
    cols = {
        "a": a,
        "b": _labels("b", card_b)[every % card_b],
        "flag": rng.integers(0, 2, nrows) == 1,
        "k": k,
        "v": rng.integers(-1000, 1000, nrows),
        "d": np.where((np.arange(nrows) // 512) == 2, 0, 1),
        "w": np.round(rng.standard_normal(nrows), 3),
    }
    t = store.Table.from_arrays(
        cols, chunk_rows=chunk_rows, encode={"a": "dict", "b": "dict"}
    )
    dim = TensorFrame.from_arrays(
        {"dk": np.arange(20), "x": np.arange(20) * 1.5 - 7.0, "y": np.arange(20) * 3 - 9}
    )
    return {"t": t, "dim": dim}


AGGS = (
    "SUM(v) AS sv, SUM(w) AS sw, COUNT(w) AS cw, COUNT(*) AS n, "
    "MIN(v) AS lo, MAX(w) AS hi, AVG(w) AS aw, AVG(v) AS av"
)
CASES = {
    "every_fn": f"SELECT a, b, {AGGS} FROM t GROUP BY a, b",
    "one_key": "SELECT b, SUM(w) AS sw, MIN(w) AS lo, MAX(v) AS hi FROM t GROUP BY b",
    "bool_key": "SELECT a, flag, SUM(v) AS sv, COUNT(*) AS n FROM t GROUP BY a, flag",
    # rows of every group but a few filter away: groups no row reaches
    "filtered_groups": "SELECT a, b, SUM(w) AS sw, COUNT(*) AS n FROM t "
    "WHERE v > 900 GROUP BY a, b",
    # a device filter that leaves some chunks with zero rows
    "empty_chunks": "SELECT a, b, SUM(v) AS sv, AVG(w) AS aw FROM t "
    "WHERE d + v * 0 = 1 GROUP BY a, b",
    # a left join's unmatched rows: nulls in the aggregated values
    "null_values": "SELECT b, SUM(x) AS sx, COUNT(x) AS cx, MIN(x) AS lo, "
    "MAX(x) AS hi, AVG(x) AS ax FROM t LEFT JOIN dim ON t.k = dim.dk GROUP BY b",
    # groups of a000, whose every value is null (int values: the
    # float sentinels are infinities, which the oracle cannot compare)
    "all_null_groups": "SELECT a, b, SUM(y) AS sy, COUNT(y) AS cy, MIN(y) AS lo, "
    "MAX(y) AS hi FROM t LEFT JOIN dim ON t.k = dim.dk GROUP BY a, b",
}


def _domains():
    """Key cardinalities whose packed domain is small, and at the
    dense path's limit."""
    return {"small": (3, 2), "at_limit": (4, pipeline.DENSE_MAX_GROUPS // 4)}


@pytest.mark.parametrize("form", ["small", "at_limit"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_streamed_matches_eager(ooc, case, form):
    card_a, card_b = _domains()[form]
    assert card_a * card_b <= pipeline.DENSE_MAX_GROUPS
    scope = _scope(card_a, card_b)
    query = CASES[case]
    want = _eager(query, scope)
    pipeline.reset_stats()
    got = sql.execute(query, scope)
    _assert_same(got, want)
    assert pipeline.STATS["dense_agg_chunks"] > 0
    assert pipeline.STATS["partial_merges"] == 0


@pytest.mark.parametrize(
    "query",
    [
        # an int key: the key domain is not known without a fetch
        "SELECT k, SUM(w) AS sw, COUNT(*) AS n FROM t GROUP BY k",
        # a dictionary domain just above DENSE_MAX_GROUPS
        "SELECT a, b, SUM(v) AS sv FROM t GROUP BY a, b",
    ],
    ids=["int_key", "large_domain"],
)
def test_partial_path_keeps_other_keys(ooc, query):
    card_a, card_b = 4, pipeline.DENSE_MAX_GROUPS // 4 + 1
    assert card_a * card_b > pipeline.DENSE_MAX_GROUPS
    scope = _scope(card_a, card_b)
    want = _eager(query, scope)
    pipeline.reset_stats()
    got = sql.execute(query, scope)
    _assert_same(got, want)
    assert pipeline.STATS["dense_agg_chunks"] == 0
    assert pipeline.STATS["partial_merges"] > 0


# ----------------------------------------------------------------------
# StreamAgg directly: a foreign dictionary mid-stream
# ----------------------------------------------------------------------
SPECS = [
    ("s", "sum", "v"),
    ("n", "size", ""),
    ("c", "count", "w"),
    ("lo", "min", "w"),
    ("hi", "max", "v"),
    ("m", "mean", "w"),
]


def _chunk_data(rng, n, labels):
    return {
        "g": labels[rng.integers(0, len(labels), n)],
        "h": rng.integers(0, 2, n) == 1,
        "v": rng.integers(-50, 50, n),
        "w": rng.standard_normal(n),
    }


def _reference(datas):
    cat = {k: np.concatenate([d[k] for d in datas]) for k in datas[0]}
    return TensorFrame.from_arrays(cat).groupby(["g", "h"]).agg(SPECS)


@pytest.mark.parametrize("foreign_at", [1, 3])
@pytest.mark.parametrize("spill", [False, True], ids=["in_memory", "spilled"])
def test_foreign_dictionary_falls_back(ooc, foreign_at, spill):
    """Chunks sharing one dictionary fold on the device; a chunk with
    its own dictionary (another label set) hands the accumulator to the
    partial path, whose blocks may spill and recompute."""
    rng = np.random.default_rng(foreign_at)
    labels = np.array(["p", "q", "r"], dtype=object)
    base_data = _chunk_data(rng, 600, labels)
    base = TensorFrame.from_arrays(base_data, encode={"g": "dict"})
    datas, chunks = [], []
    for i in range(6):
        if i == foreign_at:
            d = _chunk_data(rng, 100, np.array(["q", "s"], dtype=object))
            f = TensorFrame.from_arrays(d, encode={"g": "dict"})
        else:
            rows = np.arange(i * 100, (i + 1) * 100)
            d = {k: v[rows] for k, v in base_data.items()}
            f = base.take(rows)
        datas.append(d)
        chunks.append(f)
    sagg = pipeline.StreamAgg(["g", "h"], SPECS)
    for f in chunks:
        sagg.add(f, rebuild=lambda f=f: f)
        assert (sagg._dense is not None) == (len(sagg._pending) == 0 and sagg._merged is None)
    assert pipeline.STATS["dense_agg_chunks"] == foreign_at
    if spill:
        # corrupt the block the accumulator became (or its merge):
        # it recomputes from the folded chunks' closures
        h = sagg._merged if sagg._merged is not None else sagg._pending[0]
        h._do_spill()
        assert h.spilled
        with open(f"{h._path}/manifest.json", "w") as fh:
            fh.write("not json")
    got = sagg.finalize()
    if spill:
        assert SPILL.counters["recomputes"] == 1
    want = _reference(datas)
    godf, wodf = orc.frame_to_odf(got), orc.frame_to_odf(want)
    assert set(godf) == set(wodf)
    orc.assert_odf_equal(godf, wodf, sort=True, rtol=1e-9)


# ----------------------------------------------------------------------
# one fetch per stream
# ----------------------------------------------------------------------
def _syncs_for(nchunks):
    rows = 64
    scope = _scope(3, 2, nrows=nchunks * rows, chunk_rows=rows, seed=nchunks)
    assert scope["t"].n_chunks == nchunks
    query = "SELECT a, b, SUM(w) AS sw, AVG(v) AS av, COUNT(*) AS n FROM t GROUP BY a, b"
    want = _eager(query, scope)
    prims.SYNC_SITES.clear()
    pipeline.reset_stats()
    got = sql.execute(query, scope)
    syncs = dict(prims.SYNC_SITES)
    _assert_same(got, want)
    assert pipeline.STATS["dense_agg_chunks"] == nchunks
    return syncs


def test_dense_fetches_do_not_grow_with_chunks(ooc):
    few, many = _syncs_for(4), _syncs_for(32)
    assert sum(few.values()) == sum(many.values())
    assert few.get("pipeline.dense_agg") == many.get("pipeline.dense_agg") == 1


# ----------------------------------------------------------------------
# folds queued on the device stay bounded
# ----------------------------------------------------------------------
@pytest.mark.parametrize("nchunks", [4, 32])
def test_dense_folds_in_flight_stay_bounded(ooc, monkeypatch, nchunks):
    """Nothing else in the loop waits for the device, so the fold waits
    on the accumulators ``CONFIG.ooc_prefetch`` chunks back: however
    many chunks stream, at most that many folds (and their chunks'
    device buffers) are queued."""
    depth = max(1, int(CONFIG.ooc_prefetch))
    folds, waited = [], []
    fold = pipeline._dense_fold

    def counted_fold(*args, **kwargs):
        out = fold(*args, **kwargs)
        folds.append(out)
        return out

    block = pipeline.jax.block_until_ready

    def counted_block(x):
        waited.append(next(i for i, f in enumerate(folds) if f is x))
        return block(x)

    monkeypatch.setattr(pipeline, "_dense_fold", counted_fold)
    monkeypatch.setattr(pipeline.jax, "block_until_ready", counted_block)
    rng = np.random.default_rng(nchunks)
    labels = np.array(["p", "q", "r"], dtype=object)
    data = _chunk_data(rng, 50 * nchunks, labels)
    whole = TensorFrame.from_arrays(data, encode={"g": "dict"})
    sagg = pipeline.StreamAgg(["g", "h"], SPECS)
    for i in range(nchunks):
        sagg.add(whole.take(np.arange(i * 50, (i + 1) * 50)))
        assert len(folds) - len(waited) == min(len(folds), depth)
        assert len(sagg._dense._inflight) <= depth
    assert waited == list(range(nchunks - depth))
    got = sagg.finalize()
    godf, wodf = orc.frame_to_odf(got), orc.frame_to_odf(_reference([data]))
    orc.assert_odf_equal(godf, wodf, sort=True, rtol=1e-9)
