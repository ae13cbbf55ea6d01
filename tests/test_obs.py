"""Unified observability: spans, metrics registry, EXPLAIN ANALYZE,
exporters, and the serving tier's phase percentiles.

Covers the contract every layer now leans on:

- span tracing is a strict no-op when ``CONFIG.tracing="off"`` (shared
  noop instance, nothing recorded) and records nested parent/child
  spans with attributes when on;
- ``obs.metrics`` is the one registry: its own counters plus every
  legacy STATS group (join, pipeline, compile, serve, spill, pool,
  device syncs, upload bytes) readable through ``snapshot()`` and
  zeroed through ``reset()`` while the legacy names stay aliases;
- snapshot/diff/reset algebra;
- the registry and the rings stay consistent under 8-thread hammering;
- Chrome-trace and JSON exporters emit the documented schema;
- ``execute(explain="analyze")`` annotates every operator with wall
  time, row counts and the join algorithm actually chosen.
"""
import json
import threading

import numpy as np
import pytest

from repro import obs, sql
from repro.core.config import CONFIG
from repro.core.frame import TensorFrame
from repro.obs import metrics
from repro.queries.tpch_sql import sql_text


@pytest.fixture()
def tracing_on():
    saved = CONFIG.tracing
    CONFIG.tracing = "on"
    obs.clear_trace()
    try:
        yield
    finally:
        CONFIG.tracing = saved


# ----------------------------------------------------------------------
# span tracer
# ----------------------------------------------------------------------
def test_disabled_tracing_is_noop():
    assert CONFIG.tracing == "off"
    assert not obs.enabled()
    # one shared sentinel: no allocation per call on the disabled path
    assert obs.span("a") is obs.span("b", rows=1)
    with obs.span("outer") as sp:
        sp.set(rows=5)
        assert obs.current_span_id() == 0
    assert obs.spans() == []


def test_span_nesting_and_attrs(tracing_on):
    with obs.span("outer", depth=0):
        with obs.span("inner") as sp:
            sp.set(rows=7)
            obs.annotate(tag="x")
    recs = obs.spans()
    assert [r.name for r in recs] == ["outer", "inner"]  # oldest first
    by_name = {r.name: r for r in recs}
    inner, outer = by_name["inner"], by_name["outer"]
    assert inner.parent_id == outer.span_id
    assert outer.parent_id == 0
    assert inner.attrs == {"rows": 7, "tag": "x"}
    assert outer.attrs == {"depth": 0}
    assert 0 < inner.dur_ns <= outer.dur_ns
    assert outer.start_ns <= inner.start_ns


def test_detailed_span_gated(tracing_on):
    with obs.detailed_span("chunk"):
        pass
    assert obs.spans() == []  # tracing="on" drops detailed spans
    CONFIG.tracing = "detailed"
    with obs.detailed_span("chunk"):
        pass
    assert [r.name for r in obs.spans()] == ["chunk"]


def test_spans_since_mark(tracing_on):
    with obs.span("before"):
        pass
    mark = obs.mark_ns()
    with obs.span("after"):
        pass
    assert [r.name for r in obs.spans(since_ns=mark)] == ["after"]


# ----------------------------------------------------------------------
# metrics registry
# ----------------------------------------------------------------------
def test_counter_gauge_histogram_snapshot_diff_reset():
    """The registry's native instruments are counters alone (gauges and
    histograms had no reader and are gone): snapshot, diff, reset."""
    c = metrics.counter("t.count")
    idle = metrics.counter("t.idle")
    assert metrics.counter("t.count") is c  # one counter per name
    c.inc()
    c.inc(2)
    idle.inc(7)
    snap = metrics.snapshot()
    assert snap["obs"]["t.count"] == 3
    assert snap["obs"]["t.idle"] == 7
    assert not hasattr(metrics, "gauge") and not hasattr(metrics, "histogram")

    before = metrics.snapshot()
    c.inc(5)
    d = metrics.diff(before, metrics.snapshot())
    assert d["obs"]["t.count"] == 5
    assert "t.idle" not in d["obs"]  # zero deltas dropped

    metrics.reset()
    assert metrics.snapshot()["obs"] == {"t.count": 0, "t.idle": 0}


def test_legacy_stats_groups_registered():
    metrics.load_engine_groups()
    groups = set(metrics.groups())
    assert {
        "core.join",
        "core.pipeline",
        "sql.compile",
        "serve",
        "store.spill",
        "store.pool",
    } <= groups


def test_legacy_alias_and_registry_share_state():
    """Old STATS names keep working; the registry reads the same
    objects, and registry reset zeroes the legacy view too."""
    import importlib

    # repro.core re-exports a join *function*; reach the module itself
    join_mod = importlib.import_module("repro.core.join")

    join_mod.STATS["stats_unique_hits"] += 3
    assert metrics.snapshot()["core.join"]["stats_unique_hits"] == 3
    metrics.reset()
    assert join_mod.STATS["stats_unique_hits"] == 0

    from repro.sql import compile as plan_compile

    plan_compile.STATS["hits"] += 2
    assert metrics.snapshot()["sql.compile"]["hits"] == 2
    metrics.reset()
    assert plan_compile.STATS["hits"] == 0


def test_engine_run_populates_join_group():
    rng = np.random.default_rng(0)
    t = TensorFrame.from_arrays(
        {"k": rng.integers(0, 50, 300), "v": rng.normal(size=300)}
    )
    d = TensorFrame.from_arrays(
        {"k": np.arange(50), "w": rng.normal(size=50)}
    )
    before = metrics.snapshot()
    sql.execute(
        "SELECT t.k, SUM(d.w) AS s FROM t JOIN d ON t.k = d.k GROUP BY t.k",
        {"t": t, "d": d},
    )
    delta = metrics.diff(before, metrics.snapshot()).get("core.join", {})
    assert sum(delta.values()) > 0  # the join picker counted something


# ----------------------------------------------------------------------
# thread safety
# ----------------------------------------------------------------------
def test_registry_and_rings_race_free(tracing_on):
    """8 threads hammer one counter and emit nested spans: the counter
    total is exact, and every thread's spans nest consistently."""
    N_THREADS, N_ITER = 8, 400
    c = metrics.counter("race.count")
    errs = []
    # all threads alive at once: real contention, and no OS thread-id
    # reuse (each thread's ring must be its own)
    barrier = threading.Barrier(N_THREADS)

    def work(tid: int) -> None:
        try:
            barrier.wait()
            for i in range(N_ITER):
                c.inc()
                with obs.span("outer", tid=tid):
                    with obs.span("inner"):
                        pass
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [
        threading.Thread(target=work, args=(i,)) for i in range(N_THREADS)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert c.value == N_THREADS * N_ITER

    recs = obs.spans()
    # ring capacity is 64k/thread: nothing dropped at this volume
    assert obs.dropped() == 0
    assert len(recs) == N_THREADS * N_ITER * 2
    by_thread = {}
    for r in recs:
        by_thread.setdefault(r.tid, []).append(r)
    assert len(by_thread) == N_THREADS
    for tid, rs in by_thread.items():
        ids = {r.span_id: r for r in rs}
        inners = [r for r in rs if r.name == "inner"]
        assert len(inners) == N_ITER
        for r in inners:
            parent = ids[r.parent_id]  # parent recorded on SAME thread
            assert parent.name == "outer"
            assert parent.tid == r.tid


# ----------------------------------------------------------------------
# exporters
# ----------------------------------------------------------------------
def test_chrome_trace_schema(tracing_on, tmp_path):
    with obs.span("parent", rows=3):
        with obs.span("child"):
            pass
    out = tmp_path / "trace.json"
    n = obs.export_chrome_trace(str(out))
    doc = json.loads(out.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert n == len(evs)
    xs = [e for e in evs if e["ph"] == "X"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {"parent", "child"}
    assert metas and metas[0]["name"] == "thread_name"
    for e in xs:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
    parent = next(e for e in xs if e["name"] == "parent")
    assert parent["args"] == {"rows": 3, "trace_id": 0}  # outside a query


def test_export_json_operators(tracing_on):
    with obs.span("op.a"):
        with obs.span("op.b"):
            pass
    doc = obs.export_json()
    assert doc["schema"] == "repro-obs/v1"
    assert doc["spans_recorded"] == 2
    ops = doc["operators"]
    assert ops["op.a"]["count"] == 1
    # self time excludes the nested child
    assert ops["op.a"]["self_ms"] <= ops["op.a"]["total_ms"]
    assert "metrics" in doc


# ----------------------------------------------------------------------
# EXPLAIN ANALYZE
# ----------------------------------------------------------------------
@pytest.mark.parametrize("qname", ["q1", "q3", "q9"])
def test_explain_analyze_tpch(tpch_small, qname):
    _, frames = tpch_small
    res = sql.execute(sql_text(qname, 0.002), frames, explain="analyze")
    ref = sql.execute(sql_text(qname, 0.002), frames)
    assert res.frame.nrows == ref.nrows
    text = str(res)
    assert "EXPLAIN ANALYZE" in text
    assert "time=" in text and "rows=" in text and "bytes=" in text
    if qname in ("q3", "q9"):
        assert "algo=" in text  # join algorithm choice surfaced
    # tracing restored to off after the analyzed run
    assert CONFIG.tracing == "off"
    d = res.to_dict()
    assert d["total_ms"] > 0
    node = d["plan"]
    assert node["wall_ms"] >= 0 and node["rows_out"] == ref.nrows

    def walk(n):
        yield n
        for c in n["children"]:
            yield from walk(c)

    nodes = list(walk(node))
    assert all("wall_ms" in n for n in nodes)
    if qname in ("q3", "q9"):
        algos = {n.get("algorithm") for n in nodes if "algorithm" in n}
        assert algos <= {
            "direct_address", "sorted_probe", "membership", "sort_merge"
        }
        assert algos


def test_explain_analyze_rejects_unknown_mode():
    t = TensorFrame.from_arrays({"a": np.arange(4)})
    with pytest.raises(sql.SqlError):
        sql.execute("SELECT a FROM t", {"t": t}, explain="verbose")


# ----------------------------------------------------------------------
# serve phase percentiles
# ----------------------------------------------------------------------
def test_serve_phase_percentiles():
    from repro import serve

    t = TensorFrame.from_arrays(
        {"k": np.arange(100) % 7, "v": np.arange(100, dtype=float)}
    )
    with serve.Executor({"t": t}, auto_start=False) as ex:
        futs = [
            ex.submit("SELECT k, SUM(v) AS s FROM t GROUP BY k")
            for _ in range(3)
        ]
        assert ex.drain_once() == 3
        for f in futs:
            assert f.result().nrows == 7
    snap = metrics.snapshot()["serve"]
    phases = snap["phases"]
    assert set(phases) == {"queue", "plan", "compile", "execute"}
    for p in ("queue", "plan", "execute"):
        assert phases[p]["count"] >= 1
        assert {"p50_ms", "p95_ms", "p99_ms"} <= set(phases[p])
    assert phases["execute"]["p50_ms"] >= 0.0
    assert "p95_ms" in snap  # end-to-end reservoir gained p95


# ----------------------------------------------------------------------
# spans on the profiler's clock, trace ids, layer spans and counters
# ----------------------------------------------------------------------
SF = 0.01
#: Every call site of ``prims.to_host``: the engine's device->host fetches.
SYNC_SITES = {
    "agg.frame_agg",
    "compile.rows",
    "compile.unique_test",
    "expr.dict_lut",
    "frame.column",
    "frame.mask_rows",
    "groupby.transposed",
    "hashing.distinct",
    "join.direct_probe",
    "join.left_outer",
    "join.sorted_probe",
    "join.unique_test",
    "pipeline.dense_agg",
    "pipeline.probe_chunk",
    "pipeline.unique_test",
    "prims.bounds",
}
#: Spans of a micro-batch as a whole, outside any one query's trace.
BATCH_SPANS = {"serve.batch", "serve.batch_assembly", "serve.shared_scan"}


@pytest.fixture(scope="module")
def tpch_tables():
    from repro.data import tpch

    return tpch.generate(sf=SF, seed=11)


@pytest.fixture(scope="module")
def tpch_store(tpch_tables):
    """SF 0.01 TPC-H as store tables of 8,192-row chunks: lineitem
    spans eight chunks, so a streamed q1 runs the prefetch thread."""
    from repro.data import tpch

    return tpch.as_store(tpch_tables, chunk_rows=8192, sort_fact_by_date=True)


@pytest.fixture()
def streamed(monkeypatch):
    """Stream every store scan through the chunk pipeline, as SF 1's
    million-row tables do."""
    monkeypatch.setattr(CONFIG, "ooc_min_rows", 1)


def _serve(stores, qnames):
    from repro import serve

    with serve.Executor(stores) as ex:
        futs = []
        for q in qnames:
            futs.append(ex.submit(sql_text(q, SF)))
            futs[-1].result().to_dict()
    return futs


@pytest.mark.filterwarnings("ignore:builtin type event_stats")
def test_spans_land_in_the_profiler_trace(tracing_on, tpch_store, streamed, tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    _serve(tpch_store, ["q6", "q3"])  # compile outside the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with TraceAnnotation("test.window"):
            futs = _serve(tpch_store, ["q6", "q3"])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [p for p in ProfileData.from_file(path).planes if p.name.startswith("/host:")]
    events = [e for p in host for line in p.lines for e in line.events]
    (window,) = [e for e in events if e.name == "test.window"]
    ids = {f.request_id for f in futs}
    for name in ("serve.plan", "store.scan", "frame.upload", "device.sync"):
        evs = [e for e in events if e.name == name]
        assert evs, f"no {name} event on a host plane"
        for e in evs:
            assert window.start_ns <= e.start_ns
            assert e.start_ns + e.duration_ns <= window.start_ns + window.duration_ns
    plans = [dict(e.stats) for e in events if e.name == "serve.plan"]
    assert {p["trace_id"] for p in plans} == ids


def test_one_trace_id_per_query_across_threads(tracing_on, tpch_store, streamed):
    from repro.core import pipeline

    (fut,) = _serve(tpch_store, ["q1"])
    assert pipeline.STATS["chunks_streamed"] > 2
    recs = [r for r in obs.spans() if r.name not in BATCH_SPANS]
    execute = next(r for r in recs if r.name == "serve.execute")
    # the result's decode on the client thread runs outside the query
    query = [r for r in recs if r.trace_id != 0 or r.tid == execute.tid]
    assert {r.trace_id for r in query} == {fut.request_id}
    assert execute.attrs["members"] == str(fut.request_id)
    (agg,) = [r for r in query if r.name == "pipeline.stream_agg"]
    produced = [r for r in query if r.name == "store.scan" and r.tid != agg.tid]
    assert len(produced) == pipeline.STATS["chunks_streamed"]
    assert {r.parent_id for r in produced} == {agg.span_id}
    waits = [r for r in query if r.name == "pipeline.prefetch_wait"]
    assert len(waits) == len(produced) + 1  # one per chunk, one for the end
    assert not [r for r in recs if r.name == "pipeline.chunk_decode"]


def test_sync_and_upload_counters_match_spans(tracing_on, tpch_store, streamed):
    from repro.core import prims

    snap0 = metrics.snapshot()
    _serve(tpch_store, ["q1", "q3", "q4", "q6", "q14"])
    d = metrics.diff(snap0, metrics.snapshot())
    syncs = [r for r in obs.spans() if r.name == "device.sync"]
    assert len(syncs) == d["device"]["host_syncs"] > 0
    assert set(d["device"]["by_site"]) <= SYNC_SITES
    assert sum(d["device"]["by_site"].values()) == d["device"]["host_syncs"]
    assert {r.attrs["site"] for r in syncs} == set(d["device"]["by_site"])
    uploads = [r for r in obs.spans() if r.name == "frame.upload"]
    assert sum(r.attrs["bytes"] for r in uploads) == d["frame"]["bytes_uploaded"]

    before = metrics.snapshot()["frame"]["bytes_uploaded"]
    f = TensorFrame.from_arrays(
        {"k": np.arange(10), "d": np.arange(10) % 3 == 0, "v": np.ones(10)}
    )
    built = f.itensor.nbytes + f.ftensor.nbytes
    assert metrics.snapshot()["frame"]["bytes_uploaded"] - before == built == 240
    assert sum(prims.SYNC_SITES.values()) == metrics.snapshot()["device"]["host_syncs"]


@pytest.mark.parametrize("deployment", ["store", "frames"])
def test_every_served_fetch_goes_through_to_host(
    deployment, tpch_tables, tpch_store, streamed, monkeypatch
):
    """The CPU's stand-in for ``jax_transfer_guard_device_to_host``
    (which guards nothing on the CPU): every conversion of a device
    array to a host value made by engine code, for the served queries,
    happens inside ``prims.to_host`` — over streamed store tables and
    over device-resident frames (the whole-plan compiled path)."""
    from repro.data import tpch

    scope = tpch_store if deployment == "store" else tpch.as_frames(tpch_tables)
    import os
    import sys

    import jax
    import jax.numpy as jnp

    from repro.core import prims

    engine = os.path.dirname(os.path.dirname(prims.__file__)) + os.sep
    libs = (os.sep + "jax" + os.sep, os.sep + "jaxlib" + os.sep,
            os.sep + "numpy" + os.sep, "<frozen")
    outside = []

    def check(frame):
        # the first frame outside jax and numpy made the fetch
        while frame is not None:
            fn = frame.f_code.co_filename
            if not any(s in fn for s in libs):
                if fn.startswith(engine) and frame.f_code.co_name != "to_host":
                    outside.append(f"{os.path.relpath(fn, engine)}:{frame.f_lineno}")
                return
            frame = frame.f_back

    array_type = type(jnp.zeros(1))
    value = array_type.__dict__["_value"]

    def guarded_value(self):
        check(sys._getframe(1))
        return value.fget(self)

    def guard(fn):
        def wrapper(a, *args, **kwargs):
            if isinstance(a, jax.Array):
                check(sys._getframe(1))
            return fn(a, *args, **kwargs)

        return wrapper

    monkeypatch.setattr(array_type, "_value", property(guarded_value))
    monkeypatch.setattr(np, "asarray", guard(np.asarray))
    monkeypatch.setattr(np, "array", guard(np.array))
    _serve(scope, ["q1", "q3", "q4", "q6", "q14"])
    monkeypatch.undo()
    assert metrics.snapshot()["device"]["host_syncs"] > 0
    assert not outside, sorted(set(outside))


def test_queue_phase_totals_survive_the_reservoir_halving():
    from repro.serve.stats import _RESERVOIR, ServeStats

    st = ServeStats()
    n = _RESERVOIR + _RESERVOIR // 2 + 3
    readings = []
    for i in range(1, n + 1):
        st.record_phase("queue", 0.001)
        if i % 1000 == 0 or i == n:
            q = st.phase_percentiles()["queue"]
            readings.append((q["n"], q["total_ms"]))
    assert [r[0] for r in readings] == sorted({r[0] for r in readings})
    assert [r[1] for r in readings] == sorted({r[1] for r in readings})
    q = st.phase_percentiles()["queue"]
    assert q["count"] < q["n"] == n  # the reservoir halved, the totals did not
    assert q["total_ms"] == pytest.approx(n * 1.0)
    st.reset()
    assert st.phase_percentiles()["queue"]["n"] == 0


def test_tracing_off_records_nothing_but_counts(tpch_store, streamed, monkeypatch):
    from repro.obs import trace

    entered = []

    class Recorder:
        def __init__(self, name, **kwargs):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(trace, "_ANNOTATION", Recorder)
    assert CONFIG.tracing == "off"
    snap0 = metrics.snapshot()
    _serve(tpch_store, ["q1", "q6"])
    d = metrics.diff(snap0, metrics.snapshot())
    assert obs.spans() == [] and entered == []
    assert d["device"]["host_syncs"] > 0
    assert d["frame"]["bytes_uploaded"] > 0
    assert d["serve"]["phases"]["queue"]["n"] == 2
    # with tracing on, each recorded span entered one annotation
    CONFIG.tracing = "on"
    try:
        _serve(tpch_store, ["q6"])
    finally:
        CONFIG.tracing = "off"
    assert entered and len(entered) == len(obs.spans())


def test_clear_drops_the_rings_of_exited_threads(tracing_on):
    from repro.obs import trace

    def spanning():
        with obs.span("short.lived"):
            pass

    for _ in range(200):
        t = threading.Thread(target=spanning)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    # records outlive their threads until the next clear
    assert len([r for r in obs.spans() if r.name == "short.lived"]) == 200
    obs.clear_trace()
    assert len(trace._RINGS) <= threading.active_count()
    assert obs.spans() == []


def test_bind_carries_trace_id_and_parent(tracing_on):
    def producer():
        with obs.span("producer"):
            pass

    with obs.trace_scope(42), obs.span("consumer") as sp:
        t = threading.Thread(target=obs.bind(producer))
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    with obs.span("after"):
        pass
    by_name = {r.name: r for r in obs.spans()}
    assert by_name["producer"].trace_id == by_name["consumer"].trace_id == 42
    assert by_name["producer"].parent_id == sp.span_id
    assert by_name["after"].trace_id == 0  # the scope is reset on exit
