"""Resilience layer tests (ISSUE 10).

Unit coverage for every degradation tier on its own: the error
taxonomy and ``classify`` choke point, the seeded fault-injection
registry, bounded retry, deadline/cancel checkpoints at each layer
(scope primitive, pipeline chunk loop, operator dispatch, admission
dequeue), admission control (queue depth policies, per-session caps,
typed shutdown, worker-crash restart), spill write-failure retention
and corrupt-block recomputation, and the compile-failure negative
cache.  ``test_chaos.py`` composes them under randomized fault storms.
"""
import threading
import time
import weakref

import numpy as np
import pytest

from repro import serve, sql, store
from repro.core.config import CONFIG
from repro.core.frame import TensorFrame
from repro.resilience import (
    ExecutionError,
    PlanError,
    QueryCancelled,
    QueryError,
    QueryTimeout,
    ResourceExhausted,
    TransientIOError,
    checkpoint,
    classify,
    deadline_scope,
    faults,
    retry,
)
from repro.serve.stats import STATS
from repro.sql.parser import SqlError
from repro.store.spill import SPILL


@pytest.fixture(autouse=True)
def _clean_slate():
    STATS.reset()
    faults.clear()
    yield
    faults.clear()


@pytest.fixture()
def small_scope():
    n = 64
    return {
        "t": {
            "k": np.arange(n) % 8,
            "v": np.arange(n, dtype=np.float64),
        }
    }


# ----------------------------------------------------------------------
# taxonomy / classify
# ----------------------------------------------------------------------
def test_classify_mapping():
    assert isinstance(classify(SqlError("boom")), PlanError)
    assert isinstance(classify(OSError("disk")), TransientIOError)
    assert isinstance(classify(EOFError()), TransientIOError)
    assert isinstance(classify(MemoryError()), ResourceExhausted)
    assert isinstance(
        classify(RuntimeError("RESOURCE_EXHAUSTED: oom")), ResourceExhausted
    )
    assert isinstance(classify(ValueError("x")), ExecutionError)
    assert isinstance(classify(ValueError("x"), phase="plan"), PlanError)


def test_classify_idempotent_and_chains_cause():
    orig = QueryTimeout("late")
    assert classify(orig) is orig
    src = ValueError("inner")
    err = classify(src)
    assert err.__cause__ is src
    assert not err.retryable
    assert classify(OSError("io")).retryable


def test_error_codes_stable():
    assert QueryTimeout.code == "timeout"
    assert QueryCancelled.code == "cancelled"
    assert ResourceExhausted.code == "resource_exhausted"
    assert TransientIOError.code == "transient_io"
    assert PlanError.code == "plan_error"
    assert ExecutionError.code == "execution_error"
    for cls in (QueryTimeout, QueryCancelled, PlanError):
        assert issubclass(cls, QueryError)


# ----------------------------------------------------------------------
# fault injection
# ----------------------------------------------------------------------
def test_fault_injection_deterministic():
    def run(seed):
        fired = []
        with faults.inject("x", OSError, rate=0.5, seed=seed):
            for i in range(40):
                try:
                    faults.fault_point("x")
                except OSError:
                    fired.append(i)
        return fired

    a, b = run(7), run(7)
    assert a == b and a  # same seed -> identical firing subsequence
    assert run(8) != a  # different seed -> different subsequence


def test_fault_injection_limit_and_sites():
    with faults.inject("y", ValueError, limit=2) as rule:
        hits = 0
        for _ in range(5):
            try:
                faults.fault_point("y")
            except ValueError:
                hits += 1
        assert hits == 2 and rule.triggered == 2
    assert faults.sites_hit().get("y") == 2
    faults.fault_point("y")  # disarmed after the with-block: no raise


def test_fault_injection_delay():
    with faults.inject("z", delay_s=0.05):
        t0 = time.perf_counter()
        faults.fault_point("z")  # sleeps instead of raising
        assert time.perf_counter() - t0 >= 0.04
    assert faults.STATS["delayed"].get("z") == 1


def test_fault_point_reentered_by_a_finalizer():
    """A GC pass can run a weakref finalizer while ``fault_point`` holds
    the registry lock, and spill dirs are deleted from finalizers that
    call ``fault_point("spill.delete")``: the re-entry must not
    deadlock."""

    class Box:
        pass

    with faults.inject("spill.delete", rate=0.0), faults.inject("w", rate=0.0) as rule:
        box = Box()
        weakref.finalize(box, faults.fault_point, "spill.delete")
        roll = rule._roll

        def roll_and_collect():
            nonlocal box
            box = None  # the finalizer runs here, under the lock
            return roll()

        rule._roll = roll_and_collect
        t = threading.Thread(target=faults.fault_point, args=("w",), daemon=True)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        assert box is None


# ----------------------------------------------------------------------
# retry
# ----------------------------------------------------------------------
def test_retry_recovers_within_budget():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise OSError("transient")
        return "ok"

    assert retry.call(flaky, site="test", base_s=1e-4) == "ok"
    assert calls["n"] == 3
    assert retry.STATS["retries"] >= 2


def test_retry_gives_up_and_reraises():
    with pytest.raises(OSError):
        retry.call(
            lambda: (_ for _ in ()).throw(OSError("always")),
            retries=2,
            base_s=1e-4,
        )
    assert retry.STATS["giveups"] == 1


def test_retry_nonretryable_propagates_immediately():
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("semantic")

    with pytest.raises(ValueError):
        retry.call(bad, base_s=1e-4)
    assert calls["n"] == 1  # no retry on non-transient failures


# ----------------------------------------------------------------------
# deadlines / cancellation primitives
# ----------------------------------------------------------------------
def test_checkpoint_noop_without_scope():
    checkpoint("anywhere")  # must be free and silent


def test_deadline_scope_timeout_and_cancel():
    with deadline_scope(timeout_s=0.01):
        checkpoint("early")  # inside the budget
        time.sleep(0.03)
        with pytest.raises(QueryTimeout):
            checkpoint("late")
    with deadline_scope(timeout_s=None) as d:
        checkpoint("unbounded")
        d.token.cancel()
        with pytest.raises(QueryCancelled):
            checkpoint("after-cancel")


def test_pipeline_chunk_checkpoint(tmp_path):
    from repro.core.pipeline import ChunkScan

    n = 1000
    table = store.Table.from_arrays(
        {"a": np.arange(n), "b": np.arange(n) % 7}, chunk_rows=100
    )
    cs = ChunkScan(table, ["a", "b"], [])
    with deadline_scope(at=time.monotonic() - 1.0):
        with pytest.raises(QueryTimeout):
            list(cs)


def test_operator_checkpoint(small_scope):
    frames = {k: TensorFrame.from_arrays(v) for k, v in small_scope.items()}
    with deadline_scope(at=time.monotonic() - 1.0):
        with pytest.raises(QueryTimeout):
            sql.execute("SELECT SUM(v) AS s FROM t", frames)


# ----------------------------------------------------------------------
# executor deadlines / cancel / shedding
# ----------------------------------------------------------------------
def test_executor_timeout_mid_execution(small_scope):
    with serve.Executor(small_scope) as ex:
        with faults.inject("exec.operator", delay_s=0.1):
            with pytest.raises(QueryTimeout):
                ex.execute("SELECT SUM(v) AS s FROM t", timeout_s=0.03)
    assert STATS.snapshot()["errors"] == {"timeout": 1}


def test_executor_default_timeout_config(small_scope):
    CONFIG.serve_default_timeout_s = 0.03
    try:
        with serve.Executor(small_scope) as ex:
            with faults.inject("exec.operator", delay_s=0.1):
                with pytest.raises(QueryTimeout):
                    ex.execute("SELECT SUM(v) AS s FROM t")
    finally:
        CONFIG.serve_default_timeout_s = None


def test_expired_in_queue_is_shed(small_scope):
    ex = serve.Executor(small_scope, auto_start=False)
    fut = ex.submit("SELECT SUM(v) AS s FROM t", timeout_s=0.01)
    time.sleep(0.03)  # expire while queued; nothing is draining
    assert ex.drain_once() == 1
    with pytest.raises(QueryTimeout):
        fut.result(timeout=1)
    snap = STATS.snapshot()
    assert snap["shed"] == {"deadline": 1}
    assert snap["shed_requests"] == 1


def test_cancel_queued_request(small_scope):
    ex = serve.Executor(small_scope, auto_start=False)
    fut = ex.submit("SELECT SUM(v) AS s FROM t")
    assert ex.cancel(fut.request_id) is True
    assert ex.cancel(987654) is False  # unknown id
    ex.drain_once()
    with pytest.raises(QueryCancelled):
        fut.result(timeout=1)
    assert STATS.snapshot()["shed"] == {"cancelled": 1}
    # resolved request ids no longer cancel
    assert ex.cancel(fut.request_id) is False


def test_session_cancel_api(small_scope):
    ex = serve.Executor(small_scope, auto_start=False)
    s = ex.session()
    fut = s.submit("SELECT COUNT(*) AS c FROM t")
    assert s.cancel(fut.request_id) is True
    ex.drain_once()
    with pytest.raises(QueryCancelled):
        fut.result(timeout=1)


def test_timeout_does_not_starve_other_sessions(small_scope):
    """The ISSUE acceptance case: one session's query blows its
    deadline mid-execution while another session's queries are queued
    behind it — the victim gets QueryTimeout, the others complete."""
    with serve.Executor(small_scope) as ex:
        s1, s2 = ex.session(), ex.session()
        with faults.inject("exec.operator", delay_s=0.06):
            slow = s1.submit("SELECT SUM(v) AS s FROM t", timeout_s=0.02)
            queued = [
                s2.submit(f"SELECT COUNT(*) AS c FROM t WHERE k > {i}")
                for i in range(3)
            ]
            with pytest.raises(QueryTimeout):
                slow.result(timeout=30)
            for i, q in enumerate(queued):
                out = q.result(timeout=30)
                expect = int((np.arange(64) % 8 > i).sum())
                assert int(np.asarray(out.column("c"))[0]) == expect
    snap = STATS.snapshot()
    assert snap["errors"].get("timeout") == 1
    assert snap["errors_total"] == 1


def test_coalesced_group_uses_loosest_deadline(small_scope):
    """Two identical queries, one impatient: the shared execution runs
    under the loosest member deadline, so the patient member still gets
    its result and only the impatient one can time out in-queue."""
    ex = serve.Executor(small_scope, auto_start=False)
    impatient = ex.submit("SELECT SUM(v) AS s FROM t", timeout_s=0.01)
    patient = ex.submit("SELECT SUM(v) AS s FROM t")
    time.sleep(0.03)
    ex.drain_once()
    with pytest.raises(QueryTimeout):
        impatient.result(timeout=1)
    out = patient.result(timeout=1)
    assert float(np.asarray(out.column("s"))[0]) == float(
        np.arange(64, dtype=np.float64).sum()
    )


def test_cancelled_member_of_coalesced_group(small_scope):
    """Cancelling ONE member of a coalesced pair must not kill the
    other member's execution."""
    ex = serve.Executor(small_scope, auto_start=False)
    a = ex.submit("SELECT COUNT(*) AS c FROM t")
    b = ex.submit("SELECT COUNT(*) AS c FROM t")
    ex.cancel(a.request_id)
    ex.drain_once()
    with pytest.raises(QueryCancelled):
        a.result(timeout=1)
    assert int(np.asarray(b.result(timeout=1).column("c"))[0]) == 64


# ----------------------------------------------------------------------
# admission control
# ----------------------------------------------------------------------
def test_queue_depth_reject_new(small_scope):
    CONFIG.serve_queue_depth = 1
    CONFIG.serve_shed_policy = "reject-new"
    try:
        ex = serve.Executor(small_scope, auto_start=False)
        first = ex.submit("SELECT COUNT(*) AS c FROM t")
        with pytest.raises(ResourceExhausted):
            ex.submit("SELECT SUM(v) AS s FROM t")
        ex.drain_once()
        assert first.result(timeout=1).nrows == 1
    finally:
        CONFIG.serve_queue_depth = None


def test_queue_depth_drop_oldest(small_scope):
    CONFIG.serve_queue_depth = 1
    CONFIG.serve_shed_policy = "drop-oldest"
    try:
        ex = serve.Executor(small_scope, auto_start=False)
        oldest = ex.submit("SELECT COUNT(*) AS c FROM t")
        newest = ex.submit("SELECT SUM(v) AS s FROM t")
        with pytest.raises(ResourceExhausted):
            oldest.result(timeout=1)  # shed to admit the newcomer
        ex.drain_once()
        assert newest.result(timeout=1).nrows == 1
        snap = STATS.snapshot()
        assert snap["shed"] == {"queue_full": 1}
        assert snap["errors"].get("resource_exhausted") == 1
    finally:
        CONFIG.serve_queue_depth = None
        CONFIG.serve_shed_policy = "reject-new"


def test_session_inflight_cap(small_scope):
    CONFIG.serve_session_inflight = 2
    try:
        ex = serve.Executor(small_scope, auto_start=False)
        s = ex.session()
        futs = [s.submit("SELECT COUNT(*) AS c FROM t") for _ in range(2)]
        with pytest.raises(ResourceExhausted):
            s.submit("SELECT SUM(v) AS s FROM t")
        # the cap is per session: a sibling session still gets in
        other = ex.session().submit("SELECT COUNT(*) AS c FROM t")
        ex.drain_once()
        for f in futs + [other]:
            assert f.result(timeout=1).nrows == 1
        # resolution released the budget
        s.submit("SELECT COUNT(*) AS c FROM t")
    finally:
        CONFIG.serve_session_inflight = None


def test_close_drains_pending_with_typed_error(small_scope):
    ex = serve.Executor(small_scope, auto_start=False)
    futs = [ex.submit("SELECT COUNT(*) AS c FROM t") for _ in range(3)]
    ex.close()
    for f in futs:
        with pytest.raises(QueryCancelled):
            f.result(timeout=1)
    snap = STATS.snapshot()
    assert snap["shed"] == {"closed": 3}
    assert snap["errors"].get("cancelled") == 3


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_worker_crash_restarts(small_scope):
    ex = serve.Executor(small_scope)
    q = ex._queue
    real = q._run_batch
    try:
        q._run_batch = lambda batch: (_ for _ in ()).throw(SystemExit(1))
        crashed = ex.submit("SELECT COUNT(*) AS c FROM t")
        with pytest.raises(QueryError):
            crashed.result(timeout=10)
        deadline = time.monotonic() + 10
        while q._worker.is_alive() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not q._worker.is_alive()  # SystemExit killed the worker
        q._run_batch = real
        # the next submit detects the dead worker and restarts it
        out = ex.execute("SELECT COUNT(*) AS c FROM t")
        assert int(np.asarray(out.column("c"))[0]) == 64
        assert STATS["worker_restarts"] == 1
    finally:
        q._run_batch = real
        ex.close()


# ----------------------------------------------------------------------
# spill resilience
# ----------------------------------------------------------------------
def _block(seed, n=256):
    rng = np.random.default_rng(seed)
    return {
        "g": rng.integers(0, 10, n),
        "x": rng.standard_normal(n),
    }


def test_spill_write_failure_retains_in_memory(monkeypatch):
    monkeypatch.setattr(CONFIG, "memory_budget_bytes", 1)
    monkeypatch.setattr(CONFIG, "io_retry_base_s", 1e-4)
    b1, b2 = _block(1), _block(2)
    with faults.inject("spill.write", OSError, rate=1.0):
        h1 = SPILL.register(dict(b1))
        h2 = SPILL.register(dict(b2))  # tries (and fails) to evict h1
        data1, _ = h1.get()
        data2, _ = h2.get()
    try:
        np.testing.assert_array_equal(data1["x"], b1["x"])
        np.testing.assert_array_equal(data2["x"], b2["x"])
        assert SPILL.counters["write_failures"] >= 1
        assert SPILL.counters["retained_bytes"] > 0
        assert retry.STATS["giveups"] >= 1  # the budget was actually spent
    finally:
        h1.release()
        h2.release()


def test_spill_write_retry_recovers(monkeypatch):
    monkeypatch.setattr(CONFIG, "memory_budget_bytes", 1)
    monkeypatch.setattr(CONFIG, "io_retry_base_s", 1e-4)
    b1, b2 = _block(3), _block(4)
    with faults.inject("spill.write", OSError, limit=1):  # one-shot fault
        h1 = SPILL.register(dict(b1))
        h2 = SPILL.register(dict(b2))
    try:
        assert SPILL.counters["write_failures"] == 0  # retry absorbed it
        assert retry.STATS["retries"] >= 1
        data1, _ = h1.get()
        np.testing.assert_array_equal(data1["g"], b1["g"])
    finally:
        h1.release()
        h2.release()


def _spill_out(handle):
    """Force one block to disk regardless of LRU order."""
    wrote = handle._do_spill()
    assert handle.spilled
    return wrote


def test_corrupt_spill_block_recomputes(monkeypatch):
    monkeypatch.setattr(CONFIG, "io_retry_base_s", 1e-4)
    src = _block(5)
    h = SPILL.register(dict(src), recompute=lambda: (dict(src), {}))
    try:
        _spill_out(h)
        # truncate the manifest: open_store now fails to parse
        with open(f"{h._path}/manifest.json", "w") as f:
            f.write("{")
        data, _ = h.get()
        np.testing.assert_array_equal(data["x"], src["x"])
        assert SPILL.counters["corrupt_blocks"] == 1
        assert SPILL.counters["recomputes"] == 1
        # the bad file was discarded: the handle is re-spillable
        assert h._path is None
    finally:
        h.release()


def test_truncated_spill_block_detected(monkeypatch):
    """Row-count mismatch (a truncated rewrite) is caught by the
    written-block identity check, not silently served."""
    monkeypatch.setattr(CONFIG, "io_retry_base_s", 1e-4)
    src = _block(6)
    h = SPILL.register(dict(src))
    try:
        _spill_out(h)
        from repro.store import format as storefmt

        truncated = {k: v[: len(v) // 2] for k, v in src.items()}
        storefmt.write_arrays(h._path, truncated, chunk_rows=1024)
        with pytest.raises(TransientIOError):
            h.get()
        assert SPILL.counters["corrupt_blocks"] == 1
    finally:
        h.release()


def test_spill_delete_failure_counted(monkeypatch):
    from repro.store import spill as spill_mod

    def broken_rmtree(path, ignore_errors=False):
        if not ignore_errors:
            raise OSError("EBUSY")

    monkeypatch.setattr(spill_mod.shutil, "rmtree", broken_rmtree)
    before = SPILL.counters["delete_failures"]
    spill_mod._delete_dir(spill_mod._process_spill_root())  # must not raise
    assert SPILL.counters["delete_failures"] == before + 1


def test_streamagg_partial_recompute(monkeypatch):
    """A corrupt spilled partial rebuilds through its chunk closure and
    the final aggregate stays exact."""
    from repro.core.pipeline import StreamAgg

    monkeypatch.setattr(CONFIG, "io_retry_base_s", 1e-4)
    chunks = [
        TensorFrame.from_arrays(
            {"g": np.arange(100) % 5, "v": np.arange(100) + 100.0 * i}
        )
        for i in range(3)
    ]
    sagg = StreamAgg(["g"], [("s", "sum", "v"), ("c", "count", "v")])
    for f in chunks:
        sagg.add(f, rebuild=lambda f=f: f)
    assert sagg._pending, "partials should be registered"
    h = sagg._pending[0]
    _spill_out(h)
    with open(f"{h._path}/manifest.json", "w") as f:
        f.write("not json")
    out = sagg.finalize()
    assert SPILL.counters["recomputes"] == 1
    got = {
        int(g): (float(s), int(c))
        for g, s, c in zip(
            np.asarray(out.column("g")),
            np.asarray(out.column("s")),
            np.asarray(out.column("c")),
        )
    }
    all_g = np.concatenate([np.arange(100) % 5] * 3)
    all_v = np.concatenate(
        [np.arange(100) + 100.0 * i for i in range(3)]
    )
    for g in range(5):
        mask = all_g == g
        assert got[g][0] == pytest.approx(float(all_v[mask].sum()))
        assert got[g][1] == int(mask.sum())


# ----------------------------------------------------------------------
# store read retry
# ----------------------------------------------------------------------
def test_store_read_retries_through_transient_faults(tmp_path, monkeypatch):
    monkeypatch.setattr(CONFIG, "io_retry_base_s", 1e-4)
    from repro.store import format as storefmt

    src = {"a": np.arange(500), "b": (np.arange(500) % 3).astype(np.int64)}
    path = str(tmp_path / "t.tfb")
    storefmt.write_arrays(path, src, chunk_rows=128)
    # the first two reads (manifest, then its first retry) fail; the
    # retry budget (3) absorbs both deterministically
    with faults.inject("store.read", OSError, limit=2):
        table = storefmt.open_store(path)
        got = table.to_arrays()
    np.testing.assert_array_equal(got["a"], src["a"])
    np.testing.assert_array_equal(got["b"], src["b"])
    assert faults.sites_hit().get("store.read") == 2
    assert retry.STATS["retries"] >= 2


# ----------------------------------------------------------------------
# compile-failure negative cache
# ----------------------------------------------------------------------
def test_compile_failure_negative_cache(small_scope):
    from repro.sql import compile as plan_compile

    frames = {k: TensorFrame.from_arrays(v) for k, v in small_scope.items()}
    q = "SELECT k, SUM(v) AS s FROM t GROUP BY k"
    expected = sql.execute(q, frames)  # compiled off-path (small input)
    plan_compile.clear_cache()
    plan_compile.reset_stats()
    CONFIG.compiled = "force"
    try:
        with faults.inject("compile", RuntimeError, rate=1.0):
            out1 = sql.execute(q, frames)  # crash -> op-by-op fallback
            out2 = sql.execute(q, frames)  # negative cache, no re-crash
        s = plan_compile.STATS
        assert s["compile_failures"] == 1  # second call never recompiled
        assert s["fallbacks"] == 2
        assert s["compiles"] == 0
        for out in (out1, out2):
            np.testing.assert_allclose(
                np.asarray(out.column("s")), np.asarray(expected.column("s"))
            )
        # the trace lock was released: later queries aren't poisoned
        assert not plan_compile._TRACE_LOCKS
    finally:
        CONFIG.compiled = "auto"
        plan_compile.clear_cache()


# ----------------------------------------------------------------------
# shared-scan degradation stays observable
# ----------------------------------------------------------------------
def test_shared_scan_failure_falls_back(monkeypatch):
    n = 512
    table = store.Table.from_arrays(
        {"a": np.arange(n), "b": np.arange(n) % 7}, chunk_rows=128
    )
    import repro.store as store_pkg

    def broken(*a, **k):
        raise OSError("scan pass down")

    monkeypatch.setattr(store_pkg, "shared_scan", broken)
    ex = serve.Executor({"t": table}, auto_start=False)
    f1 = ex.submit("SELECT SUM(a) AS s FROM t WHERE b > 2")
    f2 = ex.submit("SELECT COUNT(*) AS c FROM t WHERE b > 2")
    ex.drain_once()
    mask = np.arange(n) % 7 > 2
    assert float(np.asarray(f1.result(1).column("s"))[0]) == float(
        np.arange(n)[mask].sum()
    )
    assert int(np.asarray(f2.result(1).column("c"))[0]) == int(mask.sum())
    assert STATS["shared_scan_errors"] == 1
    assert STATS.snapshot()["errors_total"] == 0  # degraded, not failed
