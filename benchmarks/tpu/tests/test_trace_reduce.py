"""trace_reduce on hand-made planes and on a trace recorded on a v5e
(testdata/tpu_window.xplane.pb, written by testdata/record_trace.py)."""
import os
from types import SimpleNamespace as NS

import pytest

import trace_reduce

TRACE = os.path.join(os.path.dirname(trace_reduce.__file__), "testdata", "tpu_window.xplane.pb")


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes(device_events, window=(1000, 9000)):
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev(trace_reduce.WINDOW, window[0], window[1] - window[0])])])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=device_events)])
    return [host, dev]


def test_busy_is_the_union_clipped_to_the_window():
    evs = [_ev("a", 0, 2000), _ev("b", 1500, 1000), _ev("a", 4000, 1000), _ev("c", 8500, 5000)]
    out = trace_reduce.reduce_planes(_planes(evs), inflight=[(0.0, 5e-6, "q1")])
    # [1000, 2500] + [4000, 5000] + [8500, 9000] = 3000 ns busy of 8000
    assert out["window_s"] == pytest.approx(8000e-9)
    assert out["busy_s"] == pytest.approx(3000e-9)
    assert dict(out["device_ops"]) == pytest.approx({"a": 2000e-9, "b": 1000e-9, "c": 500e-9})
    gaps = out["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([3500e-9, 1500e-9])
    # the 2500-4000 gap's midpoint is 2.25 us into the window, q1's span
    assert gaps[1][0] == "q1" and gaps[0][0] == "no query in flight"


def test_no_window_or_no_device_reads_nothing():
    assert trace_reduce.reduce_planes(_planes([])) is None
    host_only = [_planes([_ev("a", 0, 10)])[0]]
    assert trace_reduce.reduce_planes(host_only) is None


def test_recorded_v5e_trace():
    out = trace_reduce.reduce_file(TRACE)
    assert out is not None
    assert out["devices"] >= 1
    assert 0 < out["busy_s"] < out["window_s"]
    # record_trace.py sleeps 50 ms between its two programs
    assert max(g[1] for g in out["idle_gaps"]) >= 0.05
    assert out["device_ops"] and all(s > 0 for _, s in out["device_ops"])
