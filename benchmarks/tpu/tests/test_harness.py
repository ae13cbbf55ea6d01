"""The whole harness on the CPU at SF 0.01, with the look for a chip
skipped: a sound run is correct and reports its metrics; a run with the
timed path broken underneath, or with the float32 control switched on,
is not correct.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tpu/tests -q
"""
import jax
import numpy as np
import pytest

import run

SF = 0.01
SECONDS = 1.0
POWER = "tpch_sf1_store.power"


@pytest.fixture(autouse=True)
def small(monkeypatch):
    """Every configuration at SF 0.01, on whatever device JAX has."""
    load = run.load_config

    def load_small(name):
        cfg = load(name)
        cfg["scale_factor"] = SF
        return cfg

    monkeypatch.setattr(run, "load_config", load_small)


def _run(cell, seed, trace=False, **kw):
    return run.run(cell, seed, SECONDS, trace, device_check=lambda n: jax.devices(), **kw)


def _result_line_ok(res, names):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == set(names)
    for m in res["metrics"].values():
        assert m["value"] == m["value"] and m["unit"]


@pytest.mark.parametrize("trace", [False, True], ids=["power", "power-trace"])
def test_sound_run_is_correct(trace):
    res = _run(POWER, 2**31 + 101, trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    names = run.cell_metrics(spec, POWER, trace)
    # the CPU has no device plane: the device metrics read nothing here
    if trace:
        names = [n for n in names if not n.startswith("device_")]
    _result_line_ok(res, names)


def _first_float_column(frame):
    return next((n for n in frame.column_names if frame.meta(n).kind == "float"), None)


def _break_execute(monkeypatch, wrap):
    import repro.sql

    real = repro.sql.execute_plan
    monkeypatch.setattr(repro.sql, "execute_plan", lambda *a, **k: wrap(real(*a, **k)))


def test_answer_altered_where_produced(monkeypatch):
    def alter(out):
        name = _first_float_column(out)
        if name is None:
            return out
        vals = np.asarray(out.column(name)) * (1 + 1e-7)
        return out.with_column(name, vals)

    _break_execute(monkeypatch, alter)
    res = _run(POWER, 2**31 + 102)
    assert not res["correct"]
    assert res["checks"]["max_rel_err"]["value"] > res["checks"]["max_rel_err"]["limit"]


def test_half_the_rows_left_out(monkeypatch):
    from repro.core import TensorFrame

    real = TensorFrame.from_store

    def half(*a, **k):
        f = real(*a, **k)
        return f.take(np.arange(f.nrows // 2), stats="subset")

    monkeypatch.setattr(TensorFrame, "from_store", staticmethod(half))
    res = _run(POWER, 2**31 + 103)
    assert not res["correct"]
    assert (res["checks"]["wrong_answers"]["value"] > 0
            or res["checks"]["max_rel_err"]["value"] > res["checks"]["max_rel_err"]["limit"])


def test_state_returned_unchanged(monkeypatch):
    """The serving layer hands back the first answer it ever produced."""
    first = []

    def stale(out):
        if not first:
            first.append(out)
        return first[0]

    _break_execute(monkeypatch, stale)
    res = _run(POWER, 2**31 + 104)
    assert not res["correct"]
    assert (res["checks"]["wrong_answers"]["value"] > 0
            or res["checks"]["max_rel_err"]["value"] > res["checks"]["max_rel_err"]["limit"])


def test_answer_that_never_comes(monkeypatch):
    def fail(out):
        raise RuntimeError("lost")

    _break_execute(monkeypatch, fail)
    res = _run(POWER, 2**31 + 105)
    assert not res["correct"]
    assert res["failed"] == res["attempted"] == res["checks"]["missing_answers"]["value"]


def test_float32_control_is_not_correct():
    res = _run(POWER, 2**31 + 106, engine={"float_dtype": "float32"})
    assert not res["correct"]
    gap = res["checks"]["max_rel_err"]
    assert gap["value"] > 10 * gap["limit"]
