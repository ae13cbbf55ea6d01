#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/tpu/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root
of the checkout; it names a configuration (``configs/<config>.json``)
and a traffic mix (``traffic/<mix>.json``).  A run:

1. fails, printing no result, unless JAX finds a TPU with as many chips
   as the cell asks for;
2. makes the TPC-H tables (``tpch_gen.py``, ``make_data``: the same
   row counts for every seed, values permuted by ``--seed``) and
   builds the deployment the configuration names with the engine's own
   loader: chunked ``repro.store`` tables;
3. sends every text the window will send twice, so that every program
   is compiled or loaded from ``<checkout>/.jax_cache`` before the
   window;
4. drives ``serve.Executor`` for ``--seconds``: one closed-loop client
   in rounds, each answer taken to the host;
5. after the window, reads the device's peak memory, frees the
   deployment, and compares every answer with its reference
   (``refs/<query>.py``, numpy over the raw tables);
6. prints the comparison as its last lines on standard error and, as
   the last line of standard output, one JSON object with ``correct``,
   ``attempted``, ``failed``, ``metrics``, ``device`` and, last,
   ``checks``.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer ones, each computed by ``metrics/<name>.py`` from the run's
record; the traced run also records a ``jax.profiler`` trace of the
window (``trace_reduce.py``) and ``repro.obs`` spans.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Callable, Dict, List, Optional, Union  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")
# an answer due in the window may come this long after it closes
LATE_WAIT_S = 60.0

sys.path.insert(0, HERE)
import check  # noqa: E402
import loadgen  # noqa: E402
import trace_reduce  # noqa: E402


class NoChip(RuntimeError):
    pass


def _module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_config(name: str) -> Dict:
    return load_json(HERE, "configs", f"{name}.json")


def cell_metrics(spec: Dict, cell: str, trace: bool) -> List[str]:
    """Names of the metrics this run reports: the cell's end-to-end
    metrics, or with ``trace`` its per-layer ones."""
    e2e = [m["name"] for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    return [m["name"] for m in spec["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in e2e else [])]


def require_chip(chips: int) -> List:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


class XlaCompiles:
    """Backend compiles of this process, from jax.monitoring events; a
    program loaded from the persistent cache is not a compile."""

    def __init__(self) -> None:
        import jax

        self.count = 0
        self.cache_hits = 0
        hit = threading.local()

        def on_duration(event: str, secs: float, **_) -> None:
            if event != "/jax/core/compile/backend_compile_duration":
                return
            if getattr(hit, "pending", False):
                hit.pending = False
            else:
                self.count += 1

        def on_event(event: str, **_) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
                hit.pending = True

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@dataclass
class Request:
    text: int  # index into Traffic.texts
    due: float  # perf_counter seconds
    done: float = 0.0
    answer: Optional[Dict] = None
    error: Optional[str] = None


@dataclass
class RunRecord:
    """What the metric readers under ``metrics/`` read."""

    setup_s: float
    window_s: float
    latencies_s: List[float]
    xla_compiles: int
    counters: Dict = field(default_factory=dict)
    spans: List = field(default_factory=list)
    trace: Optional[Dict] = None


# ----------------------------------------------------------------------
# deployment
# ----------------------------------------------------------------------
def make_data(cfg: Dict, seed: int) -> Dict:
    """The configuration's tables for ``seed``.

    The rows come from the configuration's fixed ``data_seed``; ``seed``
    then permutes each column listed under ``shuffled`` across its rows.
    Those are columns no query filters, joins or sorts on before it
    aggregates, so every seed gives every query the same row counts,
    chunk by chunk, and so the same compiled programs, while the
    answers differ from seed to seed.  (Rows drawn from ``seed`` itself
    gave each run some 570 programs of new shapes to compile: 20 min of
    set-up on the chip.)"""
    gen = importlib.import_module(cfg["generator"])
    tables = gen.generate(float(cfg["scale_factor"]), int(cfg["data_seed"]))
    rng = np.random.default_rng(seed)
    for table, columns in cfg["shuffled"].items():
        for c in columns:
            col = tables[table][c]
            tables[table][c] = col[rng.permutation(col.shape[0])]
    return tables


def build(cfg: Dict, tables: Dict) -> Dict:
    """The scope ``serve.Executor`` serves, built by the engine's own
    loader for the configuration's layout."""
    from repro.data import tpch

    layout = cfg["layout"]
    if layout["kind"] != "store":
        raise ValueError(f"unknown layout {layout['kind']!r}")
    return tpch.as_store(tables, chunk_rows=int(layout["chunk_rows"]),
                         sort_fact_by_date=bool(layout["sort_fact_by_date"]))


# ----------------------------------------------------------------------
# the window
# ----------------------------------------------------------------------
def closed_loop(ex, traffic, rng, seconds: float) -> tuple:
    """One client, whole rounds; the window ends at the first round
    boundary after ``seconds``."""
    out: List[Request] = []
    t0 = time.perf_counter()
    for order in traffic.rounds(rng):
        for i in order:
            r = Request(i, time.perf_counter())
            try:
                fut = ex.submit(traffic.texts[i].sql)
                r.answer = fut.result(timeout=seconds + LATE_WAIT_S).to_dict()
            except Exception as e:  # counted as missing, never dropped
                r.error = f"{type(e).__name__}: {e}"
            r.done = time.perf_counter()
            out.append(r)
        if time.perf_counter() - t0 >= seconds:
            break
    return out, t0, time.perf_counter()


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    return s[len(s) // 2] if s else 0.0


def warm_up(ex, traffic) -> None:
    """Every text twice in turn: the second pass runs what only repeats
    take (cached plans, cached join verdicts).  A text that fails here
    stays in the mix: the window counts its failures."""
    for k in range(2):
        for t in traffic.texts:
            t0 = time.perf_counter()
            try:
                ex.submit(t.sql).result().to_dict()
            except Exception as e:
                log(f"[setup] warm-up {t.name} failed: {type(e).__name__}: {str(e)[:300]}")
            log(f"[setup] warm-up pass {k + 1} {t.name} {t.params}: "
                f"{time.perf_counter() - t0:.1f} s")


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------
def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def peak_bytes(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices]
    return max(peaks) if peaks else 0


def run(workload: Union[str, Dict], seed: int, seconds: float, trace: bool, *,
        engine: Optional[Dict] = None,
        device_check: Callable[[int], List] = require_chip) -> Dict:
    """One run of ``workload``, the name of a cell in ``BENCHMARK.json``
    or a cell's entry itself (a configuration and a mix that are not a
    cell yet); returns the result object.  ``engine`` overrides
    ``repro.core.config.CONFIG`` fields after the configuration's own
    (the control uses it); ``device_check`` is the look for a chip."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cell = workload
    if not isinstance(cell, dict):
        cell = next((w for w in spec["workloads"] if w["name"] == workload), None)
        if cell is None:
            raise SystemExit(f"unknown workload {workload!r}")
    cfg = load_config(cell["config"])
    wanted = cell_metrics(spec, cell["name"], trace)

    # the compile cache lives in the checkout, whatever the machine sets
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    devices = device_check(int(cell["chips"]))
    import jax

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compile_cache, obs
    from repro.core.config import CONFIG
    from repro.serve import Executor

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    compile_cache.enable()
    xla = XlaCompiles()
    obs.metrics.load_engine_groups()
    for k, v in {**cfg.get("engine", {}), **(engine or {})}.items():
        setattr(CONFIG, k, v)

    t = time.perf_counter()
    tables = make_data(cfg, seed)
    log(f"[setup] generated SF {cfg['scale_factor']} in {time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    scope = build(cfg, tables)
    log(f"[setup] built {cfg['layout']['kind']} in {time.perf_counter() - t:.1f} s")
    traffic, rng = loadgen.load(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"), seed)

    ex = Executor(scope)
    try:
        t, c0 = time.perf_counter(), xla.count
        warm_up(ex, traffic)
        log(f"[setup] warmed {len(traffic.texts)} texts in {time.perf_counter() - t:.1f} s, "
            f"{xla.count - c0} XLA compiles, {xla.cache_hits} cache loads so far")

        if trace:
            CONFIG.tracing = "on"
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        obs.trace.clear()
        mark = obs.trace.mark_ns()
        before = obs.metrics.snapshot()
        compiles0 = xla.count
        setup_s = time.perf_counter() - T_PROCESS
        window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW) if trace else contextlib.nullcontext()
        with window:
            requests, t0, t1 = closed_loop(ex, traffic, rng, seconds)
        compiles = xla.count - compiles0
        counters = obs.metrics.diff(before, obs.metrics.snapshot())
        spans = obs.trace.spans(since_ns=mark)
        summary = None
        if trace:
            jax.profiler.stop_trace()
            CONFIG.tracing = "off"
            inflight = [(r.due - t0, (r.done or t1) - t0, traffic.texts[r.text].name)
                        for r in requests]
            files = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"), recursive=True)
            if files:
                summary = trace_reduce.reduce_file(files[0], inflight=inflight)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        peak = peak_bytes(devices)
    finally:
        ex.close()
    del ex, scope
    gc.collect()

    # ---- the comparison, after the window and with the deployment freed
    refs: Dict[int, tuple] = {}  # text index -> (reference answer, ORDER BY)
    wrong, missing, gap, checked = 0, 0, 0.0, 0
    first_fault = None
    for r in requests:
        text = traffic.texts[r.text]
        if r.answer is None:
            missing += 1
            first_fault = first_fault or f"{text.name}: {r.error}"
            continue
        if r.text not in refs:
            mod = _module("refs", text.ref)
            refs[r.text] = (mod.answer(tables, **text.params), mod.ORDER)
        want, order = refs[r.text]
        fault, g = check.compare(r.answer, want, order)
        checked += 1
        if fault:
            wrong += 1
            first_fault = first_fault or f"{text.name} {text.params}: {fault}"
        else:
            gap = max(gap, g)
    limit = float(cfg["limits"]["max_rel_err"])
    checks = {
        "wrong_answers": {"value": wrong, "limit": 0},
        "missing_answers": {"value": missing, "limit": 0},
        "max_rel_err": {"value": gap, "limit": limit},
    }
    correct = checked > 0 and wrong == 0 and missing == 0 and gap <= limit

    done = [r for r in requests if r.answer is not None]
    record = RunRecord(
        setup_s=setup_s,
        window_s=t1 - t0,
        latencies_s=[r.done - r.due for r in done],
        xla_compiles=compiles,
        counters=counters,
        spans=spans,
        trace=summary,
    )
    metrics = {}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    for name in wanted:
        value = _module("metrics", name).read(record)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    result = {
        "correct": bool(correct),
        "attempted": len(requests),
        "failed": missing,
        "metrics": metrics,
        "device": device,
    }
    if trace and summary:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    by_name: Dict[str, List[float]] = {}
    for r in done:
        by_name.setdefault(traffic.texts[r.text].name, []).append(r.done - r.due)
    for name, lat in sorted(by_name.items()):
        log(f"[window] {name}: {len(lat)} answers, median {1e3 * _median(lat):.1f} ms")
    log(f"[window] {len(requests)} requests in {t1 - t0:.1f} s, {compiles} XLA compiles, "
        f"peak {peak} bytes")
    if first_fault:
        log(f"[check] first fault: {first_fault}")
    log(f"[check] answers checked: {checked}")
    for name, c in checks.items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"[device] {e}; this benchmark runs on the chip only")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
