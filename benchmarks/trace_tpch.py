"""Capture a Chrome/Perfetto trace of TPC-H queries.

    PYTHONPATH=src python -m benchmarks.trace_tpch \
        [--queries q1,q3,q9] [--sf 0.01] [--out trace.json] [--analyze]

Runs each query twice — once to warm caches, once traced under
``CONFIG.tracing="on"`` — and writes every recorded span as a Chrome
``trace_event`` JSON document (open in ``chrome://tracing`` or
https://ui.perfetto.dev).  ``--analyze`` additionally prints each
query's EXPLAIN ANALYZE tree (per-operator wall time, row counts,
join-algorithm choices).

This is the CI bench-smoke job's ``obs-trace`` artifact producer.
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", default="q1,q3,q9")
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--out", default="trace.json", metavar="PATH")
    ap.add_argument(
        "--analyze",
        action="store_true",
        help="also print each query's EXPLAIN ANALYZE tree",
    )
    args = ap.parse_args()

    from repro import compile_cache, obs, sql
    from repro.core.config import CONFIG
    from repro.queries.tpch_sql import sql_text

    from .common import tpch_frames

    compile_cache.enable()
    frames = tpch_frames(args.sf)
    qnames = [q.strip() for q in args.queries.split(",") if q.strip()]

    texts = {q: sql_text(q, args.sf) for q in qnames}
    for q in qnames:
        sql.execute(texts[q], frames)  # warm: caches + jit out of the trace

    obs.clear_trace()
    saved = CONFIG.tracing
    CONFIG.tracing = "on"
    try:
        for q in qnames:
            t0 = time.perf_counter()
            with obs.span("query", query=q):
                out = sql.execute(texts[q], frames)
            dt = (time.perf_counter() - t0) * 1e3
            print(f"# {q}: {out.nrows} row(s) in {dt:.1f}ms", flush=True)
            if args.analyze:
                print(
                    sql.execute(texts[q], frames, explain="analyze"),
                    flush=True,
                )
    finally:
        CONFIG.tracing = saved

    n = obs.export_chrome_trace(args.out)
    print(f"# wrote {n} trace events to {args.out}", flush=True)


if __name__ == "__main__":
    main()
