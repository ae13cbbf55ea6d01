"""Benchmark harness entry point: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only name,...]
                                            [--json out.json]
                                            [--compare baseline.json]

Prints ``name,us_per_call,derived`` CSV rows (see common.report);
``--json PATH`` additionally writes the rows as a JSON document (the CI
bench-smoke job uploads it as the ``BENCH_PR.json`` artifact), and
``--compare PATH`` gates the run against a committed baseline document
(exit 1 on any shared row slower than 2.5x — the CI regression gate;
the baseline refreshes from main pushes).
Default is quick mode (small scale factors) so the whole suite runs in
minutes on CPU; --full uses larger data.  A suite that raises prints a
``<suite>/SUITE_ERROR`` row, the remaining suites still run, and the
run exits 1.  JAX's persistent compilation cache is on
(``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--sf", type=float, default=None)
    ap.add_argument(
        "--json",
        default="",
        metavar="PATH",
        help="also write the result rows as a JSON document",
    )
    ap.add_argument(
        "--compare",
        default="",
        metavar="BASELINE",
        help="fail (exit 1) if any row regresses >2.5x vs this baseline JSON",
    )
    args = ap.parse_args()
    quick = not args.full
    sf = args.sf or (0.01 if quick else 0.05)

    from repro import compile_cache

    compile_cache.enable()

    from . import (
        bench_compile,
        bench_dist,
        bench_join,
        bench_loading,
        bench_memory,
        bench_operators,
        bench_roofline,
        bench_scaling,
        bench_serve,
        bench_spill,
        bench_sql,
        bench_store,
        bench_tpch,
        bench_tpcds,
    )

    suites = {
        "tpch": lambda: bench_tpch.run(sf=sf, quick=quick),
        "dist": lambda: bench_dist.run(quick=quick),
        "join": lambda: bench_join.run(sf=sf, quick=quick),
        "store": lambda: bench_store.run(sf=sf, quick=quick),
        "tpcds": lambda: bench_tpcds.run(sf=sf, quick=quick),
        "sql": lambda: bench_sql.run(sf=sf, quick=quick),
        "operators": lambda: bench_operators.run(sf=sf, quick=quick),
        "scaling": lambda: bench_scaling.run(quick=quick),
        "compile": lambda: bench_compile.run(sf=sf, quick=quick),
        "serve": lambda: bench_serve.run(sf=sf, quick=quick),
        "spill": lambda: bench_spill.run(sf=sf, quick=quick),
        "loading": lambda: bench_loading.run(sf=sf, quick=quick),
        "memory": lambda: bench_memory.run(sf=sf, quick=quick),
        "roofline": lambda: bench_roofline.run(quick=quick),
    }
    only = set(filter(None, args.only.split(",")))
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            print(f"{name}/SUITE_ERROR,0,{type(e).__name__}: {e}", flush=True)
            failed.append(name)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if args.json:
        from .common import write_json

        write_json(args.json)
    if args.compare:
        from .common import compare_baseline

        if not compare_baseline(args.compare):
            sys.exit(1)
    if failed:
        print(f"# suites raised: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
