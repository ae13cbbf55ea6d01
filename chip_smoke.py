#!/usr/bin/env python3
"""Smoke run of the SQL serving path over TPC-H on a TPU.

Run from the root of a checkout, with nothing else on the chip:

    python chip_smoke.py                # one chip, the phases below
    python chip_smoke.py --four-chips   # the sharded route on a 2x2 host

One process does everything, so the chip has one owner.  Phases on one
chip:

- ``served``: TPC-H SF 1 generated from ``--seed``, loaded into
  ``repro.store`` (fact tables date-clustered), and q1/q3/q4/q6/q14
  served through ``serve.Executor``, each cold and then warm.  q1, q6
  and q14 are checked against a vectorized numpy reference computed
  here from the raw tables.
- ``compiled``: q1/q3/q6/q14 through ``sql.execute`` over device-resident
  frames (the whole-plan compiled path); no compile may fail, and the
  answers must equal the served ones.
- ``breadth``: all 22 TPC-H queries served at SF 0.01 and compared with
  ``repro.queries.tpch_numpy``.

``--four-chips`` runs only this: SF 1 q1/q4/q6 served once with
``CONFIG.distributed="auto"`` over four devices, then once with
``"off"``, both without chunk streaming (the sharded routes live in
the eager operators) and with the routing threshold lowered so that
q4's semi-join probe is routed; the answers must agree, the
``repro.dist`` counters must show that the group-by sum route and the
semi/anti-join route were both taken, and devices 1-3 must have held
data.

Every query prints its cold and warm latency (until the result is on
the host), result rows, the path it took, compiled-path and XLA compile
counts and seconds, chunks streamed, and the plans that fell back with
the reason.  Any mismatch, exception or compile failure exits non-zero
with no ``ok`` line, and so does a run where JAX finds no TPU.  The last
line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import re
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SERVED = ("q1", "q3", "q4", "q6", "q14")
COMPILED = ("q1", "q3", "q6", "q14")
FOUR_CHIP = ("q1", "q4", "q6")
BREADTH_SF = 0.01

# Float64 sums of up to 6M positive terms, added in another order than
# numpy's: the relative error of each is below n * 2**-53 (about 7e-10
# at n = 6M), so 1e-9 separates summation order from a wrong answer.
SUM_RTOL = 1e-9
# Engine against tpch_numpy at SF 0.01: the bound tests/test_tpch_queries.py
# uses for the same comparison.
BREADTH_RTOL = 1e-8


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ----------------------------------------------------------------------
# counters
# ----------------------------------------------------------------------
class XlaCompiles:
    """XLA programs this process compiled or loaded from the persistent
    cache, from jax.monitoring events: compiles and their seconds, cache
    hits and their load seconds; and each program's name and seconds,
    from JAX's compile log."""

    def __init__(self) -> None:
        import threading

        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.load_seconds = 0.0
        # JAX records a cache hit inside the timed compile-or-load call
        # whose duration event follows it on the same thread
        hit = threading.local()
        self.named: list = []  # (seconds, program name)
        done = re.compile(r"Finished XLA compilation of (.+) in ([0-9.e-]+) sec")
        named = self.named

        class _Names(logging.Handler):
            def emit(self, record) -> None:
                m = done.match(record.getMessage())
                if m:
                    named.append((float(m.group(2)), m.group(1)))

        lg = logging.getLogger("jax._src.dispatch")
        lg.addHandler(_Names())
        lg.setLevel(logging.DEBUG)
        lg.propagate = False

        def on_duration(event: str, secs: float, **_) -> None:
            if event != "/jax/core/compile/backend_compile_duration":
                return
            if getattr(hit, "pending", False):
                hit.pending = False
                self.load_seconds += secs
            else:
                self.count += 1
                self.seconds += secs

        def on_event(event: str, **_) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
                hit.pending = True

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {
            "compiles": self.count,
            "compile_s": self.seconds,
            "cache_hits": self.cache_hits,
            "load_s": self.load_seconds,
        }

    def report(self, top: int = 15) -> None:
        by_name: dict = {}
        for secs, name in self.named:
            n, tot = by_name.get(name, (0, 0.0))
            by_name[name] = (n + 1, tot + secs)
        for secs, name in sorted(self.named, reverse=True)[:top]:
            log(f"[xla] slowest compile or load: {secs:.2f} s {name}")
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:top]
        for name, (n, tot) in ranked:
            log(f"[xla] by program: {tot:.2f} s over {n} compiles or loads of {name}")


def counters(xla: XlaCompiles) -> dict:
    from repro import obs

    obs.metrics.load_engine_groups()
    snap = obs.metrics.snapshot()
    c = snap["sql.compile"]
    own = snap.get("obs", {})
    return {
        "plan_compiles": c["compiles"],
        "plan_hits": c["hits"],
        "plan_fallbacks": c["fallbacks"],
        "compile_failures": c["compile_failures"],
        "plan_compile_s": sum(
            r["trace_s"] + r["compile_s"] for r in c["plans"].values()
        ),
        "fallback_reasons": c["fallback_reasons"],
        "chunks_streamed": snap["core.pipeline"]["chunks_streamed"],
        "dist_groupby_sum": own.get("dist.groupby_sum", 0),
        "dist_semi_join": own.get("dist.semi_join", 0),
        "xla": xla.snapshot(),
    }


def delta(before: dict, after: dict) -> dict:
    from repro import obs

    return obs.metrics.diff(before, after)


def path_of(d: dict) -> str:
    if d.get("chunks_streamed"):
        return "streamed"
    if d.get("plan_compiles") or d.get("plan_hits"):
        return "compiled"
    return "op-by-op"


def peak_bytes(dev=None) -> int:
    import jax

    stats = (dev or jax.devices()[0]).memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


# ----------------------------------------------------------------------
# running one query
# ----------------------------------------------------------------------
def host_result(frame) -> dict:
    from repro.core import oracle as orc

    return orc.frame_to_odf(frame)


def timed(run, xla: XlaCompiles):
    before = counters(xla)
    t0 = time.perf_counter()
    odf = host_result(run())
    secs = time.perf_counter() - t0
    return odf, secs, delta(before, counters(xla))


def run_once(label: str, qname: str, phase: str, run, xla: XlaCompiles):
    """Run a query once and print one line; returns (answer, seconds,
    counter deltas)."""
    odf, secs, d = timed(run, xla)
    check(
        not d.get("compile_failures"),
        f"{label} {qname} {phase}: compile failures {d}",
    )
    rows = len(next(iter(odf.values()))) if odf else 0
    x = d.get("xla", {})
    log(
        f"[{label}] {qname} {phase}: {secs:.3f} s, rows={rows}, "
        f"path={path_of(d)}, plan compiles={d.get('plan_compiles', 0)} "
        f"hits={d.get('plan_hits', 0)} fallbacks={d.get('plan_fallbacks', 0)} "
        f"plan_compile_s={d.get('plan_compile_s', 0.0):.3f}, "
        f"xla compiles={x.get('compiles', 0)} "
        f"xla_compile_s={x.get('compile_s', 0.0):.3f} "
        f"xla_cache_hits={x.get('cache_hits', 0)} "
        f"xla_load_s={x.get('load_s', 0.0):.3f}, "
        f"chunks_streamed={d.get('chunks_streamed', 0)}, "
        f"peak_bytes_in_use={peak_bytes()}"
        + (
            f", dist groupby_sum={d.get('dist_groupby_sum', 0)} "
            f"semi_join={d.get('dist_semi_join', 0)}"
            if d.get("dist_groupby_sum") or d.get("dist_semi_join")
            else ""
        )
    )
    for reason, n in d.get("fallback_reasons", {}).items():
        log(f"[{label}] {qname} {phase}: fallback x{n}: {reason}")
    return odf, secs, d


def cold_warm(label: str, qname: str, run, xla: XlaCompiles) -> dict:
    """Run a query cold then warm and print one line for each."""
    out = {
        phase: run_once(label, qname, phase, run, xla)
        for phase in ("cold", "warm")
    }
    check(
        _same(out["cold"][0], out["warm"][0], 0.0),
        f"{label} {qname}: warm answer differs from cold",
    )
    return out


# ----------------------------------------------------------------------
# comparing answers
# ----------------------------------------------------------------------
def _rows(odf: dict) -> list:
    cols = sorted(odf)
    rows = list(zip(*(odf[c] for c in cols)))

    def key(row):
        return tuple((v is None, round(v, 6) if isinstance(v, float) else v)
                     for v in row)

    return [cols] + sorted(rows, key=key)


def _same(a: dict, b: dict, rtol: float) -> bool:
    ra, rb = _rows(a), _rows(b)
    if ra[0] != rb[0] or len(ra) != len(rb):
        return False
    for ta, tb in zip(ra[1:], rb[1:]):
        for va, vb in zip(ta, tb):
            if isinstance(va, float) or isinstance(vb, float):
                if va is None or vb is None:
                    return False
                if abs(va - vb) > rtol * max(abs(va), abs(vb), 1.0):
                    return False
            elif va != vb:
                return False
    return True


def check_same(label: str, got: dict, want: dict, rtol: float) -> None:
    if not _same(got, want, rtol):
        ga, wa = _rows(got), _rows(want)
        raise SmokeFailure(
            f"{label}: answers differ (rtol {rtol})\n got  {ga[:4]}\n want {wa[:4]}"
        )
    log(f"[check] {label}: match (rtol {rtol})")


# ----------------------------------------------------------------------
# vectorized numpy reference for q1, q6, q14 (independent of repro.core)
# ----------------------------------------------------------------------
def _days(s: str) -> np.datetime64:
    return np.datetime64(s, "D")


def ref_q1(T) -> dict:
    li = T["lineitem"]
    m = li["l_shipdate"].astype("datetime64[D]") <= _days("1998-12-01") - 90
    rf = li["l_returnflag"][m].astype(str)
    ls = li["l_linestatus"][m].astype(str)
    groups, inv = np.unique(np.char.add(rf, ls), return_inverse=True)
    qty, price = li["l_quantity"][m], li["l_extendedprice"][m]
    disc, tax = li["l_discount"][m], li["l_tax"][m]
    disc_price = price * (1 - disc)

    def gsum(w):
        return np.bincount(inv, weights=w, minlength=len(groups))

    count = np.bincount(inv, minlength=len(groups))
    out = {
        "l_returnflag": [g[0] for g in groups],
        "l_linestatus": [g[1] for g in groups],
        "sum_qty": gsum(qty),
        "sum_base_price": gsum(price),
        "sum_disc_price": gsum(disc_price),
        "sum_charge": gsum(disc_price * (1 + tax)),
        "avg_qty": gsum(qty) / count,
        "avg_price": gsum(price) / count,
        "avg_disc": gsum(disc) / count,
        "count_order": count,
    }
    return {k: [v.item() if hasattr(v, "item") else v for v in vs]
            for k, vs in out.items()}


def ref_q6(T) -> dict:
    li = T["lineitem"]
    ship = li["l_shipdate"].astype("datetime64[D]")
    disc, qty = li["l_discount"], li["l_quantity"]
    m = (
        (ship >= _days("1994-01-01"))
        & (ship < _days("1994-01-01") + 365)
        & (disc >= 0.05)
        & (disc <= 0.07)
        & (qty < 24)
    )
    return {"revenue": [float(np.sum(li["l_extendedprice"][m] * disc[m]))]}


def ref_q14(T) -> dict:
    li, part = T["lineitem"], T["part"]
    ship = li["l_shipdate"].astype("datetime64[D]")
    m = (ship >= _days("1995-09-01")) & (ship < _days("1995-10-01"))
    order = np.argsort(part["p_partkey"])
    keys = part["p_partkey"][order]
    pos = np.searchsorted(keys, li["l_partkey"][m])
    hit = (pos < len(keys)) & (keys[np.minimum(pos, len(keys) - 1)] == li["l_partkey"][m])
    ptype = part["p_type"][order][np.minimum(pos, len(keys) - 1)].astype(str)
    rev = (li["l_extendedprice"][m] * (1 - li["l_discount"][m]))[hit]
    promo = np.char.startswith(ptype[hit], "PROMO")
    return {"promo_revenue": [float(100.0 * rev[promo].sum() / rev.sum())]}


REFERENCE = {"q1": ref_q1, "q6": ref_q6, "q14": ref_q14}


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------
def load_sf(sf: float, seed: int, *, frames: bool = False):
    from repro.data import tpch

    t0 = time.perf_counter()
    tables = tpch.generate(sf=sf, seed=seed)
    t1 = time.perf_counter()
    stores = tpch.as_store(tables, sort_fact_by_date=True)
    t2 = time.perf_counter()
    log(
        f"[load] SF {sf}: {len(tables['lineitem']['l_orderkey'])} lineitem rows, "
        f"generate {t1 - t0:.1f} s, store {t2 - t1:.1f} s"
    )
    return tables, stores


def phase_served(tables, stores, sf: float, xla) -> dict:
    from repro.queries.tpch_sql import sql_text
    from repro.serve import Executor

    answers = {}
    with Executor(stores) as ex:
        for q in SERVED:
            text = sql_text(q, sf)
            res = cold_warm("served", q, lambda: ex.execute(text), xla)
            answers[q] = res["warm"][0]
    for q, ref in REFERENCE.items():
        check_same(f"served SF {sf} {q} vs numpy reference", answers[q],
                   ref(tables), SUM_RTOL)
    return answers


def phase_compiled(tables, sf: float, served: dict, xla) -> None:
    from repro import sql
    from repro.data import tpch
    from repro.queries.tpch_sql import sql_text

    t0 = time.perf_counter()
    frames = tpch.as_frames(tables)
    log(f"[compiled] frames on device in {time.perf_counter() - t0:.1f} s")
    for q in COMPILED:
        text = sql_text(q, sf)
        res = cold_warm("compiled", q, lambda: sql.execute(text, frames), xla)
        check_same(f"compiled SF {sf} {q} vs served", res["warm"][0],
                   served[q], SUM_RTOL)


def phase_breadth(seed: int, xla) -> None:
    from repro.queries import tpch_numpy
    from repro.queries.tpch_sql import SCALAR_SQL, TPCH_SQL, sql_text
    from repro.serve import Executor

    tables, stores = load_sf(BREADTH_SF, seed)
    qnames = sorted(TPCH_SQL, key=lambda s: int(s[1:]))
    with Executor(stores) as ex:
        for q in qnames:
            text = sql_text(q, BREADTH_SF)
            odf, _, _ = run_once("breadth", q, "cold", lambda: ex.execute(text), xla)
            want = tpch_numpy.ALL[q](tables, sf=BREADTH_SF)
            if q in SCALAR_SQL:
                want = {k: [v] for k, v in want.items()}
            else:
                want = {k: [r[k] for r in want] for k in want[0]} if want else {}
            if not want:
                check(all(len(v) == 0 for v in odf.values()),
                      f"breadth {q}: expected an empty answer")
            else:
                check_same(f"breadth SF {BREADTH_SF} {q} vs tpch_numpy", odf,
                           want, BREADTH_RTOL)


def phase_four_chips(seed: int, sf: float, xla) -> None:
    import jax
    from repro.core.config import CONFIG
    from repro.queries.tpch_sql import sql_text
    from repro.serve import Executor

    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, saw {jax.devices()}")
    tables, stores = load_sf(sf, seed)
    # the sharded routes live in the eager operators (core/agg.py,
    # core/join.py); a streamed semi-join probes its own sorted build.
    # q4's semi-join probe is one quarter of orders (about 57k rows at
    # SF 1), under the default dist_min_rows of 2^16.
    CONFIG.out_of_core = "off"
    CONFIG.dist_min_rows = 1 << 12
    log("[dist] out_of_core=off, dist_min_rows=4096: every plan runs the "
        "eager operators, and q4's semi-join probe is routed")
    answers = {}
    try:
        for mode in ("auto", "off"):
            CONFIG.distributed = mode
            with Executor(stores) as ex:
                for q in FOUR_CHIP:
                    text = sql_text(q, sf)
                    answers[mode, q] = run_once(
                        f"dist={mode}", q, "cold", lambda: ex.execute(text), xla
                    )
    finally:
        CONFIG.distributed = "auto"
        CONFIG.out_of_core = "auto"
        CONFIG.dist_min_rows = 1 << 16
    routed = {"dist_groupby_sum": 0, "dist_semi_join": 0}
    for q in FOUR_CHIP:
        for k in routed:
            routed[k] += answers["auto", q][2].get(k, 0)
            check(not answers["off", q][2].get(k), f"dist=off {q} took a sharded route")
        check_same(f"four chips SF {sf} {q} routed vs unrouted",
                   answers["auto", q][0], answers["off", q][0], SUM_RTOL)
    log(f"[dist] routed calls with distributed=auto: {routed}")
    check(routed["dist_groupby_sum"] > 0, "group-by sum route never taken")
    check(routed["dist_semi_join"] > 0, "semi/anti-join route never taken")
    for q in REFERENCE:
        if q in FOUR_CHIP:
            check_same(f"four chips SF {sf} {q} vs numpy reference",
                       answers["auto", q][0], REFERENCE[q](tables), SUM_RTOL)
    peaks = [peak_bytes(d) for d in jax.devices()]
    log(f"[dist] peak_bytes_in_use per device: {peaks}")
    check(all(p > 0 for p in peaks[1:]), "devices 1-3 held nothing: all work stayed on device 0")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sf", type=float, default=1.0,
                    help="scale factor of the served and compiled phases")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-route phase on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    log(f"[device] {device}")
    if device["platform"] != "tpu":
        log("[device] JAX found no TPU; this smoke runs on the chip only")
        return 2

    from repro import compile_cache
    from repro.sql import compile as plan_compile

    log(f"[cache] persistent compilation cache: {compile_cache.enable()}")
    xla = XlaCompiles()
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            phase_four_chips(args.seed, args.sf, xla)
        else:
            tables, stores = load_sf(args.sf, args.seed)
            served = phase_served(tables, stores, args.sf, xla)
            log(f"[served] done at {time.perf_counter() - t0:.1f} s, "
                f"peak_bytes_in_use {peak_bytes()}")
            phase_compiled(tables, args.sf, served, xla)
            log(f"[compiled] done at {time.perf_counter() - t0:.1f} s, "
                f"peak_bytes_in_use {peak_bytes()}")
            del tables, stores
            phase_breadth(args.seed, xla)
            log(f"[breadth] done at {time.perf_counter() - t0:.1f} s")
    except Exception as e:
        traceback.print_exc()
        log(f"[fail] {type(e).__name__}: {e}")
        return 1
    finally:
        xla.report()
        for digest, reason in plan_compile.negative_plans().items():
            log(f"[negative-cache] plan {digest}: {reason}")
        end = counters(xla)
        log(
            f"[total] {time.perf_counter() - t0:.1f} s, plan compiles "
            f"{end['plan_compiles']} ({end['plan_compile_s']:.1f} s), "
            f"compile_failures {end['compile_failures']}, xla {end['xla']}, "
            f"peak_bytes_in_use {[peak_bytes(d) for d in devices]}"
        )
    if end["compile_failures"]:
        log("[fail] compile failures")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
