"""The numpy references under refs/ against the engine's row-at-a-time
reference (repro.queries.tpch_numpy) at SF 0.01, on the validation
parameters the power mix sends."""
import json
import os

import numpy as np
import pytest

import check
import run
import tpch_gen
from repro.queries import tpch_numpy

POWER = json.load(open(os.path.join(run.HERE, "traffic", "power.json")))


@pytest.fixture(scope="module")
def tables():
    return tpch_gen.generate(0.01, 2**31 + 17)


def _columns(rows, like):
    """tpch_numpy rows (dates as day numbers) as columns typed like ours."""
    if isinstance(rows, dict):
        rows = [rows]
    out = {}
    for k, v in like.items():
        vals = [r[k] for r in rows]
        if np.issubdtype(v.dtype, np.datetime64):
            out[k] = np.array(vals, dtype=np.int64).astype("datetime64[D]")
        else:
            out[k] = np.array(vals, dtype=v.dtype)
    return out


@pytest.mark.parametrize("entry", POWER["queries"], ids=lambda e: e["name"])
def test_reference_matches_tpch_numpy(tables, entry):
    mod = run._module("refs", entry["ref"])
    got = mod.answer(tables, **entry["params"])
    want = _columns(tpch_numpy.ALL[entry["name"]](tables, sf=0.01), got)
    assert len(next(iter(got.values()))) > 0
    fault, gap = check.compare(got, want, mod.ORDER)
    assert fault is None, fault
    assert gap < 1e-12


def test_generator_is_deterministic_and_complete():
    a = tpch_gen.generate(0.002, 2**33 + 1)
    b = tpch_gen.generate(0.002, 2**33 + 1)
    assert sorted(a) == sorted(b) == [
        "customer", "lineitem", "nation", "orders", "part", "partsupp", "region", "supplier"]
    for t in a:
        for c in a[t]:
            assert np.array_equal(a[t][c], b[t][c]), (t, c)
    li = a["lineitem"]
    assert np.isin(li["l_orderkey"], a["orders"]["o_orderkey"]).all()
    assert (li["l_receiptdate"] > li["l_shipdate"]).all()


def test_generator_derives_what_clause_4_2_3_derives():
    t = tpch_gen.generate(0.005, 2**33 + 3)
    li, orders, part = t["lineitem"], t["orders"], t["part"]
    now = tpch_gen.CURRENTDATE
    open_ = li["l_shipdate"] > now
    assert (li["l_linestatus"] == np.where(open_, "O", "F")).all()
    received = li["l_receiptdate"] <= now
    assert np.isin(li["l_returnflag"][received], ["R", "A"]).all()
    assert (li["l_returnflag"][~received] == "N").all()
    # q1 groups over the spec's four keys
    assert {f + s for f, s in zip(li["l_returnflag"], li["l_linestatus"])} == {"AF", "NF", "NO", "RF"}
    pk = part["p_partkey"]
    assert np.allclose(part["p_retailprice"], (90000 + (pk // 10) % 20001 + 100 * (pk % 1000)) / 100)
    assert np.allclose(li["l_extendedprice"], li["l_quantity"] * part["p_retailprice"][li["l_partkey"] - 1])
    by_order = {}
    for k, s, p in zip(li["l_orderkey"], li["l_linestatus"],
                       li["l_extendedprice"] * (1 + li["l_tax"]) * (1 - li["l_discount"])):
        st, tot = by_order.get(k, (set(), 0.0))
        by_order[k] = (st | {s}, tot + p)
    for k, st, total in zip(orders["o_orderkey"], orders["o_orderstatus"], orders["o_totalprice"]):
        lines, want = by_order[k]
        assert st == ("F" if lines == {"F"} else "O" if lines == {"O"} else "P")
        assert abs(total - want) < 0.006
    assert (orders["o_custkey"] % 3 != 0).all()
    assert ((orders["o_orderkey"] - 1) % 32 < 8).all()
    ps = t["partsupp"]
    assert np.isin(li["l_partkey"] * 100_000 + li["l_suppkey"],
                   ps["ps_partkey"] * 100_000 + ps["ps_suppkey"]).all()
