import numpy as np
import pytest

from repro.core import TensorFrame, col, d, if_else, lit, udf
from repro.core import oracle as orc


def sample_data(n=100, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "k": rng.integers(0, 10, n),
        "v": rng.normal(size=n),
        "cat": rng.choice(["red", "green", "blue"], n).astype(object),
        "name": np.array([f"name_{i:05d}" for i in rng.integers(0, n * 2, n)], dtype=object),
        "dt": np.datetime64("1994-01-01") + rng.integers(0, 1000, n).astype("timedelta64[D]"),
        "flag": rng.integers(0, 2, n).astype(bool),
    }


def test_construction_and_encoding():
    data = sample_data(200)
    f = TensorFrame.from_arrays(data)
    # low-cardinality 'cat' is dict-encoded into the int tensor
    assert f.meta("cat").kind == "dict"
    # high-cardinality 'name' is offloaded
    assert f.meta("name").kind == "obj"
    assert f.meta("dt").kind == "date"
    assert f.meta("flag").kind == "bool"
    assert f.nrows == 200
    np.testing.assert_array_equal(f.column("k"), data["k"])
    np.testing.assert_allclose(f.column("v"), data["v"])
    assert list(f.column("cat")) == list(data["cat"])
    assert list(f.column("name")) == list(data["name"])
    np.testing.assert_array_equal(f.column("dt"), data["dt"].astype("datetime64[D]"))


def test_filter_numeric_and_string():
    data = sample_data(300)
    f = TensorFrame.from_arrays(data)
    out = f.filter((col("k") >= 5) & (col("cat") == "red"))
    mask = (data["k"] >= 5) & (data["cat"] == "red")
    assert out.nrows == int(mask.sum())
    np.testing.assert_array_equal(out.column("k"), data["k"][mask])
    assert list(out.column("cat")) == list(data["cat"][mask])
    # offloaded column follows via its row indexer
    assert list(out.column("name")) == list(data["name"][mask])


def test_filter_string_ops():
    names = np.array(["alpha", "beta", "alphabet", "gamma", "beta-x"], dtype=object)
    f = TensorFrame.from_arrays({"s": names, "i": np.arange(5)}, encode={"s": "dict"})
    assert f.filter(col("s").str.startswith("alpha")).nrows == 2
    assert f.filter(col("s").str.contains("bet")).nrows == 3
    assert f.filter(col("s").str.like("%a")).nrows == 3  # alpha, gamma... and?
    out = f.filter(col("s").str.like("alpha%"))
    assert set(out.column("s")) == {"alpha", "alphabet"}


def test_string_order_comparisons():
    vals = np.array(["b", "a", "c", "b", "d"], dtype=object)
    f = TensorFrame.from_arrays({"s": vals}, encode={"s": "dict"})
    assert f.filter(col("s") < "c").nrows == 3
    assert f.filter(col("s") >= "b").nrows == 4
    assert f.filter(col("s") != "b").nrows == 3


def test_dates_and_year():
    data = sample_data(50)
    f = TensorFrame.from_arrays(data)
    out = f.filter((col("dt") >= d("1995-01-01")) & (col("dt") < d("1996-01-01")))
    expect = (data["dt"] >= np.datetime64("1995-01-01")) & (
        data["dt"] < np.datetime64("1996-01-01")
    )
    assert out.nrows == int(expect.sum())
    years = f.with_column("y", col("dt").dt.year()).column("y")
    np.testing.assert_array_equal(
        years, data["dt"].astype("datetime64[Y]").astype(int) + 1970
    )


def test_with_column_and_arith():
    data = sample_data(80)
    f = TensorFrame.from_arrays(data)
    f2 = f.with_column("x", col("k") * 2 + 1).with_column("r", col("v") / (col("k") + 1))
    np.testing.assert_array_equal(f2.column("x"), data["k"] * 2 + 1)
    np.testing.assert_allclose(f2.column("r"), data["v"] / (data["k"] + 1))
    f3 = f.with_column("c", if_else(col("v") > 0, col("v"), lit(0.0)))
    np.testing.assert_allclose(f3.column("c"), np.where(data["v"] > 0, data["v"], 0.0))


def test_stateless_udf():
    data = sample_data(60)
    f = TensorFrame.from_arrays(data)
    import jax.numpy as jnp

    # the paper's Fig. 4 cyclical-feature UDF
    e = udf(
        lambda x, c: jnp.sin(2 * jnp.pi * x) > jnp.cos(2 * jnp.pi * c),
        col("v"),
        col("k"),
        returns="bool",
    )
    out = f.filter(e)
    expect = np.sin(2 * np.pi * data["v"]) > np.cos(2 * np.pi * data["k"])
    assert out.nrows == int(expect.sum())


def test_groupby_against_oracle():
    data = sample_data(500, seed=3)
    f = TensorFrame.from_arrays(data)
    specs = [
        ("total", "sum", "v"),
        ("cnt", "size", ""),
        ("kmax", "max", "k"),
        ("nuniq", "nunique", "k"),
        ("avg", "mean", "v"),
    ]
    got = f.groupby(["cat", "flag"]).agg(specs)
    odf = orc.from_numpy(data)
    expect = orc.o_groupby(odf, ["cat", "flag"], specs)
    orc.assert_odf_equal(
        orc.frame_to_odf(got.select(["cat", "flag", "total", "cnt", "kmax", "nuniq", "avg"])),
        expect,
    )


def test_groupby_multikey_with_offloaded():
    data = sample_data(400, seed=4)
    f = TensorFrame.from_arrays(data)
    specs = [("n", "size", ""), ("s", "sum", "k")]
    got = f.groupby(["name", "cat"]).agg(specs)
    odf = orc.from_numpy(data)
    expect = orc.o_groupby(odf, ["name", "cat"], specs)
    orc.assert_odf_equal(orc.frame_to_odf(got), expect)


def test_join_inner_against_oracle():
    rng = np.random.default_rng(7)
    left = {
        "id": rng.integers(0, 50, 200),
        "lv": rng.normal(size=200),
        "cat": rng.choice(["x", "y"], 200).astype(object),
    }
    right = {
        "id": np.arange(50),
        "rv": rng.normal(size=50),
        "tag": rng.choice(["a", "b", "c"], 50).astype(object),
    }
    fl = TensorFrame.from_arrays(left)
    fr = TensorFrame.from_arrays(right)
    got = fl.join(fr, on="id")
    expect = orc.o_join(orc.from_numpy(left), orc.from_numpy(right), ["id"], ["id"])
    orc.assert_odf_equal(orc.frame_to_odf(got), expect)


def test_join_many_to_many():
    left = {"k": np.array([1, 1, 2, 3]), "a": np.array([10, 11, 12, 13])}
    right = {"k": np.array([1, 1, 3, 4]), "b": np.array([100, 101, 102, 103])}
    fl, fr = TensorFrame.from_arrays(left), TensorFrame.from_arrays(right)
    got = fl.join(fr, on="k")
    expect = orc.o_join(orc.from_numpy(left), orc.from_numpy(right), ["k"], ["k"])
    orc.assert_odf_equal(orc.frame_to_odf(got), expect)
    # sort-merge gives identical rows
    got_sm = fl.join(fr, on="k", algorithm="sortmerge")
    orc.assert_odf_equal(orc.frame_to_odf(got_sm), expect)


def test_join_left_semi_anti():
    rng = np.random.default_rng(11)
    left = {
        "k": rng.integers(0, 30, 100),
        "lv": rng.integers(0, 5, 100),
        "s": rng.choice(["p", "q", "r"], 100).astype(object),
    }
    right = {"k": rng.choice(np.arange(40), 20, replace=False), "rv": rng.normal(size=20)}
    fl, fr = TensorFrame.from_arrays(left), TensorFrame.from_arrays(right)
    ol, orr = orc.from_numpy(left), orc.from_numpy(right)
    for how in ("left", "semi", "anti"):
        got = fl.join(fr, on="k", how=how)
        expect = orc.o_join(ol, orr, ["k"], ["k"], how=how)
        orc.assert_odf_equal(orc.frame_to_odf(got), expect)


def test_left_join_count_nulls():
    # TPC-H Q13 shape: count(col) must skip nulls from the outer join
    left = {"c": np.array([1, 2, 3, 4])}
    right = {"c": np.array([1, 1, 3]), "o": np.array([10, 11, 12])}
    fl, fr = TensorFrame.from_arrays(left), TensorFrame.from_arrays(right)
    j = fl.join(fr, on="c", how="left")
    got = j.groupby("c").agg([("cnt", "count", "o")]).sort_values("c")
    np.testing.assert_array_equal(got.column("c"), [1, 2, 3, 4])
    np.testing.assert_array_equal(got.column("cnt"), [2, 0, 1, 0])


def test_multikey_string_join():
    rng = np.random.default_rng(13)
    left = {
        "a": rng.choice(["u", "v", "w"], 60).astype(object),
        "b": rng.integers(0, 4, 60),
        "x": rng.normal(size=60),
    }
    right = {
        "a": np.array(["u", "u", "v", "w", "z"], dtype=object),
        "b": np.array([0, 1, 2, 3, 0]),
        "y": np.arange(5) * 1.5,
    }
    fl, fr = TensorFrame.from_arrays(left), TensorFrame.from_arrays(right)
    got = fl.join(fr, on=["a", "b"])
    expect = orc.o_join(orc.from_numpy(left), orc.from_numpy(right), ["a", "b"], ["a", "b"])
    orc.assert_odf_equal(orc.frame_to_odf(got), expect)


def test_sort_values():
    data = sample_data(150, seed=9)
    f = TensorFrame.from_arrays(data)
    got = f.sort_values(["cat", "k"], ascending=[True, False])
    odf = orc.from_numpy(data)
    expect = orc.o_sort(odf, ["cat", "k"], [True, False])
    ga = orc.frame_to_odf(got.select(["cat", "k"]))
    assert ga["cat"] == expect["cat"]
    assert ga["k"] == expect["k"]


def test_head_select_rename():
    f = TensorFrame.from_arrays(sample_data(30))
    assert f.head(7).nrows == 7
    s = f.select(["k", "cat"])
    assert s.column_names == ["k", "cat"]
    r = f.rename({"k": "kk"})
    assert "kk" in r.column_names and "k" not in r.column_names


def test_full_frame_agg():
    data = sample_data(100)
    f = TensorFrame.from_arrays(data)
    out = f.agg([("s", "sum", "v"), ("c", "size", ""), ("m", "mean", "k")])
    assert out["s"] == pytest.approx(float(data["v"].sum()))
    assert out["c"] == 100
    assert out["m"] == pytest.approx(float(data["k"].mean()))


def test_exists_before_udf():
    comments = np.array(
        [
            "nothing interesting here",
            "a special package of requests arrived",
            "requests before special do not count",
            "special but no r-word",
            "very special, many requests!",
        ],
        dtype=object,
    )
    f = TensorFrame.from_arrays({"c": comments}, encode={"c": "dict"})
    hit = f.filter(col("c").str.exists_before("special", "requests"))
    assert hit.nrows == 2
    miss = f.filter(col("c").str.not_exists_before("special", "requests"))
    assert miss.nrows == 3


@pytest.mark.parametrize("span", [1 << 40, 1 << 61])
def test_sort_values_wide_keys_match_lexsort(span):
    """Key spans whose product overflows one packed int64 code: the codes
    are densified first, and the order stays that of a stable lexsort."""
    rng = np.random.default_rng(span % 97)
    a = rng.choice(rng.integers(0, span, 5), 300)  # wide, few values: ties
    b = rng.integers(0, span, 300)
    f = TensorFrame.from_arrays({"a": a, "b": b, "row": np.arange(300)})
    got = f.sort_values(["a", "b"], ascending=[True, False])
    want = np.lexsort((-b, a))
    np.testing.assert_array_equal(np.asarray(got.column("row")), want)
