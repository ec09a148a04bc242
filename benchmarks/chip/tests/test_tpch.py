import json

import numpy as np
import pytest

from harness import core, tpch
from reference import join


def config(name):
    return json.loads((core.BENCH / "configs" / f"{name}.json").read_text())


# the snowflake's tables, for the generator's CUSTOMER, NATION and REGION
SNOW = {"tables": [
    {"name": "lineitem", "keys": ["orderkey", "partkey"], "features": ["l_quantity"]},
    {"name": "orders", "keys": ["orderkey", "custkey"], "features": ["o_orderdate"]},
    {"name": "customer", "keys": ["custkey", "nationkey"], "features": ["c_mktsegment"]},
    {"name": "nation", "keys": ["nationkey", "regionkey"], "features": ["n_name"]},
    {"name": "region", "keys": ["regionkey"], "features": ["r_name"]},
    {"name": "part", "keys": ["partkey"], "features": ["p_size"]}],
    "label": ["lineitem", "revenue"], "group_by": "lineitem"}
SNOW_ROWS = {"lineitem": 4096, "orders": 1024, "customer": 256, "nation": 25,
             "region": 5, "part": 137}


@pytest.mark.parametrize("rows_key", ["rows", "train_rows"])
def test_cardinalities_are_the_configured_rows(rows_key):
    cfg = config("tpch_star")
    data = tpch.generate(cfg, 2 ** 40 + 3, cfg[rows_key])
    for t in cfg["tables"]:
        n = {len(v) for v in data[t["name"]].values()}
        assert n == {cfg[rows_key][t["name"]]}, t["name"]


@pytest.mark.parametrize("rows_key,sf_key", [("rows", "scale_factor"),
                                             ("train_rows", "train_scale_factor")])
def test_star_rows_follow_the_scale_factor(rows_key, sf_key):
    cfg = config("tpch_star")
    sf, rows = cfg[sf_key], cfg[rows_key]
    assert abs(rows["orders"] - 1_500_000 * sf) < 1
    assert abs(rows["part"] - 200_000 * sf) < 1
    assert abs(rows["supplier"] - 10_000 * sf) < 1
    assert rows["orders"] * 4 == rows["lineitem"]
    assert cfg["rows"]["lineitem"] == 2 ** 20


def test_every_splittable_column_is_a_feature():
    cfg = config("tpch_star")
    d = tpch.generate(cfg, 5, {t: max(5, n // 64) for t, n in cfg["rows"].items()})
    feats = {c for t in cfg["tables"] for c in t["features"]}
    keys = {c for t in cfg["tables"] for c in t["keys"]}
    made = {c for t in d.values() for c in t}
    assert made == feats | keys | {"revenue"}
    assert len(feats) == 24 and "l_extendedprice" not in made


def test_columns_follow_the_spec():
    cfg = config("tpch_star")
    d = tpch.generate(cfg, 11, {t: max(5, n // 64) for t, n in cfg["rows"].items()})
    li, o, p = d["lineitem"], d["orders"], d["part"]
    assert set(np.unique(np.round(li["l_discount"].astype(np.float64) * 100))) <= set(range(11))
    assert li["l_quantity"].min() >= 1 and li["l_quantity"].max() <= 50
    assert np.all(li["l_shipdate"] > 0) and np.all(li["l_receiptdate"] > li["l_shipdate"])
    assert np.all((o["orderkey"] - 1) % 32 < 8)           # sparse keys
    # each line's revenue is quantity * retailprice * (1 - discount)
    price = tpch.retail_price(li["partkey"])
    want = li["l_quantity"].astype(np.float64) * price * (1 - li["l_discount"].astype(np.float64))
    np.testing.assert_allclose(li["revenue"], want, rtol=1e-6)
    # flags and statuses follow the dates (A = 0, N = 1, R = 2; F = 0, O = 1, P = 2)
    received = li["l_receiptdate"] <= tpch.CURRENTDATE
    assert set(np.unique(li["l_returnflag"][received])) == {0, 2}
    assert np.all(li["l_returnflag"][~received] == 1)
    np.testing.assert_array_equal(li["l_linestatus"], li["l_shipdate"] > tpch.CURRENTDATE)
    row = {k: i for i, k in enumerate(o["orderkey"])}
    of_line = np.array([row[k] for k in li["orderkey"]])
    n_open = np.bincount(of_line, weights=li["l_linestatus"], minlength=len(row))
    n_line = np.bincount(of_line, minlength=len(row))
    want = np.where(n_open == 0, 0, np.where(n_open == n_line, 1, 2))
    np.testing.assert_array_equal(o["o_orderstatus"], want)
    assert set(np.unique(o["o_orderstatus"])) == {0, 1, 2}
    assert o["o_clerk"].min() >= 1 and o["o_clerk"].max() <= max(1, len(row) * 1000 // 1_500_000)
    assert li["l_shipinstruct"].max() <= 3 and li["l_shipmode"].max() <= 6
    np.testing.assert_array_equal(p["p_brand"] // 10, p["p_mfgr"])
    assert p["p_type"].max() < 150 and p["p_container"].max() < 40
    assert d["supplier"]["s_nationkey"].max() <= 24


def test_snowflake_tables():
    d = tpch.generate(SNOW, 3, SNOW_ROWS)
    for t in SNOW["tables"]:
        assert {len(v) for v in d[t["name"]].values()} == {SNOW_ROWS[t["name"]]}
    assert np.all(d["orders"]["custkey"] % 3 != 0)
    assert np.all(d["nation"]["regionkey"] == tpch.NATION_REGION)


@pytest.mark.parametrize("name", ["tpch_star", "snow"])
def test_same_shapes_for_every_seed_and_every_line_joins(name):
    cfg = SNOW if name == "snow" else config(name)
    rows = SNOW_ROWS if name == "snow" else {t: max(5, n // 128)
                                             for t, n in cfg["rows"].items()}
    for seed in (0, 1, 2 ** 33 + 1):
        d = tpch.generate(cfg, seed, rows)
        X, y, root = join.materialize(cfg, d)
        assert len(root) == rows["lineitem"] and len(np.unique(root)) == len(root)
        assert X.shape[1] == sum(len(t["features"]) for t in cfg["tables"])


def test_lines_per_order_hit_the_total_exactly():
    rng = np.random.default_rng(5)
    cnt = tpch.lines_per_order(rng, 1000, 4000)
    assert cnt.sum() == 4000 and cnt.min() >= 1 and cnt.max() <= 7

