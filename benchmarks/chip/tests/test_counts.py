import numpy as np

from harness import counts


def test_rescore_bytes_counts_keys_features_and_two_outputs():
    cfg = {"group_by": "a",
           "tables": [{"name": "a", "keys": ["k"], "features": ["x", "y"]},
                      {"name": "b", "keys": ["k"], "features": ["z"]}]}
    data = {"a": {"k": np.zeros(10), "x": np.zeros(10), "y": np.zeros(10)},
            "b": {"k": np.zeros(4), "z": np.zeros(4)}}
    # a: 10 rows x 3 columns x 4 B; b: 4 x 2 x 4; out: 10 x 2 x 4
    assert counts.rescore_bytes(cfg, data) == 120 + 32 + 80


def test_rescore_bytes_of_tpch_star_full_size():
    import json
    from harness import core

    cfg = json.loads((core.BENCH / "configs" / "tpch_star.json").read_text())
    rows = cfg["rows"]
    data = {t["name"]: {c: np.zeros(rows[t["name"]])
                        for c in t["keys"] + t["features"]} for t in cfg["tables"]}
    want = 4 * (rows["lineitem"] * 14 + rows["orders"] * 6 + rows["part"] * 7
                + rows["supplier"] * 3) + 8 * rows["lineitem"]
    assert counts.rescore_bytes(cfg, data) == want
    assert 70e6 < want < 80e6              # about 74 MB
