import json
from pathlib import Path

import numpy as np
import pytest

from harness import trace

FIX = Path(__file__).parent / "fixtures" / "small_trace.json"


def test_reduce_small_recorded_trace():
    rec = json.loads(FIX.read_text())
    out = trace.reduce(rec)
    # window [2000, 13000]; ops clipped to it: [2000,4000] (merged),
    # [6000,7000], [9000,9500], [12000,13000]
    assert out["window_s"] == pytest.approx(11000e-9)
    assert out["busy_s"] == pytest.approx((2000 + 1000 + 500 + 1000) * 1e-9)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(2500e-9)]
    assert [n for n, _ in out["device_ops"]] == ["fusion.1", "fusion.2", "copy.3"]
    # gaps: [4000,6000] under bench.factor, [7000,9000] mostly bench.factor
    # (1500 of 2000), [9500,12000] under bench.score
    assert out["idle_gaps"][0] == ["bench.score", pytest.approx(2500e-9)]
    assert sorted(out["idle_gaps"][1:]) == [["bench.factor", pytest.approx(2000e-9)],
                                            ["bench.factor", pytest.approx(2000e-9)]]


def test_gap_is_named_by_the_innermost_span():
    rec = json.loads(FIX.read_text())
    rec["host"].append(["bench.rescore", 3000, 13000])    # holds both spans
    out = trace.reduce(rec)
    # [4000,6000] lies in bench.factor and [9500,12000] in bench.score;
    # [7000,9000] straddles the two, so only the outer span holds it all
    assert sorted(out["idle_gaps"]) == [["bench.factor", pytest.approx(2e-6)],
                                        ["bench.rescore", pytest.approx(2e-6)],
                                        ["bench.score", pytest.approx(2.5e-6)]]


def test_union_merges_overlaps_and_keeps_order():
    iv = np.array([[5, 7], [1, 3], [2, 4], [7, 8], [10, 11]])
    assert trace._union(iv).tolist() == [[1, 4], [5, 8], [10, 11]]


def test_reduce_without_device_or_window_is_empty():
    rec = json.loads(FIX.read_text())
    assert trace.reduce({"devices": [], "host": rec["host"]}) == {}
    assert trace.reduce({"devices": rec["devices"], "host": []}) == {}


def test_idle_reader_gives_nothing_without_a_trace():
    from harness.readers import idle_pct

    class Ctx:
        trace = {}
    assert idle_pct(Ctx()) is None
    Ctx.trace = {"window_s": 2.0, "busy_s": 0.5}
    assert idle_pct(Ctx()) == pytest.approx(75.0)


def test_reduce_recorded_v5e_trace():
    rec = json.loads((FIX.parent / "v5e_trace.json").read_text())
    out = trace.reduce(rec)
    (w0, w1), = [(s, e) for n, s, e in rec["host"] if n == "bench.window"]
    assert out["window_s"] == pytest.approx((w1 - w0) / 1e9)
    # busy: a plain sweep over the clipped intervals
    iv = sorted((max(s, w0), min(e, w1)) for _, s, e in rec["devices"][0]["ops"]
                if min(e, w1) > max(s, w0))
    busy, end = 0, None
    for s, e in iv:
        if end is None or s > end:
            busy, end = busy + e - s, e
        elif e > end:
            busy, end = busy + e - end, e
    assert out["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < out["busy_s"] < out["window_s"]
    # the matmul fusion leads; the while loop holding it is not counted
    assert out["device_ops"][0][0] == "jit_f:fusion.8"
    assert all(not n.endswith(":while") for n, _ in out["device_ops"])
    # the host slept 10 ms between steps: the longest gaps are those
    assert all(g >= 1e-6 for _, g in out["idle_gaps"])
    assert out["idle_gaps"][0][1] > 9e-3 and out["idle_gaps"][1][1] > 9e-3
