"""The reduction by the program's own scopes (``harness.scopes``) and the
readers of the program's layer counters."""
import json
from pathlib import Path

import pytest

from harness import scopes, trace

FIX = Path(__file__).parent / "fixtures"


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(no: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(no << 3 | 2) + _varint(len(data)) + data


def _xspace() -> bytes:
    """A device plane whose one operation has ``tf_op`` (interned by
    reference) and ``program_id`` stats, and a host plane whose metadata
    must be passed over."""
    stat_meta = b"".join(
        _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, name)))
        for i, name in [(1, "tf_op"), (2, "program_id"),
                        (3, "jit(level_step)/boost.stats/boost.sketch/while/body/mul:")])
    op = _field(2, "%fusion.7 = f32[8]{0} fusion(%p)")
    op += _field(5, _field(1, 1) + _field(7, 3))          # tf_op by reference
    op += _field(5, _field(1, 2) + _field(3, 2 ** 63 + 5))  # program_id, uint64
    plain = _field(2, "%copy.1 = f32[8]{0} copy(%p)") + _field(5, _field(1, 2) + _field(3, 9))
    device = (_field(2, "/device:TPU:0") + _field(3, _field(2, "XLA Ops"))
              + _field(4, _field(1, 11) + _field(2, op))
              + _field(4, _field(1, 12) + _field(2, plain)) + stat_meta)
    host = _field(2, "/host:CPU") + _field(4, _field(1, 1) + _field(2, op)) + stat_meta
    return _field(1, device) + _field(1, host)


def test_op_names_read_from_event_metadata(tmp_path):
    path = tmp_path / "x.xplane.pb"
    path.write_bytes(_xspace())
    assert scopes.op_names(str(path)) == {
        ("/device:TPU:0", 2 ** 63 + 5, "%fusion.7 = f32[8]{0} fusion(%p)"):
            "jit(level_step)/boost.stats/boost.sketch/while/body/mul:"}


def test_scope_path_keeps_program_scopes_outermost_first():
    assert scopes.scope_path(
        "jit(level_step)/boost.stats/boost.sketch/while/body/closed_call/sumprod.emit/add:"
    ) == "boost.stats/boost.sketch/sumprod.emit"
    assert scopes.scope_path("jit(run)/serve.contract:") == "serve.contract"
    assert scopes.scope_path("jit(_leaf_masks_impl)/jit(_take)/gather:") == ""
    assert scopes.scope_path("") == ""


US = 1000     # the synthetic record's times are microseconds


def _synthetic() -> dict:
    """A window [0, 100] holding a round and a rescore.  Device ops:
    [0,10] under boost.stats/boost.sketch, [12,20] under boost.sweep,
    [10,12] inside a while (control), [40,45] and [60,62] unscoped
    eager launches, [90,95] the scorer."""
    rec = {
        "host": [["bench.window", 0, 100], ["bench.round", 0, 30],
                 ["boost.level", 0, 21], ["bench.rescore", 30, 100],
                 ["serve.factor", 35, 65], ["serve.scorer_build", 66, 88],
                 ["serve.score", 88, 96]],
        "devices": [{
            "name": "/device:TPU:0",
            "ops": [["jit_level_step:fusion.1", 0, 10, "boost.stats/boost.sketch"],
                    ["jit_level_step:while", 10, 12, "boost.stats"],
                    ["jit_level_step:fusion.2", 12, 20, "boost.sweep"],
                    ["jit_mul:mul", 40, 45, ""],
                    ["jit_add:add", 60, 62, ""],
                    ["jit_run:fusion.3", 90, 95, "serve.contract"]],
            "modules": [["jit_level_step", 0, 20], ["jit_mul", 40, 45],
                        ["jit_add", 60, 62], ["jit_run", 90, 95]]}]}
    rec["host"] = [[n, s * US, e * US] for n, s, e in rec["host"]]
    for d in rec["devices"]:
        d["ops"] = [[n, s * US, e * US, sc] for n, s, e, sc in d["ops"]]
        d["modules"] = [[n, s * US, e * US] for n, s, e in d["modules"]]
    return rec


def test_reduce_names_ops_and_gaps_by_program_scopes():
    out = scopes.reduce(_synthetic())
    assert out["window_s"] == pytest.approx(100e-6)
    assert out["busy_s"] == pytest.approx(32e-6)
    assert out["device_ops"][0] == ["jit_level_step/boost.stats/boost.sketch:fusion.1",
                                    pytest.approx(10e-6)]
    assert ["jit_add:add", pytest.approx(2e-6)] in out["device_ops"]
    assert not any(n.endswith(":while") for n, _ in out["device_ops"])
    # gaps: [20,40] (boost.level 1 of it, serve.factor 5: factor wins),
    # [62,90] (scorer build 22 of 28), [45,60] and [95,100] (score
    # overlaps [95,96] only: still a program span)
    assert out["idle_gaps"] == [["serve.scorer_build", pytest.approx(28e-6)],
                                ["serve.factor", pytest.approx(20e-6)],
                                ["serve.factor", pytest.approx(15e-6)],
                                ["serve.score", pytest.approx(5e-6)]]
    assert out["scope_s"] == {"boost.stats": pytest.approx(10e-6),
                              "boost.sketch": pytest.approx(10e-6),
                              "boost.sweep": pytest.approx(8e-6),
                              "serve.contract": pytest.approx(5e-6)}
    sp = out["spans"]
    assert sp["serve.factor"] == {"count": 1, "s": pytest.approx(30e-6), "launches": 2}
    assert sp["bench.rescore"]["launches"] == 3
    assert sp["boost.level"]["launches"] == 1
    assert sp["serve.scorer_build"]["launches"] == 0


def test_gap_outside_program_spans_keeps_its_bench_name():
    rec = _synthetic()
    rec["host"] = [h for h in rec["host"] if h[0] != "serve.factor"]
    out = scopes.reduce(rec)
    assert ["bench.rescore", pytest.approx(15e-6)] in out["idle_gaps"]


@pytest.mark.parametrize("fixture", ["small_trace.json", "v5e_trace.json"])
def test_unscoped_fixtures_reduce_as_before(fixture):
    rec = json.loads((FIX / fixture).read_text())
    want = trace.reduce(rec)
    got = scopes.reduce(rec)
    assert {k: got[k] for k in want} == want
    assert got["scope_s"] == {}


def test_reduce_recorded_v5e_scoped_record():
    """A small round and rescore of the program on one v5e, traced with
    its scopes: ``extract`` of the profile, kept as a fixture."""
    rec = json.loads((FIX / "v5e_scoped_record.json").read_text())
    out = scopes.reduce(rec)
    plain = {"host": [h for h in rec["host"] if h[0].startswith("bench.")],
             "devices": [{"name": d["name"], "ops": [o[:3] for o in d["ops"]]}
                         for d in rec["devices"]]}
    base = trace.reduce(plain)
    assert out["window_s"] == base["window_s"] and out["busy_s"] == base["busy_s"]
    # the level programs are named, and their heaviest ops carry boost.*
    assert all("jit__unknown" not in n for n, _ in out["device_ops"])
    assert all(n.startswith("jit_level_step/boost.") for n, _ in out["device_ops"])
    # the scorer's build is the longest gap, then the factor build's
    assert out["idle_gaps"][0][0] == "serve.scorer_build"
    assert {n for n, _ in out["idle_gaps"][1:]} == {"serve.factor"}
    assert out["scope_s"]["boost.stats"] >= out["scope_s"]["boost.sketch"] > 0
    assert out["spans"]["serve.factor"]["count"] == 3
    # the eager factor build launches one program per operation
    assert out["spans"]["serve.factor"]["launches"] > 100
    assert out["spans"]["serve.score"]["launches"] == 1


# ---------------------------------------------------------------- readers --

def _reader(name):
    import run
    from harness import core

    return run.load_module(core.BENCH / "metrics" / f"{name}.py")


@pytest.fixture
def fresh_registry(monkeypatch):
    import repro.obs.metrics as metrics

    monkeypatch.setattr(metrics, "_global_registry", metrics.MetricsRegistry())
    return metrics.get_registry()


def test_new_readers_give_nothing_without_the_program_counters(fresh_registry):
    class Ctx:
        trace: dict = {}
        extra: dict = {}
    for name in ("train.level_hbm_gib", "rescore.scorer_build_ms"):
        assert _reader(name).read(Ctx()) is None


def test_new_readers_read_the_program_counters(fresh_registry):
    fresh_registry.gauge("train.level_program_bytes").set(3 * 2 ** 29)
    for ms in (15000.0, 600.0, 610.0, 620.0, 630.0):
        fresh_registry.histogram("serve.scorer_build_ms").observe(ms)
    assert _reader("train.level_hbm_gib").read(None) == pytest.approx(1.5)
    assert _reader("rescore.scorer_build_ms").read(None) == pytest.approx(620.0, rel=0.1)
