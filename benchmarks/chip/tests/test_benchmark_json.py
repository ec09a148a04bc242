"""BENCHMARK.json keeps to its contract, and everything it names is
found by name: a cell, configuration or metric is added by adding files
and entries."""
import json
import re

import pytest

from harness import core

B = json.loads((core.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
        "per_layer"}


def test_top_level_keys_and_sizes():
    assert set(B) == KEYS
    assert (core.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= B["run_seconds"] <= 51 and isinstance(B["run_seconds"], int)
    for p in B["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and not p.startswith("/")
        assert (core.ROOT / p).is_dir()
    assert all(".." not in w and not w.startswith("/") for w in B["command"])


def test_names_units_and_entry_keys():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in B[k]]
    assert len(names) == len(set(names))


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in B["workloads"]:
        e2e = [m["name"] for m in core.metric_specs(w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = core.metric_specs(w["name"], "per_layer")
        assert layer
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


@pytest.mark.parametrize("cell", [w["name"] for w in B["workloads"]])
def test_cell_files_are_found_by_name(cell):
    w, config, traffic = core.cell_of(cell)
    conf = {c["name"]: c for c in B["configs"]}[w["config"]]
    assert conf["file"].startswith(B["paths"][0] + "/")
    assert config["name"] == w["config"]
    assert set(conf["reduced"]) <= set(config)
    assert (core.BENCH / "drivers" / f"{traffic['driver']}.py").is_file()
    for m in core.metric_specs(cell, "per_layer"):
        assert (core.BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_every_config_is_used():
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}
