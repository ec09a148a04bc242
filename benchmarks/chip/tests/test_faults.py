"""The comparison that decides ``correct`` fails what it must.

Each cell's run is driven on the CPU at a tiny size (``--rehearse``),
past the harness's look for a chip: set-up, window, collect, check.
With the timed path broken underneath, ``correct`` has to come out
false, once per fault the cell can have; and the control (the reference
one precision lower, bfloat16, in the program's place) has to fail at
least one compared number.  A minute or two on the CPU, most of it
compiling the trainer's level programs.
"""
import numpy as np
import pytest

import run
from harness import core
from reference import bf16

SHRINK = 256


class Cell:
    def __init__(self, config, traffic, seed=2 ** 33 + 5):
        name = f"{config}.{traffic}"
        args = run.parse(["--workload", name, "--seed", str(seed),
                          "--seconds", "0.5", "--rehearse", str(SHRINK)])
        cell = {"name": name, "config": config, "traffic": traffic, "chips": 1}
        config, traffic = core.config_of(config), core.traffic_of(traffic)
        self.ctx = run.Context(args, cell, config, traffic, core.CompileClock())
        self.driver = run.load_module(core.BENCH / "drivers" / f"{traffic['driver']}.py")
        self.st = self.driver.setup(self.ctx)

    def drive(self, seconds=0.5):
        """Window, collect and compare, as ``run.main`` does."""
        self.ctx.seconds = seconds
        self.driver.window(self.ctx, self.st)
        out = self.driver.collect(self.ctx, self.st)
        checks = core.Checks()
        for name, value in self.driver.readings(self.ctx, out).items():
            checks.add(name, value, self.ctx.traffic["limits"][name])
        return checks, out


@pytest.fixture(scope="module")
def train():
    return Cell("tpch_star", "train_round")


@pytest.fixture(scope="module")
def rescore():
    return Cell("tpch_star", "rescore")


def fails_control(cell, out):
    got = cell.driver.readings(cell.ctx, out, q=bf16)
    limits = cell.ctx.traffic["limits"]
    return [k for k, v in got.items() if v > limits[k]]


# ---------------------------------------------------------------- train --

def test_train_sound_run_is_correct_and_control_fails(train):
    checks, out = train.drive()
    assert checks.ok, checks.items
    assert fails_control(train, out)


def test_train_round_that_returns_its_state_unchanged(train, monkeypatch):
    from repro.core import FitTrace

    monkeypatch.setattr(train.st["booster"], "boost",
                        lambda trees, n, trace=None: (list(trees), FitTrace()))
    checks, _ = train.drive()
    assert not checks.ok and checks.items["trees"]["value"] > 0


def test_train_round_with_an_altered_leaf(train, monkeypatch):
    boost = train.st["booster"].boost

    def altered(trees, n, trace=None):
        out, tr = boost(trees, n, trace)
        t = out[-1]
        out[-1] = type(t)(feat=t.feat, thr=t.thr, leaf=t.leaf.at[0].multiply(1.01))
        return out, tr

    monkeypatch.setattr(train.st["booster"], "boost", altered)
    checks, _ = train.drive()
    assert not checks.ok and checks.items["leaf_rel"]["value"] > 1e-4


def test_train_round_on_half_the_rows(train, monkeypatch):
    from repro.core.trainer import _jit_hoisting_consts

    b = train.st["booster"]
    g = train.ctx.config["group_by"]
    grouped_c3 = b.engine.grouped_c3
    half = np.arange(b.engine.n_rows(g)) % 2 == 0

    def half_rows(table, masks, extra=None):
        masks = dict(masks)
        masks[g] = masks[g] & half
        return grouped_c3(table, masks, extra)

    monkeypatch.setattr(b.engine, "grouped_c3", half_rows)
    # a new function object, so no trace of the sound step is reused
    monkeypatch.setattr(b, "_level_step",
                        _jit_hoisting_consts(lambda *a: b._level_step_impl(*a)))
    checks, _ = train.drive()
    assert checks.items["leaf_rel"]["value"] > 1e-4, checks.items


# -------------------------------------------------------------- rescore --

def test_rescore_sound_run_is_correct_and_control_fails(rescore):
    checks, out = rescore.drive(seconds=8.0)
    assert checks.ok, checks.items
    assert fails_control(rescore, out)


def _patch_scores(monkeypatch, fn):
    import repro.serving as serving

    score = serving.score_grouped
    monkeypatch.setattr(serving, "score_grouped", lambda ens, g: fn(*score(ens, g)))


def test_rescore_half_the_rows_left_out(rescore, monkeypatch):
    _patch_scores(monkeypatch, lambda t, c: (t.at[::2].set(0.0), c.at[::2].set(0.0)))
    checks, _ = rescore.drive(seconds=8.0)
    assert checks.items["count_err"]["value"] > 0


def test_rescore_answer_altered(rescore, monkeypatch):
    _patch_scores(monkeypatch, lambda t, c: (t.at[3].add(1.0), c))
    checks, _ = rescore.drive(seconds=8.0)
    assert not checks.ok and checks.items["score_rel"]["value"] > 1e-4


def test_rescore_that_returns_its_state_unchanged(rescore, monkeypatch):
    import repro.serving as serving

    compile_ensemble, first = serving.compile_ensemble, []

    def stale(schema, trees, **kw):
        if not first:
            first.append(compile_ensemble(schema, trees, **kw))
        return first[0]

    monkeypatch.setattr(serving, "compile_ensemble", stale)
    checks, _ = rescore.drive(seconds=8.0)
    assert checks.items["score_rel"]["value"] > 1e-4
