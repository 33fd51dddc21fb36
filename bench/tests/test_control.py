"""The comparison that decides ``correct`` fails its control and the
planted faults, at PN(5) on the CPU, under the pn31.points limits.

The control is the plain reference one precision below the
configuration's (bfloat16 for float32) in the program's place.  The
faults break the program underneath the harness: a step that returns
its state unchanged, half of the sources' injection left out, the
delivered count altered where the step produces it, and a sweep whose
bisection is left out, so that its knee is the grid's stable probe.  The cells
run on one chip, so there is no exchange between chips to leave out.
"""

from __future__ import annotations

import numpy as np
import pytest

from conftest import last_json
from test_harness import ARGS

SEED = 9223372036854775783


def test_control_fails_where_the_program_passes(tiny_cell):
    import check
    run, config, mix = tiny_cell
    s = run.setup(config, mix, SEED)
    sweeps = run.window(s, config, mix, 0.0)["sweeps"]
    ref = run.replay(s, config, sweeps, config["precision"])
    limits = check.load_limits("pn31.points", mix)
    prog = run.compare(s, sweeps, ref)
    ctl = run.compare(s, sweeps, ref, run.replay(s, config, sweeps,
                                                 "bfloat16"))
    assert check.verdict(prog["values"], limits), prog
    assert not check.verdict(ctl["values"], limits), ctl


def _unchanged(step):
    def broken(state, inj, inj_cap):
        _new, stats = step(state, inj, inj_cap)
        return state, stats
    return broken


def _half_sources(step):
    def broken(state, inj, inj_cap):
        inj = np.array(inj)
        inj[: len(inj) // 2] = 0
        return step(state, inj, inj_cap)
    return broken


def _altered(step):
    def broken(state, inj, inj_cap):
        new, stats = step(state, inj, inj_cap)
        stats = np.array(stats, dtype=np.float64)
        stats[0] *= 1.01
        return new, stats
    return broken


def _no_bisection(monkeypatch):
    import repro.sim
    sweep = repro.sim.saturation_sweep
    monkeypatch.setattr(repro.sim, "saturation_sweep",
                        lambda *a, **kw: sweep(*a, **dict(kw, refine=0)))


def _in_step(fault):
    def plant(monkeypatch):
        from repro.sim import Simulator
        make = Simulator._make_step
        monkeypatch.setattr(Simulator, "_make_step",
                            lambda self, tb: fault(make(self, tb)))
    return plant


@pytest.mark.parametrize("plant", [_in_step(_unchanged),
                                   _in_step(_half_sources),
                                   _in_step(_altered), _no_bisection],
                         ids=["state_unchanged", "half_sources",
                              "answer_altered", "no_bisection"])
def test_planted_fault_is_not_correct(tiny_cell, monkeypatch, capsys,
                                      plant):
    run, _config, _mix = tiny_cell
    plant(monkeypatch)
    assert run.main(ARGS) == 0
    res = last_json(capsys.readouterr().out)
    assert res["correct"] is False and res["failed"] >= 1
