"""``trace_reduce`` against a small trace recorded on a TPU v5e.

``data/pn5_points.xplane.pb`` is one traced PN(5) points sweep on the
Pallas step (``record_trace.py``); ``data/pn5_points.trace.json.gz`` is
the profiler's Chrome-format twin of the same trace, read here with
``json`` alone as the second witness; ``data/pn5_points.json`` holds the
run's obs spans, their clock anchor and its step count.
"""

from __future__ import annotations

import gzip
import json
import re

import pytest

from conftest import BENCH

DATA = BENCH / "tests" / "data"


@pytest.fixture(scope="module")
def recorded():
    import trace_reduce
    side = json.loads((DATA / "pn5_points.json").read_text())
    red = trace_reduce.reduce(str(DATA / "pn5_points.xplane.pb"),
                              side["window_name"], spans=side["spans"],
                              span_clock=side["span_clock"])
    return side, red


def _chrome(window_name):
    ev = json.load(gzip.open(DATA / "pn5_points.trace.json.gz"))["traceEvents"]
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in ev
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    win = next(e for e in ev if e.get("name") == window_name)
    w0, w1 = win["ts"], win["ts"] + win["dur"]
    inside = [e for e in ev if e.get("ph") == "X" and w0 <= e["ts"] < w1]
    line = lambda e: threads.get((e["pid"], e.get("tid")))
    return ([e for e in inside if line(e) == "XLA Modules"],
            [e for e in inside if line(e) == "XLA Ops"], (w1 - w0) / 1e6)


def test_step_program_matches_the_chrome_twin(recorded):
    side, red = recorded
    modules, _ops, window_s = _chrome(side["window_name"])
    step = [e for e in modules if e["name"].startswith(red["step_module"]
                                                        + "(")]
    assert red["step_module"] == "jit_step_impl"
    assert red["step_calls"] == len(step) == side["steps"]
    # the xplane keeps whole nanoseconds per event, the twin picoseconds
    assert red["step_device_s"] == pytest.approx(
        sum(e["dur"] for e in step) / 1e6, abs=1e-9 * len(step))
    assert red["window_s"] == pytest.approx(window_s, abs=1e-9)


def test_pallas_time_is_the_custom_calls(recorded):
    side, red = recorded
    _modules, ops, _w = _chrome(side["window_name"])
    kernels = [e for e in ops
               if re.match(r"(fused_step_update|fused_decision)",
                           e["name"])]
    assert kernels
    assert red["pallas_s"] == pytest.approx(
        sum(e["dur"] for e in kernels) / 1e6, abs=1e-9 * len(kernels))
    assert 0 < red["pallas_s"] < red["step_device_s"]


def test_busy_idle_and_breakdown(recorded):
    side, red = recorded
    assert 0 < red["step_device_s"] <= red["busy_s"] <= red["window_s"]
    bd = red["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert any(name.startswith("sim.") for name, _s in bd["idle_gaps"])
    idle = sum(s for _n, s in bd["idle_gaps"])
    assert idle <= red["window_s"] - red["busy_s"] + 1e-9
    assert red == side["reduced"]
