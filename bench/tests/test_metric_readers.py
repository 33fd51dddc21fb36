"""The readers of the host step loop's and the Simulator build's spans
and byte counters, on a synthetic ``ctx``: the value from what the
program recorded, and nothing where it recorded none (a program without
those spans and counters)."""

from __future__ import annotations

import pytest

import run

SPANS = {"sim.route_tables": {"count": 2, "total_s": 3.0, "max_s": 2.0},
         "sim.table_put": {"count": 2, "total_s": 0.5, "max_s": 0.3},
         "sim.state_put": {"count": 8, "total_s": 1.2, "max_s": 0.2},
         "sim.state_fetch": {"count": 8, "total_s": 0.8, "max_s": 0.1},
         "sim.step_dispatch": {"count": 240, "total_s": 0.06, "max_s": 1e-3},
         "sim.stats_pull": {"count": 240, "total_s": 6.0, "max_s": 0.03}}
COUNTERS = {"sim.state_put_bytes": 4e9, "sim.state_fetch_bytes": 4e9,
            "sim.table_put_bytes": 1e9}


@pytest.mark.parametrize("name,expected", [
    ("route_tables_s_per_sweep", 1.5),
    ("table_put_s_per_sweep", 0.25),
    ("state_put_s_per_sweep", 0.6),
    ("state_fetch_s_per_sweep", 0.4),
    ("dispatch_ms_per_step", 0.25),
    ("stats_pull_ms_per_step", 25.0),
    ("host_device_gb_per_sweep", 4.5),
])
def test_reader_value_and_absence(name, expected):
    read = run.load_reader(name)
    ctx = {"spans": SPANS, "counters": COUNTERS, "sweeps": 2, "steps": 240}
    assert read(ctx) == pytest.approx(expected, rel=1e-12)
    assert read(dict(ctx, spans={}, counters={})) is None
