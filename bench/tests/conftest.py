"""Shared set-up of the benchmark's self-tests (``python -m pytest
bench/tests``): the benchmark's own modules on ``sys.path``, and a tiny
cell that runs on the CPU."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def tiny_cell(monkeypatch):
    """Point the harness at PN(5) under the ``points`` mix and the
    pn31.points limits, with its look for a TPU bypassed."""
    import run
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((BENCH / "configs" / "pn31_ugal0.json").read_text())
    config.update(name="pn5_ugal0", topology_args={"q": 5})
    mix = run.traffic.load_mix("points")
    cell = {"name": "pn31.points", "config": "pn5_ugal0",
            "traffic": "points", "chips": 1}
    monkeypatch.setattr(run, "load_cell",
                        lambda name: (bench, cell, config, mix))
    monkeypatch.setattr(run, "check_device", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    return run, config, mix


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])
