"""chip_smoke.py's phases on the CPU at a tiny size.

The script itself runs only on a TPU; here its phase functions run on
PN(3) with the pallas kernel under the interpreter, so a broken phase is
found before it costs chip time.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_phase_refuses_cpu(smoke):
    with pytest.raises(RuntimeError, match="no TPU"):
        smoke.check_device()


def test_phases_run_tiny_on_cpu(smoke):
    b = smoke.phase_parity(q=3, steps=8, backend="pallas_interpret",
                           build="pallas_interpret")
    assert b["parity"] <= smoke.PARITY_BUDGET
    c = smoke.phase_main(q=3, steps=30, backend="pallas_interpret",
                         resolved="pallas_interpret",
                         build="pallas_interpret", timed_steps=2)
    assert c["backend"] == "pallas_interpret"
    assert c["knee_err"] <= smoke.KNEE_BUDGET
    assert c["dests"] == 13 and c["step_ms"] > 0 and c["compile_s"] > 0
    d = smoke.phase_loads(q=3, branch="interpret")
    assert d["max_rel"] <= smoke.LOADS_RTOL
