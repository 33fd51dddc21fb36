"""Record the small chip trace that tests/test_trace_reduce.py reads.

    python bench/tests/record_trace.py <out_dir>

Runs on one TPU: PN(5) with every router sending to the 31 points under
ugal_threshold(0), the step forced onto the Pallas kernels, one traced
sweep through the harness's own traced window.  Writes into
``<out_dir>``: ``pn5_points.xplane.pb`` and ``pn5_points.trace.json.gz``
(the profiler's two formats of one trace) and ``pn5_points.json`` (the
obs spans, their clock anchor, and the run's step and sweep counts).
Copy them to ``bench/tests/data/``.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import run  # noqa: E402


def main(out: str) -> int:
    out = Path(out)
    out.mkdir(parents=True, exist_ok=True)
    config = json.loads((HERE.parent / "configs" / "pn31_ugal0.json")
                        .read_text())
    config.update(name="pn5_points", topology_args={"q": 5},
                  backend="pallas")
    mix = run.traffic.load_mix("points")
    from repro.jaxenv import enable_compile_cache
    enable_compile_cache()
    run.check_device(1)
    s = run.setup(config, mix, 20261016)
    tdir = out / "trace"
    w = run.traced_window(s, config, mix, str(tdir))
    import trace_reduce
    xp = Path(trace_reduce.find_xplane(tdir))
    shutil.copy(xp, out / "pn5_points.xplane.pb")
    shutil.copy(next(xp.parent.glob("*.trace.json.gz")),
                out / "pn5_points.trace.json.gz")
    steps = sum(r.steps for sw in w["sweeps"] for r in sw.runs)
    (out / "pn5_points.json").write_text(json.dumps({
        "window_name": "bench.window", "span_clock": w["span_clock"],
        "spans": w["spans"], "steps": steps, "sweeps": len(w["sweeps"]),
        "reduced": w["trace"]}, indent=1))
    shutil.rmtree(tdir)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
