"""The comparison that decides ``correct``.

Three numbers, each against a limit of its own:

* ``knee_err`` — the timed sweep's knee against the reference's analytic
  theta, relative.  Limit: the mix's ``knee_limit`` (the bisection
  resolution the mix states).
* ``hist_gap`` — the timed probes' delivered, accepted and occupancy
  histories against the plain reference step run at the same offered
  load from empty queues: the widest gap of any step, relative to the
  largest magnitude of that history.  A step is compared only while the
  reference's threshold decision is well conditioned: the comparison of
  a probe stops at the first step whose start-of-step vc0 or vc1
  occupancy lies within ``TIE_EPS`` of capacity somewhere, because the
  threshold-0 rule diverts on any positive backlog, so the rounding of
  an occupancy sum at exactly capacity decides a discontinuous choice.
* ``residual`` — the worst conservation residual of any timed probe
  (``SimRun.residual``): injected = delivered + in flight + backlog.

Limits per cell live in ``bench/limits/<cell>.json`` with the readings
they were set from; ``PERF.md`` lists them.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HISTORIES = ("delivered", "accepted", "occupancy")
LIMITS = Path(__file__).resolve().parent / "limits"


def load_limits(cell: str, mix: dict) -> dict:
    lim = json.loads((LIMITS / f"{cell}.json").read_text())
    return {"knee_err": float(mix["knee_limit"]),
            "hist_gap": float(lim["hist_gap"]),
            "residual": float(lim["residual"])}


def compared_steps(ref_stats: np.ndarray) -> int:
    """Steps of a probe before the reference's first occupancy tie."""
    ties = np.nonzero(ref_stats[:, 6] > 0)[0]
    return int(ties[0]) if len(ties) else len(ref_stats)


def hist_gap(prog_hist: dict, ref_hist: dict, n_steps: int) -> float:
    """Widest relative gap over the first ``n_steps`` steps."""
    gap = 0.0
    for key in HISTORIES:
        ref = np.asarray(ref_hist[key][:n_steps], np.float64)
        got = np.asarray(prog_hist[key][:n_steps], np.float64)
        if not len(ref):
            continue
        scale = max(float(np.abs(ref).max()), 1e-30)
        gap = max(gap, float(np.abs(got - ref).max()) / scale)
    return gap


def verdict(values: dict, limits: dict) -> bool:
    return all(np.isfinite(values[k]) and values[k] <= limits[k]
               for k in limits)
