"""Fused sparse-destination step kernel benchmarks (BENCH_7).

Five rows pin the kernel seam (repro.sim.kernel / repro.kernels):

* ``step_timing`` — per-step wall time of the pn16 uniform step on every
  backend (dense numpy float64 oracle, dense jax, fused blocked
  ``pallas``), plus the delivered-history parity of the fused backend in
  its production dtype (float32) against the oracle.
* ``pn16_sweep`` — the PR 7 acceptance row: the BENCH_5 headline case
  (pn16 uniform ugal_threshold(0) saturation sweep) on the fused
  backend.  ``max_rel_err`` is the knee's parity vs analytic theta;
  ``speedup`` is wall-clock vs the dense-backend BENCH_5 row (read from
  BENCH_5.json when present, else the recorded CI-machine baseline).
* ``pn16_ugal_compacted`` — the adaptive-compaction acceptance row: a
  24-column neighbor-fed demand swept under threshold-UGAL with the
  per-VC compacted dest axis (``compact="auto"``), then the SAME probe
  loads re-swept with ``compact="off"`` (the PR 7 all-columns path).
  Fails loud (err forced to 1.0) when the compacted sweep is not >= 3x
  faster.  The demand feeds each dest column only from its direct
  neighbors, so minimal routing is single-hop and perfectly
  ingress-balanced: NO routing scheme — analytic blend or per-flow
  adaptive — can beat the dest-ingress bound, and the measured knee
  must land on the analytic theta exactly.  (A scattered all-sources
  demand is NOT a parity case: per-flow UGAL genuinely sustains ~3-8%
  more than the best single-alpha blend when interior links bind, so
  the knee overshoots the analytic reference on every backend.)  The
  UGAL threshold is set high enough that over-capacity probes do not
  divert: diversion cannot add ingress capacity here, and suppressing
  the churn is precisely what the threshold is for.
* ``pn27_sweep`` — the beyond-the-cap minimal row: PN(27) (1514
  routers, 64.2M dense cells > SIM_MAX_CELLS) swept end-to-end on the
  fused backend with static dest compaction.  The backend is pinned to
  ``pallas``: since the active-set shrink now runs before backend
  selection, the post-shrink cell count (1514*28*757 ~ 32.1M) fits the
  dense guard and ``auto`` would resolve to jax.  The demand is all
  sources -> the point partition: the collineation group is transitive
  on points and flag-transitive on incidences, so every point column
  (and every point->line arc) is equivalent — saturation collapses
  globally and the measured knee is sharp enough to hold against the
  analytic theta.  (A random dest subset is NOT: its one bottleneck
  link carries a vanishing share of the aggregate delivered/offered
  ratio, so the 0.98-stable knee overshoots by ~10% on *every*
  backend — a measurement property, not a kernel one.)
* ``pn27_ugal`` — the compacted-adaptive-at-scale row: the same PN(27)
  points demand under ugal_threshold(0).  Adaptive routing keeps the
  full mid axis live (q1/stage2 spread over all 1514 routers), so no
  active-set shrink applies and the dense layout (64.2M cells) trips
  SIM_MAX_CELLS on every dense backend; ``auto`` escalates to pallas
  and the per-VC dest compaction (757 point columns) makes the sweep
  feasible end-to-end — impossible before the compacted pool.

``benchmarks.run --only kernels`` serializes the table into BENCH_7.json
and exits nonzero when any row's parity exceeds ``--err-budget``
(scripts/ci.sh passes 0.025, the ISSUE's 2.5% acceptance bound).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core import pn_graph
from repro.core.traffic import make_pattern, normalize_demand, saturation_report
from repro.sim import SIM_MAX_CELLS, SimConfig, Simulator, saturation_sweep

# BENCH_5's sim[pn16:uniform:ugal0] wall time on the CI machine — the
# dense-backend baseline the fused sweep is held to 10x against.  The
# live BENCH_5.json value supersedes this when the artifact is present.
BASELINE_PN16_UGAL0_SECONDS = 100.78


def _bench5_baseline() -> tuple[float, str]:
    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_5.json")
    try:
        with open(path) as fh:
            for e in json.load(fh)["entries"]:
                if e["name"] == "sim[pn16:uniform:ugal0]":
                    return float(e["seconds"]), "BENCH_5.json"
    except (OSError, KeyError, ValueError):
        pass
    return BASELINE_PN16_UGAL0_SECONDS, "recorded"


def points_demand(q: int):
    """All sources -> every point of PG(2, q): the transitive-orbit
    demand whose saturation knee is globally sharp (module docstring)."""
    g = pn_graph(q)
    npts = q * q + q + 1
    dem = np.zeros((g.n, g.n))
    dem[:, :npts] = 1.0
    np.fill_diagonal(dem, 0.0)
    return g, normalize_demand(dem)


def step_timing(steps: int = 24, offered: float = 0.5) -> tuple[dict, float]:
    """Per-step wall time per backend + fused-vs-oracle parity."""
    g = pn_graph(16)
    dem = normalize_demand(make_pattern("uniform").demand(g, None))
    ms = {}
    hist = {}
    for backend in ("numpy", "jax", "pallas"):
        sim = Simulator(g, SimConfig(routing="ugal_threshold(0)",
                                     backend=backend), demand=dem)
        sim.run(dem, offered, 2)  # warm the jit/tables caches
        t0 = time.perf_counter()
        r = sim.run(dem, offered, steps)
        ms[backend] = (time.perf_counter() - t0) / steps * 1e3
        hist[backend] = r.history["delivered"]
    ref = hist["numpy"]
    scale = max(float(np.abs(ref).max()), 1e-30)
    parity = float(np.abs(hist["pallas"] - ref).max() / scale)
    row = {"case": "pn16:uniform:ugal0:step", "steps": steps,
           "ms_per_step": {k: round(v, 3) for k, v in ms.items()},
           "parity_err": parity}
    return row, parity


def pn16_sweep() -> tuple[dict, float]:
    """The BENCH_5 headline sweep on the fused backend, timed against
    the dense baseline."""
    g = pn_graph(16)
    cfg = SimConfig(routing="ugal_threshold(0)", backend="pallas")
    ref = saturation_report(g, "uniform", routing="ugal")
    t0 = time.perf_counter()
    sweep = saturation_sweep(g, "uniform", routing="ugal_threshold(0)",
                             loads=np.array([0.97, 1.08]) * ref.theta,
                             steps=40, refine=2, config=cfg,
                             theta_analytic=ref.theta)
    seconds = time.perf_counter() - t0
    baseline, src = _bench5_baseline()
    parity = abs(sweep.theta - ref.theta) / ref.theta
    row = {"case": "pn16:uniform:ugal0", "backend": "pallas",
           "theta_sim": sweep.theta, "theta_analytic": ref.theta,
           "parity_err": parity, "seconds": round(seconds, 3),
           "baseline_seconds": baseline, "baseline_source": src,
           "speedup": round(baseline / seconds, 2)}
    return row, parity


def _neighbor_demand(q: int, n_cols: int, seed: int = 0):
    """``n_cols`` random dest columns, each fed equally by its direct
    neighbors only.  Minimal routing is single-hop and ingress-balanced,
    so the saturation knee is EXACTLY the analytic dest-ingress bound
    for every routing scheme (module docstring, pn16_ugal_compacted)."""
    g = pn_graph(q)
    rng = np.random.default_rng(seed)
    cols = np.sort(rng.choice(g.n, size=n_cols, replace=False))
    dem = np.zeros((g.n, g.n))
    for c in cols:
        dem[g.neighbors(c), c] = 1.0
    return g, normalize_demand(dem), cols


def pn16_ugal_compacted(n_cols: int = 24, steps: int = 40) -> tuple[dict, float]:
    """Compacted adaptive sweep vs the PR 7 all-columns path.

    Sweeps the neighbor-fed ``n_cols``-column demand under
    ugal_threshold(16) with the per-VC compacted dest axis and the
    per-dest knee criterion, then re-sweeps the SAME probe loads with
    ``compact="off"`` (refine=0 pins the probe set, so both paths do
    identical numerical work).  Err is knee parity vs the analytic
    blend — forced to 1.0 (fail-loud) when the speedup is < 3x."""
    g, dem, cols = _neighbor_demand(16, n_cols)
    ref = saturation_report(g, dem, routing="ugal")
    routing = "ugal_threshold(16)"
    cfg = SimConfig(routing=routing, backend="pallas")
    t0 = time.perf_counter()
    sweep = saturation_sweep(g, dem, routing=routing, config=cfg,
                             loads=np.array([0.96, 1.0, 1.05]) * ref.theta,
                             steps=steps, refine=3, stable_ratio=0.998,
                             theta_analytic=ref.theta, knee="per_dest")
    t_comp = time.perf_counter() - t0
    cfg_off = SimConfig(routing=routing, backend="pallas", compact="off")
    probe_loads = np.sort([r.offered for r in sweep.runs])
    t0 = time.perf_counter()
    saturation_sweep(g, dem, routing=routing, config=cfg_off,
                     loads=probe_loads, steps=steps, refine=0,
                     stable_ratio=0.998, theta_analytic=ref.theta,
                     knee="per_dest")
    t_off = time.perf_counter() - t0
    speedup = t_off / t_comp
    parity = abs(sweep.theta - ref.theta) / ref.theta
    err = parity if speedup >= 3.0 else max(parity, 1.0)
    row = {"case": f"pn16:nbr{n_cols}:ugal16", "backend": "pallas",
           "knee": "per_dest", "compacted_dests": int(len(cols)),
           "dense_dests": int(g.n),
           "theta_sim": sweep.theta, "theta_analytic": ref.theta,
           "parity_err": parity, "probes": len(sweep.runs),
           "seconds": round(t_comp, 3),
           "all_columns_seconds": round(t_off, 3),
           "speedup": round(speedup, 2)}
    return row, err


def pn27_ugal(steps: int = 30) -> tuple[dict, float]:
    """PN(27) adaptive sweep end-to-end — feasible only compacted.

    Under ugal the full mid axis stays live (no active-set shrink), so
    the dense layout trips SIM_MAX_CELLS and ``auto`` escalates to the
    fused backend; the per-VC dest compaction (757 point columns of
    1514) is what lets the sweep run at all (module docstring)."""
    g, dem = points_demand(27)
    cells = g.n * g.max_degree * g.n
    assert cells > SIM_MAX_CELLS  # dense layout must be infeasible
    ref = saturation_report(g, dem, routing="ugal")
    cfg = SimConfig(routing="ugal_threshold(0)")  # backend=auto
    sim = Simulator(g, cfg, demand=dem)
    assert sim.backend == "pallas"
    t0 = time.perf_counter()
    sweep = saturation_sweep(g, dem, routing="ugal_threshold(0)",
                             config=cfg,
                             loads=np.array([0.95, 1.08]) * ref.theta,
                             steps=steps, refine=2,
                             theta_analytic=ref.theta)
    seconds = time.perf_counter() - t0
    parity = abs(sweep.theta - ref.theta) / ref.theta
    n_cols = len(sim.dest_cols) if sim.dest_cols is not None else g.n
    row = {"case": "pn27:points:ugal0", "backend": sim.backend,
           "routers": g.n, "dense_cells": cells,
           "compacted_dests": int(n_cols),
           "theta_sim": sweep.theta, "theta_analytic": ref.theta,
           "parity_err": parity, "seconds": round(seconds, 3)}
    return row, parity


def pn27_sweep() -> tuple[dict, float]:
    """PN(27) past the dense cap: fused backend + dest compaction.

    ``backend`` is pinned to pallas — the minimal active-set shrink now
    runs before backend selection, so ``auto`` sizes from the
    post-shrink cells (32.1M < SIM_MAX_CELLS) and would pick jax; this
    row exists to time the fused path at scale (module docstring)."""
    g, dem = points_demand(27)
    cells = g.n * g.max_degree * g.n
    assert cells > SIM_MAX_CELLS  # the row exists to cross the cap
    ref = saturation_report(g, dem, routing="minimal")
    cfg = SimConfig(routing="minimal", backend="pallas")
    sim = Simulator(g, cfg, demand=dem)
    t0 = time.perf_counter()
    sweep = saturation_sweep(g, dem, routing="minimal", config=cfg,
                             loads=np.array([0.90, 1.08]) * ref.theta,
                             steps=40, refine=2, theta_analytic=ref.theta)
    seconds = time.perf_counter() - t0
    parity = abs(sweep.theta - ref.theta) / ref.theta
    row = {"case": "pn27:points:minimal", "backend": sim.backend,
           "routers": g.n, "dense_cells": cells,
           "compacted_dests": len(sim.active),
           "theta_sim": sweep.theta, "theta_analytic": ref.theta,
           "parity_err": parity, "seconds": round(seconds, 3)}
    return row, parity
