"""Chip smoke run: the fluid simulator's main path, once, on one TPU.

    python chip_smoke.py

Runs from the root of a checkout, in one process (the chip belongs to
one process at a time), through the entry points a user calls:

  a. device   — the platform, device kind and count; anything but a TPU
                is an error.
  b. parity   — pn16 uniform traffic under ugal_threshold(0), 24 steps at
                offered 0.5: the pallas kernel in float32 against the
                dense numpy float64 reference.  The delivered history may
                differ by at most 1e-4 relative.
  c. main     — ``saturation_sweep`` on PN(27) (1514 routers, 64.2M
                dense cells) with every source sending to the 757 points
                under ugal_threshold(0), backend left on ``auto``: it
                must resolve to the pallas kernel on the chip, and the
                knee must land within 2.5% of the analytic theta.
                Prints compile seconds, steady per-step time, sweep wall
                time and the device's peak memory.
  d. loads    — ``arc_loads(pn_graph(31), engine="pallas")`` compiled in
                float32 against the numpy engine: loads within rtol 1e-5,
                mean distance and diameter exact.

Any failed check raises, so the run exits non-zero.  The last line of
standard output is one JSON object naming the device; it is printed only
when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

PARITY_BUDGET = 1e-4     # max relative delivered-history difference
KNEE_BUDGET = 0.025      # knee vs analytic theta, relative
LOADS_RTOL = 1e-5        # float32 kernel loads vs float64 numpy loads


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def _counter(sess, name: str) -> float:
    m = sess.metrics.get(name)
    return 0.0 if m is None else float(m.value)


def check_device() -> dict:
    """Phase a: the device JAX runs on; a non-TPU device is an error."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[a] device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    _check(dev["platform"] == "tpu",
           f"no TPU: JAX runs on {dev['platform']!r}")
    return dev


def phase_parity(q: int = 16, steps: int = 24, offered: float = 0.5,
                 backend: str = "pallas", build: str = "pallas_tpu") -> dict:
    """Phase b: the float32 kernel step against the float64 reference."""
    from repro import obs
    from repro.core import pn_graph
    from repro.core.traffic import make_pattern, normalize_demand
    from repro.sim import SimConfig, Simulator
    g = pn_graph(q)
    dem = normalize_demand(make_pattern("uniform").demand(g, None))
    hist = {}
    with obs.session(mode="metrics") as sess:
        for b in (backend, "numpy"):
            cfg = SimConfig(routing="ugal_threshold(0)", backend=b,
                            dtype="float32" if b == backend else "float64")
            hist[b] = Simulator(g, cfg, demand=dem).run(
                dem, offered, steps).history["delivered"]
        _check(_counter(sess, f"sim.step_build[{build}]") >= 1,
               f"the parity step was not built through {build}")
    ref = hist["numpy"]
    err = float(np.abs(hist[backend] - ref).max()
                / max(float(np.abs(ref).max()), 1e-30))
    _check(err <= PARITY_BUDGET,
           f"delivered history differs by {err:.3e} > {PARITY_BUDGET}")
    return {"routers": g.n, "steps": steps, "parity": err}


def phase_main(q: int = 27, steps: int = 30, refine: int = 2,
               backend: str = "auto", resolved: str = "pallas",
               build: str = "pallas_tpu", timed_steps: int = 10) -> dict:
    """Phase c: the saturation sweep at scale, through ``auto``."""
    import jax

    from benchmarks.kernel_bench import points_demand
    from repro import obs
    from repro.core.traffic import saturation_report
    from repro.sim import SimConfig, Simulator, saturation_sweep
    from repro.sim.engine import init_state
    g, dem = points_demand(q)
    ref = saturation_report(g, dem, routing="ugal").theta
    cfg = SimConfig(routing="ugal_threshold(0)", backend=backend)
    with obs.session(mode="metrics") as sess:
        sim = Simulator(g, cfg, demand=dem)
        built = _counter(sess, f"sim.step_build[{build}]")
    _check(sim.backend == resolved,
           f"backend {backend!r} resolved to {sim.backend!r}, "
           f"not {resolved!r}")
    _check(built >= 1, f"the step was not built through {build}")

    # one step alone: compile time, then the steady per-step time
    t, cols = sim.tables, sim.dest_cols
    inj = dem[:, t.active] if cols is None else dem[:, t.active[cols]]
    inj = (0.9 * ref * inj).astype(sim.dtype)
    inj_cap = inj.sum(axis=1)
    state = sim._step.put(
        init_state(t, sim.dtype, dest_cols=cols).as_tuple())
    compile_s = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        t0 = time.perf_counter()
        state, stats = sim._step(state, inj, inj_cap)
        jax.block_until_ready((state, stats))
        first_s = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    t0 = time.perf_counter()
    for _ in range(timed_steps):
        state, stats = sim._step(state, inj, inj_cap)
    jax.block_until_ready((state, stats))
    step_ms = (time.perf_counter() - t0) / timed_steps * 1e3
    del sim, state, stats

    t0 = time.perf_counter()
    sweep = saturation_sweep(g, dem, routing="ugal_threshold(0)", config=cfg,
                             loads=np.array([0.95, 1.08]) * ref,
                             steps=steps, refine=refine, theta_analytic=ref)
    sweep_s = time.perf_counter() - t0
    err = abs(sweep.theta - ref) / ref
    _check(err <= KNEE_BUDGET,
           f"knee {sweep.theta:.6f} is {err:.4%} from analytic {ref:.6f}")
    stats = jax.devices()[0].memory_stats() or {}
    return {"routers": g.n, "dense_cells": g.n * g.max_degree * g.n,
            "dests": inj.shape[1], "backend": sweep.runs[0].backend,
            "theta_sim": sweep.theta, "theta_analytic": ref, "knee_err": err,
            "probes": len(sweep.runs), "compile_s": sum(compile_s),
            "first_step_s": first_s, "step_ms": step_ms,
            "sweep_s": sweep_s,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def phase_loads(q: int = 31, branch: str = "compiled") -> dict:
    """Phase d: arc loads through the mask+GEMM kernels vs numpy."""
    from repro import obs
    from repro.core import pn_graph
    from repro.core.utilization import arc_loads
    g = pn_graph(q)
    with obs.session(mode="metrics") as sess:
        loads, kbar, diam = arc_loads(g, engine="pallas")
        _check(_counter(sess, f"util.pallas[{branch}]") >= 1,
               f"the pallas arc-load engine did not take its {branch} "
               f"branch")
    ref, kbar_ref, diam_ref = arc_loads(g, engine="numpy")
    rel = float(np.max(np.abs(loads - ref) / np.abs(ref)))
    _check(np.allclose(loads, ref, rtol=LOADS_RTOL, atol=0.0),
           f"arc loads differ by {rel:.3e} relative (rtol {LOADS_RTOL})")
    _check(kbar == kbar_ref and diam == diam_ref,
           f"kbar/diameter {kbar}/{diam} != {kbar_ref}/{diam_ref}")
    return {"routers": g.n, "arcs": len(loads), "max_rel": rel,
            "kbar": kbar, "diameter": diam}


def main(argv=None) -> int:
    argparse.ArgumentParser(description=__doc__.splitlines()[0]) \
        .parse_args(argv)
    from repro.jaxenv import enable_compile_cache
    cache = enable_compile_cache()
    dev = check_device()
    print(f"[a] compile cache: {cache}", flush=True)
    where = f"chip reading of this smoke run on {dev['kind']}"

    b = phase_parity()
    print(f"[b] pn16 ugal_threshold(0), {b['steps']} steps: pallas float32 "
          f"vs numpy float64 delivered history max rel diff "
          f"{b['parity']:.3e} (budget {PARITY_BUDGET})", flush=True)

    c = phase_main()
    print(f"[c] PN(27) points ugal_threshold(0): auto -> {c['backend']}, "
          f"{c['routers']} routers, {c['dense_cells']} dense cells, "
          f"{c['dests']} compacted dests", flush=True)
    print(f"[c] knee {c['theta_sim']!r} vs analytic {c['theta_analytic']!r}: "
          f"{c['knee_err']:.4%} (budget {KNEE_BUDGET:.1%}), "
          f"{c['probes']} probes", flush=True)
    print(f"[c] {where}: step compile {c['compile_s']!r} s, first step "
          f"{c['first_step_s']!r} s, steady step {c['step_ms']!r} ms",
          flush=True)
    print(f"[c] {where}: sweep wall {c['sweep_s']!r} s, peak_bytes_in_use "
          f"{c['peak_bytes_in_use']!r}", flush=True)

    d = phase_loads()
    print(f"[d] PN(31) arc loads, pallas float32 vs numpy: max rel diff "
          f"{d['max_rel']:.3e} over {d['arcs']} arcs (rtol {LOADS_RTOL}), "
          f"kbar {d['kbar']!r}, diameter {d['diameter']}", flush=True)

    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
