"""One run of one benchmark cell on one accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout.  Everything is found by name from
``BENCHMARK.json``: the cell's configuration in ``bench/configs/``, its
traffic mix in ``bench/traffic/``, its topology in
``bench/topologies/``, its correctness limits in ``bench/limits/``, the
per-layer metric readers in ``bench/metrics/`` and the device peaks in
``bench/peaks.json``.

Set-up builds the seeded fabric and demand, computes the reference's
analytic theta, builds one ``Simulator`` and runs one step, so that the
cell's step program is compiled (or read from the persistent cache)
before the clock starts.  The measured window then calls
``repro.sim.saturation_sweep`` back to back through ``backend="auto"``;
it starts no sweep once ``--seconds`` have passed and ends when the last
started sweep returns.  With ``--trace 1`` the window is one sweep under
the JAX profiler and a ``repro.obs`` session that records spans and
counters only, and the result carries the per-layer metrics instead of
the end-to-end ones.  ``setup_s`` leaves out the seconds the reference
spends on its distances and theta: they are the yardstick's, not the
program's.

After the window the device's peak memory is read, the program's state
is dropped, and the plain reference (``bench/reference.py``) replays the
probes for the comparison that decides ``correct`` (``bench/check.py``).
The last line of standard output is one JSON object; the numbers that
were compared, each with its limit, are the last lines of standard error
and the last key of that object.

Exits non-zero without a result when JAX finds no TPU, fewer chips than
the cell asks for, or ``REPRO_PERF`` is set (``auto`` must resolve as it
does for users).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import numpy as np  # noqa: E402

import check  # noqa: E402
import reference  # noqa: E402
from traffic import demand as traffic  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """A run that must end without a result."""


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """``(bench, cell, config, mix)`` for the workload ``name``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; options: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    return bench, cell, config, traffic.load_mix(cell["traffic"])


def check_device(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def sim_config(config: dict):
    from repro.sim import SimConfig
    a = config["assumed"]
    return SimConfig(routing=config["routing"], buffer=float(a["buffer"]),
                     capacity=float(a["capacity"]),
                     inj_factor=float(a["inj_factor"]),
                     backend=config["backend"], dtype=config["precision"])


def sweep(g, dem, config, mix, theta):
    from repro.sim import saturation_sweep
    return saturation_sweep(
        g, dem, routing=config["routing"],
        loads=np.asarray(mix["grid"], np.float64) * theta,
        steps=int(mix["steps"]), config=sim_config(config),
        refine=int(mix["refine"]), stable_ratio=float(mix["stable_ratio"]),
        theta_analytic=theta, knee=mix["knee"])


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def setup(config: dict, mix: dict, seed: int) -> dict:
    """Fabric, demand, theta, and one warmed Simulator step."""
    from repro import obs
    from repro.core.graph import Graph
    from repro.sim import Simulator
    fab = traffic.fabric(config, seed)
    dem = traffic.demand(fab, mix)
    t0 = time.perf_counter()
    dist = reference.distances(fab["n"], fab["edges"])
    theta = reference.theta(fab["n"], fab["edges"], dem, config["routing"],
                            dist=dist)
    ref_s = time.perf_counter() - t0
    g = Graph(fab["n"], fab["edges"], name=config["name"])
    with obs.session(mode="metrics") as sess:
        sim = Simulator(g, sim_config(config), demand=dem)
    backends = [k[len("sim.backend["):-1] for k in sess.metrics.names()
                if k.startswith("sim.backend[")]
    sim.run(dem, float(mix["grid"][0]) * theta, steps=1)
    sizes = {"n": sim.tables.n, "k": sim.tables.k, "m": sim.tables.m,
             "c": (sim.tables.m if sim.dest_cols is None
                   else len(sim.dest_cols)),
             "itemsize": np.dtype(sim.dtype).itemsize}
    del sim
    gc.collect()
    return {"fab": fab, "dem": dem, "dist": dist, "theta": theta, "g": g,
            "backend": ",".join(backends), "sizes": sizes, "ref_s": ref_s}


def window(s: dict, config: dict, mix: dict, seconds: float) -> dict:
    """Sweeps back to back; none starts after ``seconds``."""
    import jax
    compiles = []
    listen = lambda ev, dur, **kw: (compiles.append(dur)
                                    if ev == COMPILE_EVENT else None)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        t0 = time.perf_counter()
        sweeps, ends = [], []
        while not sweeps or ends[-1] - t0 < seconds:
            sweeps.append(sweep(s["g"], s["dem"], config, mix, s["theta"]))
            ends.append(time.perf_counter())
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    return {"sweeps": sweeps, "seconds": ends[-1] - t0,
            "each": np.diff([t0] + ends).tolist(), "compiles": len(compiles)}


def traced_window(s: dict, config: dict, mix: dict, keep: str | None):
    """One sweep under the profiler and an obs session that records the
    program's spans and counters and nothing more."""
    import jax

    from repro import obs
    tdir = keep or tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # runtime events only: no per-call cost
        opts.enable_hlo_proto = False
        with obs.session(mode="trace", series=False) as sess:
            # an enabled session makes every Simulator.run publish its
            # metrics and pass over the whole final state in float64 for
            # balance statistics, work an untraced sweep never does;
            # spans and the sweep's probe counters record without it
            sess.enabled = False
            jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    p0 = time.perf_counter_ns()
                    w = window(s, config, mix, 0.0)
            finally:
                jax.profiler.stop_trace()
        import trace_reduce
        w["spans"] = [(n, sess._t0_ns + t, d, depth)
                      for n, t, d, _tid, depth, _a in sess.events]
        w["span_clock"] = p0
        w["trace"] = trace_reduce.reduce(
            trace_reduce.find_xplane(tdir), window_name="bench.window",
            spans=w["spans"], span_clock=p0)
    finally:
        if keep is None:
            shutil.rmtree(tdir, ignore_errors=True)
    w["obs"] = sess.snapshot()
    return w


def replay(s: dict, config: dict, sweeps: list, dtype: str) -> dict:
    """Raw reference stats of every distinct timed probe, keyed by its
    offered load, computed at ``dtype``."""
    import jax
    fab, dem = s["fab"], s["dem"]
    tb = reference.tables(fab["n"], fab["edges"], dist=s["dist"])
    a = config["assumed"]
    kw = dict(routing=config["routing"], capacity=float(a["capacity"]),
              buffer=float(a["buffer"]), inj_factor=float(a["inj_factor"]))
    out = {}
    with jax.enable_x64(dtype == "float64"):
        dtb = reference.device_tables(tb, dtype)
        for sw in sweeps:
            for r in sw.runs:
                if r.offered not in out:
                    out[r.offered] = reference.run(tb, dtb, dem, r.offered,
                                                   r.steps, dtype=dtype, **kw)
        del dtb
    return out


def compare(s: dict, sweeps: list, ref: dict, got: dict | None = None
            ) -> dict:
    """The compared numbers over every timed probe: the program's
    histories, or ``got`` (stats keyed by offered load) in their place."""
    dem = s["dem"]
    gap, resid, knee, compared, total = 0.0, 0.0, 0.0, 0, 0
    for sw in sweeps:
        knee = max(knee, abs(sw.theta - s["theta"]) / s["theta"])
        for r in sw.runs:
            st = ref[r.offered]
            n_cmp = check.compared_steps(st)
            h = (r.history if got is None
                 else reference.histories(got[r.offered], dem))
            gap = max(gap, check.hist_gap(h, reference.histories(st, dem),
                                          n_cmp))
            resid = max(resid, r.residual if got is None else h["residual"])
            compared += n_cmp
            total += len(st)
    return {"values": {"knee_err": knee, "hist_gap": gap, "residual": resid},
            "compared_steps": compared, "total_steps": total}


def per_layer(bench: dict, cell: dict, w: dict, s: dict, peak: int,
              device: dict) -> dict:
    peaks = json.loads((HERE / "peaks.json").read_text())
    if device["kind"] not in peaks:
        raise BenchError(f"no peaks for device kind {device['kind']!r} in "
                         f"bench/peaks.json")
    snap = w["obs"]
    ctx = {"spans": snap["spans"], "trace": w["trace"],
           "counters": {k: v["value"] for k, v in snap["metrics"].items()
                        if v.get("type") == "counter"},
           "sweeps": len(w["sweeps"]),
           "steps": sum(r.steps for sw in w["sweeps"] for r in sw.runs),
           "sizes": s["sizes"], "peaks": peaks[device["kind"]],
           "peak_bytes": peak}
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_PERF"):
        raise BenchError("REPRO_PERF is set; the benchmark measures the "
                         "defaults users get")
    bench, cell, config, mix = load_cell(args.workload)

    import jax

    from repro.jaxenv import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = check_device(int(cell["chips"]))
    s = setup(config, mix, args.seed)
    setup_s = time.perf_counter() - T_START - s["ref_s"]
    print(f"[bench] {cell['name']}: seed {args.seed}, backend "
          f"{s['backend']}, compile cache {cache}, set-up {setup_s!r} s "
          f"besides the reference's {s['ref_s']!r} s", flush=True)

    if args.trace:
        w = traced_window(s, config, mix, None)
    else:
        w = window(s, config, mix, args.seconds)
    print(f"[bench] window {w['seconds']!r} s, sweeps of {w['each']} s, "
          f"compiles in window {w['compiles']}", flush=True)
    peak = peak_bytes()

    sweeps = w["sweeps"]
    steps = sum(r.steps for sw in sweeps for r in sw.runs)
    if args.trace:
        metrics = per_layer(bench, cell, w, s, peak, device)
        device.update(busy_s=w["trace"]["busy_s"],
                      window_s=w["trace"]["window_s"])
    else:
        metrics = {
            "sweep_s": {"value": w["seconds"] / len(sweeps), "unit": "s"},
            "sim_steps_per_s": {"value": steps / w["seconds"],
                                "unit": "steps/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    device["memory_peak_bytes"] = peak
    limits = check.load_limits(cell["name"], mix)
    res = compare(s, sweeps, replay(s, config, sweeps, config["precision"]))
    res["correct"] = check.verdict(res["values"], limits)
    failed = sum(1 for sw in sweeps
                 if abs(sw.theta - s["theta"]) / s["theta"]
                 > limits["knee_err"])
    if not res["correct"]:
        failed = max(failed, 1)
    print(f"[bench] knee {sweeps[0].theta!r} vs reference theta "
          f"{s['theta']!r}; probes {[r.offered for r in sweeps[0].runs]}; "
          f"steps compared {res['compared_steps']} of "
          f"{res['total_steps']}", flush=True)
    result = {"correct": res["correct"], "attempted": len(sweeps),
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = w["trace"]["breakdown"]
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in res["values"].items()}
    for k, v in res["values"].items():
        print(f"check {k} {v!r} limit {limits[k]!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
