"""§Observability (repro.obs): span tracing, the metrics registry, the
simulator's bit-exact conservation counters, the obs=none no-op fast
path, and benchmarks/compare.py's regression gate.

The counter tests are the load-bearing ones: the simulator publishes
its conservation totals from the SAME floats its own residual/alpha
identities consume, so recomputing those identities from the counters
must equal the returned SimRun fields EXACTLY (==, not approx) — on
pn16, on the 8x16 torus, and through a mid-run fault event.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.core import mms_graph, pn_graph, random_faults
from repro.fabric.model import torus3d_graph
from repro.obs import MetricsRegistry, balance_stats
from repro.sim import SimConfig, Simulator

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC_PATH = os.path.join(REPO_ROOT, "src")


def _uniform(g):
    d = np.ones((g.n, g.n)) - np.eye(g.n)
    return d / d.sum(axis=1, keepdims=True)


# -- tracing ---------------------------------------------------------------


def test_span_nesting_and_chrome_trace(tmp_path):
    with obs.session(mode="trace") as sess:
        with obs.span("outer.work", n=3):
            with obs.span("inner.work"):
                pass
            with obs.span("inner.work"):
                pass
    assert [e[0] for e in sess.events] == ["inner.work", "inner.work",
                                           "outer.work"]  # close order
    depths = {e[0]: e[4] for e in sess.events}
    assert depths["outer.work"] == 0 and depths["inner.work"] == 1
    summ = sess.span_summary()
    assert summ["inner.work"]["count"] == 2
    assert summ["outer.work"]["total_s"] >= summ["inner.work"]["total_s"]

    path = tmp_path / "trace.json"
    sess.write_chrome(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M"                       # process_name metadata
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 3
    outer = next(e for e in xs if e["name"] == "outer.work")
    assert outer["args"] == {"n": 3}
    for e in xs:                                     # Perfetto essentials
        assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)


def test_span_releases_what_it_synced():
    """A span kept past its block (``with ... as sp``) does not keep the
    arrays it waited on alive: a Simulator run's placed zero state would
    otherwise stay on the device for the whole run."""
    import gc
    import weakref

    import jax.numpy as jnp
    with obs.session(mode="trace"):
        x = jnp.zeros(4)
        ref = weakref.ref(x)
        with obs.span("placed") as sp:
            sp.sync(x)
        del x
        gc.collect()
        assert ref() is None and sp.seconds > 0


def test_timed_measures_with_obs_off():
    assert obs.current() is None
    with obs.timed("standalone.step") as sp:
        sum(range(1000))
    assert sp.seconds > 0


def test_metrics_mode_records_no_spans():
    with obs.session(mode="metrics") as sess:
        with obs.span("should.be.noop"):
            obs.counter("c").add(2.0)
    assert sess.events == []
    assert sess.metrics.counter("c").value == 2.0


def test_session_modes_validate():
    with pytest.raises(ValueError, match="unknown obs mode"):
        with obs.session(mode="bogus"):
            pass
    with obs.session(mode="none") as sess:
        assert not sess.enabled
        assert sess.snapshot() is None


# -- metrics registry ------------------------------------------------------


def test_registry_kinds_and_mismatch():
    reg = MetricsRegistry()
    reg.counter("a").add(1.5)
    reg.counter("a").add(1.5)                 # get-or-create, same object
    reg.gauge("g").set(7.0)
    reg.histogram("h").observe_many([1.0, 2.0, 3.0])
    reg.series("s").append(1.0)
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("a")
    snap = reg.snapshot()
    assert snap["a"] == {"type": "counter", "value": 3.0}
    assert snap["g"] == {"type": "gauge", "value": 7.0}
    assert snap["h"]["count"] == 3 and snap["h"]["p50"] == 2.0
    assert snap["s"] == {"type": "series", "count": 1, "mean": 1.0,
                         "min": 1.0, "max": 1.0, "last": 1.0}


def test_balance_stats_known_inputs():
    flat = balance_stats(np.ones(100))
    assert flat["gini"] == pytest.approx(0.0, abs=1e-12)
    assert flat["max_over_mean"] == pytest.approx(1.0)
    assert flat["p99_over_mean"] == pytest.approx(1.0)
    # one link carries everything: gini -> (n-1)/n
    onehot = balance_stats([0.0] * 99 + [1.0])
    assert onehot["gini"] == pytest.approx(0.99)
    assert onehot["max_over_mean"] == pytest.approx(100.0)
    assert balance_stats([])["gini"] == 0.0
    assert balance_stats([0.0, 0.0])["max_over_mean"] == 1.0


# -- simulator counters: bit-exact with SimRun -----------------------------


def _counters_match_run(sess, run):
    """Recompute SimRun's residual/alpha identities from the published
    counters; every comparison is EXACT (same floats, same ops)."""
    m = sess.metrics
    inj = m.counter("sim.injected").value
    dlv = m.counter("sim.delivered").value
    acc = m.counter("sim.accepted").value
    div = m.counter("sim.diverted").value
    drop = m.counter("sim.dropped").value
    occ = m.get("sim.final_occupancy").value
    src = m.get("sim.final_src_backlog").value
    assert drop == run.dropped
    assert m.get("sim.residual").value == run.residual
    assert m.get("sim.alpha").value == run.alpha
    assert abs(inj - dlv - occ - src - drop) / max(inj, 1e-30) \
        == run.residual
    assert 1.0 - div / max(acc, 1e-30) == run.alpha
    assert m.get("sim.theta").value == run.theta
    assert run.residual < 1e-9


def test_sim_counters_bit_exact_pn16():
    g = pn_graph(16)
    sim = Simulator(g, SimConfig(routing="ugal_threshold(0)"))
    with obs.session(mode="metrics") as sess:
        run = sim.run(_uniform(g), offered=0.3, steps=120, window=30)
    _counters_match_run(sess, run)
    assert sess.metrics.counter("sim.steps").value == 120.0
    assert sess.metrics.counter("sim.runs").value == 1.0
    # final-state link utilization + balance publish even without series
    snap = sess.snapshot()
    assert snap["metrics"]["sim.link_util_final"]["count"] > 0
    assert 0.0 <= snap["metrics"]["sim.balance.gini"]["value"] < 1.0


def test_sim_counters_bit_exact_torus_with_fault_event():
    g = torus3d_graph(8, 16, 1)
    fs = random_faults(g, k_links=3, seed=1)
    sim = Simulator(g, SimConfig(routing="minimal"))
    with obs.session(mode="metrics") as sess:
        run = sim.run(_uniform(g), offered=0.2, steps=160, window=40,
                      events=[(60, fs)])
    _counters_match_run(sess, run)
    assert sess.metrics.counter("sim.fault_events").value == 1.0


def test_sim_router_fault_drop_counter_exact():
    from repro.core import FaultSet
    g = pn_graph(16)
    sim = Simulator(g, SimConfig(routing="ugal_threshold(0)"))
    with obs.session(mode="metrics") as sess:
        run = sim.run(_uniform(g), offered=0.3, steps=150, window=40,
                      events=[(50, FaultSet(routers=[5]))])
    assert run.dropped > 0
    _counters_match_run(sess, run)


def test_sim_series_capture_under_trace():
    g = pn_graph(16)
    with obs.session(mode="trace") as sess:
        # built inside the session so the sim.build_tables span records
        sim = Simulator(g, SimConfig(routing="ugal_threshold(0)"))
        run = sim.run(_uniform(g), offered=0.3, steps=80, window=20)
    m = sess.metrics
    assert len(m.series("sim.occ_vc0")) == 80
    assert len(m.series("sim.src_backlog")) == 80
    # the per-step occupancy series sums to the history's occupancy
    occ = (np.asarray(m.series("sim.occ_vc0"))
           + np.asarray(m.series("sim.occ_vc1"))
           + np.asarray(m.series("sim.occ_vc2")))
    np.testing.assert_allclose(occ, run.history["occupancy"], rtol=1e-12)
    snap = sess.snapshot()
    assert snap["metrics"]["sim.link_util"]["count"] > 0
    assert snap["metrics"]["sim.dest_stability"]["count"] == g.n
    # uniform demand well below the knee: every dest column is stable
    assert snap["metrics"]["sim.dest_stability.min"]["value"] > 0.9
    names = [e[0] for e in sess.events]
    assert "sim.run" in names and "sim.build_tables" in names


def test_sim_series_capture_on_a_laid_out_kernel_step():
    """Series capture under a trace session on the kernel step at Slim
    Fly MMS(5), whose 7 out-slots the step lays at 8: the per-step
    observers see the graph's K, so the measured link utilization has
    one entry per arc and matches the dense float64 oracle's."""
    g = mms_graph(5)
    dem = _uniform(g)
    util, stab = {}, {}
    for backend in ("numpy", "pallas_interpret"):
        with obs.session(mode="trace") as sess:
            sim = Simulator(g, SimConfig(routing="ugal_threshold(0)",
                                         backend=backend, dtype="float64"),
                            demand=dem)
            sim.run(dem, offered=2.0, steps=40, window=10)
        m = sess.metrics
        util[backend] = m.histogram("sim.link_util").values
        stab[backend] = m.histogram("sim.dest_stability").values
        assert len(m.series("sim.occ_vc0")) == 40
    assert sim.tables.k == 7 and sim._step.pad == 1
    assert len(util["pallas_interpret"]) == len(g.indices)
    np.testing.assert_allclose(util["pallas_interpret"], util["numpy"],
                               rtol=1e-7, atol=1e-12)
    np.testing.assert_allclose(stab["pallas_interpret"], stab["numpy"],
                               rtol=1e-7)


# -- the Simulator's host spans, byte counters and profiler annotations ----

G16 = torus3d_graph(4, 4, 1)
JAX_UGAL = SimConfig(routing="ugal_threshold(0)", backend="jax")


def _assert_nested(sess, child, parent, count):
    """``count`` spans ``child``, each one level inside ``parent``'s one
    span, on its thread and within its interval."""
    (_, p0, pdur, ptid, pdepth, _), = [e for e in sess.events
                                       if e[0] == parent]
    kids = [e for e in sess.events if e[0] == child]
    assert len(kids) == count, child
    for _, t0, dur, tid, depth, _ in kids:
        assert tid == ptid and depth == pdepth + 1
        assert p0 <= t0 and t0 + dur <= p0 + pdur


def test_sim_spans_per_build_and_run():
    with obs.session(mode="trace") as sess:
        sim = Simulator(G16, JAX_UGAL)
        sim.run(_uniform(G16), 0.3, steps=7)
    for child in ("sim.route_tables", "sim.step_tables", "sim.table_put"):
        _assert_nested(sess, child, "sim.build_tables", 1)
    for child in ("sim.run_inputs", "sim.state_put", "sim.state_fetch",
                  "sim.run_result"):
        _assert_nested(sess, child, "sim.run", 1)
    _assert_nested(sess, "sim.step_dispatch", "sim.run", 7)
    _assert_nested(sess, "sim.stats_pull", "sim.run", 7)


def _state_bytes(sim):
    """Bytes of one run's state as its step lays it out."""
    from repro.sim.engine import state_shapes
    return sum(a.nbytes for a in sim._step.zeros(
        state_shapes(sim.tables, sim.dest_cols).as_tuple(), sim.dtype))


@pytest.mark.parametrize("backend,enabled", [("jax", True), ("jax", False),
                                             ("pallas_interpret", False)])
def test_sim_byte_counters_match_moved_arrays(backend, enabled):
    """A fault-free run makes its zero state on the device and keeps its
    final state there: no state bytes are sent, and one state comes back
    per run only where something reads ``last_state`` (an enabled
    session's balance statistics).  The tables are placed once per
    build, also in a session with ``enabled = False``: the step's host
    tables, and the inputs of the program that makes the dense planes
    on the device, never the planes themselves."""
    import jax
    dem = _uniform(G16)
    with obs.session(mode="trace", series=False) as sess:
        sess.enabled = enabled
        sim = Simulator(G16, SimConfig(routing="ugal_threshold(0)",
                                       backend=backend), demand=dem)
        sim.run(dem, 0.3, steps=3)
        sim.run(dem, 0.4, steps=3)
    state = _state_bytes(sim)
    t, tabs = sim.tables, sim._step.tabs
    full = tabs.get("F", tabs)              # the kernel step's full axis
    made = [tabs["w_val"], full["split"], full["deliver"]]
    slots = tabs["w_val"].shape[1]
    # distances, laid head (int32) and slot mask, the K + 1 reciprocals
    # and the dest ids (int32); the spread goes as an input and stays
    inputs = (t.dist.nbytes + t.n * slots * 5
              + (t.k + 1) * np.dtype(sim.dtype).itemsize + 4 * t.m)
    tables = (sum(a.nbytes for a in jax.tree.leaves(tabs))
              - sum(a.nbytes for a in made) + inputs)
    m = sess.metrics
    assert m.counter("sim.table_put_bytes").value == tables > 0
    assert m.counter("sim.state_put_bytes").value == 0
    assert m.counter("sim.state_device_zeros").value == 2
    reads = 2 if enabled else 0
    assert m.counter("sim.state_fetch_bytes").value == reads * state
    assert m.counter("sim.last_state_fetches").value == reads


@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
def test_sim_last_state_fetched_once_on_read(backend):
    """``last_state`` after a device run is, to the bit, the state the
    same run leaves on the host path; the first read copies it off the
    device, the second reads the cached copy."""
    dem = _uniform(G16)
    sim = Simulator(G16, SimConfig(routing="ugal_threshold(0)",
                                   backend=backend), demand=dem)
    # the host path: zeros made on the host and placed, as the step
    # lays them out (the kernel step lays G16's 4 out-slots at 8)
    sim._step.zeros = lambda shapes, dtype: sim._step.put(
        tuple(np.zeros(s, dtype) for s in shapes))
    sim.run(dem, 0.5, steps=12)
    host = sim.last_state
    del sim._step.zeros
    with obs.session(mode="trace", series=False) as sess:
        sess.enabled = False
        sim.run(dem, 0.5, steps=12)
        m = sess.metrics
        assert m.counter("sim.state_fetch_bytes").value == 0
        first = sim.last_state
        assert sim.last_state is first
    assert m.counter("sim.state_fetch_bytes").value == _state_bytes(sim)
    assert m.counter("sim.last_state_fetches").value == 1
    assert m.counter("sim.state_device_zeros").value == 1
    assert sess.span_summary()["sim.state_fetch"]["count"] == 2
    for a, b in zip(first.as_tuple(), host.as_tuple()):
        assert type(a) is np.ndarray and a.dtype == b.dtype == sim.dtype
        np.testing.assert_array_equal(a, b)


def test_sim_new_run_releases_previous_state_before_its_zeros():
    """A run lets go of the previous run's device state, and of its host
    copy once read, before it makes its own zeros."""
    import weakref
    dem = _uniform(G16)
    sim = Simulator(G16, JAX_UGAL)
    real, alive, refs = sim._step.zeros, [], []

    def zeros(*args):
        alive.append([r() is not None for r in refs])
        return real(*args)

    sim._step.zeros = zeros
    sim.run(dem, 0.3, steps=3)
    refs = [weakref.ref(a) for a in sim._final]
    sim.run(dem, 0.3, steps=3)          # the device state, never read
    refs = [weakref.ref(a) for a in sim.last_state.as_tuple()]
    assert sim._final is None
    sim.run(dem, 0.3, steps=3)          # the cached host copy
    assert alive == [[], [False] * 6, [False] * 6]
    assert sim._final_host is None and len(sim._final) == 6


@pytest.mark.parametrize("event_step", [0, 5])
def test_sim_fault_segments_place_state_from_host(event_step):
    """A fault at step 0 does its surgery on host zeros and places them
    once; a later fault fetches the state at the segment's end and
    places it again after the surgery."""
    dem = _uniform(G16)
    sim = Simulator(G16, JAX_UGAL)
    with obs.session(mode="trace", series=False) as sess:
        sess.enabled = False
        sim.run(dem, 0.5, steps=12,
                events=[(event_step, random_faults(G16, k_links=2, seed=0))])
    state, m = _state_bytes(sim), sess.metrics
    assert m.counter("sim.state_put_bytes").value == state
    assert m.counter("sim.state_device_zeros").value == (event_step > 0)
    assert m.counter("sim.state_fetch_bytes").value == \
        (state if event_step else 0)
    spans = sess.span_summary()
    assert spans["sim.state_put"]["count"] == 2
    assert spans["sim.state_fetch"]["count"] == (2 if event_step else 1)


def test_sim_numpy_backend_moves_nothing():
    with obs.session(mode="trace") as sess:
        sim = Simulator(G16, SimConfig(backend="numpy"))
        sim.run(_uniform(G16), 0.3, steps=3)
    assert not any(k.endswith("_bytes") for k in sess.metrics.names())
    assert "sim.table_put" not in sess.span_summary()
    _assert_nested(sess, "sim.state_put", "sim.run", 1)


@pytest.mark.parametrize("event_step", [None, 0, 5])
def test_sim_histories_bitwise_with_session_and_placement(event_step):
    """A run's histories are the same bits with no session, under a
    trace session, and with the state handed to the first step as host
    arrays instead of placed on the device first."""
    dem = _uniform(G16)
    events = (None if event_step is None else
              [(event_step, random_faults(G16, k_links=2, seed=0))])
    sim = Simulator(G16, JAX_UGAL)
    runs = [sim.run(dem, 0.5, steps=12, events=events)]
    with obs.session(mode="trace"):
        runs.append(sim.run(dem, 0.5, steps=12, events=events))
    sim._put = None
    runs.append(sim.run(dem, 0.5, steps=12, events=events))
    for r in runs[1:]:
        for key, h in runs[0].history.items():
            np.testing.assert_array_equal(r.history[key], h, err_msg=key)


def test_sim_run_without_session_never_syncs(monkeypatch):
    import jax
    calls = []
    real = jax.block_until_ready

    def counted(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counted)
    assert obs.current() is None
    assert obs.span("sim.state_put") is obs.NULL_SPAN
    Simulator(G16, JAX_UGAL).run(_uniform(G16), 0.3, steps=4)
    assert calls == []
    with obs.session(mode="trace"):
        Simulator(G16, JAX_UGAL).run(_uniform(G16), 0.3, steps=4)
    # sim.route_tables (the planes), sim.table_put and sim.state_put
    assert len(calls) == 3


def test_sim_spans_land_in_a_jax_profile(tmp_path):
    import jax
    dem = _uniform(G16)
    sim = Simulator(G16, JAX_UGAL)
    sim.run(dem, 0.3, steps=2)  # compiles outside the capture
    with obs.session(mode="trace"):
        jax.profiler.start_trace(str(tmp_path))
        try:
            sim.run(dem, 0.3, steps=3)
        finally:
            jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    names = [ev.name for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events]
    assert names.count("sim.run") == 1
    assert names.count("sim.stats_pull") == 3


# -- the obs=none fast path ------------------------------------------------


def test_null_span_singleton_and_no_allocation():
    assert obs.current() is None
    assert obs.span("a") is obs.span("b") is obs.NULL_SPAN
    assert obs.counter("x") is obs.gauge("y") is obs.NULL_METRIC
    # the PR 10 hooks share the no-session fast path: no recorder, no
    # watchdog, and emit is a silent no-op
    assert obs.recorder() is None and obs.watchdog() is None
    obs.emit("nobody", listening=True)

    def seam():
        # the exact shape of every instrumented hot-loop seam
        with obs.span("hot.loop", k=1):
            obs.counter("hot.count").add(1.0)
        if obs.recorder() is not None or obs.watchdog() is not None:
            raise AssertionError("no session: hooks must stay None")
        obs.emit("hot.event", k=1)

    seam()  # warm up any lazy caches
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(200):
        seam()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    growth = sum(s.size_diff for s in after.compare_to(before, "filename")
                 if s.size_diff > 0)
    # 200 no-op seams must not accumulate memory: a handful of KB covers
    # tracemalloc's own bookkeeping noise, while a real per-call record
    # (one dict + one tuple each) would exceed it several-fold
    assert growth < 8192, f"obs=none seam leaked {growth} B over 200 calls"


def test_perf_flag_obs_default_none():
    from repro.perf import flags
    assert flags().obs == "none"
    with obs.session() as sess:  # mode=None resolves from the flag
        assert not sess.enabled


# -- benchmarks/compare.py regression gate ---------------------------------


def _write_bench(path, seconds, err):
    payload = {"schema_version": 2, "git_rev": "test0000",
               "entries": [{"name": "sim[pn16:ugal]",
                            "seconds": seconds, "max_rel_err": err},
                           {"name": "tables[t2]", "seconds": 0.001}],
               "errors": []}
    path.write_text(json.dumps(payload))


def _compare(argv):
    env = dict(os.environ, PYTHONPATH=SRC_PATH)
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.compare", *argv],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True)


def test_compare_flags_synthetic_regression(tmp_path):
    base, new = tmp_path / "BENCH_a.json", tmp_path / "BENCH_b.json"
    _write_bench(base, seconds=10.0, err=0.01)
    _write_bench(new, seconds=12.0, err=0.01)      # +20% wall
    r = _compare([str(base), str(new), "--wall-pct", "15"])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "sim[pn16:ugal]" in r.stdout and "wall" in r.stdout
    # same regression under a generous budget: passes
    r = _compare([str(base), str(new), "--wall-pct", "50"])
    assert r.returncode == 0, r.stdout + r.stderr


def test_compare_parity_regression_and_floors(tmp_path):
    base, new = tmp_path / "BENCH_a.json", tmp_path / "BENCH_b.json"
    _write_bench(base, seconds=10.0, err=0.01)
    _write_bench(new, seconds=10.0, err=0.05)      # 5x parity drift
    r = _compare([str(base), str(new), "--wall-pct", "500"])
    assert r.returncode == 1
    assert "err" in r.stdout
    # microsecond-entry noise stays under the absolute-seconds floor:
    # tables[t2] doubling from 1 ms to 2 ms must NOT trip the gate
    _write_bench(base, seconds=10.0, err=0.01)
    payload = json.loads(new.read_text())
    payload["entries"][0].update(seconds=10.0, max_rel_err=0.01)
    payload["entries"][1]["seconds"] = 0.002
    new.write_text(json.dumps(payload))
    r = _compare([str(base), str(new), "--wall-pct", "15"])
    assert r.returncode == 0, r.stdout + r.stderr


def test_compare_trajectory_mode(tmp_path):
    _write_bench(tmp_path / "BENCH_1.json", seconds=10.0, err=0.01)
    _write_bench(tmp_path / "BENCH_2.json", seconds=10.5, err=0.01)
    _write_bench(tmp_path / "BENCH_3.json", seconds=30.0, err=0.01)
    r = _compare(["--dir", str(tmp_path), "--wall-pct", "100"])
    assert r.returncode == 1                       # the 10.5 -> 30 hop
    r = _compare(["--dir", str(tmp_path), "--wall-pct", "400"])
    assert r.returncode == 0
    r = _compare(["--dir", str(tmp_path), "--glob", "NOPE_*.json"])
    assert r.returncode == 0 and "nothing to compare" in r.stdout


def test_compare_trajectory_presence_and_err_regression(tmp_path):
    # an entry disappearing mid-trajectory is informational, never a
    # failure (sections come and go across PRs)...
    p1 = {"schema_version": 2,
          "entries": [{"name": "sim[a]", "seconds": 1.0,
                       "max_rel_err": 0.01},
                      {"name": "sim[b]", "seconds": 1.0}],
          "errors": []}
    p2 = {"schema_version": 2,
          "entries": [{"name": "sim[a]", "seconds": 1.0,
                       "max_rel_err": 0.01}],
          "errors": []}
    (tmp_path / "BENCH_1.json").write_text(json.dumps(p1))
    (tmp_path / "BENCH_2.json").write_text(json.dumps(p2))
    r = _compare(["--dir", str(tmp_path)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sim[b]" in r.stdout                    # the presence row prints
    # ...but a mid-trajectory parity regression fails on that hop even
    # when wall time is flat and later files stay bad-but-stable
    p3 = dict(p2, entries=[{"name": "sim[a]", "seconds": 1.0,
                            "max_rel_err": 0.2}])
    (tmp_path / "BENCH_3.json").write_text(json.dumps(p3))
    (tmp_path / "BENCH_4.json").write_text(json.dumps(p3))
    r = _compare(["--dir", str(tmp_path), "--wall-pct", "1000"])
    assert r.returncode == 1
    assert "err" in r.stdout


def test_compare_verbose_shows_clean_rows(tmp_path):
    base, new = tmp_path / "BENCH_a.json", tmp_path / "BENCH_b.json"
    _write_bench(base, seconds=10.0, err=0.01)
    _write_bench(new, seconds=10.1, err=0.01)      # within every budget
    r = _compare([str(base), str(new)])
    assert r.returncode == 0
    assert "sim[pn16:ugal]" not in r.stdout        # quiet by default
    r = _compare([str(base), str(new), "-v"])
    assert r.returncode == 0
    assert "sim[pn16:ugal]" in r.stdout            # verbose lists them all


def test_compare_bad_file_fails_loud(tmp_path):
    bad = tmp_path / "BENCH_x.json"
    bad.write_text("{not json")
    good = tmp_path / "BENCH_y.json"
    _write_bench(good, seconds=1.0, err=0.01)
    r = _compare([str(bad), str(good)])
    assert r.returncode == 2
