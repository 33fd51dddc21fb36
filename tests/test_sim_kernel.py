"""The fused sparse-destination step kernel seam (repro.sim.kernel +
repro.kernels.sim_step/mask_gemm) and the PR's sim-reporting fixes.

Parity contract: the dense numpy float64 engine is the oracle.  Both
sparse backends run the one jitted kernel step the TPU runs;
``backend="pallas"`` off a TPU gives its two kernels their ``jnp``
bodies (the kernels' own formulas on whole arrays, round-off-level
identity at float64), and ``backend="pallas_interpret"`` runs the actual
pallas kernels through the interpreter — same fluid, TPU summation
order, so float64 agreement to round-off.  Dest compaction must be
EXACT in both shapes: the minimal-mode active-set shrink, and the
ugal/valiant per-VC compacted dest axis (q0/q2/src/pend-dest on the
demanded columns, q1/stage2 on the full mid axis) — dropping
never-addressed dest columns is a reindexing, not an approximation.

The reporting regressions pinned here:
  * run histories are normalized per fault segment (a pre-event curve
    segment is in pre-event surviving-demand units);
  * saturation_sweep curves include every probe (bracket extensions and
    bisection refinements), sorted by offered load;
  * default_steps sizes from the max distance over the run's fault
    segments, not just the pristine tables.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import mms_graph, pn_graph, random_faults
from repro.core.traffic import make_pattern, normalize_demand
from repro.core.utilization import arc_loads, arc_loads_weighted
from repro.fabric.model import torus3d_graph
from repro.sim import (SIM_MAX_CELLS, SimConfig, Simulator, saturation_sweep)
from repro.sim.kernel import SPARSE_BACKENDS, resolve_dtype

G16 = torus3d_graph(4, 4, 1)
PN3 = pn_graph(3)
# Slim Fly MMS(5) and MMS(7): K = 7 and 11, off the sublane multiple that
# the kernel step lays its out-slot axis at
MMS5, MMS7 = mms_graph(5), mms_graph(7)
ROUTINGS = ["minimal", "valiant", "ugal_threshold(0)"]


def _uniform(g):
    return normalize_demand(make_pattern("uniform").demand(g, None))


def _random_demand(g, seed, density=0.4):
    rng = np.random.default_rng(seed)
    dem = rng.random((g.n, g.n)) * (rng.random((g.n, g.n)) < density)
    np.fill_diagonal(dem, 0.0)
    for r in np.nonzero(dem.sum(axis=1) == 0)[0]:  # no all-zero rows
        dem[r, (r + 1) % g.n] = 0.5
    return normalize_demand(dem)


def _histories_close(a, b, rtol, atol=1e-12):
    for key in ("delivered", "accepted", "offered", "occupancy",
                "src_backlog", "diverted"):
        np.testing.assert_allclose(
            a.history[key], b.history[key], rtol=rtol, atol=atol,
            err_msg=f"history[{key!r}] diverges")


def _run_backend(g, demand, backend, routing="minimal", offered=0.5,
                 steps=24, buffer=float("inf"), events=None):
    cfg = SimConfig(routing=routing, backend=backend, dtype="float64",
                    buffer=buffer)
    return Simulator(g, cfg, demand=demand).run(demand, offered, steps,
                                                events=events)


# ---------------------------------------------------------------------------
# dense reference vs kernel step parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "g,routing",
    [(G16, r) for r in ROUTINGS]
    + [(g, r) for g in (MMS5, MMS7) for r in ROUTINGS],
    ids=ROUTINGS + [f"{g}-{r}" for g in ("mms5", "mms7") for r in ROUTINGS])
def test_fused_step_matches_dense_float64(g, routing):
    """The 'pallas' backend off a TPU (the kernel step with its kernels'
    jnp bodies) against the dense oracle, all routing modes, float64:
    round-off-level identity."""
    dem = _uniform(g)
    offered = 0.7 * g.max_degree / G16.max_degree
    a = _run_backend(g, dem, "numpy", routing, offered=offered)
    b = _run_backend(g, dem, "pallas", routing, offered=offered)
    _histories_close(a, b, rtol=1e-9)
    assert a.residual < 1e-9 and b.residual < 1e-9


@pytest.mark.parametrize("g,seed", [(G16, 0), (G16, 1), (MMS5, 0),
                                    (MMS7, 0)],
                         ids=["0", "1", "mms5-0", "mms7-0"])
def test_interpret_kernel_parity_random_demand(g, seed):
    """The ACTUAL pallas kernel (interpret mode) against the dense numpy
    oracle on random demand with finite buffers and a mid-run fault,
    float64 end to end; on MMS(5) and MMS(7) with the kernel step's
    out-slot axis padded to 8 and 16."""
    dem = _random_demand(g, seed)
    fs = random_faults(g, k_links=3, seed=seed)
    kw = dict(routing="ugal_threshold(0)", offered=0.6, steps=24,
              buffer=6.0, events=[(8, fs)])
    a = _run_backend(g, dem, "numpy", **kw)
    b = _run_backend(g, dem, "pallas_interpret", **kw)
    # the kernel's TPU summation order differs from the dense einsum's;
    # the threshold rule amplifies that round-off through its diversion
    # decisions, so float64 agreement is ~1e-8, not 1e-15
    _histories_close(a, b, rtol=1e-6, atol=1e-9)
    assert b.residual < 1e-7


def test_last_state_shows_the_graphs_out_slots():
    """The kernel step lays MMS(5)'s 7 out-slots at 8; ``last_state``
    shows callers the graph's 7, and equals the dense oracle's state."""
    from repro.sim.kernel import laid_slots
    dem = _uniform(MMS5)
    runs = {}
    for backend in ("numpy", "pallas_interpret"):
        sim = Simulator(MMS5, SimConfig(routing="ugal_threshold(0)",
                                        backend=backend, dtype="float64"),
                        demand=dem)
        sim.run(dem, 3.0, steps=12)
        runs[backend] = (sim, sim.last_state)
    sim, st = runs["pallas_interpret"]
    assert MMS5.max_degree == sim.tables.k == 7
    assert 7 + sim._step.pad == laid_slots(7) == 8
    assert sim._step.tabs["F"]["split"].shape == (MMS5.n, 8, MMS5.n)
    want = runs["numpy"][1]
    for name in ("q0", "q1", "q2"):
        got = getattr(st, name)
        assert got.shape == (MMS5.n, 7, MMS5.n), name
        np.testing.assert_allclose(got, getattr(want, name), rtol=1e-9,
                                   atol=1e-12, err_msg=name)


def test_sublane_multiple_degrees_get_no_padding():
    """At K = 32, the out-slot count of PN(31) (here the complete graph
    on 33 routers), the kernel step's state and tables keep the graph's
    K and the tables are the route tables unchanged."""
    from repro.core.graph import Graph
    from repro.sim.kernel import kernel_program, laid_slots, step_aux
    from repro.sim.tables import build_tables
    assert [laid_slots(k) for k in (24, 32, 41)] == [24, 32, 48]
    n = 33
    i, j = np.triu_indices(n, 1)
    g = Graph(n, np.column_stack([i, j]))
    t = build_tables(g, np.arange(n), dtype=np.float32)
    assert t.k == 32
    cfg = SimConfig(routing="ugal_threshold(0)")
    jitted, tabs = kernel_program(t, cfg, np.float32, "pallas_interpret")
    np.testing.assert_array_equal(np.asarray(tabs["F"]["split"]), t.split)
    np.testing.assert_array_equal(np.asarray(tabs["F"]["deliver"]),
                                  t.deliver)
    np.testing.assert_array_equal(tabs["head_flat"], t.head.reshape(-1))
    aux = step_aux(t)
    np.testing.assert_array_equal(tabs["F"]["fix_arc"], aux.fix_arc)
    np.testing.assert_array_equal(
        tabs["rev"], np.where(aux.rev >= 0, aux.rev, n * 32).reshape(n, 32))
    sim = Simulator(g, SimConfig(routing="ugal_threshold(0)",
                                 backend="pallas_interpret"))
    assert sim._step.pad == 0


def test_sparse_dest_compaction_is_exact():
    """Empty dest columns (a permutation over half the routers) must not
    change the fluid: compacted sparse-dest run == dense run, and the
    compaction must actually have happened."""
    rng = np.random.default_rng(3)
    sub = rng.choice(G16.n, size=8, replace=False)
    dem = np.zeros((G16.n, G16.n))
    dem[sub, np.roll(sub, 1)] = 1.0  # cycle permutation on the subset
    dem = normalize_demand(dem)

    cfg = SimConfig(routing="minimal", backend="pallas", dtype="float64")
    sim = Simulator(G16, cfg, demand=dem)
    assert len(sim.active) == 8  # compacted to the populated columns

    a = _run_backend(G16, dem, "numpy", offered=0.8)
    b = sim.run(dem, 0.8, 24)
    _histories_close(a, b, rtol=1e-9)


def _sparse_cols_demand(g, seed, n_cols=5, n_srcs=6):
    """Demand addressing only a scattered subset of dest columns — the
    shape the per-VC compacted dest axis exists for."""
    rng = np.random.default_rng(seed)
    cols = np.sort(rng.choice(g.n, size=n_cols, replace=False))
    dem = np.zeros((g.n, g.n))
    for c in cols:
        srcs = rng.choice(g.n, size=n_srcs, replace=False)
        dem[srcs, c] = rng.random(n_srcs)
    np.fill_diagonal(dem, 0.0)
    return normalize_demand(dem)


@pytest.mark.parametrize("routing", ["ugal_threshold(0)", "valiant"])
def test_compacted_adaptive_matches_dense_float64(routing):
    """The per-VC compacted dest axis under adaptive routing against the
    all-columns dense float64 oracle — finite buffers and a mid-run
    FaultSet event included, so the compacted surgery path is covered."""
    dem = _sparse_cols_demand(G16, 11)
    fs = random_faults(G16, k_links=3, seed=5)
    a = _run_backend(G16, dem, "numpy", routing, offered=0.6, steps=24,
                     buffer=6.0, events=[(8, fs)])
    cfg = SimConfig(routing=routing, backend="pallas", dtype="float64",
                    buffer=6.0)
    sim = Simulator(G16, cfg, demand=dem)
    assert sim.dest_cols is not None and len(sim.dest_cols) < G16.n
    assert len(sim.active) == G16.n      # the active set stays whole
    b = sim.run(dem, 0.6, 24, events=[(8, fs)])
    _histories_close(a, b, rtol=1e-9)
    assert b.residual < 1e-7


def test_compacted_run_rejects_foreign_demand():
    """A compacted Simulator must refuse a demand addressing columns it
    dropped, not silently lose the fluid."""
    dem = _sparse_cols_demand(G16, 11)
    cfg = SimConfig(routing="ugal_threshold(0)", backend="pallas",
                    dtype="float64")
    sim = Simulator(G16, cfg, demand=dem)
    other = _uniform(G16)
    with pytest.raises(ValueError, match="compact"):
        sim.run(other, 0.5, 8)


def test_fused_decision_interior_blend_parity():
    """torus2d_8x16 tornado at ugal_threshold(0): the blend optimum is
    interior (0 < alpha < 1), so both branches of the fused decision —
    divert and keep — carry fluid.  The kernel step's fused decision (its
    jnp body off a TPU) vs the dense einsum decision, float64."""
    g = torus3d_graph(8, 16, 1)
    dem = normalize_demand(make_pattern("tornado").demand(g, None))
    a = _run_backend(g, dem, "numpy", "ugal_threshold(0)", offered=0.38,
                     steps=40)
    b = _run_backend(g, dem, "pallas", "ugal_threshold(0)", offered=0.38,
                     steps=40)
    _histories_close(a, b, rtol=1e-9)
    assert 0.0 < a.alpha < 1.0       # both decision branches were live


def test_ugal_keeps_active_set_but_compacts_dest_axis():
    """ugal spreads diversions over the whole active set — the active
    set must stay whole — while the FINAL-dest axes compact to the
    demanded columns on the fused backends (and only there)."""
    dem = np.zeros((G16.n, G16.n))
    dem[0, 1] = dem[1, 0] = 1.0
    cfg = SimConfig(routing="ugal_threshold(0)", backend="pallas")
    sim = Simulator(G16, cfg, demand=dem)
    assert len(sim.active) == G16.n
    assert sorted(sim.dest_cols) == [0, 1]
    # dense backends have no index-mapped views: every column stays
    cfg = SimConfig(routing="ugal_threshold(0)", backend="numpy")
    assert Simulator(G16, cfg, demand=dem).dest_cols is None
    # compact="off" is the all-columns baseline on the fused path too
    cfg = SimConfig(routing="ugal_threshold(0)", backend="pallas",
                    compact="off")
    assert Simulator(G16, cfg, demand=dem).dest_cols is None


def test_guard_and_auto_sized_from_compacted_cells(monkeypatch):
    """Backend auto-selection and the SIM_MAX_CELLS guard see the state
    that will actually be allocated: post-shrink dense cells under
    minimal, so a sparse-demand instance over the cap runs dense; under
    ugal the dense guard still fires while auto escalates to the fused
    path and compacts the dest axis."""
    import repro.sim as S
    import repro.sim.engine as E
    dem = np.zeros((G16.n, G16.n))
    dem[0, 1] = dem[1, 0] = 1.0
    cells_full = G16.n * G16.max_degree * G16.n
    monkeypatch.setattr(S, "SIM_MAX_CELLS", cells_full - 1)
    monkeypatch.setattr(E, "SIM_MAX_CELLS", cells_full - 1)
    # minimal: the active set shrinks to 2 columns BEFORE the guard
    sim = Simulator(G16, SimConfig(backend="numpy"), demand=dem)
    assert len(sim.active) == 2
    # without a demand there is nothing to shrink: the guard still fires
    with pytest.raises(ValueError, match="pallas"):
        Simulator(G16, SimConfig(backend="numpy"))
    # ugal keeps every dense cell on dense backends...
    with pytest.raises(ValueError, match="pallas"):
        Simulator(G16, SimConfig(routing="ugal_threshold(0)",
                                 backend="numpy"), demand=dem)
    # ...while auto escalates to the fused path and compacts
    sim = Simulator(G16, SimConfig(routing="ugal_threshold(0)"),
                    demand=dem)
    assert sim.backend == "pallas" and len(sim.dest_cols) == 2


def test_per_dest_stability_fields():
    """per_dest=True fills the per-dest-column stability fields; a run
    far below saturation reads ~1 on every column, and the fields stay
    NaN unless asked for."""
    dem = _sparse_cols_demand(G16, 2)
    cfg = SimConfig(routing="minimal", backend="numpy", dtype="float64")
    sim = Simulator(G16, cfg, demand=dem)
    r = sim.run(dem, 0.3, 30, per_dest=True)
    assert np.isfinite(r.dest_stability_min)
    assert r.dest_stability_min >= 0.98
    assert r.dest_stability_mean >= r.dest_stability_min
    assert np.isnan(sim.run(dem, 0.3, 30).dest_stability_min)


def test_per_dest_knee_sweep():
    sw = saturation_sweep(G16, "uniform", routing="minimal",
                          loads=[0.2], steps=24, refine=0,
                          knee="per_dest")
    assert sw.knee == "per_dest"
    assert all(np.isfinite(r.dest_stability_min) for r in sw.runs)
    assert sw.theta > 0
    with pytest.raises(ValueError, match="knee"):
        saturation_sweep(G16, "uniform", loads=[0.2], knee="sharpest")


def test_backend_and_dtype_resolution():
    assert set(SPARSE_BACKENDS) == {"pallas", "pallas_interpret"}
    assert resolve_dtype("auto", "pallas") == np.float32
    assert resolve_dtype("auto", "numpy") == np.float64
    assert resolve_dtype("float32", "numpy") == np.float32
    with pytest.raises(ValueError):
        resolve_dtype("bf16", "pallas")
    # auto escalates to the sparse step above the dense cell cap, and
    # the sparse backends pass through untouched at any size
    from repro.sim.engine import pick_backend
    assert pick_backend("auto", SIM_MAX_CELLS + 1) == "pallas"
    assert pick_backend("pallas", SIM_MAX_CELLS + 1) == "pallas"
    assert pick_backend("pallas_interpret", 10) == "pallas_interpret"


def test_dense_backend_above_cap_names_the_escape_hatch():
    g27 = pn_graph(27)  # 1514 routers: 64.2M dense cells > SIM_MAX_CELLS
    assert g27.n * g27.max_degree * g27.n > SIM_MAX_CELLS
    with pytest.raises(ValueError, match="pallas"):
        Simulator(g27, SimConfig(backend="numpy"))


# ---------------------------------------------------------------------------
# the compiled step programs: tables are arguments, not constants
# ---------------------------------------------------------------------------


def test_kernel_step_program_holds_no_tables():
    """The lowered pn16 kernel step takes the route tables as an
    argument: its text stays far below the ~86 MB it had when the
    (546, 17, 546) tables were inlined as literals."""
    from repro.sim.engine import init_state, pad_slots
    from repro.sim.kernel import kernel_program, laid_slots
    from repro.sim.tables import build_tables
    g = pn_graph(16)
    t = build_tables(g, np.arange(g.n), dtype=np.float32)
    jitted, tabs = kernel_program(t, SimConfig(routing="ugal_threshold(0)"),
                                  np.float32, "pallas_interpret")
    # the program takes its state laid out: K = 17 at 24 out-slots
    pad = laid_slots(t.k) - t.k
    state = tuple(pad_slots(a, pad) if a.ndim == 3 else a
                  for a in init_state(t, np.float32).as_tuple())
    text = jitted.lower(tabs, state, np.zeros((t.n, t.m), np.float32),
                        np.zeros(t.n, np.float32)).as_text()
    assert len(text) < 1_000_000


@pytest.mark.parametrize("backend", ["pallas_interpret", "jax"])
def test_step_programs_name_their_scopes(backend):
    """The lowered pn16 step programs (the kernel step and the dense jax
    step) carry named scopes around the forward gather, the conversions,
    the injection and the decision, which a profile's ``tf_op`` shows,
    and stay under the same 1 MB with that debug information."""
    import contextlib

    from repro.jaxenv import x64
    from repro.sim.engine import make_step, state_shapes
    from repro.sim.kernel import make_step_sparse
    from repro.sim.tables import build_tables
    g = pn_graph(16)
    cfg = SimConfig(routing="ugal_threshold(0)")
    if backend == "jax":
        dtype, scope = np.float64, x64
        t = build_tables(g, np.arange(g.n), dtype=dtype)
        step = make_step(t, cfg, backend, dtype)
    else:
        dtype, scope = np.float32, contextlib.nullcontext
        t = build_tables(g, np.arange(g.n), dtype=dtype)
        step = make_step_sparse(t, cfg, backend, dtype)
    state = step.zeros(state_shapes(t).as_tuple(), dtype)
    with scope():
        text = step.jitted.lower(step.tabs, state,
                                 np.zeros((t.n, t.m), dtype),
                                 np.zeros(t.n, dtype)).as_text(
                                     debug_info=True)
    for name in ("forward_gather", "conversions", "injection", "decision"):
        assert f"/{name}/" in text, name
    assert len(text) < 1_000_000


@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
def test_fault_states_share_one_compile(backend):
    """Two fault states of one Simulator have tables of the same shapes,
    so the second one compiles nothing."""
    import jax
    dem = _uniform(G16)
    sim = Simulator(G16, SimConfig(routing="ugal_threshold(0)",
                                   backend=backend), demand=dem)
    sim.run(dem, 0.5, 4, events=[(2, random_faults(G16, k_links=2,
                                                   seed=0))])
    compiles = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        r = sim.run(dem, 0.5, 4, events=[(2, random_faults(G16, k_links=2,
                                                           seed=1))])
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert r.faults is not None
    assert compiles == []


def test_pallas_float64_on_tpu_raises(monkeypatch):
    """On a TPU the pallas kernel runs float32; float64 is refused by
    name instead of being compiled (or quietly interpreted), and float32
    builds the kernel step with the Mosaic kernels (``impl="pallas"``)."""
    import jax

    import repro.sim.kernel as K
    from repro import obs
    from repro.sim.tables import build_tables
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = SimConfig(routing="ugal_threshold(0)", backend="pallas",
                    dtype="float64")
    with pytest.raises(ValueError, match="float32 on TPU"):
        Simulator(G16, cfg, demand=_uniform(G16))
    built = []
    monkeypatch.setattr(K, "_make_step_kernel",
                        lambda t, cfg, dtype, impl, dest_cols=None:
                        built.append(impl))
    t = build_tables(G16, np.arange(G16.n), dtype=np.float32)
    with obs.session(mode="metrics") as sess:
        K.make_step_sparse(t, SimConfig(routing="ugal_threshold(0)"),
                           "pallas", np.float32)
    assert built == ["pallas"]
    assert sess.metrics.counter("sim.step_build[pallas_tpu]").value == 1.0


def test_pallas_off_tpu_runs_the_kernel_step_with_jnp_bodies(monkeypatch):
    """Off a TPU, ``backend="pallas"`` builds the one kernel step program
    (``_kernel_step``) with ``impl="jnp"``, and its build counter is the
    only ``sim.step_build[pallas_*]`` counter the session records."""
    import jax

    import repro.sim.kernel as K
    from repro import obs
    assert jax.default_backend() != "tpu"
    specs, programs = [], []
    real = K._kernel_step

    def spy(spec):
        specs.append(spec)
        programs.append(real(spec))
        return programs[-1]

    monkeypatch.setattr(K, "_kernel_step", spy)
    dem = _uniform(G16)
    with obs.session(mode="metrics") as sess:
        sim = Simulator(G16, SimConfig(routing="ugal_threshold(0)",
                                       backend="pallas"), demand=dem)
        sim.run(dem, 0.5, 4)
    assert [s.impl for s in specs] == ["jnp"]
    assert sim._step.jitted is programs[0]
    builds = sorted(n for n in sess.metrics.names()
                    if n.startswith("sim.step_build[pallas"))
    assert builds == ["sim.step_build[pallas_jnp]"]


def _kernel_case(k, m, masked, seed=0):
    """Random float64 inputs of both sim kernels at (N=16, K, M).  The
    out-slots past ``k`` of a K laid at 48 are empty (no fluid, zero
    split and deliver, no backlog), as the kernel step lays them; a
    ``masked`` tile holds no fluid, inflow or candidates."""
    from repro.kernels.sim_step import DEST_TILE
    rng = np.random.default_rng(seed)
    n, kl = 16, -(-k // 8) * 8
    u = lambda *sh: rng.random(sh)
    q, split, deliver = u(n, kl, m), u(n, kl, m), u(n, kl, m) < 0.1
    split[:, k:] = deliver[:, k:] = q[:, k:] = 0.0
    fac, corr, b0 = u(n, kl), u(n, kl), u(n, kl)
    b0[:, k:] = 0.0
    inflow, dist, hval, cand = u(n, m), u(n, m), u(n, m), u(n, m)
    q_val = u(n)
    b0[0] = 0.0                                # a row that never diverts
    mask = np.ones(-(-m // DEST_TILE), np.int32)
    for j in masked:
        mask[j] = 0
        cols = slice(j * DEST_TILE, (j + 1) * DEST_TILE)
        q[:, :, cols] = inflow[:, cols] = cand[:, cols] = 0.0
    upd = (q, split, deliver.astype(np.float64), fac, corr, inflow, mask)
    dec = (b0, split, dist, hval, cand, q_val, mask)
    return upd, dec


@pytest.mark.parametrize("k,m,masked", [(8, 256, ()), (8, 200, ()),
                                        (8, 384, (1,)), (41, 300, ())],
                         ids=["full_tiles", "partial_last_tile",
                              "masked_tile", "k41_laid_at_48"])
def test_jnp_kernel_bodies_match_interpreted_kernels(k, m, masked):
    """Both sim kernels' ``jnp`` bodies against the pallas kernels run by
    the interpreter, float64: whole tiles, a partial last tile, a tile
    the mask skips, and K = 41 laid at 48 out-slots."""
    from repro.jaxenv import x64
    from repro.kernels.sim_step import (DEST_TILE, fused_decision,
                                        fused_step_update)
    upd, dec = _kernel_case(k, m, masked)
    with x64():
        out = {impl: (fused_step_update(*upd, impl=impl),
                      fused_decision(*dec, thr=0.25, impl=impl))
               for impl in ("jnp", "pallas_interpret")}
    (qj, oj), dj = out["jnp"]
    (qi, oi), di = out["pallas_interpret"]
    assert qj.dtype == qi.dtype == np.float64
    np.testing.assert_allclose(qj, qi, rtol=1e-12, atol=1e-14)
    np.testing.assert_allclose(oj, oi, rtol=1e-12, atol=1e-12)
    np.testing.assert_array_equal(dj, di)
    assert 0 < np.count_nonzero(dj) < dj.size   # both decision branches
    for j in masked:
        cols = slice(j * DEST_TILE, (j + 1) * DEST_TILE)
        assert not np.asarray(qj)[:, :, cols].any()
        assert not np.asarray(dj)[:, cols].any()


# ---------------------------------------------------------------------------
# utilization: the mask+GEMM kernel engine
# ---------------------------------------------------------------------------


def test_util_pallas_engine_uniform():
    l0, k0, d0 = arc_loads(PN3, engine="numpy")
    l1, k1, d1 = arc_loads(PN3, engine="pallas")
    np.testing.assert_allclose(l1, l0, rtol=1e-12)
    assert k0 == pytest.approx(k1) and d0 == d1


def test_util_pallas_engine_weighted():
    dem = _random_demand(PN3, 7)
    l0, k0, d0 = arc_loads_weighted(PN3, dem, engine="numpy")
    l1, k1, d1 = arc_loads_weighted(PN3, dem, engine="pallas")
    np.testing.assert_allclose(l1, l0, rtol=1e-12, atol=1e-12)
    assert k0 == pytest.approx(k1) and d0 == d1


def test_util_pallas_engine_targets_mask():
    mask = np.zeros(PN3.n, dtype=bool)
    mask[:PN3.n // 2] = True
    l0, k0, d0 = arc_loads(PN3, targets_mask=mask, engine="numpy")
    l1, k1, d1 = arc_loads(PN3, targets_mask=mask, engine="pallas")
    np.testing.assert_allclose(l1, l0, rtol=1e-12, atol=1e-12)
    assert k0 == pytest.approx(k1) and d0 == d1


# ---------------------------------------------------------------------------
# reporting regressions
# ---------------------------------------------------------------------------


def test_history_normalized_per_fault_segment():
    """A router-killing event shrinks the surviving demand; each history
    segment must be in ITS OWN segment's units.  The offered series is
    then ~constant at the offered load across the event — the pre-event
    segment used to be inflated by pristine/final."""
    dem = _uniform(G16)
    fs = random_faults(G16, k_links=4, k_routers=1, seed=0)
    cfg = SimConfig(routing="minimal", backend="numpy", dtype="float64")
    sim = Simulator(G16, cfg, demand=dem)
    ev_step = 12
    r = sim.run(dem, 0.5, 30, events=[(ev_step, fs)])
    offered = r.history["offered"]
    np.testing.assert_allclose(offered[:ev_step], 0.5, rtol=1e-12)
    np.testing.assert_allclose(offered[ev_step:], 0.5, rtol=1e-12)
    # and theta stays in FINAL-state units (comparable to degraded_report)
    assert r.theta <= 0.5 + 1e-9


def test_sweep_curve_includes_all_probes():
    """A grid placed entirely below the knee: the returned curve must
    contain the bracket-extension and bisection probes, sorted."""
    sw = saturation_sweep(G16, "uniform", routing="minimal",
                          loads=[0.05, 0.1], steps=24, refine=2)
    assert len(sw.loads) == len(sw.runs) > 2
    assert np.all(np.diff(sw.loads) >= 0)
    assert sw.loads.max() > 0.1  # an extension probe made it into the curve
    for arr in (sw.delivered, sw.latency, sw.alpha):
        assert len(arr) == len(sw.loads)


def test_default_steps_sizes_from_fault_segments():
    """links[0-1,0-4,4-7,8-12] grows the 4x4 torus diameter 4 -> 5, so a
    run carrying that event must size longer than the pristine run."""
    sim = Simulator(G16, SimConfig(), demand=_uniform(G16))
    fs = random_faults(G16, k_links=4, seed=2)
    tb, _ = sim._tables_for(fs)
    assert tb.dist_act.max() > sim.tables.dist_act.max()  # fixture holds
    assert sim.default_steps(events=[(4, fs)]) > sim.default_steps()
