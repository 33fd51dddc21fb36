"""repro.sim — a JAX-vectorized flow-level network simulator: the
queueing-dynamics ground truth behind the analytical theta tables.

The analytical stack (repro.core.traffic / routing) prices every topology
under *fluid* routing models: loads are closed-form path integrals, UGAL
is the theta-optimal convex blend.  Real routers make per-hop decisions
on local queue state, divert only past a threshold, and run out of buffer
— none of which a closed form sees.  This package replays the same
demand matrices (every ``TrafficPattern``, ad-hoc matrices, and the
placement pipeline's byte matrices) through a time-stepped simulator
whose inner loop is fully vectorized over ``(router, out-slot, dest)``
tensors — numpy float64 as the reference backend, a jit-compiled JAX
step for large instances — with:

  * ``minimal`` / ``valiant`` / per-hop ``ugal_threshold(T)`` router
    models (UGAL-L on local output-queue backlog),
  * three virtual channels (minimal, Valiant leg 1, leg 2) with finite
    per-router buffers and credit-based backpressure,
  * open-loop injectors driven by any pattern from the traffic registry.

Entry points
------------
``simulate(g, pattern, routing=..., offered=...)`` runs one offered load
and reports delivered throughput, Little's-law mean latency, and the
measured minimal fraction alpha.  ``saturation_sweep`` ramps offered
load, returns the latency-vs-load curve plus the measured saturation
throughput ``theta`` — directly comparable to the analytic
``saturation_report`` theta in the zero-threshold / infinite-buffer
limit (the parity seam tested in tests/test_sim.py and benchmarked into
BENCH_5.json).  ``simulate_placement`` replays a (StepProfile,
Placement) byte matrix with fabric.placement's busiest-chip
normalization, so measured theta is comparable to ``placement_report``.

See docs/simulation.md for the step semantics, the credit model, the
threshold rule, and the exact parity conditions.
"""

from __future__ import annotations

import hashlib
import time
from collections import deque
from dataclasses import asdict, dataclass, field

import numpy as np

from .. import obs
from ..core.graph import Graph
from ..core.traffic import make_pattern, normalize_demand, saturation_report
from ..obs import balance_stats
from .engine import (SIM_JAX_MIN_WORK, SIM_MAX_CELLS, BoundStep, SimConfig,
                     SimState, init_state, make_step, parse_sim_routing,
                     pick_backend, state_shapes)
from .faults import FaultEvent, apply_fault_surgery, normalize_events
from .kernel import (SPARSE_BACKENDS, make_step_sparse, resolve_dtype,
                     step_planes)
from .tables import RouteTables, build_tables

__all__ = [
    "SimConfig", "SimRun", "SimSweep", "Simulator", "simulate",
    "saturation_sweep", "simulate_placement", "fluid_routing_spec",
    "FaultEvent", "DEFAULT_LOAD_GRID", "SIM_MAX_CELLS",
]

# offered-load grid of a sweep, as fractions of the analytic fluid theta:
# four sub-saturation points for the latency curve plus one past
# saturation to pin the delivered-throughput plateau
DEFAULT_LOAD_GRID = (0.3, 0.6, 0.85, 1.0, 1.2)


def fluid_routing_spec(sim_routing) -> str:
    """The repro.core.routing spec whose fluid theta the simulator
    converges to in the zero-threshold / infinite-buffer limit:
    ``minimal`` and ``valiant`` map to themselves, every finite
    ``ugal_threshold(T)`` to the exact ``ugal`` blend — and T = inf to
    ``minimal``, since an infinite margin never diverts (same
    degeneration as the core registry's analytic entry)."""
    mode, t = parse_sim_routing(sim_routing)
    if mode == "ugal" and np.isinf(t):
        return "minimal"
    return {"minimal": "minimal", "valiant": "valiant", "ugal": "ugal"}[mode]


@dataclass
class SimRun:
    """Steady-state measurements of one (demand, routing, offered) run.

    ``theta`` is the delivered per-step throughput in the demand's own
    normalization (busiest source = 1 unit for registry patterns, so it
    is directly comparable to the analytic theta); ``latency`` the
    Little's-law mean steps in the network (>= mean hops; meaningful
    below saturation — past it, it grows with the run length);
    ``alpha`` the measured fraction of accepted fluid that was never
    diverted; ``residual`` the relative flow-conservation defect.
    ``dest_stability_min`` / ``dest_stability_mean`` are the per-dest-
    column delivered/offered ratios over the trailing window (NaN unless
    the run was asked for them with ``per_dest=True``) — the sharp knee
    criterion for asymmetric sparse demand, where a handful of saturated
    columns hide inside a healthy aggregate ratio."""

    routing: str
    offered: float
    theta: float
    delivered_rate: float
    accepted_rate: float
    latency: float
    alpha: float
    occupancy: float
    src_backlog: float
    residual: float
    steps: int
    window: int
    backend: str
    dropped: float = 0.0         # fluid lost to fault surgery (cumulative)
    faults: str | None = None    # final fault state's label, if any
    dest_stability_min: float = float("nan")
    dest_stability_mean: float = float("nan")
    history: dict = field(repr=False, default_factory=dict)


@dataclass
class SimSweep:
    """A latency-vs-offered-load curve plus the measured saturation
    throughput.

    ``theta`` is the knee of the throughput curve: the largest offered
    load the fabric demonstrably sustains (delivered/offered >=
    ``stable_ratio`` over the measurement window), refined by bisection
    between the last stable and first unstable probe.  Past the knee an
    open-loop fluid network *collapses* (sustained over-injection lets
    young fluid crowd transit fluid out of the proportional arc shares),
    so the over-saturated delivered rate understates capacity — the knee,
    not the plateau, is the analytic theta's counterpart.
    ``theta_unstable`` is the smallest offered load observed to collapse
    (the bracket's other side; inf if every probe was stable),
    ``theta_analytic`` the fluid-model reference that scaled the grid.
    ``knee`` records which stability criterion decided the bracket:
    ``aggregate`` (delivered/offered over all columns) or ``per_dest``
    (the MINIMUM per-dest-column ratio — sharper for sparse asymmetric
    demand, see :meth:`Simulator.run`)."""

    pattern: str
    routing: str
    theta: float
    theta_unstable: float
    theta_analytic: float
    stable_ratio: float
    loads: np.ndarray
    delivered: np.ndarray
    latency: np.ndarray
    alpha: np.ndarray
    knee: str = "aggregate"
    runs: list = field(repr=False, default_factory=list)


class Simulator:
    """One compiled simulator instance: routing tables + a backend step
    function for a ``(graph, active set, config)`` triple, reusable
    across demand matrices and offered loads (one jit compilation serves
    a whole sweep)."""

    def __init__(self, g: Graph, config: SimConfig = SimConfig(),
                 targets_mask: np.ndarray | None = None,
                 demand: np.ndarray | None = None):
        self.g = g
        self.config = config
        if config.compact not in ("auto", "off"):
            raise ValueError(f"unknown compact mode {config.compact!r}; "
                             f"options: auto, off")
        if targets_mask is None:
            targets_mask = g.meta.get("leaf_mask")
        self.active = (np.arange(g.n) if targets_mask is None
                       else np.nonzero(np.asarray(targets_mask, bool))[0])
        m_dense = len(self.active)
        used = None
        if demand is not None and config.compact == "auto":
            used = np.asarray(demand)[:, self.active].sum(axis=0) > 0
        # Static dest compaction, phase 1 — the active set itself.  Under
        # minimal routing every dest column evolves independently, so
        # dropping the columns ``demand`` never addresses is exact on
        # EVERY backend; shrinking BEFORE backend selection also sizes
        # the auto choice and the dense-cell guard to the state that
        # will actually be allocated (a sparse-demand pn27 fits the
        # jit-compiled jax step without ever needing the fused path).
        if used is not None and config.mode == "minimal" and not used.all():
            self.active = self.active[used]
            used = None
        dense_cells = g.n * g.max_degree * len(self.active)
        self.backend = pick_backend(config.backend, dense_cells)
        if (self.backend not in SPARSE_BACKENDS
                and dense_cells > SIM_MAX_CELLS):
            raise ValueError(
                f"simulation state is dense (router, out-slot, dest) "
                f"tensors: {dense_cells} cells > "
                f"SIM_MAX_CELLS={SIM_MAX_CELLS} "
                f"(~{8 * 3 * SIM_MAX_CELLS >> 30} GB of queue state).  "
                f"Use backend='pallas' (the blocked sparse-dest step) or "
                f"a smaller instance of the same family.")
        # phase 2 — the per-VC dest axis.  ugal/valiant spread diversions
        # over the whole active set, so the active set must stay whole —
        # but only the FINAL-destination axes need the demanded columns.
        # The fused backends carry q0/q2/src and the PEND pool's dest
        # axis on the compacted columns while q1/stage2 keep the full
        # mid axis (repro.sim.kernel); the stage-2 closure is the
        # demanded set itself (diverted fluid keeps its destination), so
        # this is exact — and what lets a pn27-class fabric sweep
        # adaptively.
        self.dest_cols = None
        if (used is not None and config.mode in ("ugal", "valiant")
                and self.backend in SPARSE_BACKENDS and not used.all()):
            self.dest_cols = np.nonzero(used)[0]
        m_comp = (len(self.active) if self.dest_cols is None
                  else len(self.dest_cols))
        obs.gauge("sim.dest_cols.dense").set(float(m_dense))
        obs.gauge("sim.dest_cols.compacted").set(float(m_comp))
        obs.gauge("sim.compact_ratio").set(m_comp / max(m_dense, 1))
        # dense backends default to float64 (the jax step runs under a
        # scoped x64 — float32 rounding bias visibly shifts the
        # threshold rule's diversion duty cycle); the fused sparse-dest
        # backends default to float32, the TPU-native dtype, with the
        # dense float64 path as their parity oracle
        self.dtype = resolve_dtype(config.dtype, self.backend)
        with obs.span("sim.build_tables", backend=self.backend, n=g.n,
                      dests=len(self.active)):
            self.tables, self._step = self._build()
        # the jitted steps read their state on the device: host states
        # are placed explicitly (sim.state_put), never inside a step call
        bound = isinstance(self._step, BoundStep)
        self._put = self._step.put if bound else None
        obs.counter(f"sim.backend[{self.backend}]").add(1.0)
        # per build, so that a ratio of two reads the same in any session;
        # the step may lay its state at empty out-slots past the graph's K
        obs.counter("sim.arcs").add(float(len(g.indices)))
        obs.counter("sim.out_slots").add(float(self.tables.k))
        obs.counter("sim.out_slots_laid").add(
            float(self.tables.k + (self._step.pad if bound else 0)))
        # fault-state label -> (tables, step); the jitted steps compile
        # once per table shape, so fault states share one program
        self._fault_cache: dict = {}
        # the last run's final state where its step left it, and its host
        # copy once `last_state` has been read
        self._final = self._final_host = None

    def _build(self, fs=None):
        """``(tables, step)`` for fault state ``fs``.  Span
        ``sim.route_tables`` holds the host tables and the dense planes
        a jitted step reads, made on the device (``step_planes``)."""
        with obs.span("sim.route_tables") as sp:
            tb = build_tables(self.g, self.active, dtype=self.dtype,
                              faults=fs)
            planes = step_planes(tb, self.backend, self.dtype,
                                 self.dest_cols)
            if planes is not None:
                sp.sync(planes)
        return tb, self._make_step(tb)

    def _make_step(self, tb):
        if self.backend in SPARSE_BACKENDS:
            return make_step_sparse(tb, self.config, self.backend,
                                    self.dtype, dest_cols=self.dest_cols)
        return make_step(tb, self.config, self.backend, self.dtype)

    def _tables_for(self, fs):
        """Route tables + step function for one fault state (None or an
        empty FaultSet = the pristine pair)."""
        if fs is None or fs.empty:
            return self.tables, self._step
        key = fs.label
        if key not in self._fault_cache:
            with obs.span("sim.fault_tables", label=key):
                self._fault_cache[key] = self._build(fs)
        return self._fault_cache[key]

    def _place(self, st, sp):
        """Host state ``st`` on the jitted step's device, counted in
        ``sim.state_put_bytes`` and synced on span ``sp``."""
        st = self._put(st)
        sp.sync(st)
        obs.counter("sim.state_put_bytes").add(
            float(sum(a.nbytes for a in st)))
        return st

    def _view(self, st):
        """The step's state ``st`` with the graph's K out-slots."""
        return st if self._put is None else self._step.view(st)

    def _fetch(self, st):
        """State ``st`` as host arrays with the graph's K out-slots; what
        comes off the jitted step's device is counted in
        ``sim.state_fetch_bytes``."""
        if self._put is not None:
            obs.counter("sim.state_fetch_bytes").add(
                float(sum(a.nbytes for a in st)))
        return tuple(np.asarray(a) for a in self._view(st))

    @property
    def last_state(self) -> SimState | None:
        """The last run's final fluid state as host arrays (None before
        any run).  A jitted step's state is copied off the device on the
        first read, in span ``sim.state_fetch``, and cached."""
        if self._final_host is None and self._final is not None:
            with obs.span("sim.state_fetch"):
                self._final_host = SimState(*self._fetch(self._final))
            if self._put is not None:
                obs.counter("sim.last_state_fetches").add(1.0)
            self._final = None
        return self._final_host

    def default_steps(self, events=None) -> int:
        """Enough steps for the slowest feedback loop to settle: several
        two-leg traversals plus a fixed transient allowance.  Fault
        ``events`` can grow distances when the fabric degrades, so the
        sizing takes the max distance over every fault segment's tables
        (cached — a run with the same schedule reuses them)."""
        dmax = int(self.tables.dist_act.max())
        for e in normalize_events(events):
            if e.faults is not None and not e.faults.empty:
                tb, _ = self._tables_for(e.faults)
                dmax = max(dmax, int(tb.dist_act.max()))
        return 48 + 16 * 2 * dmax

    def run(self, demand: np.ndarray, offered: float,
            steps: int | None = None, window: int | None = None,
            events=None, per_dest: bool = False) -> SimRun:
        """Open-loop run: every source offers ``offered * demand[s, :]``
        per step; measurements average the trailing ``window`` steps.
        ``demand`` is a dense (N, N) matrix in the caller's normalization
        (diagonal and inactive columns must be zero).

        ``events`` is a fault schedule — FaultEvents or ``(step,
        FaultSet)`` pairs, each the CUMULATIVE fault state from that step
        on (recovery = a later event with fewer faults).  At each
        boundary the run swaps in tables compiled for the new fault state
        and passes the live fluid through
        :func:`repro.sim.faults.apply_fault_surgery`; sources stop being
        offered fluid toward unroutable dests for the duration.  theta is
        measured against the FINAL fault state's surviving demand, so a
        static fault (one event at step 0) is directly comparable to the
        analytic ``degraded_report`` theta.  Mind the window: trailing
        measurements should sit after the last event to read steady
        state.

        Under an active :mod:`repro.obs` session the run publishes its
        conservation counters (``sim.injected`` / ``sim.delivered`` /
        ``sim.accepted`` / ``sim.diverted`` / ``sim.dropped`` — the SAME
        floats this method's own residual/alpha accounting uses, so they
        match the returned :class:`SimRun` bit-exactly) plus the
        link-utilization balance statistics; with per-step series
        capture on (trace mode) also the per-VC occupancy series and the
        per-dest-column stability metric.  See docs/observability.md.

        ``per_dest=True`` additionally tracks per-dest-column mass
        conservation over the trailing window and fills the run's
        ``dest_stability_min`` / ``dest_stability_mean`` fields: the
        per-column delivered/offered ratio that
        ``saturation_sweep(knee="per_dest")`` uses as its (sharper)
        stability criterion for asymmetric sparse demand.  Costs one
        host-side pass over the final-dest tensors per window step."""
        with obs.span("sim.run", routing=self.config.routing,
                      offered=float(offered), backend=self.backend):
            return self._run(demand, offered, steps, window, events,
                             per_dest)

    def _run(self, demand, offered, steps, window, events,
             per_dest=False) -> SimRun:
        with obs.span("sim.run_inputs"):
            t = self.tables
            demand = np.asarray(demand, dtype=np.float64)
            if demand.shape != (t.n, t.n):
                raise ValueError(f"demand is {demand.shape}, graph has "
                                 f"N={t.n}")
            inj_norm = demand[:, t.active]
            lost = demand.sum() - inj_norm.sum()
            if lost > 1e-9 * max(demand.sum(), 1.0):
                raise ValueError("demand addresses routers outside the "
                                 "active set; pass a matching targets_mask")
            if (np.abs(np.diagonal(demand)).sum()
                    > 1e-9 * max(demand.sum(), 1.0)):
                raise ValueError("demand has self-addressed (diagonal) "
                                 "entries; zero the diagonal "
                                 "(TrafficPattern.demand and "
                                 "placement_demand already do)")
            if inj_norm.sum() <= 0:
                raise ValueError("demand matrix is all zero")
            cols = self.dest_cols
            if cols is not None:
                off_cols = inj_norm.sum(axis=0)
                outside = float(off_cols.sum() - off_cols[cols].sum())
                if outside > 1e-9 * max(float(off_cols.sum()), 1.0):
                    raise ValueError(
                        "demand addresses destination columns outside the "
                        "compacted dest axis this Simulator was built for; "
                        "rebuild with Simulator(demand=...) covering them, "
                        "or SimConfig(compact='off')")
                inj_norm_run = inj_norm[:, cols]
            else:
                inj_norm_run = inj_norm
            evs = normalize_events(events)
            steps = (self.default_steps(events=evs) if steps is None
                     else int(steps))
            window = max(steps // 3, 8) if window is None else int(window)
            window = min(window, steps)

            if evs and evs[-1].step >= steps:
                raise ValueError(f"fault event at step {evs[-1].step} is "
                                 f"past the run's {steps} steps")
            # segments of constant fault state: (start, end, FaultSet | None)
            marks = ([] if evs and evs[0].step == 0 else [(0, None)])
            marks += [(e.step, e.faults) for e in evs]
            segs = [(s0, (marks[i + 1][0] if i + 1 < len(marks)
                          else steps), fs)
                    for i, (s0, fs) in enumerate(marks)]

            inj = (offered * inj_norm_run).astype(self.dtype)
        # the state lives where the step reads it (the device, for the
        # jitted steps) from its zeros to each segment's end; a step-0
        # fault's surgery runs on the host zeros before placement.  The
        # previous run's final state goes first, so it never shares the
        # device with this run's.
        self._final = self._final_host = None
        with obs.span("sim.state_put") as sp:
            if self._put is not None and segs[0][2] is None:
                st = self._step.zeros(state_shapes(t, cols).as_tuple(),
                                      self.dtype)
                sp.sync(st)
                obs.counter("sim.state_device_zeros").add(1.0)
            else:
                st = init_state(t, self.dtype, dest_cols=cols).as_tuple()
        hist = np.empty((steps, 6), dtype=np.float64)
        # per-step surviving-demand total: each fault segment's history
        # is normalized by ITS OWN fault state's surviving demand, not
        # the final one — a pre-event curve segment is in pre-event units
        seg_total = np.empty(steps, dtype=np.float64)
        dropped_total = 0.0
        tb = t
        # per-step series capture is opt-in (an active obs session with
        # series on): `cap is None` is the only per-step cost otherwise
        sess = obs.current()
        cap = (_SimCapture(sess, self.config, steps, window)
               if sess is not None and sess.enabled and sess.series
               else None)
        # flight recorder + watchdog ride the same seam: armed only when
        # the session carries them, `mon is None` is the whole cost
        # otherwise (the obs-off overhead guard covers this hook too)
        rec = sess.recorder if sess is not None and sess.enabled else None
        wd = sess.watchdog if sess is not None and sess.enabled else None
        if wd is not None and wd.exhausted:
            wd = None
        mon = None
        if rec is not None or wd is not None:
            if wd is not None:
                fp = hashlib.sha256(
                    np.ascontiguousarray(inj_norm).tobytes()).hexdigest()
                wd.begin_run(config=asdict(self.config),
                             backend=self.backend,
                             offered=float(offered), steps=steps,
                             window=window, n=t.n,
                             dests=len(self.active),
                             demand_fingerprint=fp[:16])
            mon = _StepMonitor(rec, wd)
        # per-dest-column conservation over the trailing window (the
        # per-dest knee criterion): mass snapshots at the window edges
        # plus the offered inflow between them, exactly the accounting
        # _SimCapture.finalize publishes as sim.dest_stability
        win_start = steps - window
        pd_mass0 = pd_off = pd_last = None
        for s0, s1, fs in segs:
            tb, step_fn = self._tables_for(fs)
            if fs is not None:
                # on the host: the zero state, or the previous segment's
                # fetched at its end
                with obs.span("sim.fault_surgery", label=fs.label,
                              step=s0):
                    st, dropped = apply_fault_surgery(st, tb,
                                                      dest_cols=cols)
                dropped_total += dropped
                obs.counter("sim.fault_events").add(1.0)
                if self._put is not None:
                    with obs.span("sim.state_put") as sp:
                        st = self._place(st, sp)
            rt = tb.routable if cols is None else tb.routable[:, cols]
            inj_seg = (inj * rt).astype(self.dtype) if tb.faulted else inj
            inj_cap = (self.config.inj_factor
                       * inj_seg.sum(axis=1)).astype(self.dtype)
            seg_total[s0:s1] = float((inj_norm * tb.routable).sum()
                                     if tb.faulted else inj_norm.sum())
            if cap is not None:
                cap.set_segment(tb, inj_seg)
            off_dest = (np.asarray(inj_seg, np.float64).sum(axis=0)
                        if per_dest else None)
            if mon is not None:
                mon.set_segment(
                    float(seg_total[s0]),
                    (off_dest if off_dest is not None else
                     np.asarray(inj_seg, np.float64).sum(axis=0))
                    if mon.stab_win else None,
                    dropped_total)
            for i in range(s0, s1):
                with obs.span("sim.step_dispatch"):
                    st, stats = step_fn(st, inj_seg, inj_cap)
                with obs.span("sim.stats_pull"):
                    hist[i] = np.asarray(stats, dtype=np.float64)
                if (cap is not None or mon is not None
                        or per_dest and i >= win_start):
                    # what the observers read: the graph's K out-slots
                    vs = self._view(st)
                if cap is not None:
                    cap.on_step(i, vs, hist[i])
                if mon is not None:
                    mon.on_step(i, vs, hist[i])
                if per_dest and i >= win_start:
                    dm = _dest_mass_host(vs)
                    if pd_mass0 is None:
                        pd_mass0 = dm
                        pd_off = np.zeros_like(dm)
                    else:
                        pd_off = pd_off + off_dest
                    pd_last = dm
            if s1 < steps:
                with obs.span("sim.state_fetch"):
                    st = self._fetch(st)
        # the final state stays where the step left it: the sweep reads
        # only the histories, and `last_state` fetches it on demand
        with obs.span("sim.state_fetch"):
            self._final = st

        with obs.span("sim.run_result"):
            # theta in the FINAL fault state's surviving demand units — the
            # value the analytic degraded_report theta is comparable to
            total = float(seg_total[-1])
            if total <= 0:
                raise ValueError("faults removed every offered demand")
            # a mid-run segment can have zero surviving demand (recovered
            # later); its normalized history rows are identically zero
            norm = np.where(seg_total > 0, seg_total, np.inf)
            w = hist[-window:]
            delivered_rate = float(w[:, 0].mean())
            accepted_rate = float(w[:, 1].mean())
            occupancy = float(w[:, 3].mean())
            src_backlog = float(hist[-1, 4])
            injected_cum = float(hist[:, 2].sum())
            delivered_cum = float(hist[:, 0].sum())
            residual = abs(injected_cum - delivered_cum - float(hist[-1, 3])
                           - src_backlog - dropped_total) \
                / max(injected_cum, 1e-30)
            acc_cum = float(hist[:, 1].sum())
            div_cum = float(hist[:, 5].sum())
            alpha = 1.0 - div_cum / max(acc_cum, 1e-30)
            latency = occupancy / max(delivered_rate, 1e-30)
            dest_stab_min = dest_stab_mean = float("nan")
            if per_dest and pd_last is not None and pd_off is not None:
                sel = pd_off > 0
                if sel.any():
                    delivered_d = pd_mass0 - pd_last + pd_off
                    stab = np.clip(delivered_d[sel] / pd_off[sel], 0.0, None)
                    dest_stab_min = float(stab.min())
                    dest_stab_mean = float(stab.mean())
            final_fs = segs[-1][2]
            if sess is not None and sess.enabled:
                # publish the run's own accounting: the SAME float values the
                # residual/alpha identities above consumed, so the counters
                # are bit-exact with the returned SimRun (pinned in
                # tests/test_obs.py, mid-run fault surgery included)
                m = sess.metrics
                m.counter("sim.runs").add(1.0)
                m.counter("sim.steps").add(float(steps))
                m.counter("sim.injected").add(injected_cum)
                m.counter("sim.delivered").add(delivered_cum)
                m.counter("sim.accepted").add(acc_cum)
                m.counter("sim.diverted").add(div_cum)
                m.counter("sim.dropped").add(dropped_total)
                m.gauge("sim.final_occupancy").set(float(hist[-1, 3]))
                m.gauge("sim.final_src_backlog").set(src_backlog)
                m.gauge("sim.residual").set(residual)
                m.gauge("sim.alpha").set(alpha)
                m.gauge("sim.delivered_rate").set(delivered_rate)
                m.gauge("sim.theta").set(delivered_rate / total)
                if cap is not None:
                    cap.finalize()
                else:
                    # cheap one-shot balance proxy: the FINAL state's per-arc
                    # occupancy clipped at capacity (below saturation every
                    # queue drains each step, so this IS the per-link flit
                    # rate); the window-averaged sim.link_util histogram
                    # needs per-step series capture
                    ls = self.last_state
                    o_tot = (np.asarray(ls.q0, np.float64).sum(-1)
                             + np.asarray(ls.q1, np.float64).sum(-1)
                             + np.asarray(ls.q2, np.float64).sum(-1))
                    capacity = float(self.config.capacity)
                    util = (np.minimum(o_tot[np.asarray(tb.slot_ok, bool)],
                                       capacity) / capacity)
                    m.histogram("sim.link_util_final").observe_many(util)
                    _publish_balance(m, util)
            return SimRun(
                routing=self.config.routing, offered=float(offered),
                theta=delivered_rate / total, delivered_rate=delivered_rate,
                accepted_rate=accepted_rate, latency=latency, alpha=alpha,
                occupancy=occupancy, src_backlog=src_backlog,
                residual=residual,
                steps=steps, window=window, backend=self.backend,
                dropped=dropped_total,
                faults=(None if final_fs is None or final_fs.empty
                        else final_fs.label),
                dest_stability_min=dest_stab_min,
                dest_stability_mean=dest_stab_mean,
                history={"delivered": hist[:, 0] / norm,
                         "accepted": hist[:, 1] / norm,
                         "offered": hist[:, 2] / norm,
                         "occupancy": hist[:, 3], "src_backlog": hist[:, 4],
                         "diverted": hist[:, 5],
                         "fault_events": np.array([e.step for e in evs],
                                                  dtype=np.int64)})


def _dest_mass_host(st):
    """Per-FINAL-dest fluid mass of a step state, host-side: vc0 + vc2
    queues + source backlog + the (mid, dest) pool column sums.  vc1 and
    stage2 fluid is addressed to intermediates and its final-dest split
    IS the pend pool (the invariant repro.sim.faults documents), so
    adding it would double count.  Width follows the state's dest axis
    (compacted or dense)."""
    q0, q1, q2, src, pend, stage2 = (np.asarray(a, np.float64) for a in st)
    return (q0.sum(axis=(0, 1)) + q2.sum(axis=(0, 1))
            + src.sum(axis=0) + pend.sum(axis=0))


def _publish_balance(m, util) -> None:
    """Gauge the balance statistics of a per-link utilization vector —
    the paper's balanced-utilization thesis as a measured number."""
    bs = balance_stats(util)
    m.gauge("sim.balance.gini").set(bs["gini"])
    m.gauge("sim.balance.p99_over_mean").set(bs["p99_over_mean"])
    m.gauge("sim.balance.max_over_mean").set(bs["max_over_mean"])


class _SimCapture:
    """Per-step series capture for one :meth:`Simulator.run` under an
    active obs session with series on (trace mode by default).

    Publishes per-VC occupancy / injection-stall / diverted-fraction
    series, accumulates the trailing window's per-arc forwarded mass
    into the measured ``sim.link_util`` histogram + balance gauges, and
    takes per-dest mass snapshots at the window edges for the
    per-dest-column stability metric ``sim.dest_stability`` — the sharp
    per-dest knee criterion that supersedes the aggregate
    delivered/offered ("mushy knee") diagnosis for asymmetric sparse
    demand.  All sums run host-side on the post-step state (one extra
    pass over the queue tensors per step — the documented cost of series
    capture; a jax-backend state is synced to host each captured step).
    """

    def __init__(self, sess, cfg: SimConfig, steps: int, window: int):
        m = sess.metrics
        self.m = m
        self.cap = float(cfg.capacity)
        self.win_start = steps - window
        self.s_vc0 = m.series("sim.occ_vc0")
        self.s_vc1 = m.series("sim.occ_vc1")
        self.s_vc2 = m.series("sim.occ_vc2")
        self.s_src = m.series("sim.src_backlog")
        self.s_div = m.series("sim.diverted_frac")
        self.s_stall = m.series("sim.inj_stalled")
        self.tb = None
        self.off_dest = None    # (M,) per-step offered mass per dest
        self.util_sum = None    # (N, K) window forwarded-mass accumulator
        self.n_win = 0
        self.mass0 = None       # per-dest mass at the first window step
        self.off_acc = None     # offered mass between the mass snapshots
        self.mass_last = None

    def set_segment(self, tb, inj_seg) -> None:
        self.tb = tb
        self.off_dest = np.asarray(inj_seg, np.float64).sum(axis=0)

    def on_step(self, i: int, st, row) -> None:
        q0, q1, q2, src, pend, stage2 = \
            (np.asarray(a, np.float64) for a in st)
        self.s_vc0.append(float(q0.sum()))
        # stage2 fluid is converted-but-unlaunched phase-1 mass: it sits
        # between vc1 and vc2, counted with vc1 (where its credit lives)
        self.s_vc1.append(float(q1.sum() + stage2.sum()))
        self.s_vc2.append(float(q2.sum()))
        self.s_src.append(float(row[4]))
        self.s_div.append(float(row[5] / max(row[1], 1e-30)))
        self.s_stall.append(float(max(row[2] - row[1], 0.0)))
        if i < self.win_start:
            return
        # forwarded mass next step = min(occupancy, capacity) per arc
        # (processor sharing); sampled post-step — over a steady-state
        # window the one-step offset is immaterial
        o_tot = q0.sum(-1) + q1.sum(-1) + q2.sum(-1)
        if self.util_sum is None:
            self.util_sum = np.zeros_like(o_tot)
            self.mass0 = self._dest_mass(q0, q2, src, pend)
            self.off_acc = np.zeros_like(self.mass0)
        else:
            self.off_acc = self.off_acc + self.off_dest
        self.util_sum += np.minimum(o_tot, self.cap)
        self.n_win += 1
        self.mass_last = self._dest_mass(q0, q2, src, pend)

    @staticmethod
    def _dest_mass(q0, q2, src, pend):
        # per-FINAL-dest fluid mass: vc0 + vc2 queues + source backlog +
        # the (mid, dest) pool column sums.  vc1/stage2 fluid is
        # addressed to intermediates and its final-dest split IS the
        # pend pool (the invariant repro.sim.faults documents), so
        # adding q1 or stage2 would double count.
        return (q0.sum(axis=(0, 1)) + q2.sum(axis=(0, 1))
                + src.sum(axis=0) + pend.sum(axis=0))

    def finalize(self) -> None:
        if self.util_sum is None or self.tb is None or self.n_win == 0:
            return
        ok = np.asarray(self.tb.slot_ok, bool)
        util = self.util_sum[ok] / (self.n_win * self.cap)
        self.m.histogram("sim.link_util").observe_many(util)
        _publish_balance(self.m, util)
        if self.n_win >= 2:
            # per-dest conservation over the window: delivered mass =
            # mass drop + offered inflow between the snapshots; a column
            # whose ratio stays ~1 is individually stable — the per-dest
            # knee criterion (fault-surgery drops inside the window
            # lower it, correctly reading as instability)
            delivered = self.mass0 - self.mass_last + self.off_acc
            sel = self.off_acc > 0
            if sel.any():
                stab = np.clip(delivered[sel] / self.off_acc[sel],
                               0.0, None)
                self.m.histogram("sim.dest_stability").observe_many(stab)
                self.m.gauge("sim.dest_stability.min").set(float(stab.min()))
                self.m.gauge("sim.dest_stability.mean").set(
                    float(stab.mean()))


class _StepMonitor:
    """Flight-recorder + watchdog hook for one :meth:`Simulator._run`:
    computes the shared per-step digests ONCE and feeds both.

    Recorder channels mirror ``SimRun.history`` — delivered / accepted /
    offered divided per step by the SAME per-segment norm the run's
    post-loop normalization uses (IEEE float64 division is elementwise
    deterministic, so a reloaded bundle window compares bit-exactly
    against the history arrays), occupancy / src_backlog / diverted raw
    — plus the per-VC occupancy sums and the running conservation
    residual.  The per-dest mass digest (one host pass over the dest
    tensors per step) is computed only when a dest_stability trigger is
    armed; per-step wall time only when a step_time trigger is.
    """

    def __init__(self, rec, wd):
        self.rec = rec
        self.wd = wd
        self.stab_win = wd.stability_window() if wd is not None else None
        self.need_time = wd is not None and wd.needs("step_seconds")
        self._mass_hist = (deque(maxlen=self.stab_win + 1)
                          if self.stab_win else None)
        self.norm = np.inf
        self.off_dest = None
        self.dropped = 0.0
        self.inj_cum = 0.0
        self.dlv_cum = 0.0
        self._t_prev = time.perf_counter()

    def set_segment(self, seg_total: float, off_dest, dropped: float):
        self.norm = seg_total if seg_total > 0 else np.inf
        self.off_dest = off_dest
        self.dropped = dropped

    def on_step(self, i: int, st, row) -> None:
        dt = None
        if self.need_time:
            now = time.perf_counter()
            dt = now - self._t_prev
            self._t_prev = now
        self.inj_cum += float(row[2])
        self.dlv_cum += float(row[0])
        # the run's conservation identity, evaluated live: at the final
        # step this equals SimRun.residual up to summation order
        residual = (abs(self.inj_cum - self.dlv_cum - float(row[3])
                        - float(row[4]) - self.dropped)
                    / max(self.inj_cum, 1e-30))
        stab_min = float("nan")
        stab_col = mass_min = None
        arrs = None
        if self.rec is not None or self._mass_hist is not None:
            # one host view of the state per step; the digest sums below
            # accumulate in float64 WITHOUT materializing float64 copies
            # of the queue tensors (the fused backends run float32, and
            # a per-step 8-byte copy of the whole state would dominate
            # the monitor's cost)
            arrs = tuple(np.asarray(a) for a in st)
        if self._mass_hist is not None:
            q0, _q1, q2, src, pend, _s2 = arrs
            dm = (q0.sum(axis=(0, 1), dtype=np.float64)
                  + q2.sum(axis=(0, 1), dtype=np.float64)
                  + src.sum(axis=0, dtype=np.float64)
                  + pend.sum(axis=0, dtype=np.float64))
            mass_min = float(dm.min())
            self._mass_hist.append(dm)
            w, off = self.stab_win, self.off_dest
            if len(self._mass_hist) == w + 1 and off is not None:
                # delivered per column over the trailing window = mass
                # drop + offered inflow (_SimCapture's bookkeeping
                # identity, evaluated live each step)
                delivered = self._mass_hist[0] - dm + off * w
                sel = off > 0
                if sel.any():
                    stab = delivered[sel] / (off[sel] * w)
                    j = int(np.argmin(stab))
                    stab_min = float(stab[j])
                    stab_col = int(np.nonzero(sel)[0][j])
        if self.rec is not None:
            q0, q1, q2, _src, _pend, stage2 = arrs
            ch = {"delivered": float(row[0] / self.norm),
                  "accepted": float(row[1] / self.norm),
                  "offered": float(row[2] / self.norm),
                  "occupancy": float(row[3]),
                  "src_backlog": float(row[4]),
                  "diverted": float(row[5]),
                  "occ_vc0": float(q0.sum(dtype=np.float64)),
                  "occ_vc1": float(q1.sum(dtype=np.float64)
                                  + stage2.sum(dtype=np.float64)),
                  "occ_vc2": float(q2.sum(dtype=np.float64)),
                  "residual": residual}
            if self._mass_hist is not None:
                ch["dest_stability_min"] = stab_min
            self.rec.record(i, ch)
        if self.wd is not None:
            sample = {"step": i, "delivered": float(row[0]),
                      "accepted": float(row[1]),
                      "offered": float(row[2]),
                      "occupancy": float(row[3]),
                      "src_backlog": float(row[4]),
                      "diverted": float(row[5]),
                      "residual": residual}
            if dt is not None:
                sample["step_seconds"] = dt
            if mass_min is not None:
                sample["dest_mass_min"] = mass_min
                sample["dest_stability_min"] = stab_min
                if stab_col is not None:
                    sample["dest_stability_col"] = stab_col
            self.wd.on_step(sample)


def _demand_for(g: Graph, pattern, targets_mask, normalize: bool):
    if targets_mask is None:
        targets_mask = g.meta.get("leaf_mask")
    pat = make_pattern(pattern)
    demand = pat.demand(g, targets_mask)
    if normalize:
        demand = normalize_demand(demand)
    return pat, demand, targets_mask


def simulate(g: Graph, pattern, routing: str = "minimal",
             offered: float = 0.5, steps: int | None = None,
             config: SimConfig | None = None,
             targets_mask: np.ndarray | None = None,
             normalize: bool = True, events=None) -> SimRun:
    """Simulate one (pattern, routing, offered load) point.

    ``pattern`` is any repro.core.traffic spec (registry name,
    TrafficPattern, or raw (N, N) matrix); ``offered`` is the injection
    rate of the busiest source in link-equivalents (the analytic theta's
    units).  ``config`` overrides buffers/backend; its routing field is
    superseded by ``routing``.  ``events`` is a mid-run fault schedule
    (see :meth:`Simulator.run`)."""
    cfg = _config_with(config, routing)
    _, demand, targets_mask = _demand_for(g, pattern, targets_mask, normalize)
    return Simulator(g, cfg, targets_mask, demand=demand).run(
        demand, offered, steps, events=events)


def _config_with(config: SimConfig | None, routing: str) -> SimConfig:
    base = config or SimConfig()
    parse_sim_routing(routing)  # validate before building tables
    return SimConfig(routing=routing, buffer=base.buffer,
                     capacity=base.capacity, inj_factor=base.inj_factor,
                     backend=base.backend, dtype=base.dtype,
                     compact=base.compact)


def saturation_sweep(g: Graph, pattern, routing: str = "minimal",
                     loads=None, steps: int | None = None,
                     config: SimConfig | None = None,
                     targets_mask: np.ndarray | None = None,
                     refine: int = 3, stable_ratio: float = 0.98,
                     theta_analytic: float | None = None,
                     events=None, knee: str = "aggregate") -> SimSweep:
    """Latency-vs-offered-load curve and measured saturation throughput
    for one (topology, pattern, routing).

    ``loads`` defaults to :data:`DEFAULT_LOAD_GRID` times the analytic
    fluid theta of the matching registry model (minimal / valiant / the
    ugal blend), so the grid brackets the expected saturation point; the
    grid is extended when every probe lands on one side.  The measured
    ``theta`` is the largest offered load whose delivered/offered ratio
    stays >= ``stable_ratio``, sharpened by ``refine`` bisection probes
    inside the (stable, unstable) bracket.  Pass ``theta_analytic`` to
    reuse an already-computed fluid reference (skips one analytic
    solve).  ``events`` applies one fault schedule to EVERY probe (see
    :meth:`Simulator.run`) — the measured knee is then the degraded
    saturation throughput, comparable to the analytic
    ``degraded_report`` theta of the final fault state; pass a ``loads``
    grid scaled to the expected degraded theta so the bracket lands.

    ``knee`` picks the stability criterion: ``aggregate`` (default — the
    total delivered/offered ratio) or ``per_dest`` (stable only while
    the MINIMUM per-dest-column delivered/offered ratio stays >=
    ``stable_ratio``).  Aggregate knees go mushy on sparse asymmetric
    demand — a few saturated columns drown in the healthy majority and
    the measured theta overshoots; the per-dest criterion reads each
    column's own conservation over the window (``per_dest=True`` runs)
    and snaps the knee to the first column that collapses."""
    if knee not in ("aggregate", "per_dest"):
        raise ValueError(f"unknown knee criterion {knee!r}; options: "
                         f"aggregate, per_dest")
    per_dest = knee == "per_dest"
    cfg = _config_with(config, routing)
    pat, demand, targets_mask = _demand_for(g, pattern, targets_mask, True)
    sweep_span = obs.span("sim.sweep", pattern=pat.name,
                          routing=cfg.routing)
    with sweep_span:
        ref = (theta_analytic if theta_analytic is not None else
               saturation_report(g, pat, routing=fluid_routing_spec(routing),
                                 targets_mask=targets_mask).theta)
        if loads is None:
            loads = np.asarray(DEFAULT_LOAD_GRID) * ref
        loads = np.sort(np.asarray(loads, dtype=np.float64))
        simr = Simulator(g, cfg, targets_mask, demand=demand)

        def stable(r):
            if per_dest and np.isfinite(r.dest_stability_min):
                return r.dest_stability_min >= stable_ratio
            return r.theta >= stable_ratio * r.offered

        n_probes = [0]

        def probe(lam, phase):
            # each probe is one spanned run, tagged with the sweep phase
            # (grid / bracket extension / bisection refinement) and
            # counted per phase — the probe-budget telemetry
            obs.counter(f"sim.probes[{phase}]").add(1.0)
            with obs.span("sim.probe", phase=phase, offered=float(lam)):
                r = simr.run(demand, lam, steps, events=events,
                             per_dest=per_dest)
            ok = stable(r)
            n_probes[0] += 1
            # live sweep telemetry: one streamed event per probe (no-op
            # without a streaming session) + the oscillation trigger's
            # stability-frontier feed
            obs.emit("sim.probe", pattern=pat.name, routing=cfg.routing,
                     phase=phase, probe=n_probes[0], offered=float(lam),
                     theta=r.theta, latency=r.latency, stable=ok)
            s = obs.current()
            if s is not None and s.enabled and s.watchdog is not None:
                s.watchdog.on_probe(float(lam), ok)
            return r

        runs = [probe(lam, "grid") for lam in loads]

        # extend the bracket when the grid missed the knee entirely
        for _ in range(2):
            if any(stable(r) for r in runs):
                break
            runs.append(probe(0.5 * min(r.offered for r in runs),
                              "bracket"))
        for _ in range(2):
            if any(not stable(r) for r in runs):
                break
            runs.append(probe(1.4 * max(r.offered for r in runs),
                              "bracket"))

        lo = max((r.offered for r in runs if stable(r)), default=0.0)
        unstable = [r.offered for r in runs
                    if not stable(r) and r.offered > lo]
        hi = min(unstable) if unstable else float("inf")
        if lo > 0.0 and np.isfinite(hi):
            for _ in range(refine):
                r = probe(0.5 * (lo + hi), "bisect")
                runs.append(r)
                if stable(r):
                    lo = r.offered
                else:
                    hi = r.offered
        sweep_span.set(theta=lo, probes=len(runs))
    # the curve includes EVERY probe — grid, bracket extensions, and
    # bisection refinements — sorted by offered load, so a sweep whose
    # initial grid missed the knee still returns points near saturation
    curve = sorted(runs, key=lambda r: r.offered)
    return SimSweep(
        pattern=pat.name, routing=cfg.routing, theta=lo, theta_unstable=hi,
        theta_analytic=float(ref), stable_ratio=stable_ratio,
        loads=np.array([r.offered for r in curve]),
        delivered=np.array([r.theta for r in curve]),
        latency=np.array([r.latency for r in curve]),
        alpha=np.array([r.alpha for r in curve]), knee=knee, runs=runs)


def simulate_placement(placement, profile, routing: str = "ugal_threshold(0)",
                       offered: float | None = None,
                       steps: int | None = None,
                       config: SimConfig | None = None,
                       axis_of=None) -> SimRun:
    """Replay a (StepProfile, Placement) byte matrix through the
    simulator in fabric.placement's normalization: demand is scaled so
    the busiest CHIP injects one unit (``chip_wire_bytes``), making the
    measured theta directly comparable to ``placement_report``'s.
    ``offered`` defaults to 1.2x the analytic theta so the run reports
    the saturation plateau."""
    from ..fabric.placement import (chip_wire_bytes, placement_demand,
                                    placement_report)
    cfg = _config_with(config, routing)
    demand = placement_demand(profile, placement, axis_of)
    per_chip = chip_wire_bytes(profile, placement.mesh_shape,
                               placement.axis_names, axis_of)
    if per_chip == 0.0 or not demand.any():
        raise ValueError("placement demand is all router-local; "
                         "nothing to simulate")
    norm = demand / per_chip
    if offered is None:
        ref = placement_report(placement, profile,
                               routing=fluid_routing_spec(routing),
                               axis_of=axis_of).theta
        offered = 1.2 * ref
    return Simulator(placement.graph, cfg, demand=norm).run(
        norm, offered, steps)
