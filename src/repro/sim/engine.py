"""The simulator's time-stepped core: one vectorized step function,
instantiated over numpy (float64, the reference backend) or JAX (jit
compiled, float32) from the same code path.

Model (full semantics in docs/simulation.md):

* Fluid flow at one-hop-per-step granularity.  Traffic lives in per-arc
  output queues ``Q[router, out-slot, dest]`` tagged by routing
  destination, one tensor per virtual channel: vc0 carries minimal-mode
  traffic, vc1 the first Valiant leg (routing dest = the intermediate),
  vc2 the second leg — the classic two-VC deadlock assignment, which is
  also exactly the state the UGAL rule compares.
* Each step every arc forwards up to ``capacity`` flits, shared
  proportionally across (vc, dest) — processor sharing, the fluid limit
  of round-robin arbitration.  Arriving fluid is ejected when the head
  router is its routing dest, otherwise re-enqueued through the
  equal-split minimal table (per-hop ECMP).
* Credit-based finite buffers: a router's per-vc occupancy may not
  exceed ``buffer``; transit arrivals beyond the remaining space stall in
  the upstream queue (backpressure), blocked injections stay in the
  source backlog, blocked diversions continue minimally.
* Per-hop threshold-UGAL: every vc0 enqueue (fresh injection or transit
  arrival) at router r toward dest d diverts to vc1 iff

      dist(r, d) * q_min > T + hval(r, d) * q_val

  with q_min the best minimal-slot vc0 backlog, q_val the best vc1 slot
  backlog at r, both sampled at the start of the step — the local-state
  form of UGAL-L, applied progressively (a diverted packet never
  re-enters vc0).  Diverted fluid spreads uniformly over the active
  intermediates; the pairing of in-flight phase-1 fluid with its final
  destinations is kept in an aggregate ``PEND[(intermediate, dest)]``
  pool and drawn down proportionally at conversion (fluid mixing — exact
  in aggregate, which is all the rank-1 Valiant fluid model resolves
  anyway; see repro.core.routing.valiant_demands).
"""

from __future__ import annotations

import contextlib
import functools
import re
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

from .tables import RouteTables, device_planes

__all__ = ["SimConfig", "SimState", "BoundStep", "make_step", "init_state",
           "state_shapes", "pad_slots", "parse_sim_routing", "pick_backend",
           "SIM_JAX_MIN_WORK", "SIM_MAX_CELLS"]

_BIG = 1e12     # unreachable-queue sentinel for masked mins
_TINY = 1e-30   # safe-division floor

# Above this many (router, slot, dest) cells the jit-compiled JAX step
# beats numpy; below it, trace/dispatch overhead dominates.
SIM_JAX_MIN_WORK = 1_500_000

# Dense-backend ceiling on (router, slot, dest) cells (~2.4 GB of f64
# queue planes): above it the dense numpy/jax steps are refused and the
# blocked sparse-dest backends (repro.sim.kernel) take over — via
# ``auto`` resolution, or explicitly with backend="pallas".
SIM_MAX_CELLS = 50_000_000

_SIM_SPEC_RE = re.compile(
    r"^\s*(minimal|valiant|ugal|ugal_threshold)\s*(?:\(\s*([^)]*)\s*\))?\s*$")


def parse_sim_routing(spec) -> tuple[str, float]:
    """``(mode, threshold)`` from a simulator routing spec: ``minimal``,
    ``valiant``, ``ugal_threshold(T)``, or ``ugal`` (= threshold 0)."""
    m = _SIM_SPEC_RE.match(str(spec))
    if not m:
        raise ValueError(
            f"unknown sim routing {spec!r}; options: minimal, valiant, "
            f"ugal, ugal_threshold(T)")
    name, arg = m.group(1), m.group(2)
    if name in ("minimal", "valiant"):
        if arg:
            raise ValueError(f"{name} takes no argument, got {spec!r}")
        return name, 0.0
    t = float(arg) if arg else 0.0
    if not t >= 0:  # also rejects nan, matching the core registry
        raise ValueError(f"threshold must be >= 0, got {t}")
    return "ugal", t


@dataclass(frozen=True)
class SimConfig:
    """Knobs of one simulation run.

    ``routing`` is a simulator spec (:func:`parse_sim_routing`);
    ``buffer`` the per-(router, vc) occupancy limit in flit units
    (``inf`` = the fluid limit); ``capacity`` the per-arc flits/step;
    ``inj_factor`` caps the per-step source drain at ``inj_factor`` times
    the offered quantum so a backlogged source cannot flood the fabric in
    one step; ``backend`` is ``auto`` / ``numpy`` / ``jax`` /
    ``pallas`` / ``pallas_interpret`` (the fused blocked sparse-dest
    step of repro.sim.kernel — the pallas kernels on TPU, their jnp
    formulas elsewhere, or the kernels under the pallas interpreter);
    ``dtype`` is the state dtype — ``auto`` (float64 for
    the dense backends, float32 for the fused ones), ``float32``, or
    ``float64``; ``compact`` gates static dest compaction against the
    run's demand matrix — ``auto`` (default: shrink the active set under
    minimal routing, carry the per-VC compacted dest axis on the fused
    backends under ugal/valiant) or ``off`` (keep every column; the
    all-columns baseline the compaction benchmarks time against)."""

    routing: str = "minimal"
    buffer: float = float("inf")
    capacity: float = 1.0
    inj_factor: float = 1.0
    backend: str = "auto"
    dtype: str = "auto"
    compact: str = "auto"

    @property
    def mode(self) -> str:
        return parse_sim_routing(self.routing)[0]

    @property
    def threshold(self) -> float:
        return parse_sim_routing(self.routing)[1]


@dataclass
class SimState:
    """All mutable fluid of one run (a pytree of backend arrays)."""

    q0: object = field(repr=False)      # (N, K, M) minimal-mode queues
    q1: object = field(repr=False)      # (N, K, M) Valiant leg 1 queues
    q2: object = field(repr=False)      # (N, K, M) Valiant leg 2 queues
    src: object = field(repr=False)     # (N, M) source backlog
    pend: object = field(repr=False)    # (M, M) phase-1 (mid, dest) pool
    stage2: object = field(repr=False)  # (M,) converted, awaiting vc2 space

    def as_tuple(self):
        return (self.q0, self.q1, self.q2, self.src, self.pend, self.stage2)


def pick_backend(backend: str, work: int) -> str:
    """Resolve ``auto`` (and validate explicit choices) by size: the
    fused sparse-dest backend beyond the dense cell cap, JAX for large
    instances, numpy otherwise.  An ``auto`` request
    defers to the ``sim_backend`` perf flag first (REPRO_PERF), so whole
    runs can be pinned without threading a config through."""
    if backend == "auto":
        from ..perf import flags
        backend = flags().sim_backend
    if backend in ("pallas", "pallas_interpret"):
        return backend
    if backend == "numpy":
        return "numpy"
    if backend not in ("jax", "auto"):
        raise ValueError(f"unknown sim backend {backend!r}; options: "
                         f"auto, numpy, jax, pallas, pallas_interpret")
    if backend == "auto" and work > SIM_MAX_CELLS:
        return "pallas"
    if backend == "jax":
        return "jax"
    return "jax" if work >= SIM_JAX_MIN_WORK else "numpy"


def init_state(t: RouteTables, dtype, dest_cols=None) -> SimState:
    """Zero fluid state for ``t``.  With ``dest_cols`` (the fused
    backends' per-VC compacted dest axis) the final-dest tensors — q0,
    q2, src, and the pend pool's dest axis — carry only the ``C``
    demanded columns; q1 and stage2 keep the full ``M`` mid axis, since
    Valiant leg-1 fluid is addressed to intermediates."""
    return SimState(*(np.zeros(s, dtype=dtype)
                      for s in state_shapes(t, dest_cols).as_tuple()))


def state_shapes(t: RouteTables, dest_cols=None) -> SimState:
    """The shapes of :func:`init_state`'s arrays, as a ``SimState``."""
    n, k, m = t.n, t.k, t.m
    c = m if dest_cols is None else len(dest_cols)
    return SimState(q0=(n, k, c), q1=(n, k, m), q2=(n, k, c),
                    src=(n, c), pend=(m, c), stage2=(m,))


def pad_slots(a: np.ndarray, pad: int, dtype=None, fill=0) -> np.ndarray:
    """``a`` (N, K, ...) at ``dtype`` (default its own) with ``pad`` slots
    of ``fill`` appended to its out-slot axis, cast and padded in one
    copy; ``a`` itself where it needs neither."""
    if not pad:
        return np.asarray(a, dtype=dtype)
    k = a.shape[1]
    out = np.empty((a.shape[0], k + pad) + a.shape[2:],
                   dtype=a.dtype if dtype is None else dtype)
    out[:, :k] = a
    out[:, k:] = fill
    return out


# stats vector layout emitted by one step
STAT_NAMES = ("delivered", "accepted", "offered", "occupancy",
              "src_backlog", "diverted")


class _DenseSpec(NamedTuple):
    """Everything static about one dense step: the tables' sizes and the
    config scalars.  Two tables with equal specs share one compiled
    ``jax`` step (fault states of one Simulator, for instance)."""

    n: int
    k: int
    m: int
    mode: str
    thr: float
    capacity: float
    buffer: float
    faulted: bool
    dtype: str


def _dense_tables(t: RouteTables, dtype, planes=None) -> dict:
    """The route tables one dense step reads, at the state dtype.  The
    dense planes are ``planes`` (:func:`repro.sim.tables.device_planes`
    at the graph's K) where given, else made from the host tables."""
    asd = lambda a: np.asarray(a, dtype=dtype)
    if planes is None:
        planes = {"F": {"split": asd(t.split), "deliver": asd(t.deliver)},
                  "spread": asd(t.spread),
                  # expected first-hop slot usage of freshly diverted
                  # fluid: the spread over intermediates pushed through
                  # the ECMP split (rows sum to 1)
                  "w_val": asd(np.einsum("nm,nkm->nk", t.spread, t.split))}
    # mids available to a diverting router: m - 1 inside the active set
    # (never via itself), all m mids from a transit-only (spine) router
    in_active = np.zeros(t.n, dtype=bool)
    in_active[t.active] = True
    return {
        "split": planes["F"]["split"], "deliver": planes["F"]["deliver"],
        "spread": planes["spread"], "w_val": planes["w_val"],
        "dist_act": asd(t.dist_act), "hval_rem": asd(t.hval_rem),
        "head_flat": t.head.reshape(-1), "active": t.active,
        "n_mids": asd(t.m - in_active),
        "spread_T": asd(t.spread.T),             # (M, N), mids x routers
    }


def make_step(t: RouteTables, cfg: SimConfig, backend: str, dtype):
    """Build ``step(state, inj, inj_cap) -> (state, stats)`` for one
    backend.  ``inj`` is the (N, M) per-step offered quantum, ``inj_cap``
    the (N,) per-source drain limit; both are traced arguments so one
    compiled step serves a whole load sweep.  The ``jax`` step takes the
    route tables as a device-resident argument, never as constants of
    the compiled program, and its dense planes made on the device
    (:func:`repro.sim.tables.device_planes`); the numpy step reads the
    host planes."""
    from .. import obs
    obs.counter(f"sim.step_build[{backend}]").add(1.0)
    spec = _DenseSpec(t.n, t.k, t.m, cfg.mode, cfg.threshold,
                      float(cfg.capacity), float(min(cfg.buffer, _BIG)),
                      bool(getattr(t, "faulted", False)),
                      np.dtype(dtype).name)
    planes = device_planes(t, dtype, t.k) if backend == "jax" else None
    with obs.span("sim.step_tables"):
        tabs = _dense_tables(t, dtype, planes)
    if backend != "jax":
        return functools.partial(_dense_step(np, spec), tabs)
    return BoundStep(_jax_dense_step(spec), tabs, scoped_x64=True)


class BoundStep:
    """A jitted step with its route tables on the device:
    ``step(state, inj, inj_cap) = jitted(tabs, state, inj, inj_cap)``.
    The host tables are placed once, at construction (span
    ``sim.table_put``, counter ``sim.table_put_bytes``), and tables made
    on the device are taken as they are; :meth:`put` places a host state
    the same way, and :meth:`zeros` makes a zero state there.  The step may
    lay the out-slot axis of its queue tensors (q0, q1, q2) at ``pad``
    empty slots past the graph's K: :meth:`put` and :meth:`zeros` take a
    state at K and lay it out so, and :meth:`view` cuts it back.  With
    ``scoped_x64`` the placements and every call run under x64, so
    float64 tables and state stay float64."""

    def __init__(self, jitted, tabs, scoped_x64: bool, pad: int = 0):
        import jax

        from .. import obs
        from ..jaxenv import x64
        self.jitted = jitted
        self.pad = pad
        self._scope = x64 if scoped_x64 else contextlib.nullcontext
        sent = sum(a.nbytes for a in jax.tree.leaves(tabs)
                   if isinstance(a, np.ndarray))
        with self._scope(), obs.span("sim.table_put") as sp:
            self.tabs = jax.device_put(tabs)
            sp.sync(self.tabs)
        obs.counter("sim.table_put_bytes").add(float(sent))

    def put(self, state):
        """Host ``state`` (a pytree of host arrays) on the step's device,
        laid out."""
        import jax
        with self._scope():
            return jax.device_put(tuple(
                pad_slots(a, self.pad) if j < 3 else a
                for j, a in enumerate(state)))

    def zeros(self, shapes, dtype):
        """Zero arrays of a state's ``shapes``, laid out, made on the
        step's device by one program: no host buffer, nothing sent."""
        shapes = tuple((s[0], s[1] + self.pad, *s[2:]) if j < 3
                       else tuple(s) for j, s in enumerate(shapes))
        with self._scope():
            return _jax_zeros(shapes, np.dtype(dtype).name)()

    def view(self, state):
        """A laid-out ``state`` with the graph's K out-slots: ``state``
        itself where the step lays no empty slots, else host arrays whose
        queue tensors are cut back to K (views)."""
        if not self.pad:
            return state
        return tuple(np.asarray(a)[:, :-self.pad] if j < 3
                     else np.asarray(a) for j, a in enumerate(state))

    def __call__(self, state, inj, inj_cap):
        with self._scope():
            return self.jitted(self.tabs, state, inj, inj_cap)


@functools.lru_cache(maxsize=16)
def _jax_zeros(shapes: tuple, dtype: str):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda: tuple(jnp.zeros(s, dtype) for s in shapes))


@functools.lru_cache(maxsize=16)
def _jax_dense_step(spec: _DenseSpec):
    import jax
    import jax.numpy as jnp
    return jax.jit(_dense_step(jnp, spec))


def _dense_step(xp, spec: _DenseSpec):
    """The dense step over ``xp`` (numpy or jax.numpy):
    ``step(tabs, state, inj, inj_cap)`` with ``tabs`` from
    :func:`_dense_tables`."""
    if xp is np:
        def scatter_rows(values, rows, nrows):
            out = np.zeros((nrows, values.shape[-1]), values.dtype)
            np.add.at(out, rows, values)
            return out

        def zero_diag(a):
            a = a.copy()
            np.fill_diagonal(a, 0.0)
            return a

        def scope(name):
            return contextlib.nullcontext()
    else:
        import jax
        # metadata only: the device ops keep their names, and their
        # ``tf_op`` in a profile carries the scope
        scope = jax.named_scope

        def scatter_rows(values, rows, nrows):
            return xp.zeros((nrows, values.shape[-1]), values.dtype) \
                     .at[rows].add(values)

        def zero_diag(a):
            i = xp.arange(a.shape[0])
            return a.at[i, i].set(0.0)

    # the jax step runs under a scoped x64 (see make_step) — float32
    # rounding bias measurably shifts the threshold rule's duty cycle, so
    # both backends default to float64
    dtype = np.dtype(spec.dtype).type
    n, k, m = spec.n, spec.k, spec.m
    # faulted tables break the uniform-spread structure the cheap pend
    # expansion below hard-codes; fall back to the general contraction
    faulted = spec.faulted
    mode, thr = spec.mode, spec.thr
    cap = dtype(spec.capacity)
    buf = dtype(spec.buffer)

    def step(tabs, state, inj, inj_cap):
        split, deliver = tabs["split"], tabs["deliver"]
        spread, w_val = tabs["spread"], tabs["w_val"]
        dist_act, hval_rem = tabs["dist_act"], tabs["hval_rem"]
        head_flat, active = tabs["head_flat"], tabs["active"]
        n_mids, spread_T = tabs["n_mids"], tabs["spread_T"]
        midx = xp.arange(m)
        q0, q1, q2, src, pend, stage2 = state

        # -- start-of-step backlog: what the credit/decision logic sees --
        o0 = q0.sum(-1)                      # (N, K) per-slot vc occupancy
        o1 = q1.sum(-1)
        o2 = q2.sum(-1)

        # -- forward: proportional share of each arc's capacity ----------
        share = cap / xp.maximum(o0 + o1 + o2, cap)      # (N, K) <= 1
        mv0 = q0 * share[:, :, None]
        mv1 = q1 * share[:, :, None]
        mv2 = q2 * share[:, :, None]
        del0 = mv0 * deliver                 # ejected at the head router
        del1 = mv1 * deliver                 # phase-1 reaches intermediate
        del2 = mv2 * deliver
        cont0 = mv0 - del0
        cont1 = mv1 - del1
        cont2 = mv2 - del2

        # -- credits: continuing arrivals need space at the head ---------
        with scope("forward_gather"):
            arr0 = scatter_rows(cont0.reshape(n * k, m), head_flat,
                                n + 1)[:n]
            arr1 = scatter_rows(cont1.reshape(n * k, m), head_flat,
                                n + 1)[:n]
            arr2 = scatter_rows(cont2.reshape(n * k, m), head_flat,
                                n + 1)[:n]

        def throttle(q, mv, arr):
            own = q.sum(axis=(1, 2)) - mv.sum(axis=(1, 2))
            space = xp.maximum(buf - own, 0.0)
            desire = arr.sum(-1)
            return xp.minimum(1.0, space / xp.maximum(desire, _TINY))

        s0 = throttle(q0, mv0, arr0)         # (N,) admit fraction per vc
        s1v = throttle(q1, mv1, arr1)
        s2 = throttle(q2, mv2, arr2)
        one = xp.ones((1,), dtype=dtype)
        damp0 = xp.concatenate([s0, one])[head_flat].reshape(n, k)
        damp1 = xp.concatenate([s1v, one])[head_flat].reshape(n, k)
        damp2 = xp.concatenate([s2, one])[head_flat].reshape(n, k)
        q0 = q0 - del0 - cont0 * damp0[:, :, None]   # blocked fluid stays
        q1 = q1 - del1 - cont1 * damp1[:, :, None]
        q2 = q2 - del2 - cont2 * damp2[:, :, None]
        arr0 = arr0 * s0[:, None]
        arr1 = arr1 * s1v[:, None]
        arr2 = arr2 * s2[:, None]

        delivered = del0.sum() + del2.sum()

        # -- phase-1 conversions: intermediate reached, draw final dests -
        with scope("conversions"):
            stage2 = stage2 + del1.sum(axis=(0, 1))   # (M,) by intermediate
            occ2_now = q2.sum(axis=(1, 2)) + arr2.sum(-1)
            avail2 = xp.maximum(buf - occ2_now, 0.0)[active]
            pend_sum = pend.sum(-1)
            drain = xp.minimum(xp.minimum(stage2, avail2), pend_sum)
            mix = pend / xp.maximum(pend_sum, _TINY)[:, None]
            take = drain[:, None] * mix                   # (M, M) mid x dest
            pend = pend - take
            stage2 = stage2 - drain
            # a conversion whose intermediate IS the destination is delivered
            delivered = delivered + take[midx, midx].sum()
            take = zero_diag(take)
            conv2 = scatter_rows(take, active, n)         # (N, M) vc2 inflow

        # -- injection: drain the backlog up to the per-step cap ---------
        with scope("injection"):
            src = src + inj
            srcsum = src.sum(-1)
            frac = xp.minimum(srcsum, inj_cap) / xp.maximum(srcsum, _TINY)
            q_inj = src * frac[:, None]
            src = src - q_inj

        # -- routing decision on every vc0 enqueue (per-hop UGAL) --------
        with scope("decision"):
            cand = arr0 + q_inj                           # (N, M) vc0 stream
            if mode == "minimal":
                div_eff = xp.zeros_like(cand)
                s1d = xp.ones_like(s0)
            else:
                if mode == "valiant":
                    div_ind = xp.ones_like(cand)
                else:
                    # backlog = occupancy beyond what one step drains (a queue
                    # holding exactly its in-flight fluid is uncongested),
                    # averaged over the slots the fluid would actually join:
                    # minimal fluid splits per the ECMP table, diverted fluid
                    # per the expected first hop toward a uniform intermediate
                    b0 = xp.maximum(o0 - cap, 0.0)
                    b1 = xp.maximum(o1 - cap, 0.0)
                    q_min = xp.einsum("nk,nkm->nm", b0, split)
                    q_val = (b1 * w_val).sum(axis=1)
                    div_ind = (dist_act * q_min
                               > thr + hval_rem * q_val[:, None]).astype(dtype)
                div_cand = cand * div_ind
                occ1_now = q1.sum(axis=(1, 2)) + arr1.sum(-1)
                space1 = xp.maximum(buf - occ1_now, 0.0)
                desire1 = div_cand.sum(-1)
                s1d = xp.minimum(1.0, space1 / xp.maximum(desire1, _TINY))
                div_eff = div_cand * s1d[:, None]         # blocked stays vc0
                # commit (mid, dest) pairs with the SAME per-row spread the
                # vc1 fluid routes by: (r, d) fluid puts spread[r, m] on mid
                # m, i.e. pend += spread.T @ div_eff, expanded to O(N * M)
                # via spread[r, m] = (1 - [active[m] == r]) / n_mids[r];
                # faulted spreads are not uniform, so take the O(N * M^2)
                # contraction literally there
                if faulted:
                    pend = pend + spread_T @ div_eff
                else:
                    scaled = div_eff / n_mids[:, None]
                    pend = pend + scaled.sum(0)[None, :] - scaled[active, :]

        keep = cand - div_eff
        keep_frac = keep / xp.maximum(cand, _TINY)
        trans_keep = arr0 * keep_frac
        inj_keep = q_inj * keep_frac
        # fresh minimal-mode injections need vc0 credit; transit already
        # holds its claim (admitted above), blocked injections go home
        occ0_now = q0.sum(axis=(1, 2)) + trans_keep.sum(-1)
        space0 = xp.maximum(buf - occ0_now, 0.0)
        desire0 = inj_keep.sum(-1)
        s0i = xp.minimum(1.0, space0 / xp.maximum(desire0, _TINY))
        inj_adm = inj_keep * s0i[:, None]
        src = src + (inj_keep - inj_adm)

        # -- enqueue through the equal-split minimal table ---------------
        inflow0 = trans_keep + inj_adm
        inflow1 = arr1 + div_eff.sum(-1)[:, None] * spread
        inflow2 = arr2 + conv2
        q0 = q0 + inflow0[:, None, :] * split
        q1 = q1 + inflow1[:, None, :] * split
        q2 = q2 + inflow2[:, None, :] * split

        occ = q0.sum() + q1.sum() + q2.sum() + stage2.sum()
        accepted = q_inj.sum() - (inj_keep - inj_adm).sum()
        stats = xp.stack([delivered, accepted, inj.sum(), occ,
                          src.sum(), div_eff.sum()])
        return (q0, q1, q2, src, pend, stage2), stats

    return step
