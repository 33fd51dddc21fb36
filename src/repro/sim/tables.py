"""Precomputed routing structure for the flow-level simulator.

The simulator's inner loop is pure tensor algebra; everything that depends
only on the topology (shortest-path next-hop splits, delivery masks, the
Valiant intermediate spread, remaining-hop estimates for the UGAL rule) is
compiled once per ``(graph, active)`` pair into dense arrays laid out over
``(router, out-slot, dest)``:

  * out-slot ``k`` of router ``r`` is directed arc ``indptr[r] + k`` — the
    ``(N, degree)`` plane is the padded per-router view of the graph's arc
    order, so occupancy tensors are the ``(N, degree, vc)`` arrays the
    credit machinery reasons about;
  * the dest axis is restricted to the ``active`` set (all routers, or the
    leaf set of an indirect network) — spine routers of an OFT carry
    transit fluid but are never a routing destination.

``SPLIT[r, k, d]`` is the fraction of fluid at ``r`` headed for active
dest ``d`` that leaves through slot ``k`` under equal-split minimal
routing: ``1/m`` over the ``m`` out-arcs that lie on a shortest path,
0 elsewhere.  This is exactly the per-hop ECMP split the analytical
engines (repro.core.utilization) integrate in closed form, which is what
makes the zero-threshold / infinite-buffer simulation converge to the
fluid theta (see docs/simulation.md, "parity conditions").
"""

from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass, field

import numpy as np

from ..core.graph import Graph, bfs_distances_batched

__all__ = ["RouteTables", "build_tables", "route_planes", "recip_table",
           "device_planes", "UNREACHABLE"]

# hop count of an unreachable pair, and of a padded out-slot's head
UNREACHABLE = np.iinfo(np.int32).max // 2


@dataclass
class RouteTables:
    """Topology-dependent constants of one simulator instance.

    Shapes: N routers, K = max degree (padded out-slots), M active dests.

    The fault-mask block describes the degraded fabric the tables were
    compiled for (``build_tables(faults=...)``); on pristine tables every
    mask is all-alive and ``faulted`` is False, so the step function can
    keep its cheap pristine code paths.

    The dense (N, K, M) planes ``split`` and ``deliver`` are made from
    ``dist``, ``head`` and ``slot_ok`` on first read (:func:`route_planes`
    over numpy, at ``dtype``).  The jitted steps never read them: they
    take the same planes made on the device (:func:`device_planes`).
    """

    n: int
    k: int
    m: int
    active: np.ndarray          # (M,) router id of each dest index
    head: np.ndarray = field(repr=False)       # (N, K) int, pad = N
    spread: np.ndarray = field(repr=False)     # (N, M) Valiant intermediates
    dist_act: np.ndarray = field(repr=False)   # (N, M) hops to each dest
    hval_rem: np.ndarray = field(repr=False)   # (N, M) mean two-leg estimate
    dist: np.ndarray = field(repr=False)       # (N, N) int32 hops
    dtype: np.dtype = field(repr=False)        # of split and the O(N*M) tables
    slot_ok: np.ndarray = field(repr=False, default=None)    # (N, K) bool
    router_ok: np.ndarray = field(repr=False, default=None)  # (N,) bool
    dest_ok: np.ndarray = field(repr=False, default=None)    # (M,) bool
    routable: np.ndarray = field(repr=False, default=None)   # (N, M) bool
    faulted: bool = False
    _planes: tuple = field(init=False, repr=False, default=None,
                           compare=False)
    _device_planes: tuple = field(init=False, repr=False, default=None,
                                  compare=False)

    @property
    def split(self) -> np.ndarray:
        """(N, K, M) minimal ECMP split at ``dtype``."""
        return self._host_planes()[0]

    @property
    def deliver(self) -> np.ndarray:
        """(N, K, M) bool, head == dest."""
        return self._host_planes()[1]

    def _host_planes(self):
        if self._planes is None:
            from .. import obs
            obs.counter("sim.route_planes[host]").add(1.0)
            self._planes = route_planes(np, self.dist, self.head,
                                        self.slot_ok, self.active,
                                        recip_table(self.k, self.dtype))
        return self._planes


def recip_table(k: int, dtype) -> np.ndarray:
    """``r[c] = 1 / c`` for ``c`` in 1..k, divided in float64 and rounded
    once to ``dtype``, and ``r[0] = 0``.  The split plane takes its values
    from here, so it has the same bits wherever it is made: a device's
    own division need not round correctly."""
    r = np.zeros(k + 1, dtype=np.float64)
    r[1:] = 1.0 / np.arange(1, k + 1)
    return r.astype(dtype)


def route_planes(xp, dist, head, slot_ok, dests, recip):
    """``(split, deliver)`` over ``(router, out-slot, dest)`` toward the
    dest router ids ``dests``, in ``xp`` (numpy or jax.numpy).

    ``dist`` is the (N, N) hop table (:data:`UNREACHABLE` where no path
    is left), ``head`` the (N, K) head router of each out-slot (N on a
    padded slot), ``slot_ok`` the (N, K) live-slot mask and ``recip``
    :func:`recip_table` at the split's dtype.  ``split[r, k, d]`` is
    ``1/m`` on the ``m`` live slots of ``r`` whose head lies one hop
    closer to ``d``, 0 elsewhere; ``deliver`` is ``head == d`` (bool)."""
    d = dist[:, dests]                                  # (N, W) hops
    # a padded slot's head reads an unreachable row, so it never looks
    # like a next hop
    d_pad = xp.concatenate(
        [d, xp.full((1, d.shape[1]), UNREACHABLE, dtype=d.dtype)])
    min_mask = (d_pad[head] == (d[:, None, :] - 1)) & slot_ok[:, :, None]
    count = min_mask.sum(axis=1)                        # (N, W)
    split = xp.where(min_mask, recip[count][:, None, :], recip[0])
    deliver = head[:, :, None] == dests[None, None, :]
    return split, deliver


def device_planes(t: RouteTables, dtype, slots: int,
                  dest_cols=None) -> dict:
    """The dense planes a jitted step reads, made on the default device
    by one program from ``t``'s small host tables, at ``dtype`` and with
    the out-slot axis laid at ``slots`` (empty slots past ``t.k``: no
    head, zero split and deliver).  Kept on ``t``: a second call with the
    same arguments returns the same arrays.

    Returns ``{"F": {"split", "deliver"}, "w_val", "spread"}`` over the
    full active axis, plus ``"C"`` over ``t.active[dest_cols]`` when
    given.  ``w_val`` (N, slots) is the expected first-hop slot usage of
    freshly diverted fluid: ``spread`` pushed through the full-axis
    split.  ``split`` and ``deliver`` have the bits :func:`route_planes`
    gives over numpy; ``w_val`` differs from a host contraction only in
    its order of summation.  The host arrays sent are counted in
    ``sim.table_put_bytes``, each build in ``sim.route_planes[device]``.
    """
    import jax

    from .. import obs
    from ..jaxenv import x64
    dtype = np.dtype(dtype)
    dests = (t.active,) if dest_cols is None else (
        t.active, t.active[np.asarray(dest_cols, np.int64)])
    dests = tuple(np.asarray(d, np.int32) for d in dests)
    key = (dtype, slots, tuple(d.tobytes() for d in dests))
    if t._device_planes is not None and t._device_planes[0] == key:
        return t._device_planes[1]
    pad = slots - t.k
    head = np.pad(t.head.astype(np.int32), ((0, 0), (0, pad)),
                  constant_values=t.n)
    slot_ok = np.pad(np.asarray(t.slot_ok, bool), ((0, 0), (0, pad)))
    host = (t.dist, head, slot_ok, np.asarray(t.spread, dtype),
            recip_table(t.k, dtype), dests)
    obs.counter("sim.table_put_bytes").add(
        float(sum(a.nbytes for a in jax.tree.leaves(host))))
    obs.counter("sim.route_planes[device]").add(1.0)
    with x64() if dtype == np.float64 else contextlib.nullcontext():
        spread = jax.device_put(host[3])
        axes, w_val = _plane_program()(*host[:3], spread, *host[4:])
    out = {"w_val": w_val, "spread": spread}
    for name, (split, deliver) in zip("FC", axes):
        out[name] = {"split": split, "deliver": deliver}
    t._device_planes = (key, out)
    return out


@functools.lru_cache(maxsize=1)
def _plane_program():
    """The jitted plane build: one program per set of shapes and dtypes,
    so every build at one fabric's size reuses the first one's compile."""
    import jax
    import jax.numpy as jnp

    def program(dist, head, slot_ok, spread, recip, dests):
        axes = []
        for d in dests:
            split, deliver = route_planes(jnp, dist, head, slot_ok, d, recip)
            axes.append((split, deliver.astype(recip.dtype)))
        w_val = (axes[0][0] * spread[:, None, :]).sum(axis=2)
        return axes, w_val

    return jax.jit(program)


def build_tables(g: Graph, active: np.ndarray, dtype=np.float64,
                 faults=None) -> RouteTables:
    """Compile the dense routing tables for ``g`` restricted to ``active``
    destinations.  One batched all-source BFS plus the O(N * K) and
    O(N * M) tables; the dense (N, K, M) planes are made where their step
    reads them (:func:`device_planes`, or ``split`` on first read).  The
    result is reused across every run on the same instance.

    With ``faults`` (a repro.core.faults.FaultSet) the tables are compiled
    for the degraded fabric while KEEPING the pristine ``(N, K)`` state
    layout — dead routers and dead out-slots stay addressable (so fluid
    state carries across a mid-run fault event) but are masked out of
    every split/spread and flagged in ``slot_ok``/``routable``.  Distances
    and ECMP splits are recomputed on the surviving graph: per-hop ECMP
    through masked split tables IS the reroute.  Because split only ever
    sends fluid one hop closer on the alive graph, ``routable[r, d]``
    (same alive component) is invariant along every route — masked tables
    plus one state surgery (repro.sim.faults) keep fluid conserved."""
    active = np.asarray(active, dtype=np.int64)
    n, m = g.n, len(active)
    if m < 2:
        raise ValueError("need at least 2 active vertices")
    deg = g.degrees
    k = int(deg.max())

    faulted = faults is not None and not faults.empty
    if faulted:
        edge_alive = faults.edge_alive(g)
        router_ok = faults.router_mask(g)
        dist = bfs_distances_batched(g.subgraph(edge_mask=edge_alive),
                                     np.arange(n)).astype(np.int32)
        dist[dist < 0] = UNREACHABLE
    else:
        edge_alive = np.ones(g.num_edges, dtype=bool)
        router_ok = np.ones(n, dtype=bool)
        dist = bfs_distances_batched(g, np.arange(n)).astype(np.int32)
        if (dist < 0).any():
            raise ValueError("graph is disconnected")

    head = np.full((n, k), n, dtype=np.int64)
    slot_ok = np.zeros((n, k), dtype=bool)
    arc_ok = edge_alive[g.arc_edge_id]
    for r in range(n):
        d = int(deg[r])
        head[r, :d] = g.indices[g.indptr[r]: g.indptr[r + 1]]
        slot_ok[r, :d] = arc_ok[g.indptr[r]: g.indptr[r + 1]]

    dest_ok = router_ok[active]
    dist_act = dist[:, active]                        # (N, M)
    routable = (router_ok[:, None] & dest_ok[None, :]
                & (dist_act < UNREACHABLE))
    if faulted:
        if int(dest_ok.sum()) < 2:
            raise ValueError("fewer than 2 active destinations survive "
                             "the faults")
        alive_ids = np.nonzero(dest_ok)[0]
        if not routable[np.ix_(active[dest_ok], alive_ids)].all():
            raise ValueError(
                "faults disconnect the active set: surviving active "
                "vertices are not mutually reachable")

    # Valiant intermediate spread: uniform over the surviving active mids
    # this router can reach, other than itself (rows of routers outside
    # the active set use all reachable mids), normalized per row so
    # diversion conserves fluid
    not_self = active[None, :] != np.arange(n)[:, None]
    ok_mid = not_self & routable
    spread = (ok_mid / np.maximum(ok_mid.sum(axis=1, keepdims=True), 1)
              ).astype(dtype)

    # remaining-hop estimates for the per-hop UGAL rule: minimal is the
    # true distance; the Valiant detour from r to d is estimated as the
    # mean over surviving intermediates of dist(r, m) + dist(m, d)
    alive_act = active[dest_ok]
    mean_to_mid = dist[:, alive_act].mean(axis=1)     # (N,)
    mean_from_mid = dist[np.ix_(alive_act, active)].mean(axis=0)  # (M,)
    hval_rem = (mean_to_mid[:, None] + mean_from_mid[None, :]).astype(dtype)
    dist_out = dist_act.astype(dtype)
    if faulted:
        # zero the sentinel entries: unroutable pairs never carry fluid,
        # and downstream consumers (default_steps, the UGAL inequality)
        # must not see the unreachable marker as a distance
        dist_out = np.where(routable, dist_out, 0.0).astype(dtype)
        hval_rem = np.where(routable, hval_rem, 0.0).astype(dtype)

    return RouteTables(
        n=n, k=k, m=m, active=active, head=head, spread=spread,
        dist_act=dist_out, hval_rem=hval_rem, dist=dist,
        dtype=np.dtype(dtype), slot_ok=slot_ok, router_ok=router_ok,
        dest_ok=dest_ok, routable=routable, faulted=faulted)
