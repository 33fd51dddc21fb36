"""The route tables' dense (router, out-slot, dest) planes, made on the
device for the jitted steps and on first read for the numpy steps.

Parity contract: the device-made ``split`` and ``deliver`` of every dest
axis carry the bits of the host formula, the out-slot axis laid at the
step's K with empty slots zero; ``w_val`` differs from the host
contraction only in its order of summation.  The host formula itself
keeps the bits of the division the tables were filled with before the
planes moved (``min_mask / count`` in float64, then cast).
"""

from __future__ import annotations

import numpy as np
import pytest

from repro import obs
from repro.core import FaultSet, mms_graph, pn_graph
from repro.core.traffic import make_pattern, normalize_demand
from repro.fabric.model import torus3d_graph
from repro.sim import SimConfig, Simulator, saturation_sweep
from repro.sim import tables as tables_mod
from repro.sim.kernel import laid_slots, step_planes
from repro.sim.tables import UNREACHABLE, build_tables, device_planes

PN5, PN7, MMS5 = pn_graph(5), pn_graph(7), mms_graph(5)
G16 = torus3d_graph(4, 4, 1)
UGAL = "ugal_threshold(0)"


def _divided_planes(dist, head, slot_ok, dests, dtype):
    """``(split, deliver)`` as the tables were filled before the planes
    moved: the head distances gathered over every router, a bool mask
    divided by its count in float64, then cast."""
    n = dist.shape[0]
    dist_pad = np.vstack([dist, np.full((1, n), UNREACHABLE, np.int32)])
    head_dist = dist_pad[head][:, :, dests]
    mask = (head_dist == dist[:, dests][:, None, :] - 1) & slot_ok[:, :, None]
    count = mask.sum(axis=1)
    split = (mask / np.maximum(count, 1)[:, None, :]).astype(dtype)
    return split, head[:, :, None] == dests[None, None, :]


def _points_cols(g):
    """The point routers of a projective network: its compacted axis."""
    return np.arange(g.n // 2)


CASES = {
    "pn5": (PN5, None, None),
    "pn7": (PN7, None, None),
    "mms5": (MMS5, None, None),
    "pn5_points": (PN5, None, _points_cols(PN5)),
    "pn7_faulted": (PN7, FaultSet(links=[(0, int(PN7.indices[0]))],
                                  routers=[9]), None),
}


@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["float32", "float64"])
@pytest.mark.parametrize("case", list(CASES))
def test_device_planes_match_the_host_formula(case, dtype):
    """PN(5) at K = 6, PN(7) at K = 8, MMS(5) at K = 7 laid at 8, a
    compacted ``points`` axis next to the full one, and a fabric with a
    dead link and a dead router: every axis's device split and deliver
    are the host formula's bits, padded to the laid K with zero slots,
    and ``w_val`` matches the host contraction."""
    g, faults, cols = CASES[case]
    t = build_tables(g, np.arange(g.n), dtype=dtype, faults=faults)
    assert t.faulted == (faults is not None)
    kl = laid_slots(t.k)
    planes = device_planes(t, dtype, kl, cols)
    axes = {"F": t.active} if cols is None else {"F": t.active,
                                                 "C": t.active[cols]}
    assert sorted(k for k in planes if len(k) == 1) == sorted(axes)
    for name, dests in axes.items():
        want_split, want_deliver = _divided_planes(t.dist, t.head, t.slot_ok,
                                                   dests, dtype)
        split = np.asarray(planes[name]["split"])
        deliver = np.asarray(planes[name]["deliver"])
        assert split.shape == deliver.shape == (t.n, kl, len(dests))
        assert split.dtype == deliver.dtype == dtype
        assert split[:, :t.k].tobytes() == want_split.tobytes(), name
        np.testing.assert_array_equal(deliver[:, :t.k],
                                      want_deliver.astype(dtype))
        assert not split[:, t.k:].any() and not deliver[:, t.k:].any()
    # the host planes, made on first read, have the same bits
    assert t.split.tobytes() == np.asarray(
        planes["F"]["split"])[:, :t.k].tobytes()
    np.testing.assert_array_equal(t.deliver, _divided_planes(
        t.dist, t.head, t.slot_ok, t.active, dtype)[1])
    w_val = np.asarray(planes["w_val"])
    assert w_val.shape == (t.n, kl) and not w_val[:, t.k:].any()
    np.testing.assert_allclose(w_val[:, :t.k],
                               np.einsum("nm,nkm->nk", t.spread, t.split),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(planes["spread"]), t.spread)


def _counts(sess):
    m = sess.metrics
    return (m.counter("sim.route_planes[device]").value,
            m.counter("sim.route_planes[host]").value)


@pytest.mark.parametrize("backend", ["pallas_interpret", "jax"])
def test_jitted_builds_make_the_planes_once_on_the_device(backend):
    """A jitted step's Simulator makes its dense planes once, on the
    device, inside span ``sim.route_tables``, and never on the host."""
    dem = normalize_demand(make_pattern("uniform").demand(MMS5, None))
    with obs.session(mode="trace", series=False) as sess:
        sess.enabled = False
        sim = Simulator(MMS5, SimConfig(routing=UGAL, backend=backend),
                        demand=dem)
        sim.run(dem, 0.5, steps=3)
    assert _counts(sess) == (1, 0)
    assert sim.tables._planes is None
    assert sess.span_summary()["sim.route_tables"]["count"] == 1
    planes = sim.tables._device_planes[1]
    tabs = sim._step.tabs
    assert tabs.get("F", tabs)["split"] is planes["F"]["split"]
    assert tabs["w_val"] is planes["w_val"]


def test_numpy_builds_make_the_planes_on_read():
    """Route tables make no dense plane until one is read, and then one
    host build serves ``split`` and ``deliver``; the numpy step reads
    them at its build, on the host only."""
    with obs.session(mode="metrics") as sess:
        t = build_tables(PN5, np.arange(PN5.n))
        assert _counts(sess) == (0, 0) and t._planes is None
        assert t.split.shape == t.deliver.shape == (t.n, t.k, t.m)
        assert t.split is t.split
        assert _counts(sess) == (0, 1)
        assert step_planes(t, "numpy", np.float64) is None
    with obs.session(mode="metrics") as sess:
        sim = Simulator(PN5, SimConfig(routing=UGAL, backend="numpy"))
    assert _counts(sess) == (0, 1) and sim.tables._planes is not None


@pytest.mark.parametrize("backend", ["pallas_interpret", "jax"])
def test_fault_states_make_their_planes_once_each(backend):
    """Each fault state's tables make their device planes once; a second
    run through the same fault state reuses them.  The fault surgery
    reads the new state's host split."""
    dem = normalize_demand(make_pattern("uniform").demand(G16, None))
    fs = FaultSet(links=[(0, int(G16.indices[0]))], routers=[5])
    with obs.session(mode="metrics") as sess:
        sim = Simulator(G16, SimConfig(routing=UGAL, backend=backend),
                        demand=dem)
        for _ in range(2):
            sim.run(dem, 0.5, steps=6, events=[(3, fs)])
    assert _counts(sess) == (2, 1)


def test_numpy_sweep_histories_keep_their_bits(monkeypatch):
    """A numpy ``saturation_sweep`` on the backend-parity fixture (the
    8x16 torus under tornado) returns the histories it returned when the
    tables were filled by division, bit for bit."""
    torus = torus3d_graph(8, 16, 1)

    def sweep():
        return saturation_sweep(
            torus, "tornado", routing=UGAL, loads=[0.3, 0.5], steps=40,
            refine=1, config=SimConfig(backend="numpy"),
            theta_analytic=0.4147)

    now = sweep()
    monkeypatch.setattr(
        tables_mod, "route_planes",
        lambda xp, dist, head, slot_ok, dests, recip: _divided_planes(
            dist, head, slot_ok, dests, recip.dtype))
    before = sweep()
    assert len(now.runs) == len(before.runs) == 3
    for a, b in zip(now.runs, before.runs):
        assert a.offered == b.offered
        for key, h in b.history.items():
            np.testing.assert_array_equal(a.history[key], h, err_msg=key)
