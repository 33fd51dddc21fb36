"""The state count behind ``step_state_roofline`` matches what the
program allocates, on the full and on the compacted dest axis."""

from __future__ import annotations

import numpy as np
import pytest


@pytest.mark.parametrize("cols", [None, "points"])
def test_state_cells_match_init_state(cols):
    from repro.core.graph import Graph
    from repro.sim.engine import init_state
    from repro.sim.tables import build_tables

    import state_bytes
    from traffic import demand
    fab = demand.fabric({"topology": "pn", "topology_args": {"q": 5}}, 7)
    g = Graph(fab["n"], fab["edges"])
    t = build_tables(g, np.arange(g.n), dtype=np.float32)
    dest_cols = None if cols is None else fab["groups"]["points"]
    st = init_state(t, np.float32, dest_cols=dest_cols)
    c = t.m if dest_cols is None else len(dest_cols)
    want = state_bytes.state_cells(t.n, t.k, t.m, c)
    got = {k: getattr(st, k).size for k in want}
    assert got == want
    need = state_bytes.step_bytes(t.n, t.k, t.m, c, 4)
    assert need == 8 * sum(want.values())


def test_pn31_points_state_is_two_gigabytes_a_step():
    import state_bytes
    need = state_bytes.step_bytes(1986, 32, 1986, 993, 4)
    assert 2.0e9 < need < 2.1e9
