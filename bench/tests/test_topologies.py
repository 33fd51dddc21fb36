"""The benchmark's own topology builders against the program's
constructors and the paper's Table 5."""

from __future__ import annotations

import json

import numpy as np
import pytest

from conftest import BENCH


def _uniform(n):
    dem = np.ones((n, n))
    np.fill_diagonal(dem, 0.0)
    return dem / dem.sum(axis=1).max()


def _edges(g):
    src = np.repeat(np.arange(g.n), np.diff(g.indptr))
    e = np.column_stack([src, g.indices])
    return e[e[:, 0] < e[:, 1]]


@pytest.mark.parametrize("q", [5, 7, 9, 11])
def test_mms_matches_the_program(q):
    """Regular of degree (3q - d) / 2, diameter 2, and the same uniform
    theta as the program's ``mms_graph(q)``."""
    import reference
    from traffic import demand

    from repro.core import mms_graph
    fab = demand.fabric({"topology": "mms", "topology_args": {"q": q}},
                        None)
    n, e = fab["n"], fab["edges"]
    d = 1 if q % 4 == 1 else -1
    assert n == 2 * q * q
    assert set(np.bincount(e.ravel(), minlength=n)) == {(3 * q - d) // 2}
    dist = reference.distances(n, e)
    assert dist.max() == 2
    theta = reference.theta(n, e, _uniform(n), "ugal_threshold(0)",
                            dist=dist)
    g = mms_graph(q)
    want = reference.theta(g.n, _edges(g), _uniform(g.n),
                           "ugal_threshold(0)")
    assert abs(theta - want) <= 1e-9 * want


def test_mms27_is_the_table5_row():
    """SF MMS(27): 1458 routers of degree 41, 29,889 links, 18 terminals
    a router (T = 26,244, radix R = 59), subscription 18 / theta 0.976."""
    import reference
    from traffic import demand
    cfg = json.loads((BENCH / "configs" / "mms27_ugal0.json").read_text())
    fab = demand.fabric(cfg, None)
    n, e = fab["n"], fab["edges"]
    assert (n, len(e)) == (1458, 29889) == (cfg["routers"], 29889)
    assert set(np.bincount(e.ravel(), minlength=n)) == {41}
    assert cfg["degree"] + cfg["terminals_per_router"] == 59
    assert cfg["terminals"] == 26244 == n * 18
    theta = reference.theta(n, e, _uniform(n), cfg["routing"])
    assert round(cfg["terminals_per_router"] / theta, 3) == 0.976
