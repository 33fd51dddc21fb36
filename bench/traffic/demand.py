"""The one traffic generator: a seeded fabric and its demand matrix.

A traffic mix is a JSON file beside this one (``<mix>.json``) that names
the destination set and the sweep's settings; a configuration names the
topology (``bench/topologies/<topology>.py``) and its size.  ``--seed``
draws a relabeling of the router ids, applied to the graph and the
demand together: every seed gives an isomorphic fabric (same shapes,
same compacted column count, same analytic knee) whose tables hold other
contents in another column order.

The demand is the copy of ``benchmarks.kernel_bench.points_demand``
generalised to a named destination set: every router sends equally to
every router of the set (itself excluded), normalized so the busiest
source injects one unit.  ``dests: "all"`` is uniform all-to-all.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent


def load_mix(name: str) -> dict:
    path = HERE / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def _topology(name: str):
    path = BENCH / "topologies" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no topology {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_topology_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def fabric(config: dict, seed: int | None) -> dict:
    """The configuration's topology, relabeled by ``seed`` (None keeps
    the canonical labels): ``{"n", "edges", "groups"}``."""
    fab = _topology(config["topology"]).build(**config["topology_args"])
    if seed is None:
        return fab
    perm = np.random.default_rng(int(seed)).permutation(fab["n"])
    return {"n": fab["n"], "edges": perm[fab["edges"]],
            "groups": {k: np.sort(perm[v])
                       for k, v in fab["groups"].items()}}


def demand(fab: dict, mix: dict) -> np.ndarray:
    """(N, N) float64 demand of ``mix`` on ``fab``, busiest source = 1."""
    n = fab["n"]
    dests = mix["dests"]
    cols = np.arange(n) if dests == "all" else fab["groups"][dests]
    dem = np.zeros((n, n))
    dem[:, cols] = 1.0
    np.fill_diagonal(dem, 0.0)
    return dem / dem.sum(axis=1).max()
