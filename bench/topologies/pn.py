"""PN(q): the incidence graph of the projective plane PG(2, q), q prime.

Built here from its definition, independently of the program's own
constructor: points and lines of PG(2, q) are the normalized nonzero
vectors of GF(q)^3 (first nonzero coordinate 1), and point p lies on line
l iff p . l = 0 (mod q).  Routers [0, n0) are the points, [n0, 2 n0) the
lines, n0 = q^2 + q + 1; every router has degree q + 1.
"""

from __future__ import annotations

import numpy as np


def _normalized_vectors(q: int) -> np.ndarray:
    v = np.stack(np.meshgrid(np.arange(q), np.arange(q), np.arange(q),
                             indexing="ij"), axis=-1).reshape(-1, 3)
    v = v[v.any(axis=1)]
    first = v[np.arange(len(v)), (v != 0).argmax(axis=1)]
    return v[first == 1]


def build(q: int) -> dict:
    """``{"n", "edges", "groups"}``: router count, (E, 2) undirected edge
    list, and named router sets (``points``, ``lines``)."""
    q = int(q)
    if q < 2 or any(q % p == 0 for p in range(2, int(q ** 0.5) + 1)):
        raise ValueError(f"PN(q) is built here for prime q only, got {q}")
    vec = _normalized_vectors(q)
    n0 = len(vec)
    assert n0 == q * q + q + 1
    inc = (vec @ vec.T) % q == 0                  # (point, line)
    p, l = np.nonzero(inc)
    edges = np.stack([p, n0 + l], axis=1).astype(np.int64)
    return {"n": 2 * n0, "edges": edges,
            "groups": {"points": np.arange(n0),
                       "lines": np.arange(n0, 2 * n0)}}
