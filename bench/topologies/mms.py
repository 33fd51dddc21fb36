"""MMS(q): the McKay-Miller-Siran graph that Slim Fly is built on, q an
odd prime power.

Built here from its definition, independently of the program's own
constructor (Besta & Hoefler, "Slim Fly", SC'14; arXiv:1512.07574,
Section 4.2).  Routers are (s, x, y), s in {0, 1} and x, y in GF(q),
with index s q^2 + x q + y:

* local edges (s, x, y1) ~ (s, x, y2) iff y1 - y2 is in X_s;
* global edges (0, x1, y1) ~ (1, x2, y2) iff y1 - y2 = x1 x2.

With q = 4w + d (d = 1 or -1) and xi a primitive element, X_0 holds the
powers xi^e for e in {0, 2, ..., q - 3} when d = 1, and for e in
{0, 2, ..., 2w - 2} and {2w - 1, 2w + 1, ..., 4w - 3} when d = -1;
X_1 = xi X_0.  Every router has degree (3q - d) / 2 and the diameter is 2.

GF(p^k) is GF(p)[x] modulo the first monic polynomial of degree k, in
the order of its coefficients read from x^(k-1) down as a base-p number,
in which x has order q - 1; an element is the base-p number of its
coefficients (constant term lowest).  For q = 27 that polynomial is
x^3 + 2x + 1.
"""

from __future__ import annotations

import itertools

import numpy as np


def _prime_power(q: int) -> tuple[int, int]:
    for p in range(2, q + 1):
        if q % p == 0:
            k, r = 0, q
            while r % p == 0:
                r, k = r // p, k + 1
            if r != 1:
                break
            return p, k
    raise ValueError(f"MMS(q) needs a prime power q, got {q}")


def field(q: int) -> dict:
    """``{"sub", "exp"}`` of GF(q): the (q, q) table of a - b and
    ``exp[e]`` = xi^e for e in [0, q - 1)."""
    p, k = _prime_power(q)
    digits = np.array([[(a // p ** i) % p for i in range(k)]
                       for a in range(q)])
    enc = lambda v: int(sum(int(c) * p ** i for i, c in enumerate(v)))
    sub = np.array([[enc((digits[a] - digits[b]) % p) for b in range(q)]
                    for a in range(q)])
    for high in itertools.product(range(p), repeat=k):
        low = high[::-1]           # x^k + ... + low[1] x + low[0]
        if low[0] == 0:
            continue
        # x^k = -(low[0] + low[1] x + ... + low[k-1] x^(k-1))
        red = [(-c) % p for c in low]
        v, seen = [1] + [0] * (k - 1), []
        for _ in range(q - 1):
            seen.append(enc(v))
            top = v[-1]
            v = [0] + v[:-1]
            v = [(v[i] + top * red[i]) % p for i in range(k)]
        if len(set(seen)) == q - 1 and enc(v) == 1:
            return {"sub": sub, "exp": np.array(seen)}
    raise AssertionError(f"no primitive polynomial found for GF({q})")


def generator_sets(q: int, f: dict) -> tuple[np.ndarray, np.ndarray]:
    """``(X_0, X_1)`` as field elements."""
    if q % 4 == 1:
        e = list(range(0, q - 1, 2))
    elif q % 4 == 3:
        w = (q + 1) // 4
        e = list(range(0, 2 * w - 1, 2)) + list(range(2 * w - 1, 4 * w - 2,
                                                      2))
    else:
        raise ValueError(f"MMS(q) is built here for odd q, got {q}")
    e = np.array(e)
    return f["exp"][e], f["exp"][(e + 1) % (q - 1)]


def build(q: int) -> dict:
    """``{"n", "edges", "groups"}``: router count, (E, 2) undirected edge
    list, and the two halves ``s0`` and ``s1`` as named router sets."""
    q = int(q)
    f = field(q)
    x0, x1 = generator_sets(q, f)
    qq = q * q
    y = np.arange(q)
    edges = []
    for s, xs in ((0, x0), (1, x1)):
        y1, y2 = np.nonzero(np.isin(f["sub"], xs) & (y[:, None] < y))
        for x in range(q):
            base = s * qq + x * q
            edges.append(np.stack([base + y1, base + y2], axis=1))
    # (0, x1, y1) ~ (1, x2, y1 - x1 x2)
    x1g, x2g, y1g = (a.ravel() for a in np.meshgrid(y, y, y, indexing="ij"))
    log = np.zeros(q, dtype=np.int64)
    log[f["exp"]] = np.arange(q - 1)
    prod = np.where((x1g > 0) & (x2g > 0),
                    f["exp"][(log[x1g] + log[x2g]) % (q - 1)], 0)
    y2g = f["sub"][y1g, prod]
    edges.append(np.stack([x1g * q + y1g, qq + x2g * q + y2g], axis=1))
    return {"n": 2 * qq, "edges": np.concatenate(edges).astype(np.int64),
            "groups": {"s0": np.arange(qq), "s1": np.arange(qq, 2 * qq)}}
