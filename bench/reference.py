"""Plain reference of the fluid simulator and of its analytic knee.

Nothing here imports the program.  It is a straightforward restatement
of the model that ``docs/simulation.md`` and the dense step of
``repro.sim.engine`` describe, written against the same semantics:

* route tables from an all-pairs BFS (``scipy.sparse.csgraph``): the
  equal-split minimal next-hop table, the uniform Valiant intermediate
  spread, remaining-hop estimates for the threshold-UGAL rule;
* one dense step over ``(router, out-slot, dest)`` queues for the three
  virtual channels (forward share, credit throttle, phase-1 conversion,
  injection, per-hop UGAL decision, ECMP enqueue), in ``jax.numpy`` at a
  chosen precision, every state tensor on the full dest axis;
* the analytic fluid theta: arc loads of a demand matrix split evenly
  over all shortest paths (batched path counting), exact two-phase
  Valiant demands, and the theta-maximizing blend of the two for UGAL.

The step runs at the configuration's precision with matrix products at
``HIGHEST``; the correctness control runs the same code one precision
lower (``control_dtype``).  Where the model divides by a quantity that
is zero in exact arithmetic (an empty pool, no candidate fluid), the
reference divides only where it is positive, so a rounding residue just
below zero cannot become a huge factor.
"""

from __future__ import annotations

import functools

import numpy as np

BIG = 1e12
TINY = 1e-30
STATS = ("delivered", "accepted", "offered", "occupancy", "src_backlog",
         "diverted", "ties")
# a decision this close to its threshold (relative), or a start-of-step
# vc0/vc1 occupancy this close to capacity, is a tie for the threshold
# rule: rounding decides whether the fluid diverts
TIE_EPS = 1e-4


def parse_routing(spec: str) -> tuple[str, float]:
    """``(mode, threshold)``: minimal, valiant, or ugal_threshold(T)."""
    s = spec.replace(" ", "")
    if s in ("minimal", "valiant"):
        return s, 0.0
    if s.startswith("ugal_threshold(") and s.endswith(")"):
        return "ugal", float(s[len("ugal_threshold("):-1])
    raise ValueError(f"reference knows no routing {spec!r}")


def control_dtype(precision: str) -> str:
    """The nearest precision below a configuration's precision."""
    return {"float64": "float32", "float32": "bfloat16"}[precision]


# ---------------------------------------------------------------------------
# route tables
# ---------------------------------------------------------------------------


def distances(n: int, edges: np.ndarray) -> np.ndarray:
    """(N, N) int hop counts; raises on a disconnected graph."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import shortest_path
    a = sp.coo_matrix((np.ones(len(edges)), (edges[:, 0], edges[:, 1])),
                      shape=(n, n)).tocsr()
    d = shortest_path(a, method="D", directed=False, unweighted=True)
    if not np.isfinite(d).all():
        raise ValueError("graph is disconnected")
    return d.astype(np.int64)


def tables(n: int, edges: np.ndarray, active: np.ndarray | None = None,
           dist: np.ndarray | None = None) -> dict:
    """Host-side float64 tables of the dense step.  Out-slot ``k`` of
    router ``r`` is its ``k``-th neighbour in increasing id order."""
    edges = np.asarray(edges, dtype=np.int64)
    active = np.arange(n) if active is None else np.asarray(active)
    dist = distances(n, edges) if dist is None else dist
    nbrs = [[] for _ in range(n)]
    for u, v in edges:
        nbrs[u].append(v)
        nbrs[v].append(u)
    k = max(len(x) for x in nbrs)
    head = np.full((n, k), n, dtype=np.int64)          # pad slot -> n
    for r, x in enumerate(nbrs):
        head[r, :len(x)] = sorted(x)
    m = len(active)
    dist_act = dist[:, active].astype(np.float64)       # (N, M)
    dpad = np.vstack([dist[:, active], np.full((1, m), 1 << 30)])
    nxt = dpad[head] == dist[:, active][:, None, :] - 1  # (N, K, M)
    split = nxt / np.maximum(nxt.sum(axis=1), 1)[:, None, :]
    is_act = np.zeros(n, dtype=bool)
    is_act[active] = True
    not_self = active[None, :] != np.arange(n)[:, None]
    spread = not_self / not_self.sum(axis=1, keepdims=True)
    hval = (dist[:, active].mean(axis=1)[:, None]
            + dist[np.ix_(active, active)].mean(axis=0)[None, :])
    return {"n": n, "k": k, "m": m, "head": head, "active": active,
            "split": split, "spread": spread, "dist_act": dist_act,
            "hval_rem": hval, "n_mids": (m - is_act).astype(np.float64),
            "w_val": np.einsum("nm,nkm->nk", spread, split)}


# ---------------------------------------------------------------------------
# the dense step
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=8)
def _step(n, k, m, mode, thr, cap, buf, dtype):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    hi = jax.lax.Precision.HIGHEST

    def _ratio(num, den):
        # num / den where den > 0, else 0: a denominator that is zero in
        # exact arithmetic and a rounding residue below zero here must
        # not turn into a huge factor
        return jnp.where(den > 0, num / jnp.where(den > 0, den, 1), 0)

    def step(tb, state, inj, inj_cap):
        split, spread, w_val = tb["split"], tb["spread"], tb["w_val"]
        dist_act, hval_rem = tb["dist_act"], tb["hval_rem"]
        head, active, n_mids = tb["head"], tb["active"], tb["n_mids"]
        c = jnp.asarray(cap, dt)
        b = jnp.asarray(buf, dt)
        q0, q1, q2, src, pend, stage2 = state
        deliver = (head[:, :, None] == active[None, None, :]).astype(dt)

        o0, o1, o2 = q0.sum(-1), q1.sum(-1), q2.sum(-1)       # (N, K)
        share = c / jnp.maximum(o0 + o1 + o2, c)

        def forward(q):
            mv = q * share[:, :, None]
            dl = mv * deliver
            cont = mv - dl
            arr = jnp.zeros((n + 1, m), dt).at[head.reshape(-1)].add(
                cont.reshape(n * k, m))[:n]
            return mv, dl, cont, arr

        def admit(q, mv, arr):
            own = q.sum(axis=(1, 2)) - mv.sum(axis=(1, 2))
            space = jnp.maximum(b - own, 0)
            return jnp.minimum(1, space / jnp.maximum(arr.sum(-1), TINY))

        fw = [forward(q) for q in (q0, q1, q2)]
        qs = []
        arrs = []
        for q, (mv, dl, cont, arr) in zip((q0, q1, q2), fw):
            s = admit(q, mv, arr)
            damp = jnp.concatenate([s, jnp.ones(1, dt)])[head]    # (N, K)
            qs.append(q - dl - cont * damp[:, :, None])
            arrs.append(arr * s[:, None])
        q0, q1, q2 = qs
        arr0, arr1, arr2 = arrs
        delivered = fw[0][1].sum() + fw[2][1].sum()

        # phase-1 conversions at the intermediate
        stage2 = stage2 + fw[1][1].sum(axis=(0, 1))
        avail2 = jnp.maximum(b - (q2.sum(axis=(1, 2)) + arr2.sum(-1)),
                             0)[active]
        pend_sum = pend.sum(-1)
        drain = jnp.maximum(jnp.minimum(jnp.minimum(stage2, avail2),
                                        pend_sum), 0)
        take = drain[:, None] * _ratio(pend, pend_sum[:, None])
        pend = pend - take
        stage2 = stage2 - drain
        idx = jnp.arange(m)
        delivered = delivered + take[idx, idx].sum()
        take = take.at[idx, idx].set(0)
        conv2 = jnp.zeros((n, m), dt).at[active].add(take)

        # injection up to the per-source cap
        src = src + inj
        srcsum = src.sum(-1)
        q_inj = src * (jnp.minimum(srcsum, inj_cap)
                       / jnp.maximum(srcsum, TINY))[:, None]
        src = src - q_inj

        # per-hop decision on every vc0 enqueue
        cand = arr0 + q_inj
        ties = jnp.zeros((), dt)
        if mode == "minimal":
            div = jnp.zeros_like(cand)
        else:
            near = lambda o: (jnp.abs(o - c) <= TIE_EPS * c).sum()
            ties = (near(o0) + near(o1)).astype(dt)
            if mode == "valiant":
                ind = jnp.ones_like(cand)
            else:
                b0 = jnp.maximum(o0 - c, 0)
                b1 = jnp.maximum(o1 - c, 0)
                q_min = jnp.einsum("nk,nkm->nm", b0, split, precision=hi)
                q_val = (b1 * w_val).sum(axis=1)
                lhs = dist_act * q_min
                rhs = thr + hval_rem * q_val[:, None]
                ind = (lhs > rhs).astype(dt)
                top = jnp.maximum(lhs, rhs)
                close = ((jnp.abs(lhs - rhs) <= TIE_EPS * top) & (top > 0)
                         & (cand > 0))
                ties = ties + close.sum().astype(dt)
            dcand = cand * ind
            space1 = jnp.maximum(b - (q1.sum(axis=(1, 2)) + arr1.sum(-1)), 0)
            s1 = jnp.minimum(1, space1 / jnp.maximum(dcand.sum(-1), TINY))
            div = dcand * s1[:, None]
            scaled = div / n_mids[:, None]
            pend = pend + scaled.sum(0)[None, :] - scaled[active, :]
        keep_frac = jnp.where(cand > 0, _ratio(cand - div, cand), 1)
        trans_keep = arr0 * keep_frac
        inj_keep = q_inj * keep_frac
        space0 = jnp.maximum(b - (q0.sum(axis=(1, 2)) + trans_keep.sum(-1)),
                             0)
        s0 = jnp.minimum(1, space0 / jnp.maximum(inj_keep.sum(-1), TINY))
        inj_adm = inj_keep * s0[:, None]
        src = src + (inj_keep - inj_adm)

        # ECMP enqueue
        q0 = q0 + (trans_keep + inj_adm)[:, None, :] * split
        q1 = q1 + (arr1 + div.sum(-1)[:, None] * spread)[:, None, :] * split
        q2 = q2 + (arr2 + conv2)[:, None, :] * split

        occ = q0.sum() + q1.sum() + q2.sum() + stage2.sum()
        accepted = q_inj.sum() - (inj_keep - inj_adm).sum()
        stats = jnp.stack([delivered, accepted, inj.sum(), occ, src.sum(),
                           div.sum(), ties])
        return ((q0, q1, q2, src, pend, stage2),
                stats.astype(jnp.promote_types(dt, jnp.float32)))

    return jax.jit(step, donate_argnums=(1,))


def device_tables(tb: dict, dtype: str) -> dict:
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    keys_f = ("split", "spread", "w_val", "dist_act", "hval_rem", "n_mids")
    out = {key: jnp.asarray(tb[key], dt) for key in keys_f}
    out["head"] = jnp.asarray(tb["head"], jnp.int32)
    out["active"] = jnp.asarray(tb["active"], jnp.int32)
    return jax.device_put(out)


def run(tb: dict, dtb: dict, demand: np.ndarray, offered: float, steps: int,
        routing: str, dtype: str, capacity: float = 1.0,
        buffer: float = float("inf"), inj_factor: float = 1.0) -> np.ndarray:
    """(steps, 7) float64 raw stats of one open-loop run from empty
    queues, columns in :data:`STATS` order."""
    import jax
    import jax.numpy as jnp
    n, k, m = tb["n"], tb["k"], tb["m"]
    mode, thr = parse_routing(routing)
    dt = jnp.dtype(dtype)
    fn = _step(n, k, m, mode, float(thr), float(capacity),
               float(min(buffer, BIG)), dt.name)
    inj_np = offered * np.asarray(demand, np.float64)[:, tb["active"]]
    inj = jnp.asarray(inj_np, dt)
    inj_cap = jnp.asarray(inj_factor * inj_np.sum(axis=1), dt)
    z = lambda *s: jnp.zeros(s, dt)
    state = (z(n, k, m), z(n, k, m), z(n, k, m), z(n, m), z(m, m), z(m))
    rows = []
    for _ in range(steps):
        state, st = fn(dtb, state, inj, inj_cap)
        rows.append(st)
    out = np.asarray(jax.device_get(jnp.stack(rows)), np.float64)
    del state
    return out


def histories(stats: np.ndarray, demand: np.ndarray) -> dict:
    """The run's histories normalized as ``SimRun.history`` is, plus the
    conservation residual of the whole run."""
    total = float(np.asarray(demand, np.float64).sum())
    inj = stats[:, 2].sum()
    resid = abs(inj - stats[:, 0].sum() - stats[-1, 3] - stats[-1, 4]) \
        / max(inj, 1e-30)
    return {"delivered": stats[:, 0] / total, "accepted": stats[:, 1] / total,
            "occupancy": stats[:, 3], "residual": float(resid)}


# ---------------------------------------------------------------------------
# analytic theta
# ---------------------------------------------------------------------------


def _arc_loads(dist, adj, sigma, demand):
    """(N, N) load on arc v -> w of ``demand`` split evenly over all
    shortest paths: batched over sources, one level at a time."""
    import jax.numpy as jnp
    from jax import lax
    hi = lax.Precision.HIGHEST
    delta = jnp.zeros_like(demand)
    loads = jnp.zeros_like(demand)
    for lvl in range(int(dist.max()) - 1, -1, -1):
        coef = jnp.where(dist == lvl + 1,
                         (demand + delta) / jnp.maximum(sigma, 1), 0)
        sig = jnp.where(dist == lvl, sigma, 0)
        delta = delta + sig * jnp.matmul(coef, adj, precision=hi)
        loads = loads + jnp.matmul(sig.T, coef, precision=hi)
    return loads * adj


def theta(n: int, edges: np.ndarray, demand: np.ndarray, routing: str,
          active: np.ndarray | None = None, dist: np.ndarray | None = None
          ) -> float:
    """Fluid saturation throughput of ``demand`` (busiest source = 1):
    1 / the busiest arc's load under minimal, Valiant, or the UGAL blend
    that maximizes it (a finite threshold converges to the blend)."""
    import jax.numpy as jnp
    from jax import lax
    mode, thr = parse_routing(routing)
    if mode == "ugal" and not np.isfinite(thr):
        mode = "minimal"
    edges = np.asarray(edges, dtype=np.int64)
    dist = distances(n, edges) if dist is None else dist
    active = np.arange(n) if active is None else np.asarray(active)
    adj = np.zeros((n, n))
    adj[edges[:, 0], edges[:, 1]] = adj[edges[:, 1], edges[:, 0]] = 1.0
    d = jnp.asarray(dist, jnp.int32)
    a = jnp.asarray(adj, jnp.float32)
    sigma = jnp.asarray(np.eye(n), jnp.float32)
    for lvl in range(1, int(dist.max()) + 1):
        sigma = sigma + jnp.where(d == lvl, jnp.matmul(
            jnp.where(d == lvl - 1, sigma, 0), a,
            precision=lax.Precision.HIGHEST), 0)
    ld = lambda dem: np.asarray(_arc_loads(d, a, sigma,
                                           jnp.asarray(dem, jnp.float32)),
                                np.float64)[adj > 0]
    dem = np.asarray(demand, np.float64)
    if mode in ("minimal", "ugal"):
        l_min = ld(dem)
        if mode == "minimal":
            return 1.0 / l_min.max()
    act = np.zeros(n)
    act[active] = 1.0
    # exact expected two-phase Valiant: each source's row sum spread over
    # the intermediates, each target's column sum collected from them
    l_val = (ld(np.outer(dem.sum(axis=1), act) / (len(active) - 1))
             + ld(np.outer(act, dem.sum(axis=0)) / (len(active) - 1)))
    if mode == "valiant":
        return 1.0 / l_val.max()
    # max over alpha of the blend is a convex piecewise-linear minimum
    f = lambda x: float((x * l_min + (1 - x) * l_val).max())
    lo, hi = 0.0, 1.0
    for _ in range(200):
        a1, a2 = lo + (hi - lo) / 3, hi - (hi - lo) / 3
        if f(a1) <= f(a2):
            hi = a2
        else:
            lo = a1
    return 1.0 / min(f(0.0), f(1.0), f(0.5 * (lo + hi)))
