"""Link utilization at saturation under uniform traffic with minimal routing.

This is the paper's central quantitative lever (Section 2 / Theorem 3.9):
with one unit of traffic per ordered vertex pair, split evenly across all
shortest paths, each directed arc carries some load; saturation normalizes
the maximum arc to 1, so

    u = mean(arc load) / max(arc load)

and the serviceable compute nodes per router are Δ0 = Δ·u/k̄ (Eq. 1).

Implemented as Brandes-style shortest-path DAG accumulation.  Several
engines compute the same quantity (see ``arc_loads``'s ``engine`` arg and
repro.perf for the selection flags):

  naive  — the reference implementation: one Python-level BFS + forward/
           backward sweep per source.  O(S) interpreted loops; kept as the
           parity oracle and for ad-hoc graphs.
  numpy  — batched all-source engine.  A whole block of sources advances
           one BFS level per step; the forward sigma recurrence and the
           backward delta recurrence become (S, N) x (N, N) GEMMs on the
           dense adjacency (float32 for the exact integer path counts,
           float64 for the load accumulation).  Bipartite graphs (PN, OFT,
           MLFM, hypercube, K_{n,n}) run on the half-size biadjacency
           blocks — 4x fewer FLOPs and per-level load matrices that land
           directly on the arc coordinates.  Beyond ``util_dense_max``
           vertices a CSR gather + add.reduceat sweep in a transposed
           (N, S) layout replaces the GEMMs.
  jax    — the same level-synchronous dense recurrences as jnp matmuls,
           jit-compiled per (shape, level-count) and chunked over source
           blocks to bound device memory; float64 via a scoped x64 switch.
  pallas — the jax engine's recurrences through the fused mask+GEMM
           pallas kernels (repro.kernels.mask_gemm): the distance-table
           mask runs in the GEMM epilogue instead of as a second pass
           over the (S, N) level state.  Compiled float32 on TPU;
           float64 under the pallas interpreter elsewhere (the parity /
           development path).
  orbit  — automorphism shortcut (repro.core.orbits): the total load
           vector is constant on arc orbits, and per-arc-orbit sums are
           constant as the source ranges over a vertex orbit, so one
           Brandes sweep per vertex orbit (usually 1–2 for the paper's
           families) replaces N of them.  Exact, not an approximation.

``arc_loads``/``utilization`` keep the seed's drop-in signature; traffic
can be restricted to leaf vertices for indirect networks (Section 6) via
``targets_mask``.

``arc_loads_weighted`` generalizes the same recurrences from the implicit
uniform all-to-all to an arbitrary demand matrix D[s, t] (units of traffic
from s to t, split across shortest paths): the backward coefficient
``(targets + delta) / sigma`` simply becomes ``(D[s] + delta) / sigma``,
so every batched engine handles a whole block of weighted sources in one
level-synchronous sweep — a permutation pattern costs one sweep, not N.
The uniform case is ``D = ones - I`` and reproduces ``arc_loads`` exactly.
See repro.core.traffic for the pattern registry built on top.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .. import obs
from ..perf import flags
from .graph import Graph, bfs_distances

__all__ = ["arc_loads", "arc_loads_weighted", "utilization",
           "UtilizationReport", "valiant_report"]

_ENGINES = ("auto", "naive", "numpy", "csr", "jax", "pallas", "orbit")

# float32 GEMMs are exact on integer path counts below 2^24; promote to
# float64 past this guard.
_F32_EXACT_MAX = float(2**23)

# Cap BLAS threads around the GEMM engines: at the couple-hundred-row
# shapes a source block produces, OpenBLAS's own threading measures 3-4x
# SLOWER than one core, and two single-thread sweeps overlap via
# _run_units instead.  Talk to the loaded OpenBLAS directly over ctypes —
# threadpoolctl's first scan costs >100 ms, which would land inside the
# first (cold) utilization call.
_BLAS_CTL = None  # (set_fn, get_fn) | False once probed


def _openblas_ctl():
    global _BLAS_CTL
    if _BLAS_CTL is None:
        _BLAS_CTL = False
        try:
            import ctypes

            with open("/proc/self/maps") as fh:
                paths = {line.split()[-1] for line in fh
                         if "openblas" in line.lower() and line.rstrip().endswith(".so")}
            for path in sorted(paths):
                lib = ctypes.CDLL(path)
                for suffix in ("", "64_", "_64_"):
                    for prefix in ("openblas_", "scipy_openblas_"):
                        try:
                            set_fn = getattr(lib, f"{prefix}set_num_threads{suffix}")
                            get_fn = getattr(lib, f"{prefix}get_num_threads{suffix}")
                        except AttributeError:
                            continue
                        get_fn.restype = ctypes.c_int
                        _BLAS_CTL = (set_fn, get_fn)
                        return _BLAS_CTL
        except OSError:  # non-linux / static BLAS: leave the pool alone
            pass
    return _BLAS_CTL


class _blas_limit:
    """Context manager pinning OpenBLAS to util_blas_threads threads."""

    def __enter__(self):
        self._prev = None
        k = flags().util_blas_threads
        ctl = _openblas_ctl()
        if k > 0 and ctl:
            set_fn, get_fn = ctl
            self._prev = get_fn()
            set_fn(k)
        return self

    def __exit__(self, *exc):
        if self._prev is not None:
            _openblas_ctl()[0](self._prev)
        return False


def _run_units(fns):
    """Run independent work units, threaded when util_workers allows.

    numpy releases the GIL inside GEMMs and ufunc loops, so two
    single-BLAS-thread sweeps overlap almost perfectly on two cores.
    Exceptions (e.g. the disconnected-graph ValueError) re-raise in the
    caller."""
    import threading

    workers = flags().util_workers
    if len(fns) <= 1 or workers <= 1:
        return [f() for f in fns]
    results = [None] * len(fns)
    errors = [None] * len(fns)

    def run(i):
        try:
            results[i] = fns[i]()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors[i] = e

    for lo in range(0, len(fns), workers):  # waves of `workers` threads
        wave = [threading.Thread(target=run, args=(i,))
                for i in range(lo, min(lo + workers, len(fns)))]
        for t in wave:
            t.start()
        for t in wave:
            t.join()
    for e in errors:
        if e is not None:
            raise e
    return results


@dataclass
class UtilizationReport:
    u: float
    mean_load: float
    max_load: float
    loads: np.ndarray  # per directed arc, normalized so each source sends 1/(#targets)
    kbar: float  # average distance between (restricted) pairs
    diameter: int


# ---------------------------------------------------------------------------
# Engine: naive (the reference per-source implementation)
# ---------------------------------------------------------------------------


def _arc_loads_naive(g: Graph, sources: np.ndarray, targets_mask: np.ndarray,
                     demand: np.ndarray | None = None):
    n = g.n
    arc_u = g.arc_src
    arc_v = g.indices
    loads = np.zeros(arc_u.shape[0], dtype=np.float64)

    dist_sum = 0.0
    pair_count: float = 0
    diam = 0
    tmask_f = targets_mask.astype(np.float64)
    for s in sources:
        dist = bfs_distances(g, int(s))
        if (dist < 0).any():
            raise ValueError("graph is disconnected")
        lv_u = dist[arc_u]
        lv_v = dist[arc_v]
        tree = lv_v == lv_u + 1
        maxd = int(dist.max())
        if demand is None:
            w = tmask_f
            diam = max(diam, int(dist[targets_mask].max()))
            dist_sum += float(dist[targets_mask].sum())
            pair_count += int(targets_mask.sum()) - int(targets_mask[s])
        else:
            w = demand[s]
            active = w > 0
            if active.any():
                diam = max(diam, int(dist[active].max()))
            dist_sum += float((dist * w).sum())
            pair_count += float(w.sum())

        # forward: shortest-path counts
        sigma = np.zeros(n, dtype=np.float64)
        sigma[s] = 1.0
        for lvl in range(1, maxd + 1):
            m = tree & (lv_v == lvl)
            np.add.at(sigma, arc_v[m], sigma[arc_u[m]])

        # backward: accumulate traffic (terminal deliveries included)
        delta = np.zeros(n, dtype=np.float64)
        for lvl in range(maxd, 0, -1):
            m = tree & (lv_v == lvl)
            mv = arc_v[m]
            coeff = (w[mv] + delta[mv]) / sigma[mv]
            c = sigma[arc_u[m]] * coeff
            loads[m] += c
            np.add.at(delta, arc_u[m], c)

    return loads, dist_sum, pair_count, diam


# ---------------------------------------------------------------------------
# Engine: numpy, dense generic (level-synchronous GEMMs on (S, N) blocks)
# ---------------------------------------------------------------------------


def _source_block_rows(n: int) -> int:
    blk = flags().util_block
    if blk > 0:
        return blk
    # ~48 MB per (B, N) float64 working array
    return max(32, (48 << 20) // max(8 * n, 1))


def _forward_levels(a32, a64, src_pos, n):
    """Shared level-synchronous forward sweep: distances + path counts for a
    block of sources given one-hot positions.  Returns (D, sigma, maxd).

    Level 1 is a row gather from the adjacency (the one-hot GEMM is a
    copy); masked updates use arithmetic instead of boolean fancy indexing
    (the latter measures ~10x slower at these shapes)."""
    b = len(src_pos)
    rows = np.arange(b)
    dist = np.full((b, n), -1, dtype=np.int16)
    dist[rows, src_pos] = 0
    sigma = np.zeros((b, n), dtype=np.float64)
    sigma[rows, src_pos] = 1.0
    front = None
    f64 = False
    lvl = 0
    while True:
        lvl += 1
        if (dist >= 0).all():
            return dist, sigma, lvl - 1  # saves the final GEMM
        if lvl == 1:
            nxt = a32[src_pos].copy()
        else:
            nxt = front @ (a64 if f64 else a32)
            if not f64 and nxt.size and nxt.max() >= _F32_EXACT_MAX:
                front = front.astype(np.float64)
                nxt = front @ a64
                f64 = True
        new = (nxt > 0) & (dist < 0)
        if not new.any():
            return dist, sigma, lvl - 1
        nxt *= new
        dist += new * np.int16(lvl + 1)
        sigma += nxt
        front = nxt


def _loads_dense_generic(g: Graph, sources: np.ndarray, targets_mask: np.ndarray,
                         demand: np.ndarray | None = None):
    n = g.n
    a64 = g.adjacency_dense(np.float64)
    a32 = g.adjacency_dense(np.float32)
    arc_u, arc_v = g.arc_src, g.indices
    n_arcs = arc_u.shape[0]
    loads = np.zeros(n_arcs, dtype=np.float64)
    tm = targets_mask.astype(np.float64)
    t_count = int(targets_mask.sum())
    dist_sum = 0.0
    pair_count: float = 0
    diam = 0

    # With full all-to-all traffic, reversing every path gives
    # loads[u->v] == loads[v->u] in total, so only half the arcs need the
    # per-arc reduction; the mirror is a gather at the end.  An arbitrary
    # demand matrix has no such symmetry.
    symmetric = (demand is None and bool(targets_mask.all())
                 and np.array_equal(sources, np.arange(n)))
    arc_sel = np.nonzero(arc_u < arc_v)[0] if symmetric else np.arange(n_arcs)

    def sweep(src):
        b = len(src)
        dist, sigma, maxd = _forward_levels(a32, a64, src, n)
        if (dist < 0).any():
            raise ValueError("graph is disconnected")
        if demand is None:
            w = tm[None, :]
            dm = dist[:, targets_mask]
            diam = int(dm.max())
            dist_sum = float(dm.sum(dtype=np.float64))
            pair_count = b * t_count - int(targets_mask[src].sum())
        else:
            w = demand[src]  # (b, n) per-source demand rows
            active = w > 0
            diam = int(dist[active].max()) if active.any() else 0
            dist_sum = float((dist * w).sum(dtype=np.float64))
            pair_count = float(w.sum())

        sinv = 1.0 / sigma  # sigma >= 1 everywhere once connected
        delta = np.zeros((b, n), dtype=np.float64)
        ctot = np.zeros((b, n), dtype=np.float64)
        for lvl in range(maxd, 0, -1):
            coeff = (w + delta) * sinv
            coeff *= dist == lvl
            ctot += coeff
            if lvl >= 2:
                # delta_u += sigma_u * sum_{v in N(u) at lvl} coeff_v
                delta += sigma * ((coeff @ a64) * (dist == lvl - 1))

        # per-arc load: sum_s sigma[s,u] * coeff[s,v] over tree arcs, in a
        # transposed layout so every gather is a contiguous row copy
        part = np.zeros(n_arcs, dtype=np.float64)
        sig_t = np.ascontiguousarray(sigma.T)
        c_t = np.ascontiguousarray(ctot.T)
        d_t = np.ascontiguousarray(dist.T)
        achunk = max(1024, (48 << 20) // max(8 * b, 1))
        for alo in range(0, len(arc_sel), achunk):
            ids = arc_sel[alo : alo + achunk]
            au = arc_u[ids]
            av = arc_v[ids]
            e = sig_t[au] * c_t[av]
            e *= d_t[av] == d_t[au] + 1
            part[ids] = e.sum(axis=1)
        return part, dist_sum, pair_count, diam

    workers = max(1, flags().util_workers)
    block = min(_source_block_rows(n), max(1, -(-len(sources) // workers)))
    units = [sources[lo : lo + block] for lo in range(0, len(sources), block)]
    for part, dsum, pcount, dia in _run_units([lambda u=u: sweep(u) for u in units]):
        loads += part
        dist_sum += dsum
        pair_count += pcount
        diam = max(diam, dia)
    if symmetric:
        loads[g.reverse_arcs()[arc_sel]] = loads[arc_sel]
    return loads, dist_sum, pair_count, diam


# ---------------------------------------------------------------------------
# Engine: numpy, dense bipartite (half-size biadjacency blocks)
# ---------------------------------------------------------------------------


def _bip_structure(g: Graph, side: np.ndarray):
    cache = g._struct_cache
    if "bip_dense" not in cache:
        left = np.nonzero(side == 0)[0]
        right = np.nonzero(side == 1)[0]
        pos = np.empty(g.n, dtype=np.int64)
        pos[left] = np.arange(len(left))
        pos[right] = np.arange(len(right))
        b64 = np.zeros((len(left), len(right)), dtype=np.float64)
        eu, ev = g.edges[:, 0], g.edges[:, 1]
        swap = side[eu] == 1
        lu = np.where(swap, ev, eu)
        rv = np.where(swap, eu, ev)
        b64[pos[lu], pos[rv]] = 1.0
        mats = {
            (0, 64): b64,
            (0, 32): b64.astype(np.float32),
            (1, 64): np.ascontiguousarray(b64.T),
        }
        mats[(1, 32)] = mats[(1, 64)].astype(np.float32)
        # directed arcs grouped by source side, as flat indices into the
        # (nX, nY) per-level load matrices; flat_rl_sym indexes the
        # *transposed* entry of the (nL, nR) matrix, for the path-reversal
        # shortcut of the all-source engine
        arcs_lr = np.nonzero(side[g.arc_src] == 0)[0]
        arcs_rl = np.nonzero(side[g.arc_src] == 1)[0]
        flat_lr = pos[g.arc_src[arcs_lr]] * len(right) + pos[g.indices[arcs_lr]]
        flat_rl = pos[g.arc_src[arcs_rl]] * len(left) + pos[g.indices[arcs_rl]]
        flat_rl_sym = pos[g.indices[arcs_rl]] * len(right) + pos[g.arc_src[arcs_rl]]
        cache["bip_dense"] = (left, right, pos, mats,
                              (arcs_lr, flat_lr), (arcs_rl, flat_rl), flat_rl_sym)
    return cache["bip_dense"]


def _bip_forward(pos_src, nx_, ny_, bxy32, bxy64, byx32, byx64):
    """Level-alternating forward sweep for sources on side X.  Level 1 is a
    row gather from the biadjacency.  Returns (dx, dy, sig_x, sig_y, maxd)."""
    b = len(pos_src)
    rows = np.arange(b)
    dx = np.full((b, nx_), -1, dtype=np.int16)
    dy = np.full((b, ny_), -1, dtype=np.int16)
    dx[rows, pos_src] = 0
    sig_x = np.zeros((b, nx_), dtype=np.float64)
    sig_x[rows, pos_src] = 1.0
    sig_y = np.zeros((b, ny_), dtype=np.float64)
    front = None
    f64 = False
    lvl = 0
    while True:
        lvl += 1
        odd = lvl % 2 == 1
        if (dx >= 0).all() and (dy >= 0).all():
            return dx, dy, sig_x, sig_y, lvl - 1  # saves the final GEMM
        if lvl == 1:
            nxt = bxy32[pos_src].copy()
        else:
            mat32 = bxy32 if odd else byx32
            mat64 = bxy64 if odd else byx64
            nxt = front @ (mat64 if f64 else mat32)
            if not f64 and nxt.size and nxt.max() >= _F32_EXACT_MAX:
                front = front.astype(np.float64)
                nxt = front @ mat64
                f64 = True
        d_tgt = dy if odd else dx
        s_tgt = sig_y if odd else sig_x
        new = (nxt > 0) & (d_tgt < 0)
        if not new.any():
            return dx, dy, sig_x, sig_y, lvl - 1
        nxt *= new
        d_tgt += new * np.int16(lvl + 1)
        s_tgt += nxt
        front = nxt


def _loads_dense_bipartite(g: Graph, sources: np.ndarray,
                           targets_mask: np.ndarray, side: np.ndarray):
    """General bipartite engine (arbitrary sources / target masks)."""
    left, right, pos, mats, lr, rl, _ = _bip_structure(g, side)
    halves = (left, right)
    t_count = int(targets_mask.sum())
    loads = np.zeros(g.arc_src.shape[0], dtype=np.float64)
    dist_sum = 0.0
    pair_count = 0
    diam = 0

    for x in (0, 1):  # source side
        srcs = sources[side[sources] == x]
        if len(srcs) == 0:
            continue
        y = 1 - x
        nx_, ny_ = len(halves[x]), len(halves[y])
        bxy64, bxy32 = mats[(x, 64)], mats[(x, 32)]
        byx64, byx32 = mats[(y, 64)], mats[(y, 32)]
        tmx = targets_mask[halves[x]].astype(np.float64)
        tmy = targets_mask[halves[y]].astype(np.float64)
        # per-level load matrices, accumulated over source blocks
        m_xy = np.zeros((nx_, ny_), dtype=np.float64)
        m_yx = np.zeros((ny_, nx_), dtype=np.float64)

        block = _source_block_rows(max(nx_, ny_))
        for lo in range(0, len(srcs), block):
            sb = srcs[lo : lo + block]
            b = len(sb)
            dx, dy, sig_x, sig_y, maxd = _bip_forward(
                pos[sb], nx_, ny_, bxy32, bxy64, byx32, byx64)
            if (dx < 0).any() or (dy < 0).any():
                raise ValueError("graph is disconnected")
            tx_mask = targets_mask[halves[x]]
            ty_mask = targets_mask[halves[y]]
            if tx_mask.any():
                dmx = dx[:, tx_mask]
                diam = max(diam, int(dmx.max()))
                dist_sum += float(dmx.sum(dtype=np.float64))
            if ty_mask.any():
                dmy = dy[:, ty_mask]
                diam = max(diam, int(dmy.max()))
                dist_sum += float(dmy.sum(dtype=np.float64))
            pair_count += b * t_count - int(targets_mask[sb].sum())

            sinv_x = 1.0 / sig_x
            sinv_y = 1.0 / sig_y
            delta_x = np.zeros((b, nx_), dtype=np.float64)
            delta_y = np.zeros((b, ny_), dtype=np.float64)
            for lvl in range(maxd, 0, -1):
                odd = lvl % 2 == 1
                d_v, sinv_v, tm_v, delta_v = (
                    (dy, sinv_y, tmy, delta_y) if odd else (dx, sinv_x, tmx, delta_x))
                d_u, sig_u, delta_u = (
                    (dx, sig_x, delta_x) if odd else (dy, sig_y, delta_y))
                mu = d_u == lvl - 1
                coeff = (tm_v[None, :] + delta_v) * sinv_v
                coeff *= d_v == lvl
                f_prev = sig_u * mu
                if odd:
                    m_xy += f_prev.T @ coeff
                else:
                    m_yx += f_prev.T @ coeff
                if lvl >= 2:
                    # coeff @ B_vu: use the pre-transposed contiguous block
                    # so BLAS runs the NN (fastest) kernel
                    back_t = byx64 if odd else bxy64
                    delta_u += sig_u * ((coeff @ back_t) * mu)

        arcs_fwd, flat_fwd = lr if x == 0 else rl
        arcs_bwd, flat_bwd = rl if x == 0 else lr
        loads[arcs_fwd] += m_xy.ravel()[flat_fwd]
        loads[arcs_bwd] += m_yx.ravel()[flat_bwd]
    return loads, dist_sum, pair_count, diam


def _loads_dense_bipartite_all(g: Graph, targets_mask: np.ndarray, side: np.ndarray):
    """All-source full-traffic bipartite fast path.

    Beyond the general engine it exploits path reversal — total loads
    satisfy loads[u->v] == loads[v->u] — so only the (nL, nR) load matrix
    for L->R arcs is accumulated: from L-sources at odd BFS levels (level 1
    is a plain row scatter of the level-1 coefficients, no GEMM) and from
    R-sources at even levels.  delta GEMMs that only feed coefficients no
    L->R arc consumes are skipped outright.
    """
    left, right, pos, mats, lr, rl, flat_rl_sym = _bip_structure(g, side)
    halves = (left, right)
    n = g.n
    loads = np.zeros(g.arc_src.shape[0], dtype=np.float64)

    def sweep(x, sb):
        """One source block on side x; returns (m_lr partial, dist_sum, diam)."""
        y = 1 - x
        nx_, ny_ = len(halves[x]), len(halves[y])
        bxy64, bxy32 = mats[(x, 64)], mats[(x, 32)]
        byx64, byx32 = mats[(y, 64)], mats[(y, 32)]
        # parity of the levels whose tree arcs point L->R: odd levels for
        # L-sources (u in L even, v in R odd), even levels for R-sources
        want_odd = x == 0
        b = len(sb)
        dx, dy, sig_x, sig_y, maxd = _bip_forward(
            pos[sb], nx_, ny_, bxy32, bxy64, byx32, byx64)
        if (dx < 0).any() or (dy < 0).any():
            raise ValueError("graph is disconnected")
        diam = max(int(dx.max()), int(dy.max()))
        dist_sum = float(dx.sum(dtype=np.float64)) + float(dy.sum(dtype=np.float64))

        m_lr = np.zeros((len(left), len(right)), dtype=np.float64)
        sinv_x = 1.0 / sig_x
        sinv_y = 1.0 / sig_y
        delta_x = np.zeros((b, nx_), dtype=np.float64)
        delta_y = np.zeros((b, ny_), dtype=np.float64)
        for lvl in range(maxd, 0, -1):
            odd = lvl % 2 == 1
            emit = odd == want_odd  # level's tree arcs point L->R?
            if lvl == 1 and not emit:
                break  # nothing below needs coeff at level 1
            d_v, sinv_v, delta_v = (
                (dy, sinv_y, delta_y) if odd else (dx, sinv_x, delta_x))
            d_u, sig_u, delta_u = (
                (dx, sig_x, delta_x) if odd else (dy, sig_y, delta_y))
            mu = d_u == lvl - 1
            coeff = (1.0 + delta_v) * sinv_v
            coeff *= d_v == lvl
            if emit:
                if lvl == 1:
                    # only reachable for L-sources: f_prev is the one-hot
                    # source block, so the GEMM is a row scatter
                    m_lr[pos[sb]] += coeff
                else:
                    # u side is L here for either source side (odd levels
                    # sit on Y=L when sources are on R)
                    m_lr += (sig_u * mu).T @ coeff
            need_delta = lvl >= 3 or (lvl == 2 and want_odd)
            if need_delta:
                back_t = byx64 if odd else bxy64
                delta_u += sig_u * ((coeff @ back_t) * mu)
        return m_lr, dist_sum, diam

    units = []
    for x in (0, 1):  # source side
        srcs = halves[x]
        block = _source_block_rows(max(len(halves[x]), len(halves[1 - x])))
        for lo in range(0, len(srcs), block):
            units.append((x, srcs[lo : lo + block]))
    parts = _run_units([lambda u=u: sweep(*u) for u in units])
    m_lr = parts[0][0]
    for p in parts[1:]:
        m_lr += p[0]
    dist_sum = sum(p[1] for p in parts)
    diam = max(p[2] for p in parts)

    arcs_lr, flat_lr = lr
    arcs_rl, _ = rl
    flat = m_lr.ravel()
    loads[arcs_lr] = flat[flat_lr]
    loads[arcs_rl] = flat[flat_rl_sym]
    return loads, dist_sum, n * (n - 1), diam


# ---------------------------------------------------------------------------
# Engine: numpy, CSR (transposed reduceat sweeps; for N > util_dense_max)
# ---------------------------------------------------------------------------


def _loads_csr(g: Graph, sources: np.ndarray, targets_mask: np.ndarray,
               demand: np.ndarray | None = None):
    n = g.n
    arc_u, arc_v = g.arc_src, g.indices
    n_arcs = arc_u.shape[0]
    if n_arcs == 0:
        raise ValueError("graph is disconnected")
    rows_by_dst = arc_u[g.arcs_by_dst()]
    # clip trailing degree-0 offsets (== n_arcs) that reduceat rejects;
    # their rows are overwritten via the deg0 mask below
    starts = np.minimum(g.indptr[:-1], n_arcs - 1)
    deg0 = g.degrees == 0
    tm = targets_mask.astype(np.float64)
    t_count = int(targets_mask.sum())
    loads = np.zeros(n_arcs, dtype=np.float64)
    dist_sum = 0.0
    pair_count: float = 0
    diam = 0

    blk = flags().util_block
    if blk <= 0:
        blk = max(4, (96 << 20) // max(8 * n_arcs, 1))
    for lo in range(0, len(sources), blk):
        sb = sources[lo : lo + blk]
        b = len(sb)
        cols = np.arange(b)
        dist_t = np.full((n, b), -1, dtype=np.int16)
        dist_t[sb, cols] = 0
        sig_t = np.zeros((n, b), dtype=np.float64)
        sig_t[sb, cols] = 1.0
        lvl = 0
        while True:
            lvl += 1
            contrib = sig_t[rows_by_dst] * (dist_t[rows_by_dst] == lvl - 1)
            red = np.add.reduceat(contrib, starts, axis=0)
            if deg0.any():
                red[deg0] = 0.0
            new = (red > 0) & (dist_t < 0)
            if not new.any():
                maxd = lvl - 1
                break
            dist_t[new] = lvl
            sig_t[new] = red[new]
        if (dist_t < 0).any():
            raise ValueError("graph is disconnected")
        if demand is None:
            wt = tm[:, None]
            dm = dist_t[targets_mask]
            diam = max(diam, int(dm.max()))
            dist_sum += float(dm.sum(dtype=np.float64))
            pair_count += b * t_count - int(targets_mask[sb].sum())
        else:
            wt = np.ascontiguousarray(demand[sb].T)  # (n, b) demand columns
            active = wt > 0
            if active.any():
                diam = max(diam, int(dist_t[active].max()))
            dist_sum += float((dist_t * wt).sum(dtype=np.float64))
            pair_count += float(wt.sum())

        delta_t = np.zeros((n, b), dtype=np.float64)
        for lvl in range(maxd, 0, -1):
            m = dist_t == lvl
            coeff = np.zeros((n, b), dtype=np.float64)
            np.divide(wt + delta_t, sig_t, out=coeff, where=m)
            contrib = sig_t[arc_u] * coeff[arc_v]
            contrib *= dist_t[arc_u] == lvl - 1
            loads += contrib.sum(axis=1)
            if lvl >= 2:
                red = np.add.reduceat(contrib, starts, axis=0)
                if deg0.any():
                    red[deg0] = 0.0
                delta_t += red
    return loads, dist_sum, pair_count, diam


# ---------------------------------------------------------------------------
# Engine: jax (jnp GEMM recurrences, jit per shape, chunked source blocks)
# ---------------------------------------------------------------------------


def _loads_jax(g: Graph, sources: np.ndarray, targets_mask: np.ndarray,
               demand: np.ndarray | None = None):
    import jax
    import jax.numpy as jnp

    from ..jaxenv import x64
    with x64():
        return _loads_jax_x64(g, sources, targets_mask, jax, jnp, demand)


def _loads_jax_x64(g: Graph, sources, targets_mask, jax, jnp, demand=None):
    n = g.n
    adj = jnp.asarray(g.adjacency_dense(np.float64))
    arc_u = jnp.asarray(g.arc_src)
    arc_v = jnp.asarray(g.indices)
    tm = jnp.asarray(targets_mask.astype(np.float64))
    t_count = int(targets_mask.sum())

    @jax.jit
    def fwd_step(front, dist, sigma, lvl):
        nxt = front @ adj
        new = (nxt > 0) & (dist < 0)
        nxt = nxt * new
        dist = jnp.where(new, lvl, dist)
        sigma = jnp.where(new, nxt, sigma)
        return nxt, dist, sigma, new.any()

    @jax.jit
    def bwd_step(delta, ctot, dist, sigma, lvl):
        m = dist == lvl
        coeff = jnp.where(m, (tm[None, :] + delta) / jnp.where(m, sigma, 1.0), 0.0)
        delta = delta + sigma * ((coeff @ adj) * (dist == lvl - 1))
        return delta, ctot + coeff

    @jax.jit
    def bwd_step_weighted(w, delta, ctot, dist, sigma, lvl):
        m = dist == lvl
        coeff = jnp.where(m, (w + delta) / jnp.where(m, sigma, 1.0), 0.0)
        delta = delta + sigma * ((coeff @ adj) * (dist == lvl - 1))
        return delta, ctot + coeff

    @jax.jit
    def arc_sum(sigma, ctot, dist):
        s_u = sigma[:, arc_u]
        c_v = ctot[:, arc_v]
        tree = dist[:, arc_v] == dist[:, arc_u] + 1
        return (s_u * c_v * tree).sum(axis=0)

    loads = np.zeros(g.arc_src.shape[0], dtype=np.float64)
    dist_sum = 0.0
    pair_count: float = 0
    diam = 0
    block = _source_block_rows(n)
    for lo in range(0, len(sources), block):
        sb = sources[lo : lo + block]
        b = len(sb)
        rows = np.arange(b)
        front0 = np.zeros((b, n), dtype=np.float64)
        front0[rows, sb] = 1.0
        dist0 = np.full((b, n), -1, dtype=np.int32)
        dist0[rows, sb] = 0
        front = jnp.asarray(front0)
        dist = jnp.asarray(dist0)
        sigma = jnp.asarray(front0)
        lvl = 0
        while True:
            lvl += 1
            front, dist, sigma, any_new = fwd_step(front, dist, sigma, lvl)
            if not bool(any_new):
                maxd = lvl - 1
                break
        dist_np = np.asarray(dist)
        if (dist_np < 0).any():
            raise ValueError("graph is disconnected")
        if demand is None:
            dm = dist_np[:, targets_mask]
            diam = max(diam, int(dm.max()))
            dist_sum += float(dm.sum(dtype=np.float64))
            pair_count += b * t_count - int(targets_mask[sb].sum())
        else:
            w_np = demand[sb]
            active = w_np > 0
            if active.any():
                diam = max(diam, int(dist_np[active].max()))
            dist_sum += float((dist_np * w_np).sum(dtype=np.float64))
            pair_count += float(w_np.sum())
            w = jnp.asarray(w_np)

        delta = jnp.zeros((b, n), dtype=jnp.float64)
        ctot = jnp.zeros((b, n), dtype=jnp.float64)
        for l in range(maxd, 0, -1):
            if demand is None:
                delta, ctot = bwd_step(delta, ctot, dist, sigma, l)
            else:
                delta, ctot = bwd_step_weighted(w, delta, ctot, dist, sigma, l)
        loads += np.asarray(arc_sum(sigma, ctot, dist))
    return loads, dist_sum, pair_count, diam


def _loads_pallas(g: Graph, sources: np.ndarray, targets_mask: np.ndarray,
                  demand: np.ndarray | None = None):
    """``engine="pallas"``: the jax engine's level recurrences through the
    fused mask+GEMM kernels (repro.kernels.mask_gemm) — compiled float32
    on TPU, float64 under the pallas interpreter elsewhere (the parity /
    development path, same convention as repro.sim's pallas backends).
    Which of the two ran is counted (``util.pallas[compiled]`` /
    ``util.pallas[interpret]``)."""
    import jax
    import jax.numpy as jnp

    from ..jaxenv import x64
    if jax.default_backend() == "tpu":
        obs.counter("util.pallas[compiled]").add(1.0)
        return _loads_pallas_impl(g, sources, targets_mask, jax, jnp,
                                  demand, interpret=False, f64=False)
    obs.counter("util.pallas[interpret]").add(1.0)
    with x64():
        return _loads_pallas_impl(g, sources, targets_mask, jax, jnp,
                                  demand, interpret=True, f64=True)


def _loads_pallas_impl(g: Graph, sources, targets_mask, jax, jnp,
                       demand=None, *, interpret, f64):
    from ..kernels.mask_gemm import backward_step, frontier_step

    n = g.n
    dtype = jnp.float64 if f64 else jnp.float32
    adj = jnp.asarray(g.adjacency_dense(np.float64), dtype)
    arc_u = jnp.asarray(g.arc_src)
    arc_v = jnp.asarray(g.indices)
    tm = jnp.asarray(targets_mask, dtype)
    t_count = int(targets_mask.sum())

    @jax.jit
    def coeff_of(w, delta, dist, sigma, lvl):
        m = dist == lvl
        return jnp.where(m, (w + delta) / jnp.where(m, sigma, 1.0), 0.0)

    @jax.jit
    def arc_sum(sigma, ctot, dist):
        s_u = sigma[:, arc_u]
        c_v = ctot[:, arc_v]
        tree = dist[:, arc_v] == dist[:, arc_u] + 1
        return (s_u * c_v * tree).sum(axis=0)

    loads = np.zeros(g.arc_src.shape[0], dtype=np.float64)
    dist_sum = 0.0
    pair_count: float = 0
    diam = 0
    block = _source_block_rows(n)
    for lo in range(0, len(sources), block):
        sb = sources[lo : lo + block]
        b = len(sb)
        rows = np.arange(b)
        front0 = np.zeros((b, n), dtype=np.float64)
        front0[rows, sb] = 1.0
        dist0 = np.full((b, n), -1, dtype=np.int32)
        dist0[rows, sb] = 0
        front = jnp.asarray(front0, dtype)
        dist = jnp.asarray(dist0)
        sigma = jnp.asarray(front0, dtype)
        lvl = 0
        while True:
            lvl += 1
            front, dist, sigma = frontier_step(front, adj, dist, sigma,
                                               lvl, interpret=interpret)
            if not bool((front > 0).any()):
                maxd = lvl - 1
                break
        dist_np = np.asarray(dist)
        if (dist_np < 0).any():
            raise ValueError("graph is disconnected")
        if demand is None:
            w = tm[None, :]
            dm = dist_np[:, targets_mask]
            diam = max(diam, int(dm.max()))
            dist_sum += float(dm.sum(dtype=np.float64))
            pair_count += b * t_count - int(targets_mask[sb].sum())
        else:
            w_np = demand[sb]
            active = w_np > 0
            if active.any():
                diam = max(diam, int(dist_np[active].max()))
            dist_sum += float((dist_np * w_np).sum(dtype=np.float64))
            pair_count += float(w_np.sum())
            w = jnp.asarray(w_np, dtype)

        delta = jnp.zeros((b, n), dtype=dtype)
        ctot = jnp.zeros((b, n), dtype=dtype)
        for l in range(maxd, 0, -1):
            coeff = coeff_of(w, delta, dist, sigma, l)
            delta = backward_step(coeff, adj, dist, sigma, delta, l - 1,
                                  interpret=interpret)
            ctot = ctot + coeff
        loads += np.asarray(arc_sum(sigma, ctot, dist), dtype=np.float64)
    return loads, dist_sum, pair_count, diam


# ---------------------------------------------------------------------------
# Engine: orbit shortcut
# ---------------------------------------------------------------------------


def _loads_orbit(g: Graph, targets_mask: np.ndarray, inner):
    """One Brandes sweep per vertex orbit; returns None when no known
    automorphism subgroup applies (caller falls back to an exact engine)."""
    from .orbits import orbit_info

    full = bool(targets_mask.all())
    info = orbit_info(g, None if full else targets_mask)
    if info is None:
        return None
    t_count = int(targets_mask.sum())
    used = np.unique(info.vertex_orbit[targets_mask])
    n_aorb = len(info.arc_sizes)
    orbit_sums = np.zeros(n_aorb, dtype=np.float64)
    dist_sum = 0.0
    diam = 0
    for orb in used:
        rep = int(info.vertex_reps[orb])
        size = float(info.vertex_sizes[orb])
        loads_r, dsum_r, _, diam_r = inner(g, np.array([rep]), targets_mask)
        orbit_sums += size * np.bincount(info.arc_orbit, weights=loads_r,
                                         minlength=n_aorb)
        dist_sum += size * dsum_r
        diam = max(diam, diam_r)
    loads = orbit_sums[info.arc_orbit] / info.arc_sizes[info.arc_orbit]
    pair_count = t_count * (t_count - 1)
    return loads, dist_sum, pair_count, diam


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def _loads_numpy(g: Graph, sources: np.ndarray, targets_mask: np.ndarray,
                 demand: np.ndarray | None = None):
    if g.n <= flags().util_dense_max:
        with _blas_limit():
            if demand is not None:
                # arbitrary per-pair demand: the half-size bipartite fast
                # paths assume uniform weights, so run the generic engine
                return _loads_dense_generic(g, sources, targets_mask, demand)
            side = g.bipartition()
            if side is not None:
                if targets_mask.all() and np.array_equal(sources, np.arange(g.n)):
                    return _loads_dense_bipartite_all(g, targets_mask, side)
                return _loads_dense_bipartite(g, sources, targets_mask, side)
            return _loads_dense_generic(g, sources, targets_mask)
    return _loads_csr(g, sources, targets_mask, demand)


def _exact_engine(g: Graph):
    """auto's exact-path choice by graph size: dense GEMMs while the dense
    adjacency is reasonable, then jax up to util_jax_max, then the
    memory-lean CSR sweep."""
    fl = flags()
    if g.n <= fl.util_dense_max:
        obs.counter("util.engine[numpy]").add(1.0)
        return _loads_numpy
    if g.n <= fl.util_jax_max:
        obs.counter("util.engine[jax]").add(1.0)
        return _loads_jax
    obs.counter("util.engine[csr]").add(1.0)
    return _loads_csr


def arc_loads(g: Graph, sources=None, targets_mask: np.ndarray | None = None,
              engine: str | None = None) -> tuple[np.ndarray, float, int]:
    """Per-arc load under uniform traffic, plus (k̄, diameter) of the pairs used.

    ``sources`` defaults to every vertex (or every leaf if ``targets_mask``
    given); traffic flows from each source to every other target vertex,
    1 unit per ordered pair, split across shortest paths.  ``engine``
    overrides the REPRO_PERF ``util_engine`` flag (see module docstring).
    """
    n = g.n
    if targets_mask is None:
        targets_mask = np.ones(n, dtype=bool)
    else:
        targets_mask = np.asarray(targets_mask, dtype=bool)
    default_sources = sources is None
    if sources is None:
        sources = np.nonzero(targets_mask)[0]
    sources = np.asarray(sources, dtype=np.int64)

    eng = (engine if engine is not None else flags().util_engine).lower()
    if eng not in _ENGINES:
        raise ValueError(f"unknown engine {eng!r}; options: {_ENGINES}")

    with obs.span("util.arc_loads", engine=eng, n=g.n):
        obs.counter(f"util.dispatch[{eng}]").add(1.0)
        if eng == "naive":
            res = _arc_loads_naive(g, sources, targets_mask)
        elif eng == "orbit" or (eng == "auto" and flags().util_orbits
                                and default_sources):
            res = (_loads_orbit(g, targets_mask, _exact_engine(g))
                   if default_sources else None)
            if res is None:
                if eng == "orbit":
                    raise ValueError(
                        f"no known automorphism generators for "
                        f"{g.name or g.meta.get('family')!r}"
                        " (or sources/targets not orbit-compatible)")
                res = _exact_engine(g)(g, sources, targets_mask)
            else:
                obs.counter("util.engine[orbit]").add(1.0)
        elif eng == "numpy":
            res = _loads_numpy(g, sources, targets_mask)
        elif eng == "csr":
            res = _loads_csr(g, sources, targets_mask)
        elif eng == "jax":
            res = _loads_jax(g, sources, targets_mask)
        elif eng == "pallas":
            res = _loads_pallas(g, sources, targets_mask)
        else:  # auto, orbits disabled or explicit sources
            res = _exact_engine(g)(g, sources, targets_mask)

    loads, dist_sum, pair_count, diam = res
    kbar = dist_sum / pair_count
    return loads, kbar, diam


def _uniform_demand_split(demand: np.ndarray):
    """Detect a uniform-shaped demand: ``w * (ones - I)`` on some active
    vertex set, zero elsewhere.  Returns ``(w, active_mask)`` or None.

    Such a matrix commutes with the graph's full automorphism group (any
    subgroup preserving the active set), so the orbit shortcut of
    :func:`arc_loads` applies: the weighted sweep reduces to the uniform
    one scaled by w."""
    rows = demand.any(axis=1)
    if not np.array_equal(rows, demand.any(axis=0)):
        return None
    active = np.nonzero(rows)[0]
    if len(active) < 2:
        return None
    block = demand[np.ix_(active, active)]
    w = block[0, 1]
    if w <= 0.0:
        return None
    expect = np.full(block.shape, w)
    np.fill_diagonal(expect, 0.0)
    if not np.array_equal(block, expect):
        return None
    return w, rows


def arc_loads_weighted(g: Graph, demand,
                       engine: str | None = None
                       ) -> tuple[np.ndarray, float, int]:
    """Per-arc load under an arbitrary traffic matrix, split across all
    shortest paths (the demand-matrix generalization of Theorem 3.9).

    ``demand[s, t]`` is the traffic s injects for t (any nonnegative
    units); the diagonal is ignored.  A TrafficPattern instance (anything
    with a ``demand(g)`` method) is accepted directly and built against
    ``g``.  Returns ``(loads, kbar, diameter)`` where ``kbar`` is the
    demand-weighted mean hop count ``sum(D * dist) / sum(D)`` and
    ``diameter`` the longest hop count any demand actually travels.
    ``engine`` as in :func:`arc_loads`; under ``auto``/``orbit`` a
    uniform-shaped demand (``w * (ones - I)`` over an active set — the
    only matrices the automorphism shortcut is exact for) routes through
    the orbit path of :func:`arc_loads` scaled by w, and anything else
    runs the exact engines.
    """
    n = g.n
    if hasattr(demand, "demand") and callable(demand.demand):
        demand = demand.demand(g)  # TrafficPattern duck-type
    demand = np.array(demand, dtype=np.float64)  # private copy, diag zeroed
    if demand.shape != (n, n):
        raise ValueError(f"demand must be ({n}, {n}), got {demand.shape}")
    if not np.isfinite(demand).all():
        raise ValueError("demand must be finite")
    if (demand < 0).any():
        raise ValueError("demand must be nonnegative")
    np.fill_diagonal(demand, 0.0)
    total = float(demand.sum())
    if total == 0.0:
        raise ValueError("demand matrix is all zero")
    sources = np.nonzero(demand.any(axis=1))[0]
    targets_mask = np.ones(n, dtype=bool)

    eng = (engine if engine is not None else flags().util_engine).lower()
    if eng not in _ENGINES:
        raise ValueError(f"unknown engine {eng!r}; options: {_ENGINES}")

    if eng == "orbit" or (eng == "auto" and flags().util_orbits):
        uni = _uniform_demand_split(demand)
        if uni is not None:
            w, mask = uni
            try:
                loads, kbar, diam = arc_loads(g, targets_mask=mask,
                                              engine=eng)
            except ValueError:
                # engine="orbit" on a family without known generators:
                # keep the weighted path's documented contract (the exact
                # engines run instead of raising)
                pass
            else:
                return loads * w, kbar, diam

    with obs.span("util.arc_loads_weighted", engine=eng, n=g.n):
        obs.counter(f"util.dispatch[{eng}]").add(1.0)
        if eng == "naive":
            res = _arc_loads_naive(g, sources, targets_mask, demand)
        elif eng == "numpy":
            res = _loads_numpy(g, sources, targets_mask, demand)
        elif eng == "csr":
            res = _loads_csr(g, sources, targets_mask, demand)
        elif eng == "jax":
            res = _loads_jax(g, sources, targets_mask, demand)
        elif eng == "pallas":
            res = _loads_pallas(g, sources, targets_mask, demand)
        else:  # auto / orbit: the exact-path choice by graph size
            res = _exact_engine(g)(g, sources, targets_mask, demand)

    loads, dist_sum, total_demand, diam = res
    return loads, dist_sum / total_demand, diam


def utilization(g: Graph, sources=None, targets_mask: np.ndarray | None = None,
                engine: str | None = None) -> UtilizationReport:
    """The paper's u = mean/max arc load at saturation."""
    if targets_mask is None:
        targets_mask = g.meta.get("leaf_mask")
    loads, kbar, diam = arc_loads(g, sources, targets_mask, engine=engine)
    mx = float(loads.max())
    mean = float(loads.mean())
    return UtilizationReport(u=mean / mx, mean_load=mean, max_load=mx,
                             loads=loads, kbar=kbar, diameter=diam)


def valiant_report(g: Graph, sources=None) -> UtilizationReport:
    """Valiant two-phase randomized routing [paper ref 40]: every packet
    goes s -> (uniform random intermediate m) -> t via minimal paths.

    By linearity of expectation each phase is exactly one uniform-traffic
    ensemble, so the expected per-arc load is 2x the minimal-routing load,
    the load RATIOS (hence u) are unchanged, and the effective path length
    is 2·k̄ — the paper's point that randomization buys worst-case
    guarantees for non-uniform traffic at half the uniform throughput
    (Δ0 ≤ Δ·u/(2k̄) at saturation)."""
    rep = utilization(g, sources)
    return UtilizationReport(u=rep.u, mean_load=rep.mean_load * 2.0,
                             max_load=rep.max_load * 2.0,
                             loads=rep.loads * 2.0, kbar=2.0 * rep.kbar,
                             diameter=rep.diameter)
