"""Blocked sparse-destination step backends for the flow-level simulator.

The dense engine (repro.sim.engine) materializes every intermediate of
the step — ``mv``/``del``/``cont`` tensors, an ``np.add.at`` scatter —
which costs ~30 passes over the O(N·K·M) queue state per step and caps
instances at SIM_MAX_CELLS.  This module is the ``backend="pallas"``
seam: the same step semantics, restructured around one fused
forward/throttle/enqueue contraction per VC over a *blocked* dest axis
(tiles of :data:`repro.kernels.sim_step.DEST_TILE` destinations), with
only populated (router, dest-tile) blocks computed:

* ``backend="pallas"`` — on a TPU backend the contraction runs as the
  pallas kernel :func:`repro.kernels.sim_step.fused_step_update`; on CPU
  it runs a numpy implementation with the *same blocked structure*
  (mirroring the convention of ``repro.kernels.ops``: the CPU backend
  cannot lower Mosaic kernels, so the host path reproduces the kernel's
  block/bytes shape).  Five passes over the queue state instead of ~30:

    1. per-tile occupancy reduction (carried across steps while the
       state round-trips untouched, e.g. inside ``Simulator.run``),
    2. the arrival gather ``arr[h] = sum share(a)·q[a]`` over reverse
       arcs as one sparse-matrix product (delivered fluid is the
       extracted ``(router, self-dest)`` column, O(N) per tile — the
       deliver mask has at most one hit per arc),
    3. the fused update ``q·fac - q·corr·deliver + inflow·split`` tile
       by tile, which is exactly the pallas kernel's contraction.

  The contiguous live slabs of step 3 are independent work units
  (disjoint output column ranges), run in waves of ``sim_workers``
  threads past a live-cell threshold — the ``util_workers`` idiom of
  repro.core.utilization one layer down, bitwise deterministic at any
  worker count.

* ``backend="pallas_interpret"`` — the pallas kernel itself through the
  pallas interpreter on CPU: slow, but bit-for-bit the TPU program;
  this is the backend the parity tests drive against the numpy float64
  oracle (tests/test_sim_kernel.py).

Both backends accept float32 (the TPU-native dtype, default) or float64
state via ``SimConfig(dtype=...)``; the dense numpy float64 engine stays
the parity oracle, with knee-level agreement at tolerance rather than
bitwise (rounding shifts individual threshold decisions, not the knee).

Destination sparsity has a static half too, and it is per VC.  Under
``minimal`` routing the Simulator shrinks the active set itself (see
``Simulator(demand=...)``).  Under ``ugal``/``valiant`` the active set
must stay whole — diversions spread over every active intermediate —
but only the *final-destination* axes need the demanded columns: with
``dest_cols`` the fused backends carry q0/q2/src and the PEND pool's
dest axis on the compacted ``C`` demanded columns while q1/stage2 keep
the full ``M`` mid axis (:class:`_DestAxis` holds the index-remapped
views).  The stage-2 column closure is the demanded set itself —
diverted fluid keeps its final destination — so the compaction is exact,
and a pn27-class fabric (64M dense cells) sweeps adaptively in a
few-M-cell compacted state.  The per-hop UGAL decision (q_min gather +
threshold + candidate mask) is fused into the same blocked pass /
its own pallas kernel (:func:`repro.kernels.sim_step.fused_decision`)
instead of running as unfused dense ops.
"""

from __future__ import annotations

import functools
import time
from collections import defaultdict
from typing import NamedTuple

import numpy as np

from .engine import _BIG, _TINY, BoundStep, SimConfig, pad_slots
from .tables import RouteTables, device_planes

__all__ = ["make_step_sparse", "step_aux", "resolve_dtype", "laid_slots",
           "step_planes", "SPARSE_BACKENDS"]

SPARSE_BACKENDS = ("pallas", "pallas_interpret")

# dest-tile width shared with the pallas kernel (repro.kernels.sim_step,
# imported lazily: the numpy path never loads jax)
DEST_TILE = 128

# the TPU's sublane count: the kernel step lays the out-slot axis of its
# state and tables at a multiple of it (see laid_slots)
SUBLANE = 8

# live queue cells per step below which the slab loop stays serial:
# thread spawn/join per wave costs ~0.1 ms, which only pays for itself
# once the numpy work per step clears ~1M cells
SIM_THREAD_MIN_CELLS = 1_000_000


class _StepAux:
    """Arc-level index structure shared by the fused backends.

    Everything here depends only on the RouteTables: the reverse-arc
    pairing that turns the arrival scatter into a gather, the per-arc
    dest index of the head router (the deliver mask has at most one true
    per arc — delivery is O(N·K), not O(N·K·M)), and the dest tiling.
    """

    def __init__(self, t: RouteTables, tile: int = DEST_TILE):
        n, k, m = t.n, t.k, t.m
        self.n, self.k, self.m = n, k, m
        nk = n * k
        head_flat = t.head.reshape(-1)
        inv_act = np.full(n + 1, m, dtype=np.int64)
        inv_act[t.active] = np.arange(m)
        # dest index of each arc's head (m = not a dest); fluid on arc a
        # addressed to dd[a] is delivered, everything else transits
        self.dd = inv_act[head_flat]                      # (NK,)
        self.self_d = inv_act[:n]                         # (N,)
        # reverse-arc pairing from the head table alone (multi-edges are
        # matched in slot order, so the pairing is a perfect matching on
        # real arcs even on multigraphs)
        buckets: dict = defaultdict(lambda: ([], []))
        for a in range(nk):
            h = head_flat[a]
            if h >= n:
                continue
            r = a // k
            lo, hi = (r, h) if r <= h else (h, r)
            buckets[(lo, hi)][0 if r <= h else 1].append(a)
        rev = np.full(nk, -1, dtype=np.int64)
        for (lo, hi), (fwd, bwd) in buckets.items():
            if lo == hi:  # self-loop: pair consecutive slots
                for x, y in zip(fwd[0::2], fwd[1::2]):
                    rev[x], rev[y] = y, x
                continue
            if len(fwd) != len(bwd):
                raise ValueError("head table is not symmetric: cannot "
                                 "pair reverse arcs")
            for x, y in zip(fwd, bwd):
                rev[x], rev[y] = y, x
        self.rev = rev                                    # (NK,) partner
        real = np.nonzero(rev >= 0)[0]
        # deliver fixup: arcs whose head is a dest
        fr = real[self.dd[real] < m]
        self.fix_arc = fr                                 # (F,) arc flats
        self.fix_dst = self.dd[fr]                        # (F,) dest col
        self.fix_router = fr // k                         # (F,) own router
        # delivered extraction: routers that are dests themselves
        hs = np.nonzero(self.self_d < m)[0]
        self.dst_router = hs                              # (H,)
        self.dst_col = self.self_d[hs]                    # (H,)
        # arrival gather as a sparse matrix: row h sums share(a)·q[a]
        # over h's in-arcs a (the reverse arcs of h's out-slots)
        import scipy.sparse as sp
        # R[h, a] = 1 where arc a ends at router h (the reverse arcs of
        # h's out-slots); data is refilled with share[a] each step, so
        # R @ q_flat is the arrival gather arr[h] = sum share(a)·q[a]
        rows = real // k
        cols = rev[real]
        self.R = sp.csr_matrix((np.ones(len(real)), (rows, cols)),
                               shape=(n, nk))
        self.R.sum_duplicates()
        self.R.sort_indices()
        # dest tiling
        self.tile = tile
        self.starts = np.arange(0, m, tile)
        self.tiles = [(int(lo), int(min(lo + tile, m)))
                      for lo in self.starts]
        self.n_tiles = len(self.tiles)
        self.fix_tile = self.fix_dst // tile              # (F,)


class _DestAxis:
    """One destination-axis view of the blocked state: the full ``M``
    active columns, or compacted to the ``C`` demanded columns.

    ``cols`` (sorted active-set indices) remaps the deliver-fixup and
    delivered-extraction index arrays onto the compacted axis; entries
    whose dest column is outside the view are dropped — exact, because
    a compacted VC never carries fluid addressed there (injection and
    conversion only feed demanded columns, transit preserves the dest).
    """

    def __init__(self, aux: _StepAux, cols=None):
        tile = aux.tile
        if cols is None:
            self.w = aux.m
            self.fix_arc, self.fix_dst = aux.fix_arc, aux.fix_dst
            self.fix_router = aux.fix_router
            self.dst_router, self.dst_col = aux.dst_router, aux.dst_col
        else:
            cols = np.asarray(cols, dtype=np.int64)
            pos = np.full(aux.m, -1, dtype=np.int64)
            pos[cols] = np.arange(len(cols))
            self.w = len(cols)
            keep = pos[aux.fix_dst] >= 0
            self.fix_arc = aux.fix_arc[keep]
            self.fix_dst = pos[aux.fix_dst[keep]]
            self.fix_router = aux.fix_router[keep]
            keep = pos[aux.dst_col] >= 0
            self.dst_router = aux.dst_router[keep]
            self.dst_col = pos[aux.dst_col[keep]]
        self.starts = np.arange(0, self.w, tile)
        self.tiles = [(int(lo), int(min(lo + tile, self.w)))
                      for lo in self.starts]
        self.n_tiles = len(self.tiles)
        self.fix_tile = self.fix_dst // tile


def _pool_diag(t: RouteTables, cols):
    """(mid, dest-col) pairs of the compacted PEND pool's self-delivery
    diagonal: pool row ``mid`` meets column ``pos[mid]`` where the mid is
    itself a demanded dest.  ``cols=None`` is the full diagonal."""
    m = t.m
    if cols is None:
        idx = np.arange(m)
        return idx, idx
    pos = np.full(m, -1, dtype=np.int64)
    pos[np.asarray(cols, dtype=np.int64)] = np.arange(len(cols))
    diag_mid = np.nonzero(pos >= 0)[0]
    return diag_mid, pos[diag_mid]


def step_aux(t: RouteTables, tile: int = DEST_TILE) -> _StepAux:
    """The (cached) arc-index structure of one RouteTables instance."""
    aux = getattr(t, "_step_aux", None)
    if aux is None or aux.tile != tile:
        aux = _StepAux(t, tile)
        t._step_aux = aux
    return aux


def laid_slots(k: int) -> int:
    """Out-slots the kernel step lays its (N, K, W) state and tables at:
    K rounded up to the sublane multiple.  At any other K the TPU keeps
    such an array with N as its second-minor axis, while the kernels'
    blocks need K there, so every step would copy and relayout the
    state and the split and deliver tables around the kernels.  The
    extra slots are empty: head N, zero split and deliver, no fluid."""
    return -(-k // SUBLANE) * SUBLANE


def step_planes(t: RouteTables, backend: str, dtype, dest_cols=None):
    """The dense planes ``backend``'s step reads, made on the device at
    the step's layout (:func:`repro.sim.tables.device_planes`, which
    keeps them on ``t`` for the step's build): the graph's K for the
    dense ``jax`` step, :func:`laid_slots` and the ``dest_cols`` axis for
    the kernel step (``pallas_interpret``, and ``pallas`` on a TPU).
    None for the numpy steps, which read the host planes of ``t``."""
    if backend == "jax":
        return device_planes(t, dtype, t.k)
    if backend == "pallas":
        import jax
        if jax.default_backend() != "tpu":
            return None
    if backend in SPARSE_BACKENDS:
        return device_planes(t, dtype, laid_slots(t.k), dest_cols)
    return None


def resolve_dtype(name: str, backend: str):
    """State dtype for a backend: the fused backends default to float32
    (TPU-native; the dense float64 engine stays the oracle), the dense
    backends to float64."""
    if name == "auto":
        return np.float32 if backend in SPARSE_BACKENDS else np.float64
    if name in ("f32", "float32"):
        return np.float32
    if name in ("f64", "float64"):
        return np.float64
    raise ValueError(f"unknown sim dtype {name!r}; options: auto, "
                     "float32, float64")


def make_step_sparse(t: RouteTables, cfg: SimConfig, backend: str, dtype,
                     dest_cols=None):
    """Build the blocked sparse-dest ``step(state, inj, inj_cap)`` for
    ``backend`` in :data:`SPARSE_BACKENDS`.  Same contract as
    :func:`repro.sim.engine.make_step`; ``dtype`` is the state dtype
    (float32 default — the dense float64 engine is the parity oracle).
    ``dest_cols`` carries the per-VC compacted dest axis (ugal/valiant
    static compaction): state tensors q0/q2/src/pend-dest hold only
    those columns, q1/stage2 the full mid axis."""
    from .. import obs
    if cfg.mode == "ugal":
        # the decision phase runs fused (blocked q_min + threshold +
        # candidate mask) on every sparse backend — dispatch-counted
        # like the step implementations themselves
        obs.counter("sim.step_build[fused_decision]").add(1.0)
    if backend == "pallas":
        import jax
        # the pallas-vs-numpy dispatch, made observable: which fused
        # implementation actually ran is otherwise invisible to callers
        if jax.default_backend() == "tpu":
            if dtype == np.float64:
                raise ValueError(
                    "the pallas sim kernel runs float32 on TPU; float64 "
                    "state needs backend='numpy' (the dense reference) "
                    "or backend='pallas_interpret'")
            obs.counter("sim.step_build[pallas_tpu]").add(1.0)
            return _make_step_kernel(t, cfg, dtype, interpret=False,
                                     dest_cols=dest_cols)
        obs.counter("sim.step_build[fused_numpy]").add(1.0)
        return _make_step_fused_numpy(t, cfg, dtype, dest_cols=dest_cols)
    if backend == "pallas_interpret":
        obs.counter("sim.step_build[pallas_interpret]").add(1.0)
        return _make_step_kernel(t, cfg, dtype, interpret=True,
                                 dest_cols=dest_cols)
    raise ValueError(f"unknown sparse sim backend {backend!r}; "
                     f"options: {SPARSE_BACKENDS}")


# ---------------------------------------------------------------------------
# numpy fused path (CPU fast path: same blocked structure as the kernel)
# ---------------------------------------------------------------------------


def _run_slab_waves(units, run_one, workers):
    """Run independent slab units in waves of ``workers`` threads — the
    ``util_workers`` wave idiom of repro.core.utilization, under its
    OpenBLAS-pinning guard.  Units write disjoint output column ranges,
    so the result is bitwise identical at any worker count.  Per-wave
    wall times go to obs when a session is active."""
    from .. import obs
    from ..core.utilization import _blas_limit, _run_units
    sess = obs.current()
    with _blas_limit():
        for lo in range(0, len(units), workers):
            wave = units[lo:lo + workers]
            t0 = time.perf_counter() if sess is not None else 0.0
            _run_units([(lambda u=u: run_one(*u)) for u in wave],
                       workers=workers)
            if sess is not None:
                obs.counter("sim.slab_waves").add(1.0)
                obs.histogram("sim.slab_wave_seconds").observe(
                    time.perf_counter() - t0)


def _make_step_fused_numpy(t: RouteTables, cfg: SimConfig, dtype,
                           dest_cols=None):
    from ..perf import flags
    aux = step_aux(t)
    n, k, m = t.n, t.k, t.m
    nk = n * k
    asd = lambda a: np.ascontiguousarray(np.asarray(a, dtype=dtype))
    axF = _DestAxis(aux)
    axC = _DestAxis(aux, dest_cols) if dest_cols is not None else axF
    ax = (axC, axF, axC)                      # per-VC dest-axis views
    split3F = asd(t.split)                    # (N, K, M)
    reachF = asd(t.split.sum(axis=1))         # (N, M)
    if dest_cols is not None:
        csel = np.asarray(dest_cols, dtype=np.int64)
        split3C = asd(t.split[:, :, csel])    # (N, K, C)
        reachC = asd(reachF[:, csel])
        dist_c = asd(t.dist_act[:, csel])
        hval_c = asd(t.hval_rem[:, csel])
    else:
        split3C, reachC = split3F, reachF
        dist_c = asd(t.dist_act)
        hval_c = asd(t.hval_rem)
    split3_v = (split3C, split3F, split3C)
    reach_v = (reachC, reachF, reachC)
    diag_mid, diag_col = _pool_diag(t, dest_cols)
    spread = asd(t.spread)
    w_val = asd(np.einsum("nm,nkm->nk", t.spread, t.split))
    spread_T = asd(t.spread.T)
    in_active = np.zeros(n, dtype=bool)
    in_active[t.active] = True
    n_mids = asd(t.m - in_active)
    faulted = bool(getattr(t, "faulted", False))
    active = t.active
    head_flat = t.head.reshape(-1)
    mode, thr = cfg.mode, cfg.threshold
    cap = dtype(cfg.capacity)
    buf = dtype(min(cfg.buffer, _BIG))
    thr = dtype(thr)
    tiny = dtype(_TINY) if dtype == np.float64 else np.float32(1e-30)
    # private dtype-matched copy: scipy upcasts mixed-dtype products, so
    # an f64 R would silently run the whole arrival gather in f64
    R = aux.R.astype(dtype)

    # double-buffered outputs: the step is functional (inputs untouched),
    # but reuses its own previous output buffers when the caller feeds
    # the returned state back in (the run loop), avoiding allocations
    bufs = [[np.zeros((n, k, ax[v].w), dtype=dtype) for v in range(3)]
            for _ in range(2)]
    # one retention-scratch plane per VC: slab units of different VCs
    # run concurrently under sim_workers and must not share scratch
    scratch = [np.empty((nk, ax[v].w), dtype=dtype) for v in range(3)]
    # carried per-(arc, tile) occupancies, keyed by the identity of the
    # state arrays we returned; any foreign state (step 0, post-surgery)
    # triggers a fresh reduction pass
    cache = {"key": None, "ot": None}

    def occupancies(qs):
        key = tuple(id(q) for q in qs)
        if cache["key"] == key:
            return cache["ot"]
        return [np.add.reduceat(q.reshape(nk, ax[v].w), ax[v].starts,
                                axis=1)
                for v, q in enumerate(qs)]

    def step(state, inj, inj_cap):
        # f32 note: space/tiny overflows to inf and is clipped by the
        # minimum(1, .) throttle — intended, not an error
        with np.errstate(over="ignore"):
            return _step(state, inj, inj_cap)

    def _step(state, inj, inj_cap):
        q0, q1, q2, src, pend, stage2 = [np.asarray(a, dtype=dtype)
                                         for a in state]
        qs = (q0, q1, q2)
        ot = occupancies(qs)                      # 3 x (NK, T_v)
        o = [x.sum(axis=1) for x in ot]           # 3 x (NK,)
        tmass = [x.sum(axis=0) for x in ot]       # 3 x (T_v,)
        vc_live = [bool(tm.any()) for tm in tmass]

        share = cap / np.maximum(o[0] + o[1] + o[2], cap)      # (NK,)

        # -- arrivals: one sparse gather per live vc -------------------
        if any(vc_live):
            R.data[:] = share[R.indices]
        arr = []
        dl_sum = [dtype(0.0)] * 3
        stage2_add = None
        for v, q in enumerate(qs):
            axis = ax[v]
            if not vc_live[v]:
                arr.append(np.zeros((n, axis.w), dtype=dtype))
                continue
            a = np.asarray(R @ q.reshape(nk, axis.w))
            dl = a[axis.dst_router, axis.dst_col]
            if v == 1:
                stage2_add = dl.copy()
            else:
                dl_sum[v] = dl.sum()
            a[axis.dst_router, axis.dst_col] = 0.0  # transit arrivals only
            arr.append(a)

        # -- credit throttle ------------------------------------------
        s_v, damp, fac, fixdelta, rowfwd = [], [], [], [], []
        for v in range(3):
            axis = ax[v]
            own = (o[v] * (1.0 - share)).reshape(n, k).sum(axis=1)
            space = np.maximum(buf - own, 0.0)
            desire = arr[v].sum(axis=1)
            s = np.minimum(1.0, space / np.maximum(desire, tiny))
            sp = np.concatenate([s, np.ones(1, dtype=dtype)])
            d = sp[head_flat]                      # (NK,)
            f = 1.0 - share * d
            vals = qs[v].reshape(nk, axis.w)[axis.fix_arc, axis.fix_dst]
            fx = vals * share[axis.fix_arc] * (1.0 - d[axis.fix_arc])
            rf = (o[v] * f).reshape(n, k).sum(axis=1) \
                - np.bincount(axis.fix_router, weights=fx,
                              minlength=n).astype(dtype)
            arr[v] *= s[:, None]
            s_v.append(s)
            damp.append(d)
            fac.append(f)
            fixdelta.append(fx)
            rowfwd.append(rf)

        delivered = dl_sum[0] + dl_sum[2]

        # -- phase-1 conversions --------------------------------------
        if stage2_add is not None:
            stage2 = stage2.copy()
            stage2[axF.dst_col] += stage2_add
        conv2 = None
        if stage2.any() and pend.any():
            occ2_now = rowfwd[2] + arr[2].sum(axis=1)
            avail2 = np.maximum(buf - occ2_now, 0.0)[active]
            pend_sum = pend.sum(axis=1)
            drain = np.minimum(np.minimum(stage2, avail2), pend_sum)
            mix = pend / np.maximum(pend_sum, tiny)[:, None]
            take = drain[:, None] * mix            # (M, C)
            pend = pend - take
            stage2 = stage2 - drain
            delivered = delivered + take[diag_mid, diag_col].sum()
            take = take.copy()
            take[diag_mid, diag_col] = 0.0
            conv2 = np.zeros((n, axC.w), dtype=dtype)
            conv2[active] = take

        # -- injection -------------------------------------------------
        src = src + inj
        srcsum = src.sum(axis=1)
        frac = np.minimum(srcsum, inj_cap) / np.maximum(srcsum, tiny)
        q_inj = src * frac[:, None]
        src = src - q_inj

        # -- routing decision (fused: q_min + threshold + mask) --------
        cand = arr[0] + q_inj                      # (N, C)
        div_tot = dtype(0.0)
        if mode == "minimal":
            div_eff = None
            trans_keep = arr[0]
            inj_keep = q_inj
        else:
            if mode == "valiant":
                div_cand = cand
            else:
                # the per-hop UGAL decision folded into the blocked
                # pass: decisions only matter where candidate fluid
                # exists, and a zero-backlog row never diverts (the
                # inequality's LHS is 0 and thr >= 0), so the q_min
                # contraction runs over live candidate tiles x
                # backlogged rows only — reusing the occupancy carry
                b0 = np.maximum(o[0] - cap, 0.0).reshape(n, k)
                rows = np.nonzero(b0.any(axis=1))[0]
                div_cand = np.zeros_like(cand)
                if rows.size:
                    b1 = np.maximum(o[1] - cap, 0.0).reshape(n, k)
                    q_val = (b1 * w_val).sum(axis=1)
                    if rows.size > n // 4:
                        ctm = np.add.reduceat(cand.sum(axis=0), axC.starts)
                        ti = 0
                        while ti < axC.n_tiles:
                            if not ctm[ti] > 0:
                                ti += 1
                                continue
                            tj = ti
                            while (tj + 1 < axC.n_tiles
                                   and ctm[tj + 1] > 0):
                                tj += 1
                            lo, hi = axC.tiles[ti][0], axC.tiles[tj][1]
                            q_min = np.matmul(
                                b0[:, None, :],
                                split3C[:, :, lo:hi])[:, 0, :]
                            ind = (dist_c[:, lo:hi] * q_min
                                   > thr + hval_c[:, lo:hi]
                                   * q_val[:, None])
                            np.multiply(cand[:, lo:hi], ind,
                                        out=div_cand[:, lo:hi])
                            ti = tj + 1
                    else:
                        for r in rows:
                            q_min = b0[r] @ split3C[r]
                            ind = (dist_c[r] * q_min
                                   > thr + hval_c[r] * q_val[r])
                            div_cand[r] = cand[r] * ind
            occ1_now = rowfwd[1] + arr[1].sum(axis=1)
            space1 = np.maximum(buf - occ1_now, 0.0)
            desire1 = div_cand.sum(axis=1)
            s1d = np.minimum(1.0, space1 / np.maximum(desire1, tiny))
            div_eff = div_cand * s1d[:, None]
            div_tot = div_eff.sum()
            if div_tot > 0:
                if faulted:
                    pend = pend + spread_T @ div_eff
                else:
                    scaled = div_eff / n_mids[:, None]
                    pend = pend + scaled.sum(0)[None, :] - scaled[active, :]
            keep = cand - div_eff
            keep_frac = keep / np.maximum(cand, tiny)
            trans_keep = arr[0] * keep_frac
            inj_keep = q_inj * keep_frac

        occ0_now = rowfwd[0] + trans_keep.sum(axis=1)
        space0 = np.maximum(buf - occ0_now, 0.0)
        desire0 = inj_keep.sum(axis=1)
        s0i = np.minimum(1.0, space0 / np.maximum(desire0, tiny))
        inj_adm = inj_keep * s0i[:, None]
        src = src + (inj_keep - inj_adm)

        inflow = [trans_keep + inj_adm, None, None]
        if div_eff is not None and div_tot > 0:
            inflow[1] = arr[1] + div_eff.sum(axis=1)[:, None] * spread
        elif vc_live[1]:
            inflow[1] = arr[1]
        if conv2 is not None:
            inflow[2] = arr[2] + conv2
        elif vc_live[2]:
            inflow[2] = arr[2]

        # -- fused update + enqueue over live (dest-tile) slabs --------
        # contiguous runs of live tiles process as one slab: fewer numpy
        # dispatches and contiguous column ranges, same blocks skipped.
        # Slabs are independent (disjoint output columns), so they are
        # collected as work units and run in sim_workers waves when the
        # live cell count clears the threading threshold.
        out_set = 1 if any(q is bufs[0][v] for v, q in enumerate(qs)) else 0
        new_qs = [None] * 3
        new_ot = [None] * 3
        plane = [None] * 3
        occ_total = stage2.sum()
        units = []                  # (v, tile-run ti..tj, cols lo..hi)
        for v in range(3):
            q = qs[v]
            axis = ax[v]
            live = vc_live[v] or (inflow[v] is not None
                                  and bool(inflow[v].any()))
            if not live:
                new_qs[v] = q                      # all-zero: pass through
                new_ot[v] = ot[v]
                continue
            infl = inflow[v]
            if infl is None:
                infl = np.zeros((n, axis.w), dtype=dtype)
            itm = np.add.reduceat(infl.sum(axis=0), axis.starts)
            out = bufs[out_set][v]
            if out is q:                           # never alias the input
                out = bufs[1 - out_set][v]
            outf = out.reshape(nk, axis.w)
            qf = q.reshape(nk, axis.w)
            otn = np.empty_like(ot[v])
            live_t = (tmass[v] > 0) | (itm > 0)
            ti = 0
            while ti < axis.n_tiles:
                if not live_t[ti]:
                    outf[:, axis.tiles[ti][0]:axis.tiles[ti][1]] = 0.0
                    otn[:, ti] = 0.0
                    ti += 1
                    continue
                tj = ti
                while tj + 1 < axis.n_tiles and live_t[tj + 1]:
                    tj += 1
                units.append((v, ti, tj, axis.tiles[ti][0],
                              axis.tiles[tj][1]))
                ti = tj + 1
            occ_total = occ_total + rowfwd[v].sum() \
                + (infl * reach_v[v]).sum()
            new_qs[v] = out
            new_ot[v] = otn
            plane[v] = (qf, outf, otn, infl)

        def run_slab(v, ti, tj, lo, hi):
            qf, outf, otn, infl = plane[v]
            out3 = new_qs[v]
            # out = inflow*split + q*fac over the slab; the retention
            # product goes through a preallocated scratch plane (a
            # fresh 20 MB temporary per vc per step would be mmap'd
            # and page-faulted every time)
            np.multiply(infl[:, None, lo:hi], split3_v[v][:, :, lo:hi],
                        out=out3[:, :, lo:hi])
            np.multiply(qf[:, lo:hi], fac[v][:, None],
                        out=scratch[v][:, lo:hi])
            outf[:, lo:hi] += scratch[v][:, lo:hi]
            # per-(arc, tile) occupancies fall out of one reduction
            # over the finished slab (retention + enqueue together)
            otn[:, ti:tj + 1] = np.add.reduceat(
                outf[:, lo:hi], ax[v].starts[ti:tj + 1] - lo, axis=1)

        workers = flags().sim_workers
        if (workers > 1 and len(units) > 1
                and sum(nk * (hi - lo)
                        for _, _, _, lo, hi in units)
                >= SIM_THREAD_MIN_CELLS):
            _run_slab_waves(units, run_slab, workers)
        else:
            for u in units:
                run_slab(*u)

        for v in range(3):
            if plane[v] is None:
                continue
            axis = ax[v]
            if len(axis.fix_arc):
                _, outf, otn, _ = plane[v]
                outf[axis.fix_arc, axis.fix_dst] -= fixdelta[v]
                otn[axis.fix_arc, axis.fix_tile] -= fixdelta[v]

        cache["key"] = tuple(id(q) for q in new_qs)
        cache["ot"] = new_ot

        accepted = q_inj.sum() - (inj_keep - inj_adm).sum()
        stats = np.array([delivered, accepted, inj.sum(), occ_total,
                          src.sum(), div_tot], dtype=np.float64)
        return (new_qs[0], new_qs[1], new_qs[2], src, pend, stage2), stats

    return step


# ---------------------------------------------------------------------------
# pallas-kernel path (TPU deploy target; interpret mode on CPU for parity)
# ---------------------------------------------------------------------------


class _KernelSpec(NamedTuple):
    """Everything static about one kernel step: sizes, dest-axis tiling
    and config scalars.  Tables with equal specs (and equal index-array
    lengths) share one compiled step."""

    n: int
    k: int                 # out-slots as laid (laid_slots of the graph's)
    widths: tuple          # per-VC dest-axis width (C, M, C)
    compact: bool          # q0/q2 carry the compacted C axis
    mode: str
    thr: float
    capacity: float
    buffer: float
    faulted: bool
    dtype: str
    interpret: bool


def _kernel_tables(t: RouteTables, dtype, planes, dest_cols=None) -> dict:
    """The arrays the kernel step reads: the dense planes ``planes``
    (:func:`repro.sim.tables.device_planes`: the (N, K, W) split and
    deliver of each dest axis, ``w_val`` and ``spread``), the (N, W)
    decision tables and the arc-index structure (int32), all with the
    out-slot axis laid at :func:`laid_slots` and arcs numbered
    ``router * laid + slot``."""
    aux = step_aux(t)
    n, k = t.n, t.k
    kl = laid_slots(k)
    pad, nk = kl - k, n * kl
    lay = lambda a: a // k * kl + a % k        # arc number, laid
    asd = lambda a: np.asarray(a, dtype=dtype)
    idx = lambda a: np.asarray(a, dtype=np.int32)

    def axis_tables(ax, plane):
        return {"split": plane["split"], "deliver": plane["deliver"],
                "fix_arc": idx(lay(ax.fix_arc)), "fix_dst": idx(ax.fix_dst),
                "fix_router": idx(ax.fix_router),
                "dst_router": idx(ax.dst_router),
                "dst_col": idx(ax.dst_col)}

    sel = slice(None) if dest_cols is None else np.asarray(dest_cols,
                                                           np.int64)
    diag_mid, diag_col = _pool_diag(t, dest_cols)
    in_active = np.zeros(n, dtype=bool)
    in_active[t.active] = True
    tabs = {
        "F": axis_tables(_DestAxis(aux), planes["F"]),
        "dist_c": asd(t.dist_act[:, sel]), "hval_c": asd(t.hval_rem[:, sel]),
        "spread": planes["spread"], "w_val": planes["w_val"],
        "spread_T": asd(t.spread.T), "n_mids": asd(t.m - in_active),
        "active": idx(t.active),
        "head_flat": pad_slots(t.head, pad, np.int32, fill=n).reshape(-1),
        # reverse-arc gather: sentinel -> the appended zero row
        "rev": pad_slots(np.where(aux.rev >= 0, lay(aux.rev),
                                  nk).reshape(n, k), pad, np.int32, fill=nk),
        "diag_mid": idx(diag_mid), "diag_col": idx(diag_col),
    }
    if dest_cols is not None:
        tabs["C"] = axis_tables(_DestAxis(aux, dest_cols), planes["C"])
    return tabs


def kernel_program(t: RouteTables, cfg: SimConfig, dtype, interpret,
                   dest_cols=None):
    """``(jitted, tabs)``: the compiled-once kernel step
    ``jitted(tabs, state, inj, inj_cap)`` and the route tables it takes
    as its first argument; its state's out-slot axis is laid at
    :func:`laid_slots` of ``t.k``.  Its dense planes are made on the
    device (:func:`repro.sim.tables.device_planes` at that layout); the
    other tables are host arrays."""
    from .. import obs
    c = t.m if dest_cols is None else len(dest_cols)
    spec = _KernelSpec(t.n, laid_slots(t.k), (c, t.m, c),
                       dest_cols is not None,
                       cfg.mode, cfg.threshold, float(cfg.capacity),
                       float(min(cfg.buffer, _BIG)),
                       bool(getattr(t, "faulted", False)),
                       np.dtype(dtype).name, bool(interpret))
    planes = device_planes(t, dtype, laid_slots(t.k), dest_cols)
    with obs.span("sim.step_tables"):
        tabs = _kernel_tables(t, dtype, planes, dest_cols)
    return _kernel_step(spec), tabs


def _make_step_kernel(t: RouteTables, cfg: SimConfig, dtype, interpret,
                      dest_cols=None):
    jitted, tabs = kernel_program(t, cfg, dtype, interpret, dest_cols)
    return BoundStep(jitted, tabs, scoped_x64=dtype == np.float64,
                     pad=laid_slots(t.k) - t.k)


@functools.lru_cache(maxsize=16)
def _kernel_step(spec: _KernelSpec):
    """The jitted ``step(tabs, state, inj, inj_cap)`` of one spec; the
    route tables are an argument, so the program holds no table."""
    import jax
    import jax.numpy as jnp

    from ..kernels.sim_step import fused_decision, fused_step_update

    n, k = spec.n, spec.k
    nk = n * k
    tile = DEST_TILE
    widths = spec.widths
    n_tiles = tuple(-(-w // tile) for w in widths)
    interpret = spec.interpret
    faulted = spec.faulted
    mode = spec.mode
    npdt = np.dtype(spec.dtype).type
    cap = npdt(spec.capacity)
    buf = npdt(spec.buffer)
    thr = npdt(spec.thr)
    tiny = npdt(_TINY) if npdt == np.float64 else np.float32(1e-30)

    def tile_sums(x, v):                     # (..., W_v) -> (..., T_v)
        pad = n_tiles[v] * tile - widths[v]
        xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
        return xp.reshape(x.shape[:-1] + (n_tiles[v], tile)).sum(-1)

    def step_impl(tabs, state, inj, inj_cap):
        axC = tabs["C"] if spec.compact else tabs["F"]
        ax = (axC, tabs["F"], axC)           # per-VC dest-axis tables
        dist_c, hval_c = tabs["dist_c"], tabs["hval_c"]
        spread, w_val = tabs["spread"], tabs["w_val"]
        spread_T, n_mids = tabs["spread_T"], tabs["n_mids"]
        active, head_flat = tabs["active"], tabs["head_flat"]
        rev = tabs["rev"]
        diag_mid, diag_col = tabs["diag_mid"], tabs["diag_col"]
        q0, q1, q2, src, pend, stage2 = state
        qs = (q0, q1, q2)
        o = [q.reshape(nk, widths[v]).sum(axis=1)
             for v, q in enumerate(qs)]
        share = cap / jnp.maximum(o[0] + o[1] + o[2], cap)    # (NK,)

        arr, dl_sum, s_v, damp = [], [], [], []
        stage2_new = stage2
        for v, q in enumerate(qs):
            axis, w = ax[v], widths[v]
            zrow = jnp.zeros((1, w), dtype=q0.dtype)
            with jax.named_scope("forward_gather"):
                mv = jnp.concatenate([q.reshape(nk, w) * share[:, None],
                                      zrow])
                a = mv[rev.reshape(-1)].reshape(n, k, w).sum(axis=1)
            dl = a[axis["dst_router"], axis["dst_col"]]
            if v == 1:
                stage2_new = stage2_new.at[axis["dst_col"]].add(dl)
            dl_sum.append(dl.sum())
            a = a.at[axis["dst_router"], axis["dst_col"]].set(0.0)
            own = (o[v] * (1.0 - share)).reshape(n, k).sum(axis=1)
            space = jnp.maximum(buf - own, 0.0)
            desire = a.sum(axis=1)
            s = jnp.minimum(1.0, space / jnp.maximum(desire, tiny))
            d = jnp.concatenate([s, jnp.ones(1, q0.dtype)])[head_flat]
            arr.append(a * s[:, None])
            s_v.append(s)
            damp.append(d)

        delivered = dl_sum[0] + dl_sum[2]
        stage2 = stage2_new

        def rowfwd(v):
            # post-forward per-router occupancy, without touching q:
            # retention of o minus the delivered fluid's extra share
            axis = ax[v]
            f = (o[v] * (1.0 - share * damp[v])).reshape(n, k).sum(axis=1)
            arc = axis["fix_arc"]
            vals = qs[v].reshape(nk, widths[v])[arc, axis["fix_dst"]]
            fx = vals * share[arc] * (1.0 - damp[v][arc])
            return f - jnp.zeros(n, q0.dtype).at[axis["fix_router"]].add(fx)

        # -- conversions ----------------------------------------------
        with jax.named_scope("conversions"):
            occ2_now = rowfwd(2) + arr[2].sum(axis=1)
            avail2 = jnp.maximum(buf - occ2_now, 0.0)[active]
            pend_sum = pend.sum(axis=1)
            drain = jnp.minimum(jnp.minimum(stage2, avail2), pend_sum)
            mix = pend / jnp.maximum(pend_sum, tiny)[:, None]
            take = drain[:, None] * mix                # (M, C)
            pend = pend - take
            stage2 = stage2 - drain
            delivered = delivered + take[diag_mid, diag_col].sum()
            take = take.at[diag_mid, diag_col].set(0.0)
            conv2 = jnp.zeros((n, widths[2]), q0.dtype).at[active].set(take)

        # -- injection -------------------------------------------------
        with jax.named_scope("injection"):
            src = src + inj
            srcsum = src.sum(axis=1)
            frac = jnp.minimum(srcsum, inj_cap) / jnp.maximum(srcsum, tiny)
            q_inj = src * frac[:, None]
            src = src - q_inj

        # -- decision (fused kernel: q_min + threshold + mask) ---------
        with jax.named_scope("decision"):
            cand = arr[0] + q_inj
            if mode == "minimal":
                div_eff = jnp.zeros_like(cand)
            else:
                if mode == "valiant":
                    div_cand = cand
                else:
                    b0 = jnp.maximum(o[0] - cap, 0.0).reshape(n, k)
                    b1 = jnp.maximum(o[1] - cap, 0.0).reshape(n, k)
                    q_val = (b1 * w_val).sum(axis=1)
                    ctm = tile_sums(cand.sum(axis=0), 0)
                    div_cand = fused_decision(
                        b0, ax[0]["split"], dist_c, hval_c, cand, q_val,
                        (ctm > 0).astype(jnp.int32), thr=float(thr),
                        interpret=interpret)
                occ1_now = rowfwd(1) + arr[1].sum(axis=1)
                space1 = jnp.maximum(buf - occ1_now, 0.0)
                desire1 = div_cand.sum(axis=1)
                s1d = jnp.minimum(1.0, space1 / jnp.maximum(desire1, tiny))
                div_eff = div_cand * s1d[:, None]
                if faulted:
                    pend = pend + spread_T @ div_eff
                else:
                    scaled = div_eff / n_mids[:, None]
                    pend = pend + scaled.sum(0)[None, :] - scaled[active, :]

        keep = cand - div_eff
        keep_frac = keep / jnp.maximum(cand, tiny)
        trans_keep = arr[0] * keep_frac
        inj_keep = q_inj * keep_frac
        occ0_now = rowfwd(0) + trans_keep.sum(axis=1)
        space0 = jnp.maximum(buf - occ0_now, 0.0)
        desire0 = inj_keep.sum(axis=1)
        s0i = jnp.minimum(1.0, space0 / jnp.maximum(desire0, tiny))
        inj_adm = inj_keep * s0i[:, None]
        src = src + (inj_keep - inj_adm)

        inflow = [trans_keep + inj_adm,
                  arr[1] + div_eff.sum(axis=1)[:, None] * spread,
                  arr[2] + conv2]

        # -- fused kernel: forward + throttle retention + enqueue ------
        occ = stage2.sum()
        new_qs = []
        for v in range(3):
            fac2 = (1.0 - share * damp[v]).reshape(n, k)
            corr2 = (share * (1.0 - damp[v])).reshape(n, k)
            mass = tile_sums(qs[v].reshape(nk, widths[v]).sum(axis=0)
                             + inflow[v].sum(axis=0), v)
            tmask = (mass > 0).astype(jnp.int32)
            qn, on = fused_step_update(qs[v], ax[v]["split"],
                                       ax[v]["deliver"],
                                       fac2, corr2, inflow[v], tmask,
                                       interpret=interpret)
            occ = occ + on.sum()
            new_qs.append(qn)

        accepted = q_inj.sum() - (inj_keep - inj_adm).sum()
        stats = jnp.stack([delivered, accepted, inj.sum(), occ,
                           src.sum(), div_eff.sum()])
        return (new_qs[0], new_qs[1], new_qs[2], src, pend, stage2), stats

    return jax.jit(step_impl)
