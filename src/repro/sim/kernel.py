"""The blocked sparse-destination kernel step of the flow-level simulator.

The dense engine (repro.sim.engine) materializes every intermediate of
the step — ``mv``/``del``/``cont`` tensors, an ``np.add.at`` scatter —
which costs ~30 passes over the O(N·K·M) queue state per step and caps
instances at SIM_MAX_CELLS.  This module is the ``backend="pallas"``
seam: the same step semantics, restructured around one fused
forward/throttle/enqueue contraction per VC over a *blocked* dest axis
(tiles of :data:`repro.kernels.sim_step.DEST_TILE` destinations), with
only populated (router, dest-tile) blocks computed.  The arrival gather
``arr[h] = sum share(a)·q[a]`` runs over reverse arcs, delivered fluid is
the extracted ``(router, self-dest)`` column (the deliver mask has at
most one hit per arc), and the fused update
``q·fac - q·corr·deliver + inflow·split`` is
:func:`repro.kernels.sim_step.fused_step_update`.

Both sparse backends build one jitted program, :func:`_kernel_step`; they
differ only in the ``impl`` its two kernels run (chosen in
:func:`make_step_sparse`):

* ``backend="pallas"`` — the Mosaic kernels on a TPU; anywhere else the
  kernels' own formulas on whole arrays (``impl="jnp"``), which XLA
  compiles for the platform at hand.
* ``backend="pallas_interpret"`` — the Mosaic kernels through the pallas
  interpreter on CPU: slow, but the kernel bodies the TPU runs.

Both accept float32 (the TPU-native dtype, default) or float64 state via
``SimConfig(dtype=...)``, float64 off a TPU only; the dense numpy
float64 engine stays the parity oracle, with knee-level agreement at
tolerance rather than bitwise (rounding shifts individual threshold
decisions, not the knee).

Destination sparsity has a static half too, and it is per VC.  Under
``minimal`` routing the Simulator shrinks the active set itself (see
``Simulator(demand=...)``).  Under ``ugal``/``valiant`` the active set
must stay whole — diversions spread over every active intermediate —
but only the *final-destination* axes need the demanded columns: with
``dest_cols`` the step carries q0/q2/src and the PEND pool's dest axis
on the compacted ``C`` demanded columns while q1/stage2 keep the full
``M`` mid axis (:class:`_DestAxis` holds the index-remapped views).  The
stage-2 column closure is the demanded set itself — diverted fluid
keeps its final destination — so the compaction is exact, and a
pn27-class fabric (64M dense cells) sweeps adaptively in a few-M-cell
compacted state.  The per-hop UGAL decision (q_min gather + threshold +
candidate mask) is fused into its own kernel
(:func:`repro.kernels.sim_step.fused_decision`) instead of running as
unfused dense ops.
"""

from __future__ import annotations

import functools
from collections import defaultdict
from typing import NamedTuple

import numpy as np

from .engine import _BIG, _TINY, BoundStep, SimConfig, pad_slots
from .tables import RouteTables, device_planes

__all__ = ["make_step_sparse", "step_aux", "resolve_dtype", "laid_slots",
           "step_planes", "SPARSE_BACKENDS"]

SPARSE_BACKENDS = ("pallas", "pallas_interpret")

# the TPU's sublane count: the kernel step lays the out-slot axis of its
# state and tables at a multiple of it (see laid_slots)
SUBLANE = 8


class _StepAux:
    """Arc-level index structure of the kernel step.

    Everything here depends only on the RouteTables: the reverse-arc
    pairing that turns the arrival scatter into a gather, and the per-arc
    dest index of the head router (the deliver mask has at most one true
    per arc — delivery is O(N·K), not O(N·K·M)).
    """

    def __init__(self, t: RouteTables):
        n, k, m = t.n, t.k, t.m
        self.m = m
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
        if cols is None:
            self.fix_arc, self.fix_dst = aux.fix_arc, aux.fix_dst
            self.fix_router = aux.fix_router
            self.dst_router, self.dst_col = aux.dst_router, aux.dst_col
        else:
            cols = np.asarray(cols, dtype=np.int64)
            pos = np.full(aux.m, -1, dtype=np.int64)
            pos[cols] = np.arange(len(cols))
            keep = pos[aux.fix_dst] >= 0
            self.fix_arc = aux.fix_arc[keep]
            self.fix_dst = pos[aux.fix_dst[keep]]
            self.fix_router = aux.fix_router[keep]
            keep = pos[aux.dst_col] >= 0
            self.dst_router = aux.dst_router[keep]
            self.dst_col = pos[aux.dst_col[keep]]


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


def step_aux(t: RouteTables) -> _StepAux:
    """The (cached) arc-index structure of one RouteTables instance."""
    aux = getattr(t, "_step_aux", None)
    if aux is None:
        aux = _StepAux(t)
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
    the kernel step.  None for the dense numpy step, which reads the host
    planes of ``t``."""
    if backend == "jax":
        return device_planes(t, dtype, t.k)
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
    # the kernel bodies the step runs, made observable by the build
    # counter: which one ran is otherwise invisible to callers
    if backend == "pallas":
        import jax
        if jax.default_backend() == "tpu":
            if dtype == np.float64:
                raise ValueError(
                    "the pallas sim kernel runs float32 on TPU; float64 "
                    "state needs backend='numpy' (the dense reference) "
                    "or backend='pallas_interpret'")
            impl, build = "pallas", "pallas_tpu"
        else:
            # Mosaic kernels lower only for a TPU (the rule of
            # repro.kernels.ops.default_impl)
            impl, build = "jnp", "pallas_jnp"
    elif backend == "pallas_interpret":
        impl = build = "pallas_interpret"
    else:
        raise ValueError(f"unknown sparse sim backend {backend!r}; "
                         f"options: {SPARSE_BACKENDS}")
    obs.counter(f"sim.step_build[{build}]").add(1.0)
    return _make_step_kernel(t, cfg, dtype, impl, dest_cols=dest_cols)


# ---------------------------------------------------------------------------
# the kernel step
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
    impl: str              # kernel bodies: pallas, pallas_interpret or jnp


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


def kernel_program(t: RouteTables, cfg: SimConfig, dtype, impl,
                   dest_cols=None):
    """``(jitted, tabs)``: the compiled-once kernel step
    ``jitted(tabs, state, inj, inj_cap)`` and the route tables it takes
    as its first argument; its state's out-slot axis is laid at
    :func:`laid_slots` of ``t.k``.  Its dense planes are made on the
    device (:func:`repro.sim.tables.device_planes` at that layout); the
    other tables are host arrays.  ``impl`` names the kernel bodies
    (:data:`repro.kernels.sim_step.IMPLS`)."""
    from .. import obs
    c = t.m if dest_cols is None else len(dest_cols)
    spec = _KernelSpec(t.n, laid_slots(t.k), (c, t.m, c),
                       dest_cols is not None,
                       cfg.mode, cfg.threshold, float(cfg.capacity),
                       float(min(cfg.buffer, _BIG)),
                       bool(getattr(t, "faulted", False)),
                       np.dtype(dtype).name, impl)
    planes = device_planes(t, dtype, laid_slots(t.k), dest_cols)
    with obs.span("sim.step_tables"):
        tabs = _kernel_tables(t, dtype, planes, dest_cols)
    return _kernel_step(spec), tabs


def _make_step_kernel(t: RouteTables, cfg: SimConfig, dtype, impl,
                      dest_cols=None):
    jitted, tabs = kernel_program(t, cfg, dtype, impl, dest_cols)
    return BoundStep(jitted, tabs, scoped_x64=dtype == np.float64,
                     pad=laid_slots(t.k) - t.k)


@functools.lru_cache(maxsize=16)
def _kernel_step(spec: _KernelSpec):
    """The jitted ``step(tabs, state, inj, inj_cap)`` of one spec; the
    route tables are an argument, so the program holds no table."""
    import jax
    import jax.numpy as jnp

    from ..kernels.sim_step import (DEST_TILE, fused_decision,
                                    fused_step_update)

    n, k = spec.n, spec.k
    nk = n * k
    tile = DEST_TILE
    widths = spec.widths
    n_tiles = tuple(-(-w // tile) for w in widths)
    impl = spec.impl
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
                        impl=impl)
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
                                       impl=impl)
            occ = occ + on.sum()
            new_qs.append(qn)

        accepted = q_inj.sum() - (inj_keep - inj_adm).sum()
        stats = jnp.stack([delivered, accepted, inj.sum(), occ,
                           src.sum(), div_eff.sum()])
        return (new_qs[0], new_qs[1], new_qs[2], src, pend, stage2), stats

    return jax.jit(step_impl)
