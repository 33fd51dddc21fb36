"""Fused simulator-step kernel: forward share, credit throttle, and
ECMP enqueue of one virtual channel in a single pass over blocked
``(router, out-slot, dest-tile)`` state.

The flow-level simulator (repro.sim) spends its step almost entirely in
one contraction per VC: apply the proportional forward share and the
credit damping to every queue cell, eject the delivered diagonal, and
enqueue the decided inflow through the equal-split minimal table —
four sweeps over the ``(N, K, M)`` queue tensor when written naively.
This kernel fuses them into one read and one write per populated
``(router-block, dest-tile)`` block:

    q_out = q * fac[r, k]                     # forward + credit retention
          - q * corr[r, k] * deliver[r, k, d]  # ejected fluid keeps no credit
          + inflow[r, d] * split[r, k, d]      # per-hop ECMP enqueue

with ``fac = 1 - share * damp`` and ``corr = share * (1 - damp)`` folded
host-side (both are O(N·K)).  The second output accumulates the
post-step per-slot occupancy ``o_out[r, k] = sum_d q_out`` across dest
tiles (flash-attention-style revisiting of the output block along the
innermost grid axis), which the next step's share computation consumes.

The dest axis is *blocked-sparse*: ``tile_mask`` (one int32 per dest
tile, scalar-prefetched) marks the populated tiles; unpopulated tiles —
zero fluid and zero inflow, so the contraction is identically zero —
are skipped under ``pl.when`` and only pay the (clipped) output write.
This is the kernel seam behind ``SimConfig(backend="pallas")``.  Each
kernel takes an ``impl`` in the vocabulary of :mod:`repro.kernels.ops`:
``"pallas"`` (the Mosaic kernel, TPU only), ``"pallas_interpret"`` (the
same kernel through the pallas interpreter) or ``"jnp"`` (the kernel's
own formula on whole arrays, which XLA compiles on any platform).  The
dense numpy float64 engine remains the parity oracle
(tests/test_sim_kernel.py).

Block structure follows flash_attention.py / ssd_scan.py.  The router
block is chosen from K (:func:`router_block`) so the pipelined blocks fit
the TPU's default scoped VMEM at every degree the simulator runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_step_update", "fused_decision", "router_block",
           "DEST_TILE", "IMPLS"]

# dest-tile width: the TPU lane dimension; the kernel step
# (repro.sim.kernel) sums its tile masks at the same width
DEST_TILE = 128

IMPLS = ("pallas", "pallas_interpret", "jnp")

# share of the default scoped VMEM (16 MiB on TPU v5e) the pipelined
# blocks of one grid step may take; the rest holds the kernel's
# temporaries (the update and the masked occupancy sum)
_BLOCK_VMEM_BYTES = 12 * 2**20


def router_block(k: int, itemsize: int = 4) -> int:
    """Router rows per block: the largest power of two in [8, 128] whose
    double-buffered ``(rows, K, DEST_TILE)`` blocks of the step kernel
    (three inputs and one output) fit ``_BLOCK_VMEM_BYTES``.  K sits on
    the sublane axis and pads to a multiple of 8.  Both kernels use it,
    so their grids are identical."""
    k_pad = -(-k // 8) * 8
    rows = 128
    while rows > 8 and 2 * 4 * rows * k_pad * DEST_TILE * itemsize \
            > _BLOCK_VMEM_BYTES:
        rows //= 2
    return rows


def _kernel(mask_ref, q_ref, split_ref, deliver_ref, fac_ref, corr_ref,
            inflow_ref, qout_ref, oout_ref, *, m):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        oout_ref[...] = jnp.zeros_like(oout_ref)

    @pl.when(mask_ref[j] != 0)
    def _compute():
        q = q_ref[...]
        upd = q * fac_ref[...][:, :, None]
        upd -= q * corr_ref[...][:, :, None] * deliver_ref[...]
        upd += inflow_ref[...][:, None, :] * split_ref[...]
        qout_ref[...] = upd
        # a partial last tile is block-padded with undefined values (the
        # write-back is clipped, but the occupancy sum must exclude them)
        bd = q_ref.shape[-1]
        col = j * bd + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bd), 2)
        oout_ref[...] += jnp.where(col < m, upd, 0.0).sum(axis=-1)

    @pl.when(mask_ref[j] == 0)
    def _skip():
        # unpopulated tile: no fluid, no inflow -> the block stays zero
        qout_ref[...] = jnp.zeros_like(qout_ref)


def _check_impl(impl: str):
    if impl not in IMPLS:
        raise ValueError(f"unknown sim kernel impl {impl!r}; options: "
                         f"{IMPLS}")


# The "jnp" bodies below ignore tile_mask: a masked tile holds zero
# fluid, zero inflow and zero candidates, so both formulas give zero
# there, as the kernels' skipped blocks do.


@functools.partial(jax.jit, static_argnames=("impl",))
def fused_step_update(q, split, deliver, fac, corr, inflow, tile_mask,
                      impl: str = "pallas"):
    """One VC's fused forward/throttle/enqueue update.

    Args:
      q:         (N, K, M) queue tensor (float32/float64).
      split:     (N, K, M) equal-split minimal table.
      deliver:   (N, K, M) delivery mask (head == dest), same dtype as q.
      fac:       (N, K)    ``1 - share * damp`` retention factor.
      corr:      (N, K)    ``share * (1 - damp)`` delivery correction.
      inflow:    (N, M)    decided vc inflow to enqueue.
      tile_mask: (ceil(M / DEST_TILE),) int32, nonzero = populated tile.

    Returns ``(q_out, o_out)``: the updated queues and the per-slot
    post-step occupancy ``q_out.sum(-1)``.
    """
    _check_impl(impl)
    if impl == "jnp":
        upd = (q * fac[:, :, None] - q * corr[:, :, None] * deliver
               + inflow[:, None, :] * split)
        return upd, upd.sum(axis=-1)
    n, k, m = q.shape
    bn = min(router_block(k, q.dtype.itemsize), n)
    bd = min(DEST_TILE, m)
    grid = (pl.cdiv(n, bn), pl.cdiv(m, bd))

    qkd = pl.BlockSpec((bn, k, bd), lambda i, j, mask: (i, 0, j))
    nk = pl.BlockSpec((bn, k), lambda i, j, mask: (i, 0))
    nd = pl.BlockSpec((bn, bd), lambda i, j, mask: (i, j))

    return pl.pallas_call(
        functools.partial(_kernel, m=m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[qkd, qkd, qkd, nk, nk, nd],
            out_specs=[qkd, nk],
        ),
        out_shape=[jax.ShapeDtypeStruct((n, k, m), q.dtype),
                   jax.ShapeDtypeStruct((n, k), q.dtype)],
        interpret=impl == "pallas_interpret",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(jnp.asarray(tile_mask, jnp.int32), q, split, deliver, fac, corr,
      inflow)


def _decision_kernel(mask_ref, b0_ref, split_ref, dist_ref, hval_ref,
                     cand_ref, qval_ref, out_ref, *, thr):
    j = pl.program_id(1)

    @pl.when(mask_ref[j] != 0)
    def _compute():
        # ECMP-split-weighted vc0 backlog toward each dest in the tile —
        # the q_min contraction of the per-hop UGAL rule, evaluated only
        # where candidate fluid exists
        q_min = (b0_ref[...][:, :, None] * split_ref[...]).sum(axis=1)
        divert = dist_ref[...] * q_min > thr + hval_ref[...] * qval_ref[...]
        out_ref[...] = jnp.where(divert, cand_ref[...], 0.0)

    @pl.when(mask_ref[j] == 0)
    def _skip():
        # no candidate fluid in the tile: nothing can divert
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit, static_argnames=("thr", "impl"))
def fused_decision(b0, split, dist, hval, cand, q_val, tile_mask,
                   thr: float, impl: str = "pallas"):
    """The per-hop UGAL decision as one blocked pass: divert candidates.

    Folds the ``q_min = einsum("nk,nkm->nm", b0, split)`` backlog gather
    and the threshold comparison into per-(router-block, dest-tile)
    blocks, skipping tiles with no candidate fluid (``tile_mask``) — the
    decision-phase companion of :func:`fused_step_update`, sharing its
    block structure so both kernels skip identical tiles.

    Args:
      b0:        (N, K)    vc0 backlog per out-slot.
      split:     (N, K, M) equal-split minimal table (M may be the
                 compacted dest axis).
      dist:      (N, M)    remaining minimal hops.
      hval:      (N, M)    mean two-leg detour estimate.
      cand:      (N, M)    enqueueing vc0 candidate fluid.
      q_val:     (N,)      weighted vc1 backlog.
      tile_mask: (ceil(M / DEST_TILE),) int32, nonzero = candidates there.
      thr:       the threshold T in flit units (static: one compile per
                 SimConfig).

    Returns the (N, M) diverting candidate fluid ``cand * [divert]``.
    Rows with zero backlog never divert (``0 > thr + hval*q_val`` is
    false for ``thr >= 0``), so a partial last tile's block padding is
    discarded by the clipped write-back, exactly as in the step kernel.
    """
    _check_impl(impl)
    if impl == "jnp":
        q_min = jnp.einsum("nk,nkm->nm", b0, split,
                           precision=jax.lax.Precision.HIGHEST)
        divert = dist * q_min > thr + hval * q_val[:, None]
        return jnp.where(divert, cand, 0.0)
    n, k, m = split.shape
    bn = min(router_block(k, split.dtype.itemsize), n)
    bd = min(DEST_TILE, m)
    grid = (pl.cdiv(n, bn), pl.cdiv(m, bd))

    qkd = pl.BlockSpec((bn, k, bd), lambda i, j, mask: (i, 0, j))
    nk = pl.BlockSpec((bn, k), lambda i, j, mask: (i, 0))
    nd = pl.BlockSpec((bn, bd), lambda i, j, mask: (i, j))
    n1 = pl.BlockSpec((bn, 1), lambda i, j, mask: (i, 0))

    return pl.pallas_call(
        functools.partial(_decision_kernel, thr=thr),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[nk, qkd, nd, nd, nd, n1],
            out_specs=nd,
        ),
        out_shape=jax.ShapeDtypeStruct((n, m), cand.dtype),
        interpret=impl == "pallas_interpret",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(jnp.asarray(tile_mask, jnp.int32), b0, split, dist, hval, cand,
      q_val.reshape(n, 1))
