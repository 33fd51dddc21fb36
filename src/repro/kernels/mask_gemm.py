"""Fused mask+GEMM kernels for the batched-Brandes level recurrences.

repro.core.utilization's level-synchronous engines spend each BFS level
in one (S, N) x (N, N) GEMM followed by elementwise masking against the
distance table — two full passes over the (S, N) level state when
written as separate XLA ops.  These kernels fuse the mask into the GEMM
epilogue, one per recurrence direction:

  frontier_step  — forward sigma recurrence:
                     t     = front @ adj
                     new   = (t > 0) & (dist < 0)
                     nxt   = t * new
                     dist' = where(new, lvl, dist)
                     sigma'= where(new, t, sigma)
  backward_step  — backward delta recurrence (the dependency
                   accumulation; the O(S·N) coefficient itself stays
                   host-side):
                     delta' = delta + sigma * ((coeff @ adj) * (dist == lvl))

Block structure follows flash_attention.py / sim_step.py: grid
``(rows, cols, contraction)`` with the contraction axis innermost, the
output block revisited across it as the accumulator, and the mask
epilogue applied on the final contraction step.  The level index is
scalar-prefetched so one trace serves every BFS level.  Inputs are
zero-padded host-side to block multiples (``dist`` with -2, which no
mask matches) — partial pallas blocks are padded with *undefined*
values, so in-kernel masking would otherwise be needed on every tile.

This is the ``util_engine="pallas"`` seam (repro.core.utilization
``_loads_pallas``): compiled on TPU, pallas-interpreter elsewhere — the
same convention as repro.sim's ``backend="pallas_interpret"`` parity
path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["frontier_step", "backward_step"]

_BLOCK = 128


def _fwd_kernel(lvl_ref, x_ref, a_ref, dist_ref, sigma_ref,
                nxt_ref, dout_ref, sout_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        nxt_ref[...] = jnp.zeros_like(nxt_ref)

    nxt_ref[...] += jnp.dot(x_ref[...], a_ref[...],
                            preferred_element_type=nxt_ref.dtype)

    @pl.when(k == nk - 1)
    def _finalize():
        t = nxt_ref[...]
        dist = dist_ref[...]
        new = (t > 0) & (dist < 0)
        nxt_ref[...] = jnp.where(new, t, 0.0)
        dout_ref[...] = jnp.where(new, lvl_ref[0], dist)
        sout_ref[...] = jnp.where(new, t, sigma_ref[...])


def _bwd_kernel(lvl_ref, x_ref, a_ref, dist_ref, sigma_ref, delta_ref,
                out_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(x_ref[...], a_ref[...],
                            preferred_element_type=out_ref.dtype)

    @pl.when(k == nk - 1)
    def _finalize():
        t = jnp.where(dist_ref[...] == lvl_ref[0], out_ref[...], 0.0)
        out_ref[...] = delta_ref[...] + sigma_ref[...] * t


def _pad(x, rows, cols, fill=0):
    b, n = x.shape
    if b == rows and n == cols:
        return x
    return jnp.pad(x, ((0, rows - b), (0, cols - n)),
                   constant_values=fill)


def _grid_call(kernel, lvl, mats, dists, out_shapes, b, n, block,
               interpret):
    """Shared blocked (rows, cols, contraction) dispatch.

    ``mats`` = (x, adj, *dense float operands), ``dists`` = the int32
    distance table; everything is padded to ``block`` multiples and the
    outputs clipped back to (b, n).
    """
    bb = min(block, b)
    bn = min(block, n)
    rows = pl.cdiv(b, bb) * bb
    cols = pl.cdiv(n, bn) * bn
    grid = (rows // bb, cols // bn, cols // bn)

    x, adj, *rest = mats
    x = _pad(x, rows, cols)
    adj = _pad(adj, cols, cols)
    rest = [_pad(r, rows, cols) for r in rest]
    dist = _pad(dists, rows, cols, fill=-2)  # -2: matches no level mask

    xs = pl.BlockSpec((bb, bn), lambda i, j, k, lvl: (i, k))
    as_ = pl.BlockSpec((bn, bn), lambda i, j, k, lvl: (k, j))
    ys = pl.BlockSpec((bb, bn), lambda i, j, k, lvl: (i, j))

    outs = pl.pallas_call(
        functools.partial(kernel, nk=grid[2]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[xs, as_] + [ys] * (len(rest) + 1),
            out_specs=[ys] * len(out_shapes),
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, cols), dt)
                   for dt in out_shapes],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(jnp.asarray([lvl], jnp.int32), x, adj, dist, *rest)
    return [o[:b, :n] for o in outs]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def frontier_step(front, adj, dist, sigma, lvl, block: int = _BLOCK,
                  interpret: bool = False):
    """One forward BFS level: ``(nxt, dist', sigma')`` fused with the
    frontier GEMM.  ``front``/``sigma`` are (S, N) float, ``dist``
    (S, N) int32, ``lvl`` the level being claimed."""
    b, n = front.shape
    nxt, dout, sout = _grid_call(
        _fwd_kernel, lvl, (front, adj, sigma), dist,
        (front.dtype, jnp.int32, front.dtype), b, n, block, interpret)
    return nxt, dout, sout


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def backward_step(coeff, adj, dist, sigma, delta, lvl,
                  block: int = _BLOCK, interpret: bool = False):
    """One backward dependency level:
    ``delta + sigma * ((coeff @ adj) * (dist == lvl))`` in one fused
    pass (``lvl`` here is the *parent* level, the caller's lvl-1)."""
    b, n = coeff.shape
    (out,) = _grid_call(
        _bwd_kernel, lvl, (coeff, adj, sigma, delta), dist,
        (coeff.dtype,), b, n, block, interpret)
    return out
