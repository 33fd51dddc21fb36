"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Grid (batch, heads, chunks) with the chunk dimension innermost
('arbitrary'), carrying the (N, P) fp32 state in VMEM scratch across
chunks.  Each chunk does three MXU matmuls:

    scores = (C B^T) ⊙ exp(segsum)         (Q, Q)
    y      = scores @ (x·dt) + (C @ S_in) ⊙ exp(cum)    (Q, P)
    S_out  = exp(cum[-1]) S_in + B^T @ (exp(cum[-1]-cum) ⊙ x·dt)

Cumulative sums are computed as a lower-triangular matmul so everything
maps to the MXU (no serial scan inside the kernel).

Validated in interpret mode against kernels.ref.ssd_ref; TPU is the target.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["ssd_scan"]


def _kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, dskip_ref, y_ref,
            state_ref, *, chunk, n_chunks):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)         # (Q,)
    bmat = b_ref[0, :, 0, :].astype(jnp.float32)     # (Q, N)
    cmat = c_ref[0, :, 0, :].astype(jnp.float32)     # (Q, N)
    a = -jnp.exp(alog_ref[0].astype(jnp.float32))    # scalar, negative
    d_skip = dskip_ref[0].astype(jnp.float32)

    da = dt * a                                       # (Q,)
    # inclusive cumsum via lower-triangular ones matmul (MXU-friendly)
    tril = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))
    cum = jax.lax.dot_general(tril, da[:, None], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)[:, 0]  # (Q,)

    seg = cum[:, None] - cum[None, :]                 # cum_i - cum_j
    causal = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    # mask before exp: seg > 0 above the diagonal would overflow to inf
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * decay
    xdt = x * dt[:, None]                             # (Q, P)
    y = jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s_in = state_ref[...]                             # (N, P)
    y += jax.lax.dot_general(cmat, s_in, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        * jnp.exp(cum)[:, None]
    y += x * d_skip
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    w = jnp.exp(cum[-1] - cum)[:, None]               # (Q, 1)
    state_ref[...] = jnp.exp(cum[-1]) * s_in + jax.lax.dot_general(
        bmat, xdt * w, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a_log, b_mat, c_mat, d_skip, *, chunk: int = 128,
             interpret: bool = False):
    """x: (B, L, H, P); dt: (B, L, H); a_log, d_skip: (H,);
    b_mat, c_mat: (B, L, G, N).  Returns y: (B, L, H, P)."""
    bsz, length, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    assert h % g == 0
    rep = h // g
    chunk = min(chunk, length)
    assert length % chunk == 0, (length, chunk)
    n_chunks = length // chunk

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    grid = (bsz, h, n_chunks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, chunk, 1), lambda ib, ih, ic: (ib, ic, ih)),
            pl.BlockSpec((1,), lambda ib, ih, ic: (ih,)),
            pl.BlockSpec((1, chunk, 1, n),
                         lambda ib, ih, ic, r=rep: (ib, ic, ih // r, 0)),
            pl.BlockSpec((1, chunk, 1, n),
                         lambda ib, ih, ic, r=rep: (ib, ic, ih // r, 0)),
            pl.BlockSpec((1,), lambda ib, ih, ic: (ih,)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, p), lambda ib, ih, ic: (ib, ic, ih, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a_log, b_mat, c_mat, d_skip)
