"""Pallas TPU flash attention (causal / sliding-window / GQA), forward AND
backward.

Forward: grid (batch, q_heads, q_blocks, kv_blocks); the kv dimension is
innermost with 'arbitrary' semantics so the fp32 (acc, m, l) VMEM scratch
carries the online-softmax state across kv blocks; also emits the per-row
logsumexp for the backward.  Blocks are MXU-aligned (128) by default.
Fully-masked (q_block, kv_block) tiles are skipped with pl.when.

Backward (FlashAttention-2 recompute scheme, no (Sq, Skv) materialization):
  D  = rowsum(dO ∘ O)                     (jnp preprocess)
  dq : grid (b, hq, n_q, n_kv), kv innermost, dq accumulated in VMEM
  dkv: grid (b, hq, n_kv, n_q), q innermost, dk/dv accumulated in VMEM,
       per-q-head results group-summed to the kv heads outside the kernel.

Validated in interpret mode against kernels.ref.attention_ref (values AND
vjp cotangents) over a shape/dtype sweep (tests/test_kernels.py); TPU is
the compile target.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _tile_mask(q_start, k_start, *, causal, window, block_q, block_k):
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= kpos > qpos - window
    return mask


def _tile_live(q_start, k_start, *, causal, window, block_q, block_k):
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + block_q - 1
    if window is not None:
        live &= k_start + block_k - 1 > q_start - window
    return live


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale, causal, window, q_offset, block_q, block_k, n_kv):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q + q_offset
    k_start = ik * block_k

    @pl.when(_tile_live(q_start, k_start, causal=causal, window=window,
                        block_q=block_q, block_k=block_k))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _tile_mask(q_start, k_start, causal=causal, window=window,
                          block_q=block_q, block_k=block_k)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == n_kv - 1)
    def _finish():
        l = l_ref[...]
        o = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = o.astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(jnp.maximum(l, 1e-30)))

    del iq


def _fwd(q, k, v, *, causal, window, q_offset, scale, block_q, block_k,
         interpret):
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    n_q, n_kv = sq // block_q, skv // block_k
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_k=block_k, n_kv=n_kv)
    grid = (b, hq, n_q, n_kv)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, dq_ref,
               dq_acc, *, scale, causal, window, q_offset, block_q, block_k,
               n_kv):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = iq * block_q + q_offset
    k_start = ik * block_k

    @pl.when(_tile_live(q_start, k_start, causal=causal, window=window,
                        block_q=block_q, block_k=block_k))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)       # (bq, d)
        lse = lse_ref[0, 0]                          # (bq, 1)
        dsum = dsum_ref[0, 0]                        # (bq, 1)
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _tile_mask(q_start, k_start, causal=causal, window=window,
                          block_q=block_q, block_k=block_k)
        s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)                         # masked entries -> 0
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dsum)                         # (bq, bk)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ik == n_kv - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, window,
                q_offset, block_q, block_k, n_q):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = iq * block_q + q_offset
    k_start = ik * block_k

    @pl.when(_tile_live(q_start, k_start, causal=causal, window=window,
                        block_q=block_q, block_k=block_k))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        dsum = dsum_ref[0, 0]
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _tile_mask(q_start, k_start, causal=causal, window=window,
                          block_q=block_q, block_k=block_k)
        s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)                         # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # (bk, d)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dsum)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bk, d)

    @pl.when(iq == n_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_impl(q, k, v, o, lse, do, *, causal, window, q_offset, scale,
              block_q, block_k, interpret):
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    n_q, n_kv = sq // block_q, skv // block_k
    dsum = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1,
                                                                keepdims=True)

    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    kv_spec_q = pl.BlockSpec((1, 1, block_k, d),
                             lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, q_offset=q_offset, block_q=block_q,
                          block_k=block_k, n_kv=n_kv),
        grid=(b, hq, n_q, n_kv),
        in_specs=[q_spec, kv_spec_q, kv_spec_q, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, dsum)

    # dk/dv: q innermost; per-q-head partials, group-summed outside
    q_spec2 = pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, ik, iq: (ib, ih, iq, 0))
    kv_spec2 = pl.BlockSpec((1, 1, block_k, d),
                            lambda ib, ih, ik, iq, g=group: (ib, ih // g, ik, 0))
    row_spec2 = pl.BlockSpec((1, 1, block_q, 1), lambda ib, ih, ik, iq: (ib, ih, iq, 0))
    out_kv2 = pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, ik, iq: (ib, ih, ik, 0))
    dkh, dvh = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, q_offset=q_offset, block_q=block_q,
                          block_k=block_k, n_q=n_q),
        grid=(b, hq, n_kv, n_q),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[out_kv2, out_kv2],
        out_shape=[jax.ShapeDtypeStruct((b, hq, skv, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, hq, skv, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, dsum)
    dk = dkh.reshape(b, hkv, group, skv, d).sum(2).astype(k.dtype)
    dv = dvh.reshape(b, hkv, group, skv, d).sum(2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public entry point
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, window, q_offset, scale, block_q, block_k,
           interpret):
    o, _ = _fwd(q, k, v, causal=causal, window=window, q_offset=q_offset,
                scale=scale, block_q=block_q, block_k=block_k,
                interpret=interpret)
    return o


def _flash_fwd(q, k, v, causal, window, q_offset, scale, block_q, block_k,
               interpret):
    o, lse = _fwd(q, k, v, causal=causal, window=window, q_offset=q_offset,
                  scale=scale, block_q=block_q, block_k=block_k,
                  interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, q_offset, scale, block_q, block_k, interpret,
               res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _bwd_impl(q, k, v, o, lse, do, causal=causal, window=window,
                           q_offset=q_offset, scale=scale, block_q=block_q,
                           block_k=block_k, interpret=interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "scale", "block_q",
                     "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    q_offset: int = 0, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D).  Returns (B, Hq, Sq, D).
    Differentiable: the backward is the two-kernel FlashAttention-2
    recompute scheme above (no (Sq, Skv) tensor ever leaves VMEM)."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    scale = scale if scale is not None else d**-0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0, (sq, block_q, skv, block_k)
    return _flash(q, k, v, causal, window, q_offset, scale, block_q, block_k,
                  interpret)
