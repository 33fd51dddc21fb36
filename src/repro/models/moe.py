"""Mixture-of-Experts block: top-k router, shared experts, and two dispatch
paths:

* ``dense``  — loop-over-experts masked compute, exact, used for CPU smoke
  tests and as the correctness oracle for the sharded path;
* ``a2a``    — production expert parallelism via shard_map +
  jax.lax.all_to_all over the 'model' mesh axis: tokens are sharded over
  every mesh axis, experts over 'model'; each device scatters its tokens
  into per-expert capacity bins, all-to-alls them to the owning expert
  shard, runs the expert MLPs as one batched matmul, and reverses the
  exchange.  Capacity overflow drops (standard Switch-style), with the
  capacity factor in the config.

The expert weights carry logical axes (expert -> model, ff -> fsdp), so the
optimizer state is fully sharded; the forward all-gathers the ff shards
(ZeRO-3) inside the shard_map body.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..configs.base import ArchConfig
from .common import Boxed, box, truncated_normal_init
from .layers import init_mlp, apply_mlp, rms_norm

__all__ = ["init_moe", "apply_moe", "router_topk", "moe_aux_loss"]


def init_moe(cfg: ArchConfig, key):
    moe = cfg.moe
    m, f, e = cfg.d_model, moe.d_ff_expert, moe.n_experts
    ks = jax.random.split(key, 6)
    emb_ax = "fsdp" if cfg.fsdp else None
    dt = cfg.param_dtype
    p = {
        "norm": box(jnp.ones((m,), dt), (None,)),
        "router": box(truncated_normal_init(ks[0], (m, e), dt), (None, None)),
        "w_gate": box(truncated_normal_init(ks[1], (e, m, f), dt,
                                            fan_in_dims=(1,)),
                      ("expert", None, "expert_ff")),
        "w_up": box(truncated_normal_init(ks[2], (e, m, f), dt, fan_in_dims=(1,)),
                    ("expert", None, "expert_ff")),
        "w_down": box(truncated_normal_init(ks[3], (e, f, m), dt, fan_in_dims=(1,)),
                      ("expert", "expert_ff", None)),
    }
    if moe.n_shared:
        shared_cfg = cfg.replace(mlp_act="silu_glu")
        p["shared"] = init_mlp(shared_cfg, ks[4], d_ff=moe.d_ff_expert * moe.n_shared)
    return p


def router_topk(cfg: ArchConfig, logits):
    """Top-k gating with renormalized weights. logits: (T, E)."""
    k = cfg.moe.top_k
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, k)  # (T, k)
    top_w = top_w / jnp.clip(top_w.sum(-1, keepdims=True), 1e-9)
    return probs, top_w, top_idx


def moe_aux_loss(probs, top_idx, n_experts: int):
    """Switch-style load-balancing loss: E * Σ_e f_e · p_e."""
    t = probs.shape[0]
    assign = jax.nn.one_hot(top_idx[:, 0], n_experts, dtype=jnp.float32)
    f_e = assign.mean(0)
    p_e = probs.mean(0)
    return n_experts * jnp.sum(f_e * p_e)


def _expert_mlp(x, w_gate, w_up, w_down):
    """x: (E, C, M) batched per-expert MLP (fp32 operands, baseline)."""
    h = jax.nn.silu(jnp.einsum("ecm,emf->ecf", x, w_gate)) \
        * jnp.einsum("ecm,emf->ecf", x, w_up)
    return jnp.einsum("ecf,efm->ecm", h, w_down)


def _expert_mlp_any(x, w_gate, w_up, w_down):
    """Dispatch on the bf16_experts perf flag: bf16 operand streams with
    fp32 MXU accumulation instead of materialized fp32 casts of the
    (all-gathered) expert weights — halves the dominant byte stream."""
    from ..perf import flags
    if not flags().bf16_experts:
        return _expert_mlp(x.astype(jnp.float32), w_gate.astype(jnp.float32),
                           w_up.astype(jnp.float32),
                           w_down.astype(jnp.float32))
    dt = jnp.bfloat16
    xe = x.astype(dt)
    g = jnp.einsum("ecm,emf->ecf", xe, w_gate.astype(dt),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecm,emf->ecf", xe, w_up.astype(dt),
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(dt)
    return jnp.einsum("ecf,efm->ecm", h, w_down.astype(dt),
                      preferred_element_type=jnp.float32)


def _dense_path(cfg, p, x2d, top_w, top_idx):
    """Oracle path: per-expert masked compute (small configs only)."""
    moe = cfg.moe
    out = jnp.zeros_like(x2d)
    for e in range(moe.n_experts):
        w = ((top_idx == e).astype(x2d.dtype) * top_w.astype(x2d.dtype)).sum(-1)  # (T,)
        h = jax.nn.silu(x2d @ p["w_gate"][e].astype(x2d.dtype)) \
            * (x2d @ p["w_up"][e].astype(x2d.dtype))
        out = out + (h @ p["w_down"][e].astype(x2d.dtype)) * w[:, None]
    return out


def _a2a_body(x_loc, wi, wg, wu, wd, *, cfg: ArchConfig, capacity: int,
              model_axis: str, gather_axes: tuple, all_axes: tuple,
              e_pad: int | None = None):
    """shard_map body. x_loc: (t_loc, M) local tokens; wi: router (M, E);
    wg/wu/wd: local expert shards (E_loc, M, F_loc).  When n_experts does
    not divide the EP axis, callers zero-pad the expert dim to ``e_pad``
    and the router logits are -inf-padded so no token routes to a pad."""
    moe = cfg.moe
    e_total = e_pad or moe.n_experts
    t_loc, m = x_loc.shape
    if gather_axes:
        wg = jax.lax.all_gather(wg, gather_axes, axis=2, tiled=True)
        wu = jax.lax.all_gather(wu, gather_axes, axis=2, tiled=True)
        wd = jax.lax.all_gather(wd, gather_axes, axis=1, tiled=True)

    logits = x_loc @ wi.astype(x_loc.dtype)          # (t_loc, n_experts)
    probs, top_w, top_idx = router_topk(cfg, logits)  # over REAL experts

    # scatter tokens into (E, C, M) send bins; overflow beyond C drops
    flat_e = top_idx.reshape(-1)                     # (t_loc*k,)
    flat_w = top_w.reshape(-1).astype(x_loc.dtype)
    flat_t = jnp.repeat(jnp.arange(t_loc), moe.top_k)
    onehot = jax.nn.one_hot(flat_e, e_total, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) * onehot - 1   # (t_loc*k, E)
    slot = (pos_in_e * onehot).sum(-1)                   # position within expert
    keep = slot < capacity
    send = jnp.zeros((e_total, capacity, m), x_loc.dtype)
    send = send.at[flat_e, jnp.where(keep, slot, 0)].add(
        jnp.where(keep, 1.0, 0.0)[:, None] * x_loc[flat_t])

    # exchange over the model axis: (E, C, M) -> (E_loc, C*mp, M)
    recv = jax.lax.all_to_all(send, model_axis, split_axis=0, concat_axis=1,
                              tiled=True)
    y = _expert_mlp_any(recv, wg, wu, wd).astype(x_loc.dtype)
    back = jax.lax.all_to_all(y, model_axis, split_axis=1, concat_axis=0,
                              tiled=True)                # (E, C, M)

    # combine: weighted gather back to tokens
    gathered = back[flat_e, jnp.where(keep, slot, 0)]    # (t_loc*k, M)
    gathered = gathered * (flat_w * keep.astype(flat_w.dtype))[:, None]
    out = jnp.zeros_like(x_loc).at[flat_t].add(gathered)

    # global Switch balance loss: pmean the per-expert factors BEFORE the
    # product (a per-device product of local means would depend on how
    # tokens happen to be grouped across devices)
    assign = jax.nn.one_hot(top_idx[:, 0], moe.n_experts, dtype=jnp.float32)
    f_e = jax.lax.pmean(assign.mean(0), all_axes)
    p_e = jax.lax.pmean(probs.mean(0), all_axes)
    aux = moe.n_experts * jnp.sum(f_e * p_e)
    return out, aux


def _global_scatter_path(cfg: ArchConfig, p, x2d):
    """Scatter-dispatch in pjit-land (no shard_map): build (E, C, M) bins
    globally and let GSPMD place them on the expert-sharded mesh axis.
    Used for decode-scale token counts where per-device sharding of the
    token dim is impossible."""
    moe = cfg.moe
    t, m = x2d.shape
    logits = x2d @ p["router"].astype(x2d.dtype)
    probs, top_w, top_idx = router_topk(cfg, logits)
    capacity = max(1, int(np.ceil(t * moe.top_k / moe.n_experts
                                  * moe.capacity_factor)))
    flat_e = top_idx.reshape(-1)
    flat_w = top_w.reshape(-1).astype(x2d.dtype)
    flat_t = jnp.repeat(jnp.arange(t), moe.top_k)
    onehot = jax.nn.one_hot(flat_e, moe.n_experts, dtype=jnp.int32)
    slot = (jnp.cumsum(onehot, axis=0) * onehot - 1).max(-1)
    keep = (slot >= 0) & (slot < capacity)
    slot = jnp.where(keep, slot, 0)
    send = jnp.zeros((moe.n_experts, capacity, m), x2d.dtype)
    send = send.at[flat_e, slot].add(keep.astype(x2d.dtype)[:, None] * x2d[flat_t])
    y = _expert_mlp_any(send, p["w_gate"], p["w_up"],
                        p["w_down"]).astype(x2d.dtype)
    gathered = y[flat_e, slot] * (flat_w * keep.astype(flat_w.dtype))[:, None]
    out = jnp.zeros_like(x2d).at[flat_t].add(gathered)
    return out, moe_aux_loss(probs, top_idx, moe.n_experts)


def apply_moe(cfg: ArchConfig, p, x, *, mesh: Mesh | None = None,
              impl: str = "auto"):
    """x: (B, S, M) -> (y, aux_loss)."""
    moe = cfg.moe
    b, s, m = x.shape
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    x2d = h.reshape(b * s, m)

    n_dev = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    use_a2a = (impl in ("a2a", "auto") and mesh is not None
               and "model" in mesh.axis_names and n_dev > 1
               and (b * s) % n_dev == 0)
    if use_a2a:
        from ..perf import flags
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        all_axes = tuple(mesh.axis_names)
        gather_axes = tuple(a for a in all_axes if a != "model" and sizes[a] > 1)
        batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
        nb = int(np.prod([sizes[a] for a in batch_axes])) if batch_axes else 1
        t_loc = (b * s) // n_dev
        capacity = max(1, int(np.ceil(t_loc * moe.top_k / moe.n_experts
                                      * moe.capacity_factor)))
        ep = sizes["model"]
        e_pad = -(-moe.n_experts // ep) * ep  # next multiple of the EP axis
        wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
        if e_pad != moe.n_experts:
            # zero-pad dead expert slots (never routed: top_k only sees the
            # real logits); keeps EP for e.g. 40 experts on a 16-way axis
            padw = ((0, e_pad - moe.n_experts), (0, 0), (0, 0))
            wg, wu, wd = (jnp.pad(w, padw) for w in (wg, wu, wd))
        body = functools.partial(_a2a_body, cfg=cfg, capacity=capacity,
                                 model_axis="model", gather_axes=gather_axes,
                                 all_axes=all_axes, e_pad=e_pad)
        weight_specs = (P(None, None),
                        P("model", None, gather_axes or None),
                        P("model", None, gather_axes or None),
                        P("model", gather_axes or None, None))
        use_3d = (flags().moe_3d and b % nb == 0 and s % ep == 0)
        if use_3d:
            # §Perf moe_3d: enter shard_map in the residual's NATIVE layout
            # (batch->dp, seq->model) and flatten per-device INSIDE the body.
            # The 2D baseline's (B·S, M) flatten has no efficient SPMD
            # transition from that layout, so GSPMD replicates the full
            # activation ('involuntary full rematerialization': a 28 GiB
            # fp32 all-gather per MoE layer on deepseek train_4k).
            def body3d(x3, wi_, wg_, wu_, wd_):
                bl, sl, m_ = x3.shape
                out, aux = body(x3.reshape(bl * sl, m_), wi_, wg_, wu_, wd_)
                return out.reshape(bl, sl, m_), aux
            tok3 = P(batch_axes or None, "model", None)
            out3d, aux = jax.shard_map(
                body3d, mesh=mesh, in_specs=(tok3, *weight_specs),
                out_specs=(tok3, P()),
            )(h, p["router"], wg, wu, wd)
            out2d = None  # stay 3D end-to-end (no flatten round-trip)
        else:
            tok_spec = P(all_axes)  # tokens sharded over every axis
            out2d, aux = jax.shard_map(
                body, mesh=mesh,
                in_specs=(tok_spec, *weight_specs),
                out_specs=(tok_spec, P()),
            )(x2d, p["router"], wg, wu, wd)
    elif impl != "dense" and mesh is not None and n_dev > 1:
        # global scatter-dispatch path (decode-sized batches): no shard_map,
        # GSPMD shards the (E, C, M) bins over the model axis.
        out2d, aux = _global_scatter_path(cfg, p, x2d)
    else:
        logits = x2d @ p["router"].astype(x2d.dtype)
        probs, top_w, top_idx = router_topk(cfg, logits)
        out2d = _dense_path(cfg, p, x2d, top_w, top_idx)
        aux = moe_aux_loss(probs, top_idx, moe.n_experts)

    y = out3d if out2d is None else out2d.reshape(b, s, m)
    if "shared" in p:
        y = y + apply_mlp(cfg.replace(mlp_act="silu_glu"), p["shared"], h,
                          skip_norm=True)
    return y, aux * moe.router_aux_weight
