"""Mixture-of-Experts FFN with top-k routing and expert parallelism.

Two dispatch paths:

  dense:  one-hot capacity buffers on one device — the correctness oracle
          used by smoke tests and small models.
  spmd:   explicit ``shard_map`` expert parallelism — tokens stay replicated
          across the TP/EP ("model") axis (they already are, between the
          attention TP blocks); each EP rank routes all local tokens, keeps
          the ones destined to *its* expert slice, runs its experts, and the
          partial outputs are combined with one psum over the EP axis.
          Per-layer comm = |tokens_local| × d_model (same wire class as the
          TP FFN all-reduce it replaces).  See EXPERIMENTS.md §Perf for the
          all_to_all variant trade-off.

Capacity follows GShard: C = ceil(tokens·K/E · capacity_factor); overflow
tokens are dropped (their combine weight is 0), standard for dropping MoE.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.context import get_mesh_ctx
from repro.dist.sharding import Rules
from repro.models.common import dense_init

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int
    capacity_factor: float = 1.25
    aux_weight: float = 0.01
    router_dtype: Any = jnp.float32


def init_moe(key, d_model: int, cfg: MoEConfig, dtype) -> dict:
    ks = jax.random.split(key, 4)
    e, f = cfg.n_experts, cfg.d_expert
    return {
        "router": dense_init(ks[0], d_model, e, jnp.float32),
        "wi": jax.vmap(lambda k: dense_init(k, d_model, f, dtype))(
            jax.random.split(ks[1], e)),
        "wg": jax.vmap(lambda k: dense_init(k, d_model, f, dtype))(
            jax.random.split(ks[2], e)),
        "wo": jax.vmap(lambda k: dense_init(k, f, d_model, dtype))(
            jax.random.split(ks[3], e)),
    }


def _route(router_w, x2d, cfg: MoEConfig):
    """x2d: (N, d) → weights (N,K), experts (N,K), aux loss."""
    logits = (x2d.astype(cfg.router_dtype)
              @ router_w.astype(cfg.router_dtype))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # Switch load-balance loss: E · Σ_e fraction_e · prob_e
    me = probs.mean(axis=0)
    ce = jnp.zeros((cfg.n_experts,), jnp.float32).at[idx[:, 0]].add(
        1.0 / x2d.shape[0])
    aux = cfg.n_experts * jnp.sum(me * ce)
    return w.astype(x2d.dtype), idx, aux


def _positions(experts: Array, n_experts: int, capacity: int):
    """GShard k-pass positions: (N,K) slot index within each expert, and a
    keep mask for slots under capacity."""
    n, k = experts.shape
    counts = jnp.zeros((n_experts,), jnp.int32)
    pos = []
    for kk in range(k):
        onehot = jax.nn.one_hot(experts[:, kk], n_experts, dtype=jnp.int32)
        newpos = jnp.cumsum(onehot, axis=0) - 1 + counts[None, :]
        pos.append(jnp.take_along_axis(newpos, experts[:, kk][:, None],
                                       axis=1)[:, 0])
        counts = counts + onehot.sum(axis=0)
    pos = jnp.stack(pos, axis=1)                     # (N, K)
    keep = pos < capacity
    return pos, keep


def _expert_ffn(wi, wg, wo, buf):
    """buf: (E, C, d) → (E, C, d)."""
    up = jnp.einsum("ecd,edf->ecf", buf, wi)
    gate = jnp.einsum("ecd,edf->ecf", buf, wg)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up, wo)


def _dispatch_compute_combine(p, x2d, w, idx, pos, keep, e_lo, e_num,
                              capacity):
    """Scatter tokens → (E_local, C, d) buffers → FFN → combine partials."""
    n, d = x2d.shape
    k = idx.shape[1]
    local = keep & (idx >= e_lo) & (idx < e_lo + e_num)
    slot = (idx - e_lo) * capacity + pos                       # (N, K)
    flat_slot = jnp.where(local, slot, e_num * capacity)       # OOB → drop
    buf = jnp.zeros((e_num * capacity, d), x2d.dtype)
    for kk in range(k):
        buf = buf.at[flat_slot[:, kk]].set(x2d, mode="drop")
    out_buf = _expert_ffn(p["wi"], p["wg"], p["wo"],
                          buf.reshape(e_num, capacity, d))
    out_flat = out_buf.reshape(e_num * capacity, d)
    y = jnp.zeros((n, d), x2d.dtype)
    for kk in range(k):
        got = jnp.where(local[:, kk, None],
                        out_flat[jnp.minimum(flat_slot[:, kk],
                                             e_num * capacity - 1)], 0.0)
        y = y + got * w[:, kk, None]
    return y


def moe_block(p, x, cfg: MoEConfig, rules: Rules):
    """x: (B, T, d) → (y, aux_loss)."""
    b, t, d = x.shape
    ctx = get_mesh_ctx()
    if ctx is None:
        x2d = x.reshape(b * t, d)
        w, idx, aux = _route(p["router"], x2d, cfg)
        cap = int(np.ceil(b * t * cfg.top_k / cfg.n_experts
                          * cfg.capacity_factor))
        pos, keep = _positions(idx, cfg.n_experts, cap)
        y = _dispatch_compute_combine(p, x2d, w, idx, pos, keep, 0,
                                      cfg.n_experts, cap)
        return y.reshape(b, t, d), aux

    # --- explicit EP under shard_map ---------------------------------------
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    ep = mesh.shape[ctx.model_axis]
    assert cfg.n_experts % ep == 0, "experts must divide the EP axis"
    e_local = cfg.n_experts // ep
    dp = int(np.prod([mesh.shape[a] for a in ctx.batch_axes]))
    batch_axes = ctx.batch_axes if b % dp == 0 else ()  # decode batch=1
    dp = dp if batch_axes else 1
    n_local = (b // dp) * t
    cap = int(np.ceil(n_local * cfg.top_k / cfg.n_experts
                      * cfg.capacity_factor))

    def body(xl, router_w, wi, wg, wo):
        # xl: (B_l, T, d) — replicated over the model axis by construction.
        # Expert weights arrive FSDP-sharded on dim 1 over the batch axes;
        # gather per layer (re-gathered in backward under remat) — ZeRO-3.
        wi = jax.lax.all_gather(wi, ctx.batch_axes, axis=1, tiled=True)
        wg = jax.lax.all_gather(wg, ctx.batch_axes, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, ctx.batch_axes, axis=1, tiled=True)
        xl2 = xl.reshape(-1, d)
        w, idx, aux = _route(router_w, xl2, cfg)
        pos, keep = _positions(idx, cfg.n_experts, cap)
        r = jax.lax.axis_index(ctx.model_axis)
        y_part = _dispatch_compute_combine(
            {"wi": wi, "wg": wg, "wo": wo}, xl2, w, idx, pos, keep,
            r * e_local, e_local, cap)
        y = jax.lax.psum(y_part, ctx.model_axis)
        return y.reshape(xl.shape), aux[None]

    bspec = P(batch_axes, None, None)
    wspec = P(ctx.model_axis, ctx.batch_axes, None)
    # check_vma=False: the FSDP all_gather output *is* invariant over the
    # batch axes but vma inference can't statically prove it.
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(bspec, P(), wspec, wspec, wspec),
        out_specs=(bspec, P(batch_axes)), check_vma=False,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
    return y, aux.mean()
