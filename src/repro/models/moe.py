"""Mixture-of-Experts FFN block: top-k routing with capacity-based scatter
dispatch (GShard-style capacity, sort-free scatter placement).

Dispatch is the same gather/segment problem as the engine's Build/compact
kernels (DESIGN.md §4): tokens are scattered into per-expert buffers of
static capacity C = ceil(tokens*top_k/E)*cf (overflow dropped, probs
renormalized), expert FFNs run as one batched einsum over the stacked
(E, d, f) weights — sharded over the model axis (expert parallelism) —
and results scatter-add back weighted by router probabilities. A Switch-
style load-balancing auxiliary loss is returned via a side channel.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.models.layers import _dense_init
from repro.parallel.sharding import MeshAxes, constrain


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert_ff: int
    capacity_factor: float = 1.25
    n_shared_experts: int = 0  # dense always-on experts (DeepSeek-style)
    # dispatch implementation (§Perf lever):
    #   scatter  — pjit-level capacity scatter (baseline; XLA SPMD picks the
    #              collective strategy, which all-gathers tokens)
    #   ep_psum  — shard_map expert parallelism: activations are replicated
    #              across the model axis (as the TP layout already leaves
    #              them), every device dispatches ONLY into its local expert
    #              shard, combine is one psum over the model axis
    impl: str = "scatter"


def init_moe(key, d_model: int, cfg: MoEConfig) -> Dict:
    ks = jax.random.split(key, 5)
    e, f = cfg.n_experts, cfg.d_expert_ff
    p = {
        "w_router": _dense_init(ks[0], (d_model, e)),
        "experts": {
            "w_gate": _dense_init(ks[1], (e, d_model, f)),
            "w_up": _dense_init(ks[2], (e, d_model, f)),
            "w_down": _dense_init(ks[3], (e, f, d_model)),
        },
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": _dense_init(kss[0], (d_model, fs)),
            "w_up": _dense_init(kss[1], (d_model, fs)),
            "w_down": _dense_init(kss[2], (fs, d_model)),
        }
    return p


def moe_block(p, cfg: MoEConfig, axes: MeshAxes, x: jax.Array) -> jax.Array:
    if cfg.impl == "ep_psum":
        return _moe_block_ep_psum(p, cfg, axes, x)
    return _moe_block_scatter(p, cfg, axes, x)


def _moe_block_scatter(p, cfg: MoEConfig, axes: MeshAxes, x: jax.Array) -> jax.Array:
    """x: (b, s, d) -> (b, s, d)."""
    b, s, d = x.shape
    n = b * s
    e, k = cfg.n_experts, cfg.top_k
    cap = int(math.ceil(n * k / e * cfg.capacity_factor))

    xt = x.reshape(n, d)
    router_logits = (xt @ p["w_router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(router_logits, axis=-1)  # (n, e)
    top_p, top_e = jax.lax.top_k(probs, k)  # (n, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # flatten assignments and compute slot within each expert's buffer via
    # sort-based ranking (O(nk log nk) memory-lean; the cumulative-one-hot
    # alternative materializes an (nk, E) matrix)
    flat_e = top_e.reshape(-1)  # (n*k,)
    nk = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(e, dtype=sorted_e.dtype))
    rank_sorted = jnp.arange(nk, dtype=jnp.int32) - start[sorted_e].astype(jnp.int32)
    slot = jnp.zeros((nk,), jnp.int32).at[order].set(rank_sorted)
    keep = slot < cap

    token_idx = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    safe_e = jnp.where(keep, flat_e, 0)
    safe_slot = jnp.where(keep, slot, cap - 1)

    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[safe_e, safe_slot].set(
        jnp.where(keep[:, None], xt[token_idx], 0), mode="drop"
    )
    buf = constrain(buf, axes, "mp", None, None)  # expert-parallel

    we = p["experts"]
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, we["w_gate"].astype(x.dtype)))
    u = jnp.einsum("ecd,edf->ecf", buf, we["w_up"].astype(x.dtype))
    y = jnp.einsum("ecf,efd->ecd", g * u, we["w_down"].astype(x.dtype))
    y = constrain(y, axes, "mp", None, None)

    # combine: gather each assignment's expert output, weight by router prob
    out_flat = y[safe_e, safe_slot]  # (n*k, d)
    w = jnp.where(keep, top_p.reshape(-1), 0.0).astype(x.dtype)
    out = jax.ops.segment_sum(out_flat * w[:, None], token_idx, num_segments=n)

    if cfg.n_shared_experts:
        sh = p["shared"]
        gs = jax.nn.silu(xt @ sh["w_gate"].astype(x.dtype))
        us = xt @ sh["w_up"].astype(x.dtype)
        out = out + (gs * us) @ sh["w_down"].astype(x.dtype)

    return out.reshape(b, s, d)


def _dispatch_local(xt, probs, cfg: MoEConfig, we_local, my_shard, n_shards):
    """Per-device expert-parallel dispatch: tokens are fully visible
    (replicated over the model axis); only assignments routed to this
    device's expert shard are materialized and computed. Returns the
    partial output (n, d) — summing partials over shards (psum) yields the
    full MoE output because expert shards are disjoint."""
    n, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k
    e_local = e // n_shards
    cap = int(math.ceil(n * k / e * cfg.capacity_factor))

    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    flat_e = top_e.reshape(-1)
    nk = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(e, dtype=sorted_e.dtype))
    rank_sorted = jnp.arange(nk, dtype=jnp.int32) - start[sorted_e].astype(jnp.int32)
    slot = jnp.zeros((nk,), jnp.int32).at[order].set(rank_sorted)

    local_e = flat_e - my_shard * e_local
    mine = (local_e >= 0) & (local_e < e_local) & (slot < cap)
    token_idx = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    safe_e = jnp.where(mine, local_e, 0)
    safe_slot = jnp.where(mine, slot, cap - 1)

    buf = jnp.zeros((e_local, cap, d), xt.dtype)
    buf = buf.at[safe_e, safe_slot].set(
        jnp.where(mine[:, None], xt[token_idx], 0), mode="drop"
    )
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, we_local["w_gate"].astype(xt.dtype)))
    u = jnp.einsum("ecd,edf->ecf", buf, we_local["w_up"].astype(xt.dtype))
    y = jnp.einsum("ecf,efd->ecd", g * u, we_local["w_down"].astype(xt.dtype))

    out_flat = y[safe_e, safe_slot]
    w = jnp.where(mine, top_p.reshape(-1), 0.0).astype(xt.dtype)
    return jax.ops.segment_sum(out_flat * w[:, None], token_idx, num_segments=n)


def _moe_block_ep_psum(p, cfg: MoEConfig, axes: MeshAxes, x: jax.Array) -> jax.Array:
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or axes.mp not in mesh.shape:
        # no mesh (smoke tests): single-shard path, numerically identical
        xt = x.reshape(b * s, d)
        probs = jax.nn.softmax(
            (xt @ p["w_router"].astype(x.dtype)).astype(jnp.float32), axis=-1
        )
        out = _dispatch_local(xt, probs, cfg, p["experts"], 0, 1)
        if cfg.n_shared_experts:
            out = out + _shared(p, xt)
        return out.reshape(b, s, d)

    n_shards = mesh.shape[axes.mp]
    dp_axes = tuple(a for a in axes.dp if a in mesh.shape)

    def local(xt, router_w, experts_local):
        probs = jax.nn.softmax(
            (xt @ router_w.astype(xt.dtype)).astype(jnp.float32), axis=-1
        )
        my = jax.lax.axis_index(axes.mp)
        partial = _dispatch_local(xt, probs, cfg, experts_local, my, n_shards)
        return jax.lax.psum(partial, axes.mp)

    xt = x.reshape(b * s, d)
    dp_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    out = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(dp_spec, None), P(None, None), P(axes.mp, None, None)),
        out_specs=P(dp_spec, None),
    )(xt, p["w_router"], p["experts"])
    if cfg.n_shared_experts:
        out = out + _shared(p, xt)
    return out.reshape(b, s, d)


def _shared(p, xt):
    sh = p["shared"]
    gs = jax.nn.silu(xt @ sh["w_gate"].astype(xt.dtype))
    us = xt @ sh["w_up"].astype(xt.dtype)
    return (gs * us) @ sh["w_down"].astype(xt.dtype)


def load_balance_loss(router_probs: jax.Array, top_e: jax.Array, n_experts: int):
    """Switch-transformer aux loss: E * sum_e f_e * P_e."""
    me = jnp.mean(jax.nn.one_hot(top_e[..., 0], n_experts), axis=0)
    pe = jnp.mean(router_probs, axis=0)
    return n_experts * jnp.sum(me * pe)
