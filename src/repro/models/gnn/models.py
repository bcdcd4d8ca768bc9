"""The four assigned GNN architectures.

  graphsage-reddit  [arXiv:1706.02216]  2L, d=128, mean aggregator, 25-10 fanout
  gat-cora          [arXiv:1710.10903]  2L, d=8, 8 heads, attention aggregator
  gin-tu            [arXiv:1810.00826]  5L, d=64, sum aggregator, learnable eps
  dimenet           [arXiv:2003.03123]  6 blocks, d=128, bilinear=8, sph=7, rad=6

All take a Graph of padded static shapes (DESIGN.md §4): node features
(N, F), edge_index (2, E) int32 with -1 padding, optional labels / 3D
positions / triplet lists (DimeNet). Each exposes init(key, cfg) and
loss(params, cfg, graph) for the train_step, plus apply() for inference.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.models.gnn import common as C


@dataclasses.dataclass(frozen=True)
class GraphShape:
    n_nodes: int
    n_edges: int
    d_feat: int
    n_classes: int = 16
    n_triplets: int = 0  # DimeNet only
    n_graphs: int = 1  # batched molecule graphs


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    name: str
    kind: str  # graphsage | gat | gin | dimenet
    n_layers: int
    d_hidden: int
    n_heads: int = 1
    aggregator: str = "mean"
    # dimenet extras
    n_bilinear: int = 8
    n_spherical: int = 7
    n_radial: int = 6


def make_graph_inputs(shape: GraphShape, rng_seed: int = 0) -> Dict[str, jnp.ndarray]:
    """Concrete random graph (smoke tests); dry-run uses ShapeDtypeStructs
    of identical structure."""
    rng = jax.random.PRNGKey(rng_seed)
    k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
    g = {
        "x": jax.random.normal(k1, (shape.n_nodes, shape.d_feat), jnp.float32),
        "edge_src": jax.random.randint(k2, (shape.n_edges,), 0, shape.n_nodes, jnp.int32),
        "edge_dst": jax.random.randint(k3, (shape.n_edges,), 0, shape.n_nodes, jnp.int32),
        "labels": jax.random.randint(k4, (shape.n_nodes,), 0, shape.n_classes, jnp.int32),
        "label_mask": jnp.ones((shape.n_nodes,), jnp.float32),
    }
    if shape.n_triplets:
        # triplets (k->j->i): indices into the edge list
        g["trip_kj"] = jax.random.randint(k5, (shape.n_triplets,), 0, shape.n_edges, jnp.int32)
        g["trip_ji"] = jax.random.randint(k5, (shape.n_triplets,), 0, shape.n_edges, jnp.int32)
        g["pos"] = jax.random.normal(k5, (shape.n_nodes, 3), jnp.float32)
    return g


def graph_input_specs(shape: GraphShape) -> Dict[str, jax.ShapeDtypeStruct]:
    s = {
        "x": jax.ShapeDtypeStruct((shape.n_nodes, shape.d_feat), jnp.float32),
        "edge_src": jax.ShapeDtypeStruct((shape.n_edges,), jnp.int32),
        "edge_dst": jax.ShapeDtypeStruct((shape.n_edges,), jnp.int32),
        "labels": jax.ShapeDtypeStruct((shape.n_nodes,), jnp.int32),
        "label_mask": jax.ShapeDtypeStruct((shape.n_nodes,), jnp.float32),
    }
    if shape.n_triplets:
        s["trip_kj"] = jax.ShapeDtypeStruct((shape.n_triplets,), jnp.int32)
        s["trip_ji"] = jax.ShapeDtypeStruct((shape.n_triplets,), jnp.int32)
        s["pos"] = jax.ShapeDtypeStruct((shape.n_nodes, 3), jnp.float32)
    return s


# ---------------------------------------------------------------------------
# GraphSAGE (mean aggregator)
# ---------------------------------------------------------------------------


def init_graphsage(key, cfg: GNNConfig, shape: GraphShape):
    dims = [shape.d_feat] + [cfg.d_hidden] * cfg.n_layers
    layers = []
    for i in range(cfg.n_layers):
        k1, k2, key = jax.random.split(key, 3)
        layers.append(
            {"w_self": C._dense(k1, (dims[i], dims[i + 1])),
             "w_neigh": C._dense(k2, (dims[i], dims[i + 1]))}
        )
    kout, _ = jax.random.split(key)
    return {"layers": layers, "w_out": C._dense(kout, (cfg.d_hidden, shape.n_classes))}


def apply_graphsage(params, cfg: GNNConfig, g):
    x = g["x"]
    n = x.shape[0]
    for lp in params["layers"]:
        msgs = C.gather_src(x, g["edge_src"])
        agg = C.scatter_mean(msgs, g["edge_dst"], n)
        x = jax.nn.relu(x @ lp["w_self"] + agg @ lp["w_neigh"])
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)
    return x @ params["w_out"]


# ---------------------------------------------------------------------------
# GAT
# ---------------------------------------------------------------------------


def init_gat(key, cfg: GNNConfig, shape: GraphShape):
    layers = []
    d_in = shape.d_feat
    for i in range(cfg.n_layers):
        k1, k2, k3, key = jax.random.split(key, 4)
        h = cfg.n_heads if i < cfg.n_layers - 1 else 1
        d_out = cfg.d_hidden if i < cfg.n_layers - 1 else shape.n_classes
        layers.append(
            {
                "w": C._dense(k1, (d_in, h * d_out)),
                "a_src": C._dense(k2, (h, d_out)),
                "a_dst": C._dense(k3, (h, d_out)),
            }
        )
        d_in = h * d_out
    return {"layers": layers}


def apply_gat(params, cfg: GNNConfig, g):
    x = g["x"]
    n = x.shape[0]
    n_layers = len(params["layers"])
    for i, lp in enumerate(params["layers"]):
        h = lp["a_src"].shape[0]
        d_out = lp["a_src"].shape[1]
        z = (x @ lp["w"]).reshape(n, h, d_out)
        s_src = jnp.einsum("nhd,hd->nh", z, lp["a_src"])
        s_dst = jnp.einsum("nhd,hd->nh", z, lp["a_dst"])
        src, dst = g["edge_src"], g["edge_dst"]
        ssafe, dsafe = jnp.maximum(src, 0), jnp.maximum(dst, 0)
        scores = jax.nn.leaky_relu(s_src[ssafe] + s_dst[dsafe], 0.2)  # (E, H)
        alpha = C.edge_softmax(scores, dst, n)  # (E, H)
        msgs = z[ssafe] * alpha[:, :, None]  # (E, H, D)
        agg = C.scatter_sum(msgs.reshape(-1, h * d_out), dst, n).reshape(n, h, d_out)
        if i < n_layers - 1:
            x = jax.nn.elu(agg).reshape(n, h * d_out)
        else:
            x = agg.mean(axis=1)
    return x


# ---------------------------------------------------------------------------
# GIN
# ---------------------------------------------------------------------------


def init_gin(key, cfg: GNNConfig, shape: GraphShape):
    dims = [shape.d_feat] + [cfg.d_hidden] * cfg.n_layers
    layers = []
    for i in range(cfg.n_layers):
        k1, k2, key = jax.random.split(key, 3)
        layers.append(
            {
                "eps": jnp.zeros(()),  # learnable
                "w1": C._dense(k1, (dims[i], cfg.d_hidden)),
                "w2": C._dense(k2, (cfg.d_hidden, dims[i + 1])),
            }
        )
    kout, _ = jax.random.split(key)
    return {"layers": layers, "w_out": C._dense(kout, (cfg.d_hidden, shape.n_classes))}


def apply_gin(params, cfg: GNNConfig, g):
    x = g["x"]
    n = x.shape[0]
    for lp in params["layers"]:
        msgs = C.gather_src(x, g["edge_src"])
        agg = C.scatter_sum(msgs, g["edge_dst"], n)
        h = (1.0 + lp["eps"]) * x + agg
        x = jax.nn.relu(jax.nn.relu(h @ lp["w1"]) @ lp["w2"])
    return x @ params["w_out"]


# ---------------------------------------------------------------------------
# DimeNet (directional message passing; simplified basis — DESIGN.md §4)
# ---------------------------------------------------------------------------


def init_dimenet(key, cfg: GNNConfig, shape: GraphShape):
    d = cfg.d_hidden
    ks = jax.random.split(key, 6 + cfg.n_layers * 6)
    p = {
        "embed_x": C._dense(ks[0], (shape.d_feat, d)),
        "rbf_w": C._dense(ks[1], (cfg.n_radial, d)),
        "edge_mlp": C._dense(ks[2], (3 * d, d)),
        "blocks": [],
        "out_w1": C._dense(ks[3], (d, d)),
        "out_w2": C._dense(ks[4], (d, shape.n_classes)),
    }
    for b in range(cfg.n_layers):
        o = 5 + b * 6
        p["blocks"].append(
            {
                "w_kj": C._dense(ks[o], (d, d)),
                "w_sbf": C._dense(ks[o + 1], (cfg.n_spherical * cfg.n_radial, cfg.n_bilinear)),
                "w_bil": jax.random.normal(ks[o + 2], (cfg.n_bilinear, d, d)) / math.sqrt(d),
                "w_rbf": C._dense(ks[o + 3], (cfg.n_radial, d)),
                "w_upd1": C._dense(ks[o + 4], (d, d)),
                "w_upd2": C._dense(ks[o + 5], (d, d)),
            }
        )
    return p


def _bessel_rbf(dist, n_radial: int, cutoff: float = 5.0):
    """sin(n pi d/c)/d radial basis [DimeNet eq. 7]."""
    d = jnp.maximum(dist, 1e-3)[:, None]
    n = jnp.arange(1, n_radial + 1, dtype=jnp.float32)[None, :]
    return jnp.sqrt(2.0 / cutoff) * jnp.sin(n * jnp.pi * d / cutoff) / d


def _angular_sbf(angle, dist, n_spherical: int, n_radial: int, cutoff: float = 5.0):
    """Simplified spherical basis: cos(l*angle) x Bessel(d) outer products
    (exact spherical Bessel functions replaced by their leading harmonics;
    orthogonal on the same domain — documented simplification)."""
    ca = jnp.cos(angle[:, None] * jnp.arange(n_spherical, dtype=jnp.float32)[None, :])
    rb = _bessel_rbf(dist, n_radial, cutoff)  # (T, n_radial)
    return (ca[:, :, None] * rb[:, None, :]).reshape(angle.shape[0], -1)


def apply_dimenet(params, cfg: GNNConfig, g):
    node_out = dimenet_node_messages(params, cfg, g)
    h = jax.nn.silu(node_out @ params["out_w1"])
    return h @ params["out_w2"]


def dimenet_node_messages(params, cfg: GNNConfig, g):
    """Everything up to (and including) the edge→node scatter. Factored out
    so the edge-partitioned distributed path can psum the per-shard node
    partials before the output MLP (§Perf: gnn_impl='partitioned')."""
    x = g["x"] @ params["embed_x"]  # (N, d)
    pos = g["pos"]
    src, dst = g["edge_src"], g["edge_dst"]
    ssafe, dsafe = jnp.maximum(src, 0), jnp.maximum(dst, 0)
    evalid = (src >= 0)[:, None]

    dvec = pos[dsafe] - pos[ssafe]  # (E, 3)
    dist = jnp.linalg.norm(dvec + 1e-9, axis=-1)
    rbf = _bessel_rbf(dist, cfg.n_radial)  # (E, n_radial)

    m = jnp.concatenate([x[ssafe], x[dsafe], rbf @ params["rbf_w"]], axis=-1)
    m = jax.nn.silu(m @ params["edge_mlp"]) * evalid  # (E, d) edge messages

    kj, ji = jnp.maximum(g["trip_kj"], 0), jnp.maximum(g["trip_ji"], 0)
    tvalid = (g["trip_kj"] >= 0) & (g["trip_ji"] >= 0)
    # angle between edge kj and edge ji
    v1, v2 = dvec[kj], dvec[ji]
    cosang = jnp.sum(v1 * v2, -1) / jnp.maximum(
        jnp.linalg.norm(v1, axis=-1) * jnp.linalg.norm(v2, axis=-1), 1e-9
    )
    angle = jnp.arccos(jnp.clip(cosang, -1 + 1e-6, 1 - 1e-6))
    sbf = _angular_sbf(angle, dist[kj], cfg.n_spherical, cfg.n_radial)  # (T, S*R)

    n_edges = src.shape[0]
    for blk in params["blocks"]:
        # directional message passing: edge kj -> edge ji modulated by angle
        mk = jax.nn.silu(m @ blk["w_kj"])[kj]  # (T, d)
        sb = sbf @ blk["w_sbf"]  # (T, n_bilinear)
        inter = jnp.einsum("tb,bde,td->te", sb, blk["w_bil"], mk)  # (T, d)
        inter = jnp.where(tvalid[:, None], inter, 0.0)
        agg = jax.ops.segment_sum(inter, ji, num_segments=n_edges)  # (E, d)
        upd = m + jax.nn.silu((agg + rbf @ blk["w_rbf"]) @ blk["w_upd1"])
        m = jax.nn.silu(upd @ blk["w_upd2"]) * evalid

    n = x.shape[0]
    return C.scatter_sum(m, dst, n)


def dimenet_loss_partitioned(params, cfg: GNNConfig, g, mesh, axis_names):
    """Edge-partitioned DimeNet (DESIGN.md §Perf / DistDGL-style locality):

      * node features / positions / labels REPLICATED (N·F fits per device);
      * edge + triplet arrays sharded over every mesh axis, with the
        locality contract that triplet indices point into the local edge
        shard (the pipeline samples triplets per edge partition);
      * all directional message passing is shard-local — the only
        cross-device traffic is ONE psum of the (N, d_hidden) node partials
        (+ the param-grad psums AD inserts), replacing the baseline's
        all-gathers of the (E, d) edge-message tensor.
    """
    from jax.sharding import PartitionSpec as P

    edge_keys = ("edge_src", "edge_dst", "trip_kj", "trip_ji")
    rep_keys = tuple(k for k in g if k not in edge_keys)

    def local(params, g_rep, g_edge):
        gl = {**g_rep, **g_edge}
        partial = dimenet_node_messages(params, cfg, gl)
        node_out = jax.lax.psum(partial, axis_names)
        h = jax.nn.silu(node_out @ params["out_w1"])
        logits = h @ params["out_w2"]
        return C.cross_entropy_nodes(logits, gl["labels"], gl.get("label_mask"))

    shard = axis_names if len(axis_names) > 1 else axis_names[0]
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(), params),
            {k: P() for k in rep_keys},
            {k: P(shard) for k in edge_keys},
        ),
        out_specs=P(),
    )(params, {k: g[k] for k in rep_keys}, {k: g[k] for k in edge_keys})


# ---------------------------------------------------------------------------
# dispatch + loss
# ---------------------------------------------------------------------------

_INIT = {
    "graphsage": init_graphsage,
    "gat": init_gat,
    "gin": init_gin,
    "dimenet": init_dimenet,
}
_APPLY = {
    "graphsage": apply_graphsage,
    "gat": apply_gat,
    "gin": apply_gin,
    "dimenet": apply_dimenet,
}


def init(key, cfg: GNNConfig, shape: GraphShape):
    return _INIT[cfg.kind](key, cfg, shape)


def apply(params, cfg: GNNConfig, g):
    return _APPLY[cfg.kind](params, cfg, g)


def loss(params, cfg: GNNConfig, g):
    logits = apply(params, cfg, g)
    return C.cross_entropy_nodes(logits, g["labels"], g.get("label_mask"))
