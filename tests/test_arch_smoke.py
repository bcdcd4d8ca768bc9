"""Per-architecture smoke tests: REDUCED config of the same family, one
forward/train step on CPU, asserting output shapes + no NaNs (brief (f)).
The FULL configs are exercised only via the dry-run."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_smoke_mesh
from repro.launch.steps import _gnn_graph_shape, build_step
from repro.models.gnn import models as GNN
from repro.pipeline.data import recsys_batch, token_batch
from repro.train.optimizer import OptimizerConfig, init_opt_state

SMOKE_SHAPES = {
    "lm": {"train_4k": {"global_batch": 4, "seq_len": 64}},
    "gnn": {
        "full_graph_sm": {"n_nodes": 128, "n_edges": 512, "d_feat": 24,
                          "n_classes": 6},
    },
    "recsys": {"train_batch": {"batch": 64}},
}


def _smoke_arch(arch_id):
    arch = get_config(arch_id)
    shape_name, override = next(iter(SMOKE_SHAPES[arch.kind].items()))
    shapes = {shape_name: {**arch.shapes[shape_name], **override}}
    return dataclasses.replace(arch, shapes=shapes), shape_name


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_reduced_train_step(arch_id):
    arch, shape_name = _smoke_arch(arch_id)
    mesh = make_smoke_mesh()
    opt_cfg = OptimizerConfig(warmup_steps=2, total_steps=10)
    with jax.set_mesh(mesh):
        bundle = build_step(arch, shape_name, mesh, opt_cfg, use_reduced=True)
        key = jax.random.PRNGKey(0)
        reduced = arch.reduced_model
        if arch.kind == "lm":
            from repro.models.transformer import init_params

            params = init_params(reduced, key)
            d = token_batch(0, 0, 4, 64, reduced.vocab)
            args = (d["tokens"], d["labels"])
        elif arch.kind == "gnn":
            gshape = _gnn_graph_shape(arch, shape_name, reduced)
            params = GNN.init(key, reduced, gshape)
            args = (GNN.make_graph_inputs(gshape),)
        else:
            from repro.models.recsys.dcn import init_params as dcn_init

            params = dcn_init(reduced, key)
            d = recsys_batch(0, 0, 64, reduced.n_dense, reduced.n_sparse,
                             [reduced.table_rows(i) for i in range(reduced.n_sparse)])
            args = (d["dense"], d["sparse"], d["labels"])
        opt = init_opt_state(params)
        step = jax.jit(bundle.fn)
        new_params, new_opt, metrics = step(params, opt, *args)

    loss = float(metrics["loss"])
    assert np.isfinite(loss), f"{arch_id}: non-finite loss"
    assert float(metrics["grad_norm"]) > 0, f"{arch_id}: zero grads"
    assert int(new_opt["step"]) == 1
    # param tree structure and shapes preserved by the update
    jax.tree.map(lambda a, b: (_ for _ in ()).throw(AssertionError())
                 if a.shape != b.shape else None, params, new_params)
    # one leaf actually changed
    changed = jax.tree.reduce(
        lambda acc, pair: acc or bool(jnp.any(pair)),
        jax.tree.map(lambda a, b: jnp.any(a != b), params, new_params),
        False,
    )
    assert changed, f"{arch_id}: no parameter moved"


@pytest.mark.parametrize("arch_id", ["qwen3-8b", "qwen3-moe-30b-a3b"])
def test_reduced_decode_matches_prefill(arch_id):
    """Serving path consistency on reduced configs."""
    from repro.models.transformer import (
        decode_step, init_cache, init_params, prefill,
    )
    from repro.parallel.sharding import MeshAxes

    arch = get_config(arch_id)
    cfg = dataclasses.replace(arch.reduced_model, remat="none")
    if cfg.moe is not None:
        # capacity dropping is batch-size-dependent by design (GShard);
        # disable drops so prefill and decode see identical expert outputs
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0)
        )
    axes = MeshAxes()
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    logits_p, _ = prefill(params, cfg, axes, toks)
    cache = init_cache(cfg, 2, 12)
    for t in range(12):
        logits_d, cache = decode_step(
            params, cfg, axes, cache, toks[:, t : t + 1],
            jnp.full((2, 1), t, jnp.int32),
        )
    np.testing.assert_allclose(
        np.asarray(logits_p, np.float32), np.asarray(logits_d, np.float32),
        rtol=1e-3, atol=1e-3,
    )


def test_all_arch_ids_have_full_config_fields():
    for arch_id in ARCH_IDS:
        arch = get_config(arch_id)
        assert arch.shapes, arch_id
        assert arch.reduced_model is not None, arch_id
        if arch.kind == "lm":
            m = arch.model
            assert m.param_count() > 1e9, f"{arch_id} param count suspicious"


def test_assigned_configs_match_brief():
    """The exact published numbers from the assignment block."""
    q = get_config("qwen3-8b").model
    assert (q.n_layers, q.d_model, q.n_heads, q.n_kv_heads, q.d_ff, q.vocab) == (
        36, 4096, 32, 8, 12288, 151936) and q.qk_norm
    d = get_config("deepseek-7b").model
    assert (d.n_layers, d.d_model, d.n_heads, d.n_kv_heads, d.d_ff, d.vocab) == (
        30, 4096, 32, 32, 11008, 102400)
    c = get_config("command-r-plus-104b").model
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab) == (
        64, 12288, 96, 8, 33792, 256000)
    qm = get_config("qwen3-moe-30b-a3b").model
    assert (qm.n_layers, qm.d_model, qm.n_heads, qm.n_kv_heads, qm.vocab) == (
        48, 2048, 32, 4, 151936)
    assert (qm.moe.n_experts, qm.moe.top_k, qm.moe.d_expert_ff) == (128, 8, 768)
    mo = get_config("moonshot-v1-16b-a3b").model
    assert (mo.n_layers, mo.d_model, mo.n_heads, mo.n_kv_heads, mo.vocab) == (
        48, 2048, 16, 16, 163840)
    assert (mo.moe.n_experts, mo.moe.top_k, mo.moe.d_expert_ff) == (64, 6, 1408)
    gs = get_config("graphsage-reddit").model
    assert (gs.n_layers, gs.d_hidden, gs.aggregator) == (2, 128, "mean")
    dn = get_config("dimenet").model
    assert (dn.n_layers, dn.d_hidden, dn.n_bilinear, dn.n_spherical, dn.n_radial) == (
        6, 128, 8, 7, 6)
    gi = get_config("gin-tu").model
    assert (gi.n_layers, gi.d_hidden, gi.aggregator) == (5, 64, "sum")
    ga = get_config("gat-cora").model
    assert (ga.n_layers, ga.d_hidden, ga.n_heads) == (2, 8, 8)
    dc = get_config("dcn-v2").model
    assert (dc.n_dense, dc.n_sparse, dc.embed_dim, dc.n_cross_layers) == (13, 26, 16, 3)
    assert dc.mlp_dims == (1024, 1024, 512)
