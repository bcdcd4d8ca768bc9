"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs REDUCED configs end-to-end on the local device(s) (CPU here) with the
full production substrate: jitted train step, AdamW, async checkpointing,
restart/resume, watchdog. The FULL configs are exercised via the dry-run
(-m repro.launch.dryrun); on a real fleet this same launcher runs them by
pointing --mesh at the production mesh.
"""

from __future__ import annotations

import argparse
import logging

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_smoke_mesh
from repro.launch.steps import build_step, _gnn_graph_shape
from repro.models.gnn import models as GNN
from repro.pipeline.data import recsys_batch, token_batch
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.trainer import Trainer, TrainerConfig


def _make_batch_fn(arch, shape_name, bundle, seed, reduced_model):
    sh = arch.shapes[shape_name]
    if arch.kind == "lm":
        b, s = sh["global_batch"], sh["seq_len"]
        vocab = reduced_model.vocab

        def fn(step):
            d = token_batch(seed, step, b, s, vocab)
            return (d["tokens"], d["labels"])

        return fn
    if arch.kind == "gnn":
        gshape = _gnn_graph_shape(arch, shape_name, reduced_model)

        def fn(step):
            g = GNN.make_graph_inputs(gshape, rng_seed=seed + step)
            return (g,)

        return fn
    # recsys
    b = sh["batch"]
    cfg = reduced_model

    def fn(step):
        d = recsys_batch(seed, step, b, cfg.n_dense, cfg.n_sparse,
                         [cfg.table_rows(i) for i in range(cfg.n_sparse)])
        return (d["dense"], d["sparse"], d["labels"])

    return fn


def run(arch_id: str, shape_name: str, steps: int, ckpt_dir: str,
        seed: int = 0, lr: float = 3e-4, log_every: int = 10,
        override_shape: dict = None):
    arch = get_config(arch_id)
    if override_shape:
        shapes = dict(arch.shapes)
        shapes[shape_name] = {**shapes[shape_name], **override_shape}
        import dataclasses as _dc

        arch = _dc.replace(arch, shapes=shapes)
    mesh = make_smoke_mesh()
    opt_cfg = OptimizerConfig(lr=lr, warmup_steps=max(steps // 20, 5),
                              total_steps=steps)
    with jax.set_mesh(mesh):
        bundle = build_step(arch, shape_name, mesh, opt_cfg, use_reduced=True)
        step_jit = jax.jit(bundle.fn, donate_argnums=bundle.donate_argnums)

        reduced = arch.reduced_model
        batch_fn = _make_batch_fn(arch, shape_name, bundle, seed, reduced)

        def init_state():
            if arch.kind == "lm":
                from repro.models.transformer import init_params

                params = init_params(reduced, jax.random.PRNGKey(seed))
            elif arch.kind == "gnn":
                gshape = _gnn_graph_shape(arch, shape_name, reduced)
                params = GNN.init(jax.random.PRNGKey(seed), reduced, gshape)
            else:
                from repro.models.recsys.dcn import init_params as dcn_init

                params = dcn_init(reduced, jax.random.PRNGKey(seed))
            return (params, init_opt_state(params))

        def train_step(state, batch):
            params, opt = state
            out = step_jit(params, opt, *batch)
            params, opt, metrics = out
            return (params, opt), metrics

        trainer = Trainer(
            TrainerConfig(total_steps=steps, ckpt_every=max(steps // 4, 10),
                          ckpt_dir=ckpt_dir, log_every=log_every),
            train_step,
            init_state,
            batch_fn,
        )
        return trainer.run(), trainer


def main():
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    arch = get_config(args.arch)
    shape = args.shape or next(
        s for s, v in arch.shapes.items()
        if v["step"] in ("train", "gnn_full", "gnn_minibatch", "gnn_molecule",
                         "recsys_train")
    )
    # keep CPU smoke training tractable
    override = None
    if arch.kind == "lm":
        override = {"global_batch": 8, "seq_len": 128}
    elif arch.kind == "recsys":
        override = {"batch": 256}
    elif arch.shapes[shape]["step"] == "gnn_full":
        override = {"n_nodes": 512, "n_edges": 2048, "d_feat": 32, "n_classes": 8}
    elif arch.shapes[shape]["step"] == "gnn_minibatch":
        override = {"batch_nodes": 32, "fanouts": (5, 3), "d_feat": 32,
                    "n_classes": 8}
    elif arch.shapes[shape]["step"] == "gnn_molecule":
        override = {"batch": 8}
    result, trainer = run(args.arch, shape, args.steps, args.ckpt_dir,
                          args.seed, args.lr)
    print("final:", result)
    losses = [m["loss"] for m in trainer.metrics_history]
    print(f"loss: first={losses[0]:.4f} last={losses[-1]:.4f}")


if __name__ == "__main__":
    main()
