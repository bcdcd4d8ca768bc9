"""BARQ as the GNN data pipeline: fanout neighbor sampling expressed as
merge-join scans over the sorted quad store, feeding GraphSAGE minibatch
training (DESIGN.md §3 — the paper's engine as a first-class framework
feature).

    PYTHONPATH=src python examples/gnn_pipeline.py --steps 30
"""

import argparse
import time

import jax
import numpy as np

from repro import compile_cache
from repro.core.storage import QuadStore
from repro.models.gnn.models import GNNConfig, GraphShape, init, loss as gnn_loss
from repro.models.gnn.sampler import BARQSampler, CSRSampler
from repro.pipeline.data import GraphPipeline, block_to_model_inputs
from repro.train.optimizer import OptimizerConfig, adamw_update, init_opt_state


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--n-nodes", type=int, default=2000)
    ap.add_argument("--sampler", choices=("barq", "csr"), default="barq")
    args = ap.parse_args()

    # synthetic power-law graph
    rng = np.random.RandomState(0)
    n = args.n_nodes
    src = rng.randint(0, n, n * 8).astype(np.int32)
    dst = (rng.pareto(1.5, n * 8) * n / 10).astype(np.int64) % n
    keep = src != dst
    edge_index = np.unique(np.stack([src[keep], dst[keep].astype(np.int32)]), axis=1)
    # labels recoverable from the id-keyed synthetic features (learnable task)
    labels = ((np.arange(n) % 977) * 5 // 977).astype(np.int32)
    print(f"graph: {n} nodes, {edge_index.shape[1]} edges")

    if args.sampler == "barq":
        store = QuadStore()
        for i in range(n):
            store.dict.encode(i)  # node ids encode as themselves
        pred = store.dict.encode(":edge")
        g = store.dict.encode(":default")
        quads = np.stack(
            [edge_index[0], np.full(edge_index.shape[1], pred, np.int32),
             edge_index[1], np.full(edge_index.shape[1], g, np.int32)], axis=1)
        store.add_encoded(quads)
        store.build()
        sampler = BARQSampler(store, ":edge", seed=0)
        print("sampler: BARQ merge-join scans over the quad store")
    else:
        sampler = CSRSampler(edge_index, n, seed=0)
        print("sampler: CSR")

    fanouts = [5, 3]
    batch_nodes = 64
    pipe = GraphPipeline(sampler, labels, n, batch_nodes, fanouts, seed=1)

    d_feat = 32
    n_total = batch_nodes * (1 + fanouts[0] + fanouts[0] * fanouts[1])
    shape = GraphShape(n_total, batch_nodes * fanouts[0] * (1 + fanouts[1]),
                       d_feat, 5)
    cfg = GNNConfig("sage", "graphsage", 2, 64)
    params = init(jax.random.PRNGKey(0), cfg, shape)
    opt = init_opt_state(params)
    opt_cfg = OptimizerConfig(lr=3e-3, warmup_steps=5, total_steps=args.steps)

    @jax.jit
    def train_step(params, opt, graph):
        l, grads = jax.value_and_grad(gnn_loss)(params, cfg, graph)
        params, opt, m = adamw_update(opt_cfg, params, grads, opt)
        return params, opt, l

    t0 = time.perf_counter()
    losses = []
    for step in range(args.steps):
        block = pipe.batch(step)
        graph = {k: jax.numpy.asarray(v) for k, v in
                 block_to_model_inputs(block, d_feat).items()}
        params, opt, l = train_step(params, opt, graph)
        losses.append(float(l))
        if step % 10 == 0:
            print(f"step {step}: loss {float(l):.4f}")
    k = max(min(10, len(losses) // 3), 1)
    first, last = np.mean(losses[:k]), np.mean(losses[-k:])
    print(f"\n{args.steps} steps in {time.perf_counter() - t0:.1f}s; "
          f"loss {first:.4f} -> {last:.4f}")
    assert last < first, "loss should decrease"
    print("training with the BARQ-backed pipeline works ✓")


if __name__ == "__main__":
    main()
