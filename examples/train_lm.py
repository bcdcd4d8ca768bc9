"""Train a reduced LM config end-to-end with the production substrate
(jitted train step, AdamW, async checkpointing, watchdog), including a
mid-run restart to demonstrate checkpoint/resume fault tolerance.

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""

import argparse
import logging
import shutil
import tempfile

from repro import compile_cache
from repro.launch.train import run


def main():
    compile_cache.enable()
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args()

    ckpt_dir = tempfile.mkdtemp(prefix="repro_lm_")
    override = {"global_batch": 8, "seq_len": 128}
    try:
        # phase 1: train the first half, then 'lose the job'
        half = args.steps // 2
        result1, t1 = run(args.arch, "train_4k", half, ckpt_dir,
                          override_shape=override)
        print(f"\nphase 1 done at step {result1['step']} "
              f"(loss {result1['loss']:.4f}); simulating preemption...\n")

        # phase 2: a fresh trainer resumes from the checkpoint
        result2, t2 = run(args.arch, "train_4k", args.steps, ckpt_dir,
                          override_shape=override)
        assert t2.metrics_history[0]["step"] == half + 1, "did not resume!"
        losses = [m["loss"] for m in t1.metrics_history + t2.metrics_history]
        print(f"\nresumed at step {half + 1} ✓")
        print(f"loss: start={losses[0]:.4f} mid={losses[half - 1]:.4f} "
              f"final={losses[-1]:.4f}")
        assert losses[-1] < losses[0], "loss did not improve"
        print("loss improved over training ✓")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
