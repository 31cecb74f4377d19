"""End-to-end fault-tolerant training (deliverable (b) driver).

Trains an internlm2-family model on a LOG.io-protected data pipeline with
checkpoint write actions, kills a pipeline worker AND the trainer mid-run,
and verifies the run resumes bit-identically from the last checkpoint.

CPU demo (reduced model, ~2 min):
    PYTHONPATH=src python examples/train_e2e.py
Larger (~100M params, float32 — slow on CPU):
    PYTHONPATH=src python examples/train_e2e.py --big --steps 300
The same A/B check at full internlm2-1.8b width on one TPU chip is
``chip_smoke.py`` at the repository root.
"""
import argparse
import shutil
import tempfile

import numpy as np

import jax

from repro.launch.train import run_training


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--big", action="store_true",
                    help="~100M params (d_model=768, 12 layers)")
    args = ap.parse_args()
    dim, layers = (768, 12) if args.big else (128, 2)

    dir_a = tempfile.mkdtemp(prefix="logio_ta_")
    dir_b = tempfile.mkdtemp(prefix="logio_tb_")
    try:
        print("== run A: failure-free ==")
        a = run_training(steps=args.steps, ckpt_every=6, seq_len=64,
                         batch_size=4, ckpt_dir=dir_a, d_model=dim,
                         n_layers=layers, seed=7, log_every=6)
        print("\n== run B: kill a pipeline worker at ~batch 4 and the "
              "trainer at step {} ==".format(args.steps * 2 // 3))
        b = run_training(steps=args.steps, ckpt_every=6, seq_len=64,
                         batch_size=4, ckpt_dir=dir_b, d_model=dim,
                         n_layers=layers, seed=7, log_every=6,
                         kill_worker_at=4,
                         kill_trainer_at=args.steps * 2 // 3)
        same = all(np.allclose(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree.leaves(a["final_state"]),
                                   jax.tree.leaves(b["final_state"])))
        print(f"\npipeline failures in B: {b['engine'].failures}; "
              f"final states identical: {same}")
        assert same, "resume was not bit-identical!"
        print("OK: crash-recovery resumed the exact trajectory.")
    finally:
        shutil.rmtree(dir_a, ignore_errors=True)
        shutil.rmtree(dir_b, ignore_errors=True)


if __name__ == "__main__":
    main()
