"""End-to-end LM training driver (deliverable b): train a ~100M-param dense
model for a few hundred steps on synthetic data, with checkpoints.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

(A ~100M config is built by scaling llama3.2 down; on the production mesh
the same launcher trains the full assigned configs — launch/train.py.)
"""
import argparse
import dataclasses
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np

from repro.configs import registry
from repro.core import runtime as RT
from repro.launch import train as LT
from repro.models import transformer as T


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()

    # ~100M-parameter llama-style config
    base = registry.get_config("llama3.2-3b")
    cfg = dataclasses.replace(
        base, n_layers=6, d_model=512, n_heads=8, n_kv_heads=4, d_ff=2048,
        vocab=32768, param_dtype="float32", compute_dtype="float32")
    shapes = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(shapes))
    print(f"training a {n / 1e6:.0f}M-param model for {args.steps} steps")

    # reuse the fault-tolerant launcher with an inline config
    import repro.configs.registry as R
    R._MODULES["_example100m"] = type(
        "M", (), {"FULL": cfg, "REDUCED": cfg})
    LT.main(["--arch", "_example100m", "--steps", str(args.steps),
             "--batch", str(args.batch), "--seq", str(args.seq),
             "--ckpt-dir", "artifacts/train_lm_100m", "--ckpt-every", "100",
             "--log-every", "20"])


if __name__ == "__main__":
    RT.enable_compile_cache()
    main()
