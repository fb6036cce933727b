"""Batched serving example: prefill a batch of prompts, greedy-decode
continuations with the KV/SSM caches (works for every assigned arch).

    PYTHONPATH=src python examples/serve_lm.py --arch mamba2-780m
"""
import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.core import runtime as RT
from repro.models import transformer as T
from repro.training import serve as SV


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b",
                    choices=registry.ARCH_NAMES)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()

    cfg = registry.get_config(args.arch, reduced=True)
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab)
    t0 = time.perf_counter()
    out = SV.greedy_generate(cfg, params, prompt, args.gen,
                             s_max=args.prompt_len + args.gen)
    dt = time.perf_counter() - t0
    print(f"{args.arch} (reduced): generated {out.shape} tokens in {dt:.1f}s")
    print("first sequence:", out[0].tolist())


if __name__ == "__main__":
    RT.enable_compile_cache()
    main()
