"""Quickstart: train a reduced assigned architecture for a few steps, then
serve a few greedy tokens from it — the whole public API in one file.

  PYTHONPATH=src python examples/quickstart.py [--arch tinyllama-1.1b]
"""

import argparse

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.data.synthetic import TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import greedy_decode
from repro.launch.steps import make_train_step
from repro.models import model as M
from repro.optim import adamw


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    print(f"== {cfg.name} (reduced: {cfg.num_layers}L d={cfg.d_model}) ==")

    # --- train ---
    params = M.init_params(cfg, jax.random.key(0))
    step_fn, opt = make_train_step(cfg, adamw(1e-3))
    opt_state = opt.init(params)
    stream = TokenStream(cfg.vocab_size, seq_len=64, batch_size=8, seed=0)
    jstep = jax.jit(step_fn)
    for i in range(args.steps):
        params, opt_state, m = jstep(params, opt_state, stream.batch(i))
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  loss {float(m['loss']):.4f}")

    # --- serve ---
    prompt = stream.batch(999)["tokens"][:2, :8]
    gen = greedy_decode(cfg, params, prompt, gen_len=12)
    print("prompt :", prompt[0].tolist())
    print("greedy :", gen[0].tolist())


if __name__ == "__main__":
    main()
