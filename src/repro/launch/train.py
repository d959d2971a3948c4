"""Training launcher.

Runs real steps on the local devices (CPU smoke / TPU slice) with the same
sharded step functions the dry-run lowers for the production mesh:

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --steps 20 --batch 8 --seq 128

On real hardware drop ``--reduced`` and pass --data/--model axis sizes.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs.base import INPUT_SHAPES, get_config
from repro.data.synthetic import TokenStream, synthetic_batch_for
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step, param_pspecs
from repro.models import model as M
from repro.optim import cosine_schedule


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--data", type=int, default=1, help="data-axis size")
    ap.add_argument("--model", type=int, default=1, help="model-axis size")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh(args.data, args.model)

    sched = cosine_schedule(args.lr, args.warmup, args.steps)
    from repro.optim import adamw
    opt = adamw(sched, b1=0.9, b2=0.95, weight_decay=0.1)
    step_fn, opt = make_train_step(cfg, opt)

    pspecs = param_pspecs(cfg, mesh)
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))
    params = jax.jit(lambda k: M.init_params(cfg, k),
                     out_shardings=p_sh)(jax.random.key(args.seed))
    opt_state = jax.jit(opt.init)(params)

    start = 0
    if args.ckpt_dir and (ls := latest_step(args.ckpt_dir)) is not None:
        params = restore_checkpoint(args.ckpt_dir, ls, params)
        print(f"[train] restored step {ls} from {args.ckpt_dir}")
        start = ls

    stream = TokenStream(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    jstep = jax.jit(step_fn, donate_argnums=(0, 1))

    t0 = time.perf_counter()
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] {cfg.name}: {n_params/1e6:.1f}M params, "
          f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    for i in range(start, args.steps):
        batch = stream.batch(i)
        if cfg.arch_type == "audio":
            batch = dict(batch, **{
                "src_embeds": jax.random.normal(
                    jax.random.key(i),
                    (args.batch, max(args.seq // cfg.encoder_downsample, 1),
                     cfg.d_model), jnp.float32)})
        params, opt_state, metrics = jstep(params, opt_state, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            m = jax.device_get(metrics)
            print(f"[train] step {i}: loss={float(m['loss']):.4f} "
                  f"ce={float(m['ce']):.4f} gnorm={float(m['grad_norm']):.2f} "
                  f"({time.perf_counter()-t0:.1f}s)", flush=True)
        if args.ckpt_every and args.ckpt_dir and \
                (i + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, i + 1, params)
    print(f"[train] done in {time.perf_counter()-t0:.1f}s")


if __name__ == "__main__":
    main()
