#!/usr/bin/env python3
"""The readings a cell's check limits are set from, on the chip, at the
cell's own size, in one process:

* ``program``  -- the system's timed path against the reference (the
                  lower reading is the largest over a dozen seeds);
* ``control``  -- the reference computed in bfloat16, the precision below
                  the configuration's float32, put in the program's place;
* ``half_batch`` (training) -- the reference fed half of each batch, the
                  mean taken over the rest, put in the program's place.

A step that returns its state unchanged reads 1 on ``change_gap`` by
construction and needs no run.

    python3 bench/control.py --workload mlp784.fused_store_u1024 --seeds 1 2 3

One JSON line per seed.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def train_readings(mod, cfg, fed, seed) -> dict:
    import jax
    import jax.numpy as jnp

    from bench.lib import checks, data, federation, reference
    shards = data.make_shards(fed["data"], mod.sample_shape(cfg),
                              fed["users"], seed)
    sess = federation.build_session(mod, cfg, fed, seed,
                                    data.dataset(shards))
    prog = federation.first_steps(sess, cfg, fed,
                                  federation.d_unravel(mod, cfg))
    sess.close()
    del sess
    gc.collect()
    ref = federation.run_reference(mod, cfg, fed, seed, shards)
    ctl = federation.run_reference(mod, cfg, fed, seed, shards,
                                   dtype=jnp.bfloat16)
    half = dict(fed, batch=fed["batch"] // 2)
    sched = reference.schedule(fed["scheduler"], fed["users"], fed["cohort"],
                               fed["check_steps"])
    batches = data.replay_batches(shards, seed, sched,
                                  fed["batch"])[:, :, :half["batch"]]
    with jax.default_matmul_precision("highest"):
        hb = federation.reference_readings(reference.run(
            mod, cfg, federation.hyper(cfg), half, seed, batches))
    return {"seed": seed,
            "program": checks.train_numbers(prog, ref),
            "control": checks.train_numbers(ctl, ref),
            "half_batch": checks.train_numbers(hb, ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.lib import device, registry
    from repro.launch.compile_cache import enable_compile_cache

    cell = registry.cell(registry.benchmark(ROOT), args.workload)
    device.require(cell["chips"])
    enable_compile_cache()
    cfg, mod = registry.config(cell["config"])
    traffic = registry.traffic(cell["traffic"])
    for s in args.seeds:
        print(json.dumps(train_readings(mod, cfg, traffic, s)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
