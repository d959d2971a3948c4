"""The federation traffic: one general runner for every training cell.

A traffic file of kind ``federation`` states the run: approach, backend,
users U, cohort C, scheduler, rounds per compiled window K, batch,
selection, codec and the data split.  It builds one
``FederationSession`` from it, drives that same session through its first
``check_steps`` rounds (one ``run(1)`` each, through the window's own
compiled K-round program), warms one full window, then measures whole
``run(K)`` windows, each ending in a block, for ``--seconds``.  The
reference follows the first steps once the window has closed.
"""

from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from bench.lib import checks, data, reference


def round_flops(layers: dict, batch: int, cohort: int) -> int:
    """Model FLOPs of one approach-1 round (Alg. 1): G forward for the
    fakes; C D-updates on B real plus B fake (forward, weight gradients,
    input gradients past the first layer); one G update through the
    server D (G and D forward, D input gradients, G weight gradients and
    input gradients past the first layer).  Recomputation is not counted;
    element-wise work (Adam, selection, codec, fold) is not counted."""
    g, d = layers["g"], layers["d"]
    fg, fd = sum(g), sum(d)
    fakes = batch * fg
    d_updates = cohort * 2 * batch * (3 * fd - d[0])
    g_update = batch * (3 * fg - g[0] + 2 * fd)
    return fakes + d_updates + g_update


def hyper(cfg: dict) -> dict:
    opt = cfg["optimizer"]
    return {"lr": opt["lr"], "b1": opt["b1"], "b2": opt["b2"],
            "eps": opt["eps"], "server_scale": cfg["server_scale"]}


def build_session(mod, cfg: dict, fed: dict, seed: int, ds):
    from repro.core.approaches import DistGANConfig
    from repro.core.session import FederationSession
    from repro.core.spec import (BackendSpec, CombineSpec, CompressionSpec,
                                 EngineSpec, FederationSpec,
                                 ParticipationSpec)
    opt = cfg["optimizer"]
    fcfg = DistGANConfig(
        num_users=fed["users"], g_lr=opt["lr"], d_lr=opt["lr"], b1=opt["b1"],
        b2=opt["b2"], selection=fed["selection"],
        upload_frac=fed["upload_frac"],
        use_topk_kernel=fed["use_topk_kernel"],
        server_scale=cfg["server_scale"])
    spec = FederationSpec(
        approach=fed["approach"], batch_size=fed["batch"], seed=seed,
        eval_samples=0,
        engine=EngineSpec("fused", rounds_per_jit=fed["rounds_per_jit"],
                          fuse_store_rounds=fed["fuse_store_rounds"]),
        participation=ParticipationSpec(fed["scheduler"],
                                        cohort_size=fed["cohort"]),
        backend=BackendSpec(fed["backend"]),
        combine=CombineSpec(combiner=cfg["combiner"],
                            compression=CompressionSpec(
                                codec=fed["codec"],
                                error_feedback=fed["error_feedback"])))
    return FederationSession(mod.program_pair(cfg), fcfg, ds, spec)


def _host(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float64), tree)


def _rows(tree, idx):
    return [jax.tree.map(lambda x: np.asarray(x[i], np.float64), tree)
            for i in idx]


def d_unravel(mod, cfg):
    """(N,) flat D row -> D tree, in the leaf order the system's flat rows
    use (jax tree order)."""
    return ravel_pytree(jax.tree.map(
        lambda s: np.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda k: reference.init_pair(mod, cfg, k)[1],
                       jax.random.key(0))))[1]


@jax.jit
def _changed_rows(ds, d0):
    """(U,) whether each user's D row differs from ``d0`` in any bit."""
    bits = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)
    flags = [jnp.any(bits(x) != bits(y)[None], axis=tuple(range(1, x.ndim)))
             for x, y in zip(jax.tree.leaves(ds), jax.tree.leaves(d0))]
    return functools.reduce(jnp.logical_or, flags)


def store_readings(sess, res, st, d0, sched) -> dict:
    """Which users' rows and ``last_round`` the first steps changed,
    against the schedule: ``rows_off`` counts the users whose D row
    changed though they never trained, or did not change though they
    did; ``last_round_off`` (where the run reports ages) counts the users
    whose last round is not the last the schedule gave them."""
    trained = np.zeros(len(jax.tree.leaves(st.ds)[0]), bool)
    trained[sched.ravel()] = True
    out = {"rows_off": int(np.sum(np.asarray(_changed_rows(st.ds, d0))
                                  != trained))}
    stale = res.extra.get("staleness")
    if stale is not None:
        want = np.zeros(len(trained), np.int64)
        for r, row in enumerate(sched):
            want[row] = r + 1
        got = len(sched) - np.asarray(stale, np.int64)
        out["last_round_off"] = int(np.sum(got != want))
    return out


def first_steps(sess, cfg, fed, unravel) -> dict:
    """Drive the session through its first ``check_steps`` rounds and take
    the readings the check compares (see ``checks.train_numbers``)."""
    b1 = cfg["optimizer"]["b1"]
    steps = fed["check_steps"]
    sched = reference.schedule(fed["scheduler"], fed["users"],
                               fed["cohort"], steps)
    g0 = _host(sess.generator_params())
    d0_dev = unravel(jnp.asarray(sess.user_d_flat(0)))
    d0 = _host(d0_dev)
    out = {"g_loss": [], "d_loss": []}
    for r in range(steps):
        res = sess.run(1)
        out["g_loss"].append(float(res.g_losses[0]))
        out["d_loss"].append(np.asarray(res.d_losses[0], np.float64))
        st = res.state
        if r == 0:
            out["g_grad"] = jax.tree.map(lambda m: m / (1 - b1),
                                         _host(st.g_opt["mu"]))
            out["d_grad"] = [jax.tree.map(lambda m: m / (1 - b1), t)
                             for t in _rows(st.d_opts["mu"], sched[0])]
        if r == steps - 1:
            sub = lambda a, b: jax.tree.map(np.subtract, a, b)
            out["g_change"] = sub(_host(st.g), g0)
            out["server_change"] = sub(_host(st.server_d), d0)
            users = sorted({int(u) for u in sched.ravel()})
            out["row_change"] = {u: sub(t, d0) for u, t in
                                 zip(users, _rows(st.ds, users))}
            out.update(store_readings(sess, res, st, d0_dev, sched))
        del res, st
    return out


def reference_readings(ref: dict) -> dict:
    unravel = ref["unravel"]
    tree = lambda v: jax.tree.map(lambda x: np.asarray(x, np.float64),
                                  unravel(jnp.asarray(v)))
    server0 = tree(ref["server0"])
    sub = lambda a, b: jax.tree.map(np.subtract, a, b)
    return {"g_loss": ref["g_loss"], "d_loss": ref["d_loss"],
            "g_grad": _host(ref["g_grad"]),
            "d_grad": [tree(v) for v in ref["d_grad"]],
            "g_change": sub(_host(ref["g"]), _host(ref["g0"])),
            "server_change": sub(tree(ref["server"]), server0),
            "row_change": {u: sub(tree(v), server0)
                           for u, v in ref["rows"].items()}}


def run_reference(mod, cfg, fed, seed, shards, dtype=jnp.float32) -> dict:
    """The reference's readings for the first steps (highest precision)."""
    sched = reference.schedule(fed["scheduler"], fed["users"],
                               fed["cohort"], fed["check_steps"])
    batches = data.replay_batches(shards, seed, sched, fed["batch"])
    with jax.default_matmul_precision("highest"):
        ref = reference.run(mod, cfg, hyper(cfg), fed, seed, batches,
                            dtype=dtype)
    return reference_readings(ref)


def run(ctx) -> dict:
    """One measured run of a training cell; see the module docstring."""
    mod, cfg, fed, seed = ctx.mod, ctx.cfg, ctx.traffic, ctx.seed
    shards = data.make_shards(fed["data"], mod.sample_shape(cfg),
                              fed["users"], seed)
    sess = build_session(mod, cfg, fed, seed, data.dataset(shards, ctx.span))
    prog = first_steps(sess, cfg, fed, d_unravel(mod, cfg))
    K = fed["rounds_per_jit"]
    warm = sess.run(K)
    jax.block_until_ready(warm.state)
    del warm
    setup_s = time.perf_counter() - ctx.t_start

    rounds = failed = 0
    with ctx.capture():
        with ctx.span("window"):
            t0 = time.perf_counter()
            while True:
                with ctx.span("session.run"):
                    res = sess.run(K)
                with ctx.span("block"):
                    jax.block_until_ready(res.state)
                failed += int(np.sum(~np.isfinite(res.g_losses)))
                rounds += K
                del res
                if time.perf_counter() - t0 >= ctx.seconds:
                    break
            t1 = time.perf_counter()
    peak = ctx.read_memory()
    sess.close()
    del sess
    gc.collect()

    ref = run_reference(mod, cfg, fed, seed, shards)
    layers = mod.layer_flops(cfg)
    return {
        "setup_s": setup_s,
        "window_s": t1 - t0,
        "end_to_end": {"rounds_per_s": rounds / (t1 - t0),
                       "peak_hbm_gb": peak / 1e9 if peak else None},
        "attempted": rounds, "failed": failed,
        "numbers": checks.train_numbers(prog, ref),
        "facts": {"rounds": rounds,
                  "flops_per_round": round_flops(layers, fed["batch"],
                                                 fed["cohort"] or
                                                 fed["users"]),
                  "cohort": fed["cohort"] or fed["users"],
                  "d_params": cfg["d_params"]},
    }
