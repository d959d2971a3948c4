"""The plain reference of approach 1 of the Distributed GAN (arXiv:1911.08128
Alg. 1), written from the algorithm and independent of the program: no
engine, store, kernel or layout of the system is imported.

Each configuration module supplies its parameter declarations and its
forward passes (``decls``, ``g_apply``, ``d_apply``).  The reference makes
the weights from the seed by the same recipe the system states (one key
split four ways; generator and discriminator from the first; each
declared leaf, in sorted-key order, from its own split), draws the same
noise from the same key chain, and replays the same batches.

``dtype`` is float32 for the reference (run it under
``jax.default_matmul_precision("highest")``) and bfloat16 for the control.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree


def _is_decl(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], str)


def build(decls, key, dtype=jnp.float32):
    """Materialize ``{name: (init, shape, std)}`` declarations: one key per
    leaf in sorted-key order; ``normal`` leaves draw N(0, 1) times ``std``
    (default 1/sqrt(shape[0]))."""
    leaves, treedef = jax.tree.flatten(decls, is_leaf=_is_decl)
    keys = jax.random.split(key, len(leaves))
    vals = []
    for (init, shape, std), k in zip(leaves, keys):
        if init == "zeros":
            vals.append(jnp.zeros(shape, dtype))
        elif init == "ones":
            vals.append(jnp.ones(shape, dtype))
        else:
            std = std if std is not None else 1.0 / math.sqrt(max(shape[0], 1))
            vals.append((jax.random.normal(k, shape, jnp.float32)
                         * std).astype(dtype))
    return jax.tree.unflatten(treedef, vals)


def init_pair(mod, cfg, key, dtype=jnp.float32):
    kg, kd = jax.random.split(key)
    g_decls, d_decls = mod.decls(cfg)
    return build(g_decls, kg, dtype), build(d_decls, kd, dtype)


def bce(logits, target):
    return (jnp.maximum(logits, 0) - logits * target
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def adam(p, g, st, hp):
    """One Adam step on a tree; ``st`` = (mu, nu, step)."""
    mu, nu, step = st
    step = step + 1
    dt = jax.tree.leaves(p)[0].dtype
    c1 = (1.0 - hp["b1"] ** step.astype(jnp.float32)).astype(dt)
    c2 = (1.0 - hp["b2"] ** step.astype(jnp.float32)).astype(dt)
    mu = jax.tree.map(lambda m, x: hp["b1"] * m + (1 - hp["b1"]) * x, mu, g)
    nu = jax.tree.map(lambda v, x: hp["b2"] * v + (1 - hp["b2"]) * x * x,
                      nu, g)
    p = jax.tree.map(
        lambda w, m, v: (w - hp["lr"] * ((m / c1) / (jnp.sqrt(v / c2)
                                                      + hp["eps"]))
                         ).astype(dt),
        p, mu, nu)
    return p, (mu, nu, step)


def topk_keep(x, frac: float):
    """Keep entries with |x| >= the k-th largest magnitude of the row,
    k = max(int(n * frac), 1) (ties kept)."""
    k = max(int(x.shape[0] * frac), 1)
    kth = jax.lax.top_k(jnp.abs(x), k)[0][-1]
    return jnp.where(jnp.abs(x) >= kth, x, jnp.zeros_like(x))


def int8_roundtrip(x):
    """Per-row absmax int8: scale = max|x| / 127, q = round(x / scale)."""
    scale = jnp.max(jnp.abs(x)) / 127.0
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0).astype(x.dtype)
    return jnp.clip(jnp.round(x * inv), -127.0, 127.0) * scale


def schedule(kind: str, users: int, cohort: int, rounds: int) -> np.ndarray:
    """(rounds, C) cohort members: ``full`` or ``round_robin``."""
    if kind == "full":
        return np.tile(np.arange(users), (rounds, 1))
    if kind == "round_robin":
        r = np.arange(rounds)[:, None] * cohort + np.arange(cohort)
        return r % users
    raise ValueError(f"no reference for scheduler {kind!r}")


def make_round(mod, cfg, hp: dict, *, batch: int, lossy: bool, ef: bool,
               frac: float, dtype):
    """One approach-1 round over a C-wide cohort, as a jitted function
    ``(g, g_st, server, rows, opts, res, real, key) -> new + readings``."""
    _, d_tmpl = jax.eval_shape(lambda k: init_pair(mod, cfg, k, dtype),
                               jax.random.key(0))
    _, unravel = ravel_pytree(jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), d_tmpl))
    zdim = cfg["z_dim"]

    def z(k):
        return jax.random.normal(k, (batch, zdim), jnp.float32).astype(dtype)

    def user(row, st, real, res, fake):
        dp = unravel(row)

        def loss_fn(p):
            return (jnp.mean(bce(mod.d_apply(cfg, p, real), 1.0))
                    + jnp.mean(bce(mod.d_apply(cfg, p, fake), 0.0)))
        loss, grad = jax.value_and_grad(loss_fn)(dp)
        new, st = adam(dp, grad, st, hp)
        delta = ravel_pytree(new)[0] - row
        if ef:
            delta = delta + res
        sent = topk_keep(delta, frac)
        if lossy:
            sent = int8_roundtrip(sent)
        return loss, ravel_pytree(grad)[0], st, sent, delta - sent

    def round_fn(g, g_st, server, rows, opts, res, real, key):
        if lossy:
            key, kz1, kz2, _, _ = jax.random.split(key, 5)
        else:
            key, kz1, kz2, _ = jax.random.split(key, 4)
        fake = mod.g_apply(cfg, g, z(kz1))
        d_loss, d_grad, opts, sent, new_res = jax.vmap(
            user, in_axes=(0, 0, 0, 0, None))(rows, opts, real, res, fake)
        pick = jnp.argmax(jnp.abs(sent), axis=0)
        combined = jnp.take_along_axis(sent, pick[None], axis=0)[0]
        server = server + hp["server_scale"] * combined
        sd = unravel(server)

        def g_loss_fn(gp):
            return jnp.mean(bce(mod.d_apply(cfg, sd, mod.g_apply(cfg, gp,
                                                                 z(kz2))),
                                1.0))
        g_loss, g_grad = jax.value_and_grad(g_loss_fn)(g)
        g, g_st = adam(g, g_grad, g_st, hp)
        return (g, g_st, server, opts, new_res, key,
                {"g_loss": g_loss, "d_loss": d_loss, "g_grad": g_grad,
                 "d_grad": d_grad})

    return jax.jit(round_fn), unravel


def run(mod, cfg, hp: dict, fed: dict, seed: int, batches: np.ndarray,
        dtype=jnp.float32) -> dict:
    """The first ``len(batches)`` rounds of approach 1 from ``seed``.

    ``batches`` (rounds, C, B, ...) are the replayed real batches.  Returns
    per-round losses, the first round's gradients (G tree, (C, N) D rows),
    the initial and final G tree and server row, and every trained user's
    final row (``rows``: user -> (N,))."""
    rounds = len(batches)
    sched = schedule(fed["scheduler"], fed["users"], fed["cohort"], rounds)
    lossy = fed["codec"] != "none"
    ef = lossy and fed["error_feedback"]
    fn, unravel = make_round(mod, cfg, hp, batch=fed["batch"], lossy=lossy,
                             ef=ef, frac=fed["upload_frac"], dtype=dtype)
    kg, _, _, key = jax.random.split(jax.random.key(seed), 4)
    g, d0 = jax.jit(lambda k: init_pair(mod, cfg, k))(kg)
    g, d0 = jax.tree.map(lambda x: x.astype(dtype), (g, d0))
    d0_flat = ravel_pytree(d0)[0]
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    g_st = (zeros(g), zeros(g), jnp.zeros((), jnp.int32))
    server = d0_flat
    rows, opts, res = {}, {}, {}
    out = {"g_loss": [], "d_loss": [], "g0": g, "server0": d0_flat}
    for r in range(rounds):
        members = [int(u) for u in sched[r]]
        row = jnp.stack([rows.get(u, d0_flat) for u in members])
        st = jax.tree.map(lambda *xs: jnp.stack(xs), *[
            opts.get(u, (zeros(d0), zeros(d0), jnp.zeros((), jnp.int32)))
            for u in members])
        rs = jnp.stack([res.get(u, jnp.zeros_like(d0_flat))
                        for u in members])
        g, g_st, server, st, rs, key, m = fn(
            g, g_st, server, row, st, rs, jnp.asarray(batches[r], dtype),
            key)
        for c, u in enumerate(members):
            rows[u] = server          # the cohort re-syncs to the server
            opts[u] = jax.tree.map(lambda x: x[c], st)
            res[u] = rs[c]
        out["g_loss"].append(float(m["g_loss"]))
        out["d_loss"].append(np.asarray(m["d_loss"], np.float64))
        if r == 0:
            out["g_grad"] = jax.tree.map(np.asarray, m["g_grad"])
            out["d_grad"] = np.asarray(m["d_grad"], np.float64)
            out["first_members"] = members
    out.update(g=jax.tree.map(np.asarray, g), server=np.asarray(server),
               rows={u: np.asarray(v) for u, v in rows.items()},
               unravel=unravel, schedule=sched)
    out["g0"] = jax.tree.map(np.asarray, out["g0"])
    out["server0"] = np.asarray(out["server0"])
    return out
