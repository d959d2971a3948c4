"""The paper's three Distributed-GAN training approaches as jit-able step
functions, plus the single-node "normal GAN" baseline they are compared
against (paper §5.5).

All step functions share the state layout:

    DistGANState(g, g_opt, ds, d_opts, server_d, step, key)

``ds`` holds the U local discriminators stacked on a leading user axis;
user u's real data enters only through ``real (U, B, ...)`` slice u —
the privacy boundary is structural (no cross-user term ever touches raw
slices; only deltas/logits are combined).

Each family is built in two layers:

* ``BODY_FACTORIES[name](pair, fcfg)`` -> the pure round function
  ``body(state, real) -> (state, metrics)`` — scan-able: the fused round
  engine (repro.core.engine) compiles K of these into ONE XLA program via
  ``jax.lax.scan``.  All PRNG folding goes through ``state.key``, so the
  scanned trajectory is bit-identical to the per-step loop.

  Bodies are COHORT-WIDTH AGNOSTIC: the user axis they see is whatever
  leading axis ``state.ds`` / ``real`` carry.  Under full participation
  that is all ``num_users`` users; under the cohort-virtualized engine
  (repro.core.engine.make_cohort_engine) it is a C-row slice gathered from
  the (U, N) CohortStore, with ``body(state, real, ages)`` receiving each
  member's participation age for the staleness-aware combiners.
* ``STEP_FACTORIES[name](pair, fcfg)`` -> the single-step jit of the same
  body, with the state donated (the U-stacked D/optimizer buffers update
  in place instead of being copied every round).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import losses
from repro.core.federated import (codec_transport, make_flat_layout,
                                  select_delta_flat)
from repro.core.spec import register_approach, resolve_combiner
from repro.optim import adamw, apply_updates


class DistGANState(NamedTuple):
    g: Any
    g_opt: Any
    ds: Any          # stacked (U, ...) local discriminators
    d_opts: Any      # stacked optimizer states
    server_d: Any    # approach 1 only (else None)
    step: jnp.ndarray
    key: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class DistGANConfig:
    num_users: int = 2
    g_lr: float = 2e-4
    d_lr: float = 2e-4
    b1: float = 0.5          # paper-era DCGAN Adam betas
    b2: float = 0.999
    selection: str = "topk"  # approach 1 upload policy
    upload_frac: float = 0.1
    combiner: str = "max_abs"
    server_scale: float = 1.0  # fold factor for combined deltas
    staleness_decay: float = 0.5  # delta age discount (staleness_* combiners)
    use_topk_kernel: bool = True  # Pallas global-threshold top-k (exact)
    loss_type: str = "bce"     # bce (paper) | wgan (beyond-paper, ref [1])
    wgan_clip: float = 0.05    # weight-clip for the W-GAN critic
    codec: str = "none"        # upload wire codec (spec.CODECS)
    error_feedback: bool = True   # EF-SGD residual for lossy codecs
    codec_stochastic: bool = False  # stochastic rounding (int8 codecs)
    stage_rows: bool = False   # quantize state rows crossing host/mesh


def _opts(fcfg: DistGANConfig):
    g_opt = adamw(fcfg.g_lr, b1=fcfg.b1, b2=fcfg.b2)
    d_opt = adamw(fcfg.d_lr, b1=fcfg.b1, b2=fcfg.b2)
    return g_opt, d_opt


def init_state(pair, fcfg: DistGANConfig, key, *,
               sync_ds: bool = False) -> DistGANState:
    """``sync_ds=True`` (approach 1): all users agree on one network —
    local Ds start at the server weights (paper §3.1 step 1)."""
    kg, kd, ks, kk = jax.random.split(key, 4)
    g_opt_def, d_opt_def = _opts(fcfg)
    g, d0 = pair.init(kg)
    if sync_ds:
        ds = jax.tree.map(
            lambda s: jnp.broadcast_to(s[None], (fcfg.num_users,) + s.shape),
            d0)
    else:
        ds = pair.init_user_ds(kd, fcfg.num_users)
    d_opts = jax.vmap(d_opt_def.init)(ds)
    server_d = d0  # approach 1's server discriminator
    return DistGANState(g, g_opt_def.init(g), ds, d_opts, server_d,
                        jnp.zeros((), jnp.int32), kk)


def _d_update_fn(pair, d_opt_def, fcfg: DistGANConfig | None = None):
    wgan = fcfg is not None and fcfg.loss_type == "wgan"

    @jax.named_scope("fed.d_update")
    def one(d, opt, real, fake):
        def loss_fn(dp):
            rs, fs = pair.d_apply(dp, real), pair.d_apply(dp, fake)
            if wgan:
                return losses.wgan_d_loss(rs, fs)
            return losses.d_loss(rs, fs)
        loss, grads = jax.value_and_grad(loss_fn)(d)
        updates, opt = d_opt_def.update(grads, opt, d)
        d = apply_updates(d, updates)
        if wgan:
            d = losses.clip_params(d, fcfg.wgan_clip)
        return d, opt, loss
    return one


def _g_loss_single(pair, fcfg, d, fake):
    s = pair.d_apply(d, fake)
    if fcfg.loss_type == "wgan":
        return losses.wgan_g_loss(s)
    return losses.g_loss_nonsat(s)


def _pin(*trees):
    """``jax.lax.optimization_barrier`` as a cluster pin: XLA fuses a
    subgraph with whatever consumes it, so the SAME round body embedded in
    different programs (per-step jit, fused scan, cohort gather/scatter
    scan) can tile its reductions differently and drift at ULP level.
    Pinning the update outputs gives every engine one canonical
    clustering — the bitwise-trajectory contract in tests/test_engine.py
    depends on it.  Semantically the identity function."""
    out = jax.lax.optimization_barrier(trees)
    return out[0] if len(trees) == 1 else out


@jax.named_scope("fed.g_update")
def _g_update(pair, g_opt_def, state, loss_fn):
    loss, grads = jax.value_and_grad(loss_fn)(state.g)
    grads = _pin(grads)
    updates, g_opt = g_opt_def.update(grads, state.g_opt, state.g)
    return apply_updates(state.g, updates), g_opt, loss


def d_flat_layout(pair):
    """Static FlatLayout for one discriminator of ``pair`` (built from
    abstract shapes — no params are materialized)."""
    d_shapes = jax.eval_shape(pair.init, jax.random.key(0))[1]
    return make_flat_layout(d_shapes)


def d_opt_flat_layout(pair, fcfg: DistGANConfig):
    """Static FlatLayout for one user's D-optimizer state (the CohortStore
    keeps it as an (U, No) flat buffer next to the (U, Nd) params)."""
    d_shapes = jax.eval_shape(pair.init, jax.random.key(0))[1]
    _, d_opt_def = _opts(fcfg)
    return make_flat_layout(jax.eval_shape(d_opt_def.init, d_shapes))


def _finalize_step(body):
    """Single-step jit of a round body with the state donated: the
    U-stacked D/optimizer buffers update in place instead of being copied
    every round (donation is a no-op on backends without buffer reuse)."""
    return jax.jit(body, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Approach 1: selective-gradient federated server discriminator
# ---------------------------------------------------------------------------

def make_approach1_body(pair, fcfg: DistGANConfig):
    g_opt_def, d_opt_def = _opts(fcfg)
    d_update = _d_update_fn(pair, d_opt_def, fcfg)
    combiner = resolve_combiner(fcfg.combiner)
    layout = d_flat_layout(pair)
    # transport compression is gated STRUCTURALLY: with codec="none" the
    # body takes no residual, splits no extra key, and traces the exact
    # pre-compression program (the bitwise pins depend on it)
    lossy = fcfg.codec != "none"
    ef = lossy and fcfg.error_feedback

    def body(state: DistGANState, real, ages=None, weights=None,
             residual=None):
        """real: (C, B, ...) private batches of the participating users
        (C == num_users under full participation); ``ages`` (C,) is each
        member's rounds-since-last-participation, consumed only by the
        staleness-aware combiners; ``weights`` (C,) is an optional
        per-member combine weight (the participation-adaptive
        server_scale knob — core.federated.participation_weights);
        ``residual`` (C, N) is each member's error-feedback row
        (required iff the codec is lossy AND error_feedback is on, in
        which case the body returns ``(state, metrics, new_residual)``).
        """
        assert (residual is not None) == ef, \
            "residual rows are passed iff a lossy codec runs with " \
            "error feedback"
        if lossy:
            key, kz1, kz2, ksel, kq = jax.random.split(state.key, 5)
        else:
            key, kz1, kz2, ksel = jax.random.split(state.key, 4)
        B = real.shape[1]
        U = real.shape[0]
        with jax.named_scope("fed.fakes"):
            fake = pair.g_apply(state.g, pair.sample_z(kz1, B))

        old_flat = layout.flatten_stacked(state.ds)        # (C, N)
        ds, d_opts, d_losses = _pin(*jax.vmap(
            d_update, in_axes=(0, 0, 0, None))(
            state.ds, state.d_opts, real, fake))

        # users upload selected deltas; server folds them (alg. 1 lines
        # 3-5).  Flat-buffer layout: delta is ONE (C, N) subtract, the
        # selection one masked op per user, the fold one argmax-|.| over
        # a contiguous buffer — no per-round pytree re-flattening.
        delta = layout.flatten_stacked(ds) - old_flat
        if ef:
            # EF-SGD: compensate with what last round's compression
            # dropped BEFORE selection, so persistently-small
            # coordinates accumulate until they win the mask
            delta = delta + residual
        sel_keys = jax.random.split(ksel, U)
        rows = [select_delta_flat(delta[u], fcfg.selection,
                                  frac=fcfg.upload_frac, key=sel_keys[u],
                                  use_kernel=fcfg.use_topk_kernel)
                for u in range(U)]
        masked = jnp.stack([r[0] for r in rows])           # (C, N)
        kept = jnp.stack([r[1] for r in rows])
        if lossy:
            seed = (jax.random.randint(kq, (), 0, jnp.int32(2**31 - 1))
                    if fcfg.codec_stochastic else None)
            # what the server actually reconstructs from the wire
            masked = codec_transport(masked, fcfg.codec,
                                     stochastic=fcfg.codec_stochastic,
                                     seed=seed,
                                     use_kernel=fcfg.use_topk_kernel)
        if ef:
            # residual = compensated - transported: selection drop AND
            # quantization error, re-injected next round (user-local,
            # so computed before any server-side weighting)
            new_residual = delta - masked
        if weights is not None:
            # opt-in participation-adaptive combine weight: scale each
            # member's upload BEFORE the fold (weights are normalized to
            # mean 1 host-side, so server_scale semantics are preserved)
            masked = masked * weights[:, None]
        # the fold: the combiner over the uploaded rows, and the add of
        # what it returns into the server row
        with jax.named_scope("fed.fold"):
            if getattr(combiner, "needs_ages", False):
                combined = combiner(masked, ages,
                                    decay=fcfg.staleness_decay)
            else:
                combined = combiner(masked)                # (N,)
            server_flat = (layout.flatten(state.server_d)
                           + fcfg.server_scale * combined)
        server_d = layout.unflatten(server_flat)

        # download phase (paper §3.1: "users update local model with the
        # global parameter") — local models re-sync to the server so next
        # round's deltas are w.r.t. the shared point.  Under partial
        # participation only the cohort re-syncs; absent users keep the
        # server copy from their last round (that gap is what ``ages``
        # measures next time they are drawn).
        ds = jax.tree.map(
            lambda s: jnp.broadcast_to(s[None], (U,) + s.shape), server_d)

        # G trains against the *server* D only (alg. 1 lines 7-10)
        def g_loss(gp):
            fake_ = pair.g_apply(gp, pair.sample_z(kz2, B))
            return _g_loss_single(pair, fcfg, server_d, fake_)

        g, g_opt, gl = _g_update(pair, g_opt_def, state, g_loss)
        new_state = DistGANState(g, g_opt, ds, d_opts, server_d,
                                 state.step + 1, key)
        metrics = {"d_loss": d_losses, "g_loss": gl,
                   "kept_frac": jnp.mean(kept)}
        if ef:
            return new_state, metrics, new_residual
        return new_state, metrics

    return body


def make_approach1_step(pair, fcfg: DistGANConfig):
    return _finalize_step(make_approach1_body(pair, fcfg))


# ---------------------------------------------------------------------------
# Approach 1 variant: download-first sync (cohort members pull the
# CURRENT server D before training)
# ---------------------------------------------------------------------------

def make_download_first_body(pair, fcfg: DistGANConfig):
    """Approach 1 with a download phase BEFORE local training: every
    cohort member overwrites its (possibly deeply stale) local D with the
    CURRENT server D, then trains and uploads its selected delta.

    Under partial participation the plain approach-1 rows hold the server
    copy from each member's LAST participation — at large U/C ratios that
    base is hundreds of rounds old, so the uploaded delta folds an
    ancient-base update into today's server point (the quality cliff
    ``examples/distgan_stream.py`` measures at mean age ~360).
    Downloading first re-bases every delta on the current server point,
    so deltas are always fresh; participation ages are therefore zeroed
    before the combiner (a staleness-aware fold has nothing to
    discount), while the engines' ``mean_age`` metric still reports the
    true participation lag.  Stored optimizer rows (Adam moments) are
    kept — they re-adapt within the round and preserving them keeps the
    row layout identical to approach 1.

    With full participation every member re-synced LAST round too, so
    this variant is bit-identical to ``approach1`` (pinned in
    tests/test_spec.py)."""
    base = make_approach1_body(pair, fcfg)

    def body(state: DistGANState, real, ages=None, weights=None,
             residual=None):
        U = real.shape[0]
        ds = jax.tree.map(
            lambda s: jnp.broadcast_to(s[None], (U,) + s.shape),
            state.server_d)
        zero_ages = None if ages is None else jnp.zeros_like(ages)
        return base(state._replace(ds=ds), real, zero_ages, weights,
                    residual)

    return body


def make_download_first_step(pair, fcfg: DistGANConfig):
    return _finalize_step(make_download_first_body(pair, fcfg))


# ---------------------------------------------------------------------------
# Approach 2: averaged-output multi-discriminator
# ---------------------------------------------------------------------------

def make_approach2_body(pair, fcfg: DistGANConfig):
    g_opt_def, d_opt_def = _opts(fcfg)
    d_update = _d_update_fn(pair, d_opt_def, fcfg)

    def body(state: DistGANState, real, ages=None, weights=None):
        key, kz1, kz2 = jax.random.split(state.key, 3)
        B = real.shape[1]
        fake = pair.g_apply(state.g, pair.sample_z(kz1, B))
        ds_in, opts_in, real_in, fake_in = _pin(state.ds, state.d_opts,
                                                real, fake)
        ds, d_opts, d_losses = _pin(*jax.vmap(
            d_update, in_axes=(0, 0, 0, None))(
            ds_in, opts_in, real_in, fake_in))

        # alg. 2 line 4: outputs = mean_i D_i(fake); criterion vs real labels
        def g_loss(gp):
            fake_ = pair.g_apply(gp, pair.sample_z(kz2, B))
            per_user = jax.vmap(lambda d: pair.d_apply(d, fake_))(ds)
            if fcfg.loss_type == "wgan":
                return losses.wgan_g_loss_avg(per_user)
            return losses.g_loss_avg_probs(per_user)

        g, g_opt, gl = _g_update(pair, g_opt_def, state, g_loss)
        new_state = DistGANState(g, g_opt, ds, d_opts, state.server_d,
                                 state.step + 1, key)
        return new_state, {"d_loss": d_losses, "g_loss": gl,
                           "kept_frac": jnp.float32(1.0)}

    return body


def make_approach2_step(pair, fcfg: DistGANConfig):
    return _finalize_step(make_approach2_body(pair, fcfg))


# ---------------------------------------------------------------------------
# Approach 3: round-robin one-G-vs-many-D
# ---------------------------------------------------------------------------

def make_approach3_body(pair, fcfg: DistGANConfig):
    g_opt_def, d_opt_def = _opts(fcfg)
    d_update = _d_update_fn(pair, d_opt_def, fcfg)

    def body(state: DistGANState, real, ages=None, weights=None):
        """alg. 3: for each participating user j in turn — train D_j, then
        update G against D_j alone (j ranges over the cohort width)."""
        key = state.key
        g, g_opt = state.g, state.g_opt
        ds, d_opts = state.ds, state.d_opts
        g_losses, d_losses = [], []
        U = real.shape[0]

        for j in range(U):  # cohort width is static & small; unrolled
            key, kz1, kz2 = jax.random.split(key, 3)
            B = real.shape[1]
            fake = pair.g_apply(g, pair.sample_z(kz1, B))
            d_j = jax.tree.map(lambda x: x[j], ds)
            o_j = jax.tree.map(lambda x: x[j], d_opts)
            d_j, o_j, dl = _pin(*d_update(d_j, o_j, real[j], fake))
            ds = jax.tree.map(lambda s, n: s.at[j].set(n), ds, d_j)
            d_opts = jax.tree.map(lambda s, n: s.at[j].set(n), d_opts, o_j)

            def g_loss(gp, d_j=d_j, kz2=kz2):
                fake_ = pair.g_apply(gp, pair.sample_z(kz2, B))
                return _g_loss_single(pair, fcfg, d_j, fake_)

            gl, grads = jax.value_and_grad(g_loss)(g)
            updates, g_opt = g_opt_def.update(grads, g_opt, g)
            g = apply_updates(g, updates)
            g_losses.append(gl)
            d_losses.append(dl)

        new_state = DistGANState(g, g_opt, ds, d_opts, state.server_d,
                                 state.step + 1, key)
        return new_state, {"d_loss": jnp.stack(d_losses),
                           "g_loss": jnp.mean(jnp.stack(g_losses)),
                           "kept_frac": jnp.float32(1.0)}

    return body


def make_approach3_step(pair, fcfg: DistGANConfig):
    return _finalize_step(make_approach3_body(pair, fcfg))


# ---------------------------------------------------------------------------
# Baseline: normal single-node GAN on the union data (paper fig. 14/15)
# ---------------------------------------------------------------------------

def make_baseline_body(pair, fcfg: DistGANConfig):
    g_opt_def, d_opt_def = _opts(fcfg)
    d_update = _d_update_fn(pair, d_opt_def, fcfg)

    def body(state: DistGANState, real, ages=None, weights=None):
        """real: (B, ...) union-data batch (no privacy; cohorting n/a)."""
        key, kz1, kz2 = jax.random.split(state.key, 3)
        B = real.shape[0]
        fake = pair.g_apply(state.g, pair.sample_z(kz1, B))
        d = jax.tree.map(lambda x: x[0], state.ds)
        o = jax.tree.map(lambda x: x[0], state.d_opts)
        d, o, dl = _pin(*d_update(d, o, real, fake))
        ds = jax.tree.map(lambda s, n: s.at[0].set(n), state.ds, d)
        d_opts = jax.tree.map(lambda s, n: s.at[0].set(n), state.d_opts, o)

        def g_loss(gp):
            fake_ = pair.g_apply(gp, pair.sample_z(kz2, B))
            return _g_loss_single(pair, fcfg, d, fake_)

        g, g_opt, gl = _g_update(pair, g_opt_def, state, g_loss)
        return DistGANState(g, g_opt, ds, d_opts, state.server_d,
                            state.step + 1, key), \
            {"d_loss": dl[None], "g_loss": gl, "kept_frac": jnp.float32(1.0)}

    return body


def make_baseline_step(pair, fcfg: DistGANConfig):
    return _finalize_step(make_baseline_body(pair, fcfg))


register_approach("approach1", make_approach1_body, make_approach1_step,
                  sync_ds=True, uploads=True)
register_approach("approach2", make_approach2_body, make_approach2_step)
register_approach("approach3", make_approach3_body, make_approach3_step)
register_approach("baseline", make_baseline_body, make_baseline_step,
                  user_axis=False)
register_approach("download_first", make_download_first_body,
                  make_download_first_step, sync_ds=True, uploads=True)

# legacy aliases over the registry (new approaches registered through
# repro.core.spec.register_approach show up here too)
import collections.abc  # noqa: E402

from repro.core.spec import APPROACH_REGISTRY as _APPROACHES  # noqa: E402


class _FactoryView(collections.abc.Mapping):
    """Live read-only view of one ApproachDef attribute per registry key."""

    def __init__(self, attr):
        self._attr = attr

    def __getitem__(self, name):
        return getattr(_APPROACHES.get(name), self._attr)

    def __iter__(self):
        return iter(_APPROACHES.names())

    def __len__(self):
        return len(_APPROACHES.entries)


BODY_FACTORIES = _FactoryView("body_factory")
STEP_FACTORIES = _FactoryView("step_factory")
