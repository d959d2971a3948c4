"""SPMD Distributed-GAN: the paper's federation mapped onto a mesh axis.

One user == one slice of the ``users`` mesh axis (on the production mesh
the 2-user topology is literally one user per pod).  Inside ``shard_map``:

* raw data is sharded over ``users`` and NEVER crosses the axis — the only
  cross-user collectives are on selected deltas (approach 1) or on D
  probabilities / G gradients (approaches 2/3).  That is the paper's
  privacy boundary, enforced structurally.
* approach 1's server-D fold is `combine_max_abs_spmd` (pmax + masked psum)
  — the parameter server becomes replicated state, the TPU-native idiom.
* G stays replicated: its gradient contributions are psum'd over users.

Layout convention: stacked user trees (U, ...) are sharded on dim 0; the
generator and its optimizer state are replicated.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.core import losses
from repro.core.approaches import (DistGANConfig, DistGANState,
                                   d_flat_layout, d_opt_flat_layout)
from repro.core.federated import (CohortStore, codec_transport,
                                  combine_max_abs_spmd, combine_mean_spmd,
                                  combine_shared_random_flat_spmd,
                                  select_delta_flat)
from repro.optim import adamw, apply_updates

AXIS = "users"


def _opts(fcfg):
    return (adamw(fcfg.g_lr, b1=fcfg.b1, b2=fcfg.b2),
            adamw(fcfg.d_lr, b1=fcfg.b1, b2=fcfg.b2))


def _unstack(tree):
    return jax.tree.map(lambda x: x[0], tree)


def _restack(tree):
    return jax.tree.map(lambda x: x[None], tree)


def _specs_for(state: DistGANState, mesh):
    user_sharded = lambda tree: jax.tree.map(lambda _: PS(AXIS), tree)
    replicated = lambda tree: jax.tree.map(lambda _: PS(), tree)
    return DistGANState(
        g=replicated(state.g), g_opt=replicated(state.g_opt),
        ds=user_sharded(state.ds), d_opts=user_sharded(state.d_opts),
        server_d=replicated(state.server_d),
        step=PS(), key=PS())


def make_spmd_body(pair, fcfg: DistGANConfig, approach: str,
                   width: int | None = None):
    """The per-round SPMD function ``body(state, real) -> (state, metrics)``
    as run INSIDE shard_map (one user per 'users'-axis slice).  Scan-able:
    the fused engine rolls K of these into one program
    (repro.core.engine.make_spmd_engine).

    ``width`` is the number of slices on the mesh axis — ``num_users``
    for the classic one-user-per-device layout, the cohort size C for the
    cohort-virtualized layout (repro.core.engine.make_spmd_cohort_engine).
    The optional third body argument ``age`` is this shard's scalar
    participation age, consumed only by the staleness-aware folds; the
    optional fourth, ``weight``, is this shard's scalar
    participation-adaptive combine weight (approach 1, non-shared_random
    selections) — the SPMD analogue of the host bodies' ``weights``; the
    optional fifth, ``residual``, is this shard's (N,) error-feedback
    residual, REQUIRED iff ``fcfg.codec != "none" and
    fcfg.error_feedback`` — the body then returns a third element, the
    updated residual (same EF-SGD order as the host approach1 body:
    compensate -> select -> codec -> residual, weights after)."""
    g_opt_def, d_opt_def = _opts(fcfg)
    layout = d_flat_layout(pair)
    width = fcfg.num_users if width is None else width
    lossy = fcfg.codec != "none"
    ef = lossy and fcfg.error_feedback
    if lossy:
        assert approach == "approach1", \
            "transport codecs compress approach 1's delta uploads"
        assert fcfg.selection != "shared_random", \
            "shared_random psums the fold before any per-member " \
            "encoding — there is no per-user payload to compress"

    def local_d_update(d, opt, real, fake):
        def loss_fn(dp):
            return losses.d_loss(pair.d_apply(dp, real),
                                 pair.d_apply(dp, fake))
        loss, grads = jax.value_and_grad(loss_fn)(d)
        updates, opt = d_opt_def.update(grads, opt, d)
        return apply_updates(d, updates), opt, loss

    def body(state: DistGANState, real, age=None, weight=None,
             residual=None):
        assert (residual is not None) == ef, \
            "pass residual iff the config wants error feedback"
        if lossy:
            key, kz1, kz2, ksel, kq = jax.random.split(state.key, 5)
        else:
            key, kz1, kz2, ksel = jax.random.split(state.key, 4)
        B = real.shape[1]
        my_real = real[0]                     # this shard's private slice
        d = _unstack(state.ds)
        opt = _unstack(state.d_opts)
        fake = pair.g_apply(state.g, pair.sample_z(kz1, B))

        metrics = {}
        if approach == "approach1":
            old_flat = layout.flatten(d)
            d, opt, dl = local_d_update(d, opt, my_real, fake)
            # flat-buffer boundary: the delta is one contiguous (N,)
            # subtract, and the cross-user fold psums ONE buffer instead
            # of a tree of small leaves.
            delta = layout.flatten(d) - old_flat
            if ef:
                # EF-SGD: compensate BEFORE selection so entries dropped
                # or rounded away re-enter future uploads
                delta = delta + residual
            if fcfg.selection == "shared_random":
                assert weight is None, \
                    "adaptive weights need per-user uploads (the shared_" \
                    "random fold psums before any per-member scaling)"
                # bandwidth-true: only frac*N values cross the users axis
                comb, kept = combine_shared_random_flat_spmd(
                    delta, fcfg.upload_frac, ksel, AXIS)
            else:
                masked, kept = select_delta_flat(
                    delta, fcfg.selection, frac=fcfg.upload_frac, key=ksel,
                    use_kernel=fcfg.use_topk_kernel)
                if lossy:
                    seed = None
                    if fcfg.codec_stochastic:
                        seed = jax.random.randint(kq, (), 0, 2**31 - 1)
                    masked = codec_transport(
                        masked[None], fcfg.codec,
                        stochastic=fcfg.codec_stochastic, seed=seed,
                        use_kernel=fcfg.use_topk_kernel)[0]
                if ef:
                    # user-local ledger: what the wire dropped, BEFORE
                    # any server-side weighting
                    new_residual = delta - masked
                if weight is not None:
                    # participation-adaptive combine weight, applied to
                    # this shard's upload BEFORE the cross-user fold
                    masked = masked * weight
                if fcfg.combiner.startswith("staleness"):
                    # age-discount the shard's delta BEFORE the fold (the
                    # SPMD analogue of COMBINERS['staleness_*'])
                    decay = jnp.asarray(fcfg.staleness_decay, jnp.float32)
                    if fcfg.combiner == "staleness_mean":
                        # ages relative to the youngest member, as in
                        # combine_staleness_mean: the weights are
                        # normalized anyway, and absolute decay**age
                        # underflows to 0/0 NaN for uniformly old cohorts
                        if age is None:
                            w = jnp.float32(1.0)
                        else:
                            a = age.astype(jnp.float32)
                            w = decay ** (a - jax.lax.pmin(a, AXIS))
                        comb = (jax.lax.psum(w * masked, AXIS)
                                / jax.lax.psum(w, AXIS))
                    else:  # staleness_max_abs
                        w = (jnp.float32(1.0) if age is None else
                             decay ** age.astype(jnp.float32))
                        comb = combine_max_abs_spmd(w * masked, AXIS)
                else:
                    comb = (combine_max_abs_spmd(masked, AXIS)
                            if fcfg.combiner == "max_abs"
                            else combine_mean_spmd(masked, AXIS))
            server_flat = (layout.flatten(state.server_d)
                           + fcfg.server_scale * comb)
            server_d = layout.unflatten(server_flat)
            d = server_d  # download phase: local D re-syncs to the server

            def g_loss(gp):
                f = pair.g_apply(gp, pair.sample_z(kz2, B))
                return losses.g_loss_nonsat(pair.d_apply(server_d, f))

            gl, grads = jax.value_and_grad(g_loss)(state.g)
            # server_d is replicated -> grads identical; no psum needed
            metrics["kept_frac"] = kept

        elif approach == "approach2":
            d, opt, dl = local_d_update(d, opt, my_real, fake)

            def g_loss(gp):
                f = pair.g_apply(gp, pair.sample_z(kz2, B))
                p_local = jax.nn.sigmoid(pair.d_apply(d, f))
                p_avg = jax.lax.pmean(p_local, AXIS)   # alg. 2 line 4
                return -jnp.mean(jnp.log(p_avg + 1e-7))

            gl, grads = jax.value_and_grad(g_loss)(state.g)
            # the pmean inside g_loss transposes to a psum of cotangents:
            # each shard's grad already carries ALL users' paths (verified
            # against the stacked-host oracle in tests/test_spmd.py), so
            # combine with pmean — it is idempotent on the replicated value
            # and irons out per-shard fp noise.
            grads = jax.tree.map(lambda x: jax.lax.pmean(x, AXIS), grads)
            server_d = state.server_d
            metrics["kept_frac"] = jnp.float32(1.0)

        elif approach == "approach3":
            # Round-robin: in sub-round j only slice j's D trains and only
            # slice j's D drives the G update; the G grad is broadcast from
            # shard j via a masked psum.  j ranges over the mesh-axis
            # width (the cohort, under virtualization).
            U = width
            me = jax.lax.axis_index(AXIS)
            g, g_opt = state.g, state.g_opt
            gl = jnp.float32(0.0)
            dl = jnp.float32(0.0)
            kk = key
            for j in range(U):
                kk, kz1j, kz2j = jax.random.split(kk, 3)
                fake_j = pair.g_apply(g, pair.sample_z(kz1j, B))
                nd, nopt, dlj = local_d_update(d, opt, my_real, fake_j)
                active = (me == j)
                pick = lambda a, b: jnp.where(active, a, b)
                d = jax.tree.map(pick, nd, d)
                opt = jax.tree.map(pick, nopt, opt)
                dl = dl + jnp.where(active, dlj, 0.0)

                def g_loss(gp, d=d, kz2j=kz2j):
                    f = pair.g_apply(gp, pair.sample_z(kz2j, B))
                    return losses.g_loss_nonsat(pair.d_apply(d, f))

                glj, grads_j = jax.value_and_grad(g_loss)(g)
                mask = active.astype(jnp.float32)
                grads_j = jax.tree.map(
                    lambda x: jax.lax.psum(x * mask, AXIS), grads_j)
                updates, g_opt = g_opt_def.update(grads_j, g_opt, g)
                g = apply_updates(g, updates)
                gl = gl + jax.lax.psum(glj * mask, AXIS) / U

            new_state = DistGANState(g, g_opt, _restack(d), _restack(opt),
                                     state.server_d, state.step + 1, kk)
            return new_state, {"d_loss": dl[None], "g_loss": gl,
                               "kept_frac": jnp.float32(1.0)}
        else:
            raise ValueError(approach)

        updates, g_opt = g_opt_def.update(grads, state.g_opt, state.g)
        g = apply_updates(state.g, updates)
        new_state = DistGANState(g, g_opt, _restack(d), _restack(opt),
                                 server_d, state.step + 1, key)
        metrics = {"d_loss": dl[None], "g_loss": gl, **metrics}
        if ef:
            return new_state, metrics, new_residual
        return new_state, metrics

    return body


def make_spmd_cohort_round(pair, fcfg: DistGANConfig, approach: str,
                           cohort_size: int):
    """Per-round cohort function as run INSIDE shard_map: each of the C
    mesh slices hosts ONE cohort member per round.  The (U, N) CohortStore
    is replicated; a round gathers each shard's scheduled row, runs the
    standard SPMD body on it, and scatters the updated row back with a
    one-hot psum + row REPLACEMENT (values land bit-exactly and every
    replica stays consistent).  Device count bounds C — U only sizes the
    replicated buffers.

    Scan-able: repro.core.engine.make_spmd_cohort_engine rolls K of these
    into one program.  Cohort rows are replacement-free by construction
    (core.federated.make_schedule), so scatter rows never collide.
    """
    from repro.core.engine import CohortState

    inner = make_spmd_body(pair, fcfg, approach, width=cohort_size)
    d_layout = d_flat_layout(pair)
    o_layout = d_opt_flat_layout(pair, fcfg)
    ef = fcfg.codec != "none" and fcfg.error_feedback
    stage_q = fcfg.stage_rows and fcfg.codec in ("int8", "topk_int8")

    def round_fn(carry: CohortState, inp):
        real, idx = inp            # per-shard blocks: (1, B, ...), (1,)
        store = carry.store
        u = idx[0]
        d_row = store.d_flat[u]
        o_row = store.opt_flat[u]
        age = carry.step - store.last_round[u]
        state = DistGANState(
            carry.g, carry.g_opt,
            _restack(d_layout.unflatten(d_row)),
            _restack(o_layout.unflatten(o_row)),
            carry.server_d, carry.step, carry.key)
        if ef:
            new_state, metrics, new_res = inner(state, real, age,
                                                residual=store.residual[u])
        else:
            new_state, metrics = inner(state, real, age)
            new_res = None

        new_d = d_layout.flatten(_unstack(new_state.ds))
        new_o = o_layout.flatten(_unstack(new_state.d_opts))
        onehot = (jnp.zeros((store.num_users, 1), jnp.float32)
                  .at[u, 0].set(1.0))
        part = jax.lax.psum(onehot, AXIS)                    # (U, 1)
        if stage_q:
            # stage_rows: the updated D row crosses the mesh axis as int8
            # + one f32 scale — 4x fewer bytes than the dense f32 psum.
            # Exactly one shard contributes a nonzero row per slot, so
            # the int8 psum is a lossless select of the quantized row.
            scale = jnp.max(jnp.abs(new_d)) / jnp.float32(127.0)
            inv = jnp.where(scale > 0, jnp.float32(1.0) / scale,
                            jnp.float32(0.0))
            q = jnp.clip(jnp.round(new_d * inv), -127, 127).astype(jnp.int8)
            hot = onehot > 0
            q_rows = jax.lax.psum(jnp.where(hot, q[None], jnp.int8(0)),
                                  AXIS)                      # (U, Nd) int8
            scales = jax.lax.psum(
                jnp.where(hot[:, 0], scale, 0.0), AXIS)      # (U,)
            rows_d = q_rows.astype(jnp.float32) * scales[:, None]
        else:
            rows_d = jax.lax.psum(onehot * new_d[None], AXIS)  # (U, Nd)
        rows_o = jax.lax.psum(onehot * new_o[None], AXIS)    # (U, No)
        new_store = CohortStore(
            d_flat=jnp.where(part > 0, rows_d, store.d_flat),
            opt_flat=jnp.where(part > 0, rows_o, store.opt_flat),
            # re-zeroed age convention: stamp round+1 ("trained THROUGH
            # this round"; 0 = never), matching make_cohort_engine and
            # the streaming driver
            last_round=jnp.where(part[:, 0] > 0, carry.step + 1,
                                 store.last_round),
            residual=(None if new_res is None else jnp.where(
                part > 0, jax.lax.psum(onehot * new_res[None], AXIS),
                store.residual)))
        new_carry = CohortState(new_state.g, new_state.g_opt, new_store,
                                new_state.server_d, new_state.step,
                                new_state.key)
        C = jnp.float32(cohort_size)
        metrics = dict(metrics, mean_age=jax.lax.psum(
            age.astype(jnp.float32), AXIS) / C)
        return new_carry, metrics

    return round_fn


def make_spmd_fused_store_round(pair, fcfg: DistGANConfig, approach: str,
                                cohort_size: int):
    """Per-round cohort function over a mesh-SHARDED CohortStore, as run
    INSIDE shard_map.  Where ``make_spmd_cohort_round`` replicates the
    whole (U, N) store on every device (per-device memory bounds U), here
    each of the C mesh slices holds a contiguous U/C-row block and a
    round moves exactly C rows across the axis:

    * gather — every shard contributes the scheduled rows IT owns to a
      one-hot cross-shard psum and slices out its own member's row.  The
      f32 row payloads ride the psum as bitcast int32, so the fold is a
      bit-exact select (a float psum would turn an owned -0.0 into +0.0
      against the zero contributions of the other shards);
    * scatter — each shard broadcasts its updated row the same way, then
      writes the rows it owns back into its local block with a dropped
      out-of-range index for rows owned elsewhere.

    Requires ``U % C == 0`` (the store must shard evenly).  Cohort rows
    are replacement-free per round (core.federated.make_schedule), so
    local writes never collide.  Scan-able:
    ``repro.core.engine.make_spmd_fused_store_engine`` rolls K of these
    into one program — the store stays device-resident AND sharded for
    the whole window.
    """
    from repro.core.engine import CohortState

    inner = make_spmd_body(pair, fcfg, approach, width=cohort_size)
    d_layout = d_flat_layout(pair)
    o_layout = d_opt_flat_layout(pair, fcfg)
    ef = fcfg.codec != "none" and fcfg.error_feedback
    stage_q = fcfg.stage_rows and fcfg.codec in ("int8", "topk_int8")

    def round_fn(carry: CohortState, inp):
        real, idx = inp            # per-shard blocks: (1, B, ...), (1,)
        store = carry.store        # LOCAL block: (Ul, Nd)/(Ul, No)/(Ul,)
        Ul = store.d_flat.shape[0]
        me = jax.lax.axis_index(AXIS)
        all_u = jax.lax.all_gather(idx[0], AXIS)     # (C,) scheduled users
        own = (all_u // Ul) == me                    # mine to serve/write
        loc = jnp.where(own, all_u % Ul, 0)

        def gather(local, f32):
            buf = (jax.lax.bitcast_convert_type(local, jnp.int32)
                   if f32 else local)
            mask = own[:, None] if buf.ndim == 2 else own
            rows = jax.lax.psum(jnp.where(mask, buf[loc], 0), AXIS)
            return (jax.lax.bitcast_convert_type(rows, jnp.float32)
                    if f32 else rows)

        def gather_q(local):
            # stage_rows gather: the owner quantizes its row before the
            # one-hot psum — int8 payload + one f32 scale per row crosses
            # the axis instead of the dense f32 row.  Exactly one shard
            # contributes per slot, so the psum is a lossless select of
            # the (lossy) quantized row.
            rows = local[loc]                            # (C, N) owned rows
            scale = (jnp.max(jnp.abs(rows), axis=1)
                     / jnp.float32(127.0))               # (C,)
            inv = jnp.where(scale > 0, jnp.float32(1.0) / scale,
                            jnp.float32(0.0))
            q = jnp.clip(jnp.round(rows * inv[:, None]),
                         -127, 127).astype(jnp.int8)
            q = jax.lax.psum(jnp.where(own[:, None], q, jnp.int8(0)), AXIS)
            s = jax.lax.psum(jnp.where(own, scale, 0.0), AXIS)
            return q.astype(jnp.float32) * s[:, None]

        rows_d = (gather_q(store.d_flat) if stage_q
                  else gather(store.d_flat, True))   # (C, Nd) replicated
        rows_o = gather(store.opt_flat, True)
        last = gather(store.last_round, False)       # (C,)
        age = carry.step - last[me]
        state = DistGANState(
            carry.g, carry.g_opt,
            _restack(d_layout.unflatten(rows_d[me])),
            _restack(o_layout.unflatten(rows_o[me])),
            carry.server_d, carry.step, carry.key)
        if ef:
            # the EF residual shards with the store and rides the same
            # one-hot transport, always exact f32 (it is the ledger that
            # corrects the lossy transports — quantizing it would break
            # the compensation invariant)
            rows_r = gather(store.residual, True)
            new_state, metrics, new_res = inner(state, real, age,
                                                residual=rows_r[me])
        else:
            new_state, metrics = inner(state, real, age)
            new_res = None

        new_d = d_layout.flatten(_unstack(new_state.ds))
        new_o = o_layout.flatten(_unstack(new_state.d_opts))
        C = all_u.shape[0]

        def bcast(row, f32):
            buf = (jax.lax.bitcast_convert_type(row, jnp.int32)
                   if f32 else row)
            contrib = jnp.zeros((C,) + buf.shape, buf.dtype).at[me].set(buf)
            out = jax.lax.psum(contrib, AXIS)
            return (jax.lax.bitcast_convert_type(out, jnp.float32)
                    if f32 else out)

        def bcast_q(row):
            # stage_rows scatter: broadcast the updated row int8 + scale
            scale = jnp.max(jnp.abs(row)) / jnp.float32(127.0)
            inv = jnp.where(scale > 0, jnp.float32(1.0) / scale,
                            jnp.float32(0.0))
            q = jnp.clip(jnp.round(row * inv), -127, 127).astype(jnp.int8)
            qc = jnp.zeros((C,) + q.shape, jnp.int8).at[me].set(q)
            sc = jnp.zeros((C,), jnp.float32).at[me].set(scale)
            q_all = jax.lax.psum(qc, AXIS)
            s_all = jax.lax.psum(sc, AXIS)
            return q_all.astype(jnp.float32) * s_all[:, None]

        all_nd = (bcast_q(new_d) if stage_q
                  else bcast(new_d, True))           # (C, Nd) replicated
        all_no = bcast(new_o, True)
        sel = jnp.where(own, loc, Ul)     # Ul is out of range -> dropped
        new_store = CohortStore(
            d_flat=store.d_flat.at[sel].set(all_nd, mode="drop"),
            opt_flat=store.opt_flat.at[sel].set(all_no, mode="drop"),
            # same re-zeroed age convention as make_spmd_cohort_round
            last_round=store.last_round.at[sel].set(carry.step + 1,
                                                    mode="drop"),
            residual=(None if new_res is None else
                      store.residual.at[sel].set(bcast(new_res, True),
                                                 mode="drop")))
        new_carry = CohortState(new_state.g, new_state.g_opt, new_store,
                                new_state.server_d, new_state.step,
                                new_state.key)
        metrics = dict(metrics, mean_age=jax.lax.psum(
            age.astype(jnp.float32), AXIS) / jnp.float32(cohort_size))
        return new_carry, metrics

    return round_fn


def make_spmd_cohort_rows_engine(pair, fcfg: DistGANConfig, mesh,
                                 approach: str, cohort_size: int):
    """Host-backend feed for the mesh-mapped cohort engine: the scheduled
    cohort's rows arrive SHARDED over the ``users`` mesh axis (one member
    per slice) and stream back the same way — no (U, N) store exists on
    device at all, replicated or otherwise.  Where
    ``make_spmd_cohort_engine`` replicates the whole store on every
    device (U bounded by per-device memory), this engine pairs with a
    host UserStateBackend via ``core.session.stream_cohort_rounds``: U
    is bounded by host RAM and each round moves C rows across the
    host<->device boundary, C/devices rows per device.

    Same call signature as ``make_cohort_rows_engine``:
    ``eng(shared, d_rows, opt_rows, ages, wts, real) ->
    (shared, new_d_rows, new_opt_rows, metrics)`` with the row/age/real
    inputs sharded over the mesh axis and the CohortShared carry
    replicated (donated, so it chains in place across rounds).
    """
    from repro.core.engine import CohortShared

    axis_size = mesh.shape[AXIS]
    assert axis_size == cohort_size, (
        f"cohort must equal the '{AXIS}' mesh axis (C={cohort_size}, "
        f"axis={axis_size})")
    inner = make_spmd_body(pair, fcfg, approach, width=cohort_size)
    d_layout = d_flat_layout(pair)
    o_layout = d_opt_flat_layout(pair, fcfg)
    ef = fcfg.codec != "none" and fcfg.error_feedback

    def _specs(shared, wts):
        rep = lambda tree: jax.tree.map(lambda _: PS(), tree)
        shared_specs = CohortShared(
            g=rep(shared.g), g_opt=rep(shared.g_opt),
            server_d=rep(shared.server_d), step=PS(), key=PS())
        metric_specs = {"d_loss": PS(AXIS), "g_loss": PS(),
                        "kept_frac": PS(), "mean_age": PS()}
        w_spec = None if wts is None else PS(AXIS)
        return shared_specs, metric_specs, w_spec

    if ef:
        # EF variant: the residual rows stream through the mesh exactly
        # like the d/opt rows — same signature as the host rows engine's
        # EF form, so stream_cohort_rounds drives both identically
        def round_fn_ef(shared: "CohortShared", d_rows, o_rows, res_rows,
                        ages, wts, real):
            state = DistGANState(
                shared.g, shared.g_opt,
                _restack(d_layout.unflatten(d_rows[0])),
                _restack(o_layout.unflatten(o_rows[0])),
                shared.server_d, shared.step, shared.key)
            w = None if wts is None else wts[0]
            new_state, metrics, new_res = inner(state, real, ages[0], w,
                                                residual=res_rows[0])
            new_shared = CohortShared(new_state.g, new_state.g_opt,
                                      new_state.server_d, new_state.step,
                                      new_state.key)
            nd = d_layout.flatten(_unstack(new_state.ds))[None]
            no = o_layout.flatten(_unstack(new_state.d_opts))[None]
            C = jnp.float32(cohort_size)
            metrics = dict(metrics, mean_age=jax.lax.psum(
                ages[0].astype(jnp.float32), AXIS) / C)
            return new_shared, nd, no, new_res[None], metrics

        def step_ef(shared, d_rows, o_rows, res_rows, ages, wts, real):
            shared_specs, metric_specs, w_spec = _specs(shared, wts)
            fn = jax.shard_map(
                round_fn_ef, mesh=mesh, check_vma=False,
                in_specs=(shared_specs, PS(AXIS), PS(AXIS), PS(AXIS),
                          PS(AXIS), w_spec, PS(AXIS)),
                out_specs=(shared_specs, PS(AXIS), PS(AXIS), PS(AXIS),
                           metric_specs))
            return fn(shared, d_rows, o_rows, res_rows, ages, wts, real)

        return jax.jit(step_ef, donate_argnums=(0, 1, 2, 3))

    def round_fn(shared: "CohortShared", d_rows, o_rows, ages, wts, real):
        # per-shard blocks: d_rows (1, Nd), o_rows (1, No), ages (1,),
        # wts (1,) | None, real (1, B, ...)
        state = DistGANState(
            shared.g, shared.g_opt,
            _restack(d_layout.unflatten(d_rows[0])),
            _restack(o_layout.unflatten(o_rows[0])),
            shared.server_d, shared.step, shared.key)
        w = None if wts is None else wts[0]
        new_state, metrics = inner(state, real, ages[0], w)
        new_shared = CohortShared(new_state.g, new_state.g_opt,
                                  new_state.server_d, new_state.step,
                                  new_state.key)
        nd = d_layout.flatten(_unstack(new_state.ds))[None]
        no = o_layout.flatten(_unstack(new_state.d_opts))[None]
        C = jnp.float32(cohort_size)
        metrics = dict(metrics, mean_age=jax.lax.psum(
            ages[0].astype(jnp.float32), AXIS) / C)
        return new_shared, nd, no, metrics

    def step(shared, d_rows, o_rows, ages, wts, real):
        shared_specs, metric_specs, w_spec = _specs(shared, wts)
        fn = jax.shard_map(
            round_fn, mesh=mesh, check_vma=False,
            in_specs=(shared_specs, PS(AXIS), PS(AXIS), PS(AXIS), w_spec,
                      PS(AXIS)),
            out_specs=(shared_specs, PS(AXIS), PS(AXIS), metric_specs))
        return fn(shared, d_rows, o_rows, ages, wts, real)

    return jax.jit(step, donate_argnums=(0, 1, 2))


# ---------------------------------------------------------------------------
# Spec-layer registration: the "spmd" streaming backend
# ---------------------------------------------------------------------------

from repro.core.session import HostStreamDriver as _HostStreamDriver  # noqa: E402,I001
from repro.core.spec import register_backend  # noqa: E402


class SpmdStreamDriver(_HostStreamDriver):
    """Streaming backend with the cohort mapped onto the mesh ``users``
    axis: the per-user store lives in the host backend exactly as for
    ``BackendSpec(kind="host")``, but each round's C gathered rows arrive
    SHARDED over the mesh (one member per slice) through
    ``make_spmd_cohort_rows_engine`` — no (U, N) device buffer exists,
    replicated or otherwise, and the device count bounds C.  Requires
    ``FederationSession(..., mesh=...)`` with a ``users`` axis equal to
    the cohort size."""

    backend_name = "spmd"

    def _make_engine(self):
        sess = self.sess
        if sess.mesh is None:
            raise ValueError(
                "BackendSpec(kind='spmd') needs FederationSession(mesh=...) "
                "with a 'users' axis equal to the cohort size")
        if sess.spec.approach not in ("approach1", "approach2", "approach3"):
            raise ValueError(
                f"the SPMD body families cover approach1/2/3; got "
                f"{sess.spec.approach!r}")
        return make_spmd_cohort_rows_engine(sess.pair, sess.fcfg, sess.mesh,
                                            sess.spec.approach,
                                            sess.cohort_size)


register_backend("spmd", SpmdStreamDriver, streams=True)


def make_spmd_step(pair, fcfg: DistGANConfig, mesh, approach: str):
    """Returns a jit'd SPMD step: (state, real (U,B,...)) -> (state, metrics).

    ``real`` is sharded over the users axis on dim 0.  The state is
    donated, so the per-user D/optimizer shards update in place.
    """
    body = make_spmd_body(pair, fcfg, approach)

    def step(state, real):
        state_specs = _specs_for(state, mesh)
        metric_specs = {"d_loss": PS(AXIS), "g_loss": PS(),
                        "kept_frac": PS()}
        fn = jax.shard_map(body, mesh=mesh, check_vma=False,
                           in_specs=(state_specs, PS(AXIS)),
                           out_specs=(state_specs, metric_specs))
        return fn(state, real)

    return jax.jit(step, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Static-analysis introspection (consumed by repro.analysis.tracecheck)
# ---------------------------------------------------------------------------

def spmd_trace_specimens(pair, fcfg: DistGANConfig, mesh, *,
                         approaches=None, rounds: int = 2, batch: int = 4):
    """Yield every SPMD engine family as a ``TraceSpecimen`` (see
    ``core.engine``) for the approaches the mesh bodies cover.  The SPMD
    bodies carry no ``_pin`` barriers — their reproducibility contract is
    the psum/one-hot gather structure, not barrier pins — so
    ``min_barriers`` is 0 throughout; the donation split restates each
    factory's contract (plain/rows carries donated, the two cohort
    store engines deliberately NOT — the bitwise-pin copies)."""
    import numpy as np

    from repro.core.engine import (CohortShared, TraceSpecimen, _sample_shape,
                                   init_cohort_state, init_state,
                                   make_spmd_cohort_engine,
                                   make_spmd_fused_store_engine)
    from repro.core.engine import make_spmd_engine as _mk_spmd_engine
    from repro.core.spec import resolve_approach

    spmd_capable = ("approach1", "approach2", "approach3")
    names = tuple(approaches) if approaches else spmd_capable
    K, B = rounds, batch
    U = C = mesh.shape[AXIS]
    fcfg = dataclasses.replace(fcfg, num_users=U)
    shape = _sample_shape(pair)
    dl = d_flat_layout(pair)
    ol = d_opt_flat_layout(pair, fcfg)
    ef = fcfg.codec != "none" and fcfg.error_feedback
    valid = np.ones((K,), bool)

    for name in names:
        if name not in spmd_capable:
            continue
        appr = resolve_approach(name)
        key = jax.random.key(0)
        state = init_state(pair, fcfg, key, sync_ds=appr.sync_ds)
        reals = np.zeros((K, U, B) + shape, np.float32)
        if not ef:
            yield TraceSpecimen(
                f"{name}/spmd", _mk_spmd_engine(pair, fcfg, mesh, name),
                (state, reals, valid), donate=(0,), min_barriers=0)
            yield TraceSpecimen(
                f"{name}/spmd_step", make_spmd_step(pair, fcfg, mesh, name),
                (state, reals[0]), donate=(0,), min_barriers=0,
                expect_scan=False)

        cstate = init_cohort_state(pair, fcfg, key, sync_ds=appr.sync_ds)
        idx = np.tile(np.arange(C, dtype=np.int32), (K, 1))
        yield TraceSpecimen(
            f"{name}/spmd_cohort",
            make_spmd_cohort_engine(pair, fcfg, mesh, name, C),
            (cstate, reals, idx, valid), donate=(), min_barriers=0)
        yield TraceSpecimen(
            f"{name}/spmd_fused_store",
            make_spmd_fused_store_engine(pair, fcfg, mesh, name, C),
            (cstate, reals, idx, valid), donate=(), min_barriers=0)

        shared = CohortShared(state.g, state.g_opt, state.server_d,
                              state.step, state.key)
        ages = np.zeros((C,), np.int32)
        d_rows = np.zeros((C, dl.n), np.float32)
        o_rows = np.zeros((C, ol.n), np.float32)
        rows_eng = make_spmd_cohort_rows_engine(pair, fcfg, mesh, name, C)
        if ef:
            res = np.zeros((C, dl.n), np.float32)
            yield TraceSpecimen(
                f"{name}/spmd_rows_ef", rows_eng,
                (shared, d_rows, o_rows, res, ages, None, reals[0]),
                donate=(0, 1, 2, 3), min_barriers=0, expect_scan=False)
        else:
            yield TraceSpecimen(
                f"{name}/spmd_rows", rows_eng,
                (shared, d_rows, o_rows, ages, None, reals[0]),
                donate=(0, 1, 2), min_barriers=0, expect_scan=False)
