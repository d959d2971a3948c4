"""Fused round engine: K federation rounds compiled into ONE XLA program.

The per-step harness pays Python dispatch, host round-trips, and jit-call
overhead on every single round, so measured wall-clock reflects the
interpreter, not the algorithm (the same effect MD-GAN and BGAN report
for per-round orchestration cost).  The engine removes that overhead
structurally:

* the round body (``BODY_FACTORIES[approach]``) is rolled over a
  ``(K, ...)`` stack of pre-staged real batches with ``jax.lax.scan`` —
  one compile, one dispatch per K rounds;
* the carried state is donated (``donate_argnums=(0,)``) so the stacked
  discriminator/optimizer buffers update in place across chunks;
* metrics come back K-stacked and are fetched with a single host sync per
  chunk instead of one per round.

PRNG folding goes through ``state.key`` exactly as in the per-step path,
so the scanned trajectory is bit-identical to the Python loop (pinned by
tests/test_engine.py).

Every engine takes an optional ``valid (K,) bool`` argument: rounds
flagged invalid leave the carry untouched (their metrics are garbage and
must be sliced off by the caller).  ``run_scanned`` uses this to pad the
trailing remainder chunk to a full ``rounds_per_jit`` rounds, so ANY
``steps % rounds_per_jit`` compiles exactly one program.  A valid round's
update is a ``jnp.where(True, new, old)`` — an exact select, so masking
never perturbs trajectories.  Engines whose carry is rewritten whole
every round select over the whole carry (``_masked``); the store-resident
cohort engines mask at row granularity instead: an invalid round writes
its C gathered rows back unchanged and selects only the small replicated
leaves, so no round touches more of the (U, N) store than its C rows.

Cohort virtualization (``make_cohort_engine``): a run can have U LOGICAL
users while the compiled program is shaped only by a cohort width C <= U.
The (U, N) per-user D/optimizer state lives in a ``CohortStore`` carried
through the scan; each round gathers the scheduled cohort's C rows,
runs the width-C body, and scatters the updated rows back (stamping
``last_round`` for the staleness-aware combiners).  With C == U and the
``full`` scheduler the gather/scatter is an exact permutation, so the
trajectory stays bit-identical to the non-virtualized engine (pinned by
tests/test_engine.py).

Use ``make_engine`` for the host-simulated stacked-user layout and
``make_spmd_engine`` for the mesh-mapped layout (scan *inside*
``shard_map``: collectives stay per-round, dispatch is per-chunk);
``make_spmd_cohort_engine`` maps the COHORT onto the mesh axis, so the
device count bounds C — not U.

Streamed residency (``make_cohort_rows_engine`` + ``init_host_backend``):
the (U, N) store leaves the device entirely — it lives in a host
``UserStateBackend`` and each round's dispatch consumes only the
gathered C rows, so U is bounded by host RAM (driven by
``core.session.stream_cohort_rounds``, which double-buffers staging and
offers async bounded-staleness rounds).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.approaches import (DistGANConfig, DistGANState, _opts,
                                   d_flat_layout, d_opt_flat_layout,
                                   init_state)
from repro.core.federated import (CohortStore, HostStateBackend,
                                  cohort_scatter, make_cohort_store,
                                  take_rows)
from repro.core.spec import DEFAULT_ROUNDS_PER_JIT, resolve_approach


def _masked(body):
    """Wrap a scan body so rounds with ``valid=False`` leave the carry
    untouched, by a select over EVERY carry leaf.  ``jnp.where`` on a
    scalar bool is an exact select: with ``valid=True`` the output is
    bitwise the unmasked result.  For carries the body rewrites whole
    every round; the cohort store engines mask their C rows instead
    (``_cohort_round_fn``), since a whole-carry select there is a pass
    over the (U, N) store each round."""

    def wrapped(carry, inp):
        xs, valid = inp
        new_carry, metrics = body(carry, xs)
        keep = lambda n, o: jnp.where(valid, n, o)
        with jax.named_scope("fed.window_mask"):
            new_carry = jax.tree.map(keep, new_carry, carry)
        return new_carry, metrics

    return wrapped


def make_engine(pair, fcfg: DistGANConfig, approach: str) -> Callable:
    """Scan-fused multi-round step for the host-simulated layout.

    Returns ``chunk(state, reals, valid=None) -> (state, metrics)`` where
    ``reals`` is ``(K, U, B, ...)`` (``(K, B, ...)`` for the baseline) and
    every metric leaf gains a leading K axis.  K is a trace-time constant:
    driving with a fixed ``rounds_per_jit`` reuses one compiled program
    for all full chunks; padded+masked calls (``valid`` given) reuse one
    program for EVERY chunk, remainder included.
    """
    body = resolve_approach(approach).body_factory(pair, fcfg)

    def chunk(state: DistGANState, reals, valid=None):
        if valid is None:
            return jax.lax.scan(body, state, reals)
        return jax.lax.scan(_masked(body), state, (reals, valid))

    return jax.jit(chunk, donate_argnums=(0,))


def make_spmd_engine(pair, fcfg: DistGANConfig, mesh, approach: str):
    """Scan-fused multi-round step for the SPMD (mesh-mapped) layout.

    The scan sits INSIDE shard_map, so per-round collectives (delta folds,
    logit pmeans) compile into one program; ``reals`` is ``(K, U, B, ...)``
    sharded over users on dim 1.  ``valid (K,) bool`` is replicated.
    """
    from jax.sharding import PartitionSpec as PS

    from repro.core.spmd import AXIS, _specs_for, make_spmd_body

    body = make_spmd_body(pair, fcfg, approach)

    def chunk(state: DistGANState, reals, valid=None):
        state_specs = _specs_for(state, mesh)
        metric_specs = {"d_loss": PS(None, AXIS), "g_loss": PS(),
                        "kept_frac": PS()}

        if valid is None:
            def scanned(st, rs):
                return jax.lax.scan(body, st, rs)
            in_specs = (state_specs, PS(None, AXIS))
        else:
            def scanned(st, rs, vs):
                return jax.lax.scan(_masked(body), st, (rs, vs))
            in_specs = (state_specs, PS(None, AXIS), PS())

        fn = jax.shard_map(scanned, mesh=mesh, in_specs=in_specs,
                           out_specs=(state_specs, metric_specs),
                           check_vma=False)
        return fn(state, reals) if valid is None else fn(state, reals, valid)

    return jax.jit(chunk, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Cohort-virtualized engine: U logical users, C-wide compiled program
# ---------------------------------------------------------------------------

class CohortState(NamedTuple):
    """Scan carry for the cohort engine: shared (replicated) training state
    plus the resident per-user CohortStore."""

    g: jnp.ndarray
    g_opt: jnp.ndarray
    store: CohortStore
    server_d: jnp.ndarray
    step: jnp.ndarray
    key: jnp.ndarray


def _wants_residual(fcfg: DistGANConfig) -> bool:
    """Whether the configured transport keeps per-user error-feedback
    rows: a lossy codec with error_feedback on.  The ONE gate every
    engine/driver consults, so the residual is threaded (or absent)
    consistently across device, host, and SPMD paths."""
    return fcfg.codec != "none" and fcfg.error_feedback


def init_cohort_state(pair, fcfg: DistGANConfig, key, *,
                      sync_ds: bool = False) -> CohortState:
    """Build the cohort carry from the standard ``init_state`` layout (the
    (U, ...)-stacked trees are packed into flat buffers; values transfer
    bit-exactly, so a C==U cohort run starts from the identical point)."""
    st = init_state(pair, fcfg, key, sync_ds=sync_ds)
    store = make_cohort_store(st.ds, st.d_opts, d_flat_layout(pair),
                              d_opt_flat_layout(pair, fcfg),
                              error_feedback=_wants_residual(fcfg))
    return CohortState(st.g, st.g_opt, store, st.server_d, st.step, st.key)


def store_ds(pair, store: CohortStore):
    """All U discriminator rows of the store as the stacked (U, ...) tree
    (each leaf a column slice of ``d_flat``: bitwise the rows)."""
    return d_flat_layout(pair).unflatten_stacked(store.d_flat)


def store_d_opts(pair, fcfg: DistGANConfig, store: CohortStore):
    """All U optimizer rows of the store as the stacked (U, ...) tree."""
    return d_opt_flat_layout(pair, fcfg).unflatten_stacked(store.opt_flat)


def cohort_state_to_full(pair, fcfg: DistGANConfig,
                         cstate: CohortState) -> DistGANState:
    """Unpack the store back into the stacked-tree DistGANState layout
    (evaluation / checkpointing interop)."""
    return DistGANState(cstate.g, cstate.g_opt, store_ds(pair, cstate.store),
                        store_d_opts(pair, fcfg, cstate.store),
                        cstate.server_d, cstate.step, cstate.key)


def _cohort_round_fn(pair, fcfg: DistGANConfig, approach: str) -> Callable:
    """One store-resident cohort round: read the scheduled rows, run the
    width-C body, scatter the updated rows back (stamping ``last_round``).
    Shared by ``make_cohort_engine`` and ``make_fused_store_engine`` —
    the two jits trace the IDENTICAL program and differ only in carry
    donation.

    ``inp`` is ``(real, idx, w, valid)``; ``w`` and ``valid`` may be None.
    With ``valid`` the round masks at row granularity: an invalid round
    scatters the C rows it read back unchanged (every duplicate index
    writes the same old row), stamps ``last_round`` with its old value,
    and keeps the replicated leaves — bitwise a no-op on the store, while
    a valid round's rows are the selected ones bitwise.  Every per-round
    access to the (U, N) buffers is thus a C-row access."""
    appr = resolve_approach(approach)
    assert appr.user_axis, f"{approach} has no user axis to virtualize"
    body = appr.body_factory(pair, fcfg)
    d_layout = d_flat_layout(pair)
    o_layout = d_opt_flat_layout(pair, fcfg)
    ef = _wants_residual(fcfg)

    def round_fn(carry: CohortState, inp):
        real, idx, w, valid = inp
        store = carry.store
        with jax.named_scope("fed.store_gather"):
            # C row slices, not ``cohort_gather``'s XLA gather (see
            # take_rows); error-feedback rows ride the same read/scatter
            # as the D rows: user-local state, visible only to its rounds
            ds = d_layout.unflatten_stacked(take_rows(store.d_flat, idx))
            opts = o_layout.unflatten_stacked(take_rows(store.opt_flat, idx))
            res = take_rows(store.residual, idx) if ef else None
        # materialize the gathered slices: without the barrier XLA may fuse
        # the gather/unflatten into the body's loss reductions and change
        # their tiling, breaking ULP-equality with the non-virtualized
        # engine (the C == U bitwise pin in tests/test_engine.py)
        ds, opts = jax.lax.optimization_barrier((ds, opts))
        ages = carry.step - store.last_round[idx]          # (C,) i32
        state = DistGANState(carry.g, carry.g_opt, ds, opts, carry.server_d,
                             carry.step, carry.key)
        if ef:
            new_state, metrics, new_res = body(state, real, ages, w, res)
        else:
            new_state, metrics = body(state, real, ages, w)
            new_res = None
        # same reasoning on the way out: keep the scatter's flatten from
        # fusing back into the body's update/loss clusters
        nds, nopts = jax.lax.optimization_barrier(
            (new_state.ds, new_state.d_opts))
        # last_round records the round a member has trained THROUGH, as
        # round+1 (0 = never trained): a member drawn again next round
        # carries age step - last_round == 0 — the re-zeroed age
        # convention (fresh folds are no longer uniformly discounted by
        # one decay factor by the staleness combiners)
        stamp = carry.step + 1
        shared = (new_state.g, new_state.g_opt, new_state.server_d,
                  new_state.step, new_state.key)
        if valid is not None:
            with jax.named_scope("fed.window_mask"):
                keep = lambda n, o: jnp.where(valid, n, o)
                nds, nopts, new_res = jax.tree.map(
                    keep, (nds, nopts, new_res), (ds, opts, res))
                stamp = jnp.where(valid, stamp, store.last_round[idx])
                shared = jax.tree.map(keep, shared, (
                    carry.g, carry.g_opt, carry.server_d, carry.step,
                    carry.key))
        with jax.named_scope("fed.store_scatter"):
            store = cohort_scatter(store, idx, nds, nopts, stamp,
                                   d_layout, o_layout, residual=new_res)
        g, g_opt, server_d, step, key = shared
        new_carry = CohortState(g, g_opt, store, server_d, step, key)
        metrics = dict(metrics, mean_age=jnp.mean(ages.astype(jnp.float32)))
        return new_carry, metrics

    return round_fn


def _cohort_chunk(round_fn, adaptive: bool) -> Callable:
    """``chunk(cstate, reals, idx, wts=None, valid=None)``: one scan of
    ``round_fn`` over the window, which masks its own padded rounds."""

    def chunk(cstate: CohortState, reals, idx, wts=None, valid=None):
        assert (wts is not None) == adaptive, \
            "wts must be supplied iff the engine was built adaptive=True"
        return jax.lax.scan(round_fn, cstate, (reals, idx, wts, valid))

    return chunk


def make_cohort_engine(pair, fcfg: DistGANConfig, approach: str,
                       adaptive: bool = False) -> Callable:
    """Scan-fused cohort engine for the host-simulated layout.

    Returns ``chunk(cstate, reals, idx, wts=None, valid=None)`` with
    ``reals (K, C, B, ...)`` the scheduled cohorts' private batches and
    ``idx (K, C) int32`` the cohort membership per round.  Per round the
    body sees ONLY the gathered C rows — the compiled program is shaped by
    C, while U merely sizes the resident (U, N) buffers (gather/scatter
    touch C rows; XLA updates the donated store in place).

    ``adaptive=True`` additionally scans ``wts (K, C) f32`` — host-derived
    participation-adaptive combine weights
    (core.federated.participation_weights) forwarded to the round body.
    The flag gates the extra input so the default path traces the EXACT
    program pinned bitwise against the plain fused engine.
    """
    chunk = _cohort_chunk(_cohort_round_fn(pair, fcfg, approach), adaptive)

    # NOT donated: in-place scatter into a donated (U, N) carry lets XLA
    # reschedule the update clusters and the trajectory drifts at ULP from
    # the non-virtualized engine, breaking the C == U bitwise pin.  The
    # cost is one store copy per CHUNK (amortized over rounds_per_jit).
    return jax.jit(chunk)


def make_fused_store_engine(pair, fcfg: DistGANConfig, approach: str,
                            adaptive: bool = False) -> Callable:
    """Store-resident fused window engine: ``make_cohort_engine``'s EXACT
    trace — K read→train→scatter rounds in one ``lax.scan`` over the
    resident (U, N) store, padded rounds masked on their C rows — with
    the carry DONATED, so XLA scatters the cohort rows into the store in
    place.  Every per-round store access is a C-row access (row reads,
    row-granular mask, row updates), so a round costs C rows of store
    traffic whatever U is.  One dispatch per window, zero host traffic,
    and no per-chunk (U, N) store copy: at U=4096 the copy is the
    dominant per-window cost of the non-donated engine, which is kept
    solely for its C == U bitwise pin against the non-virtualized engine
    (see the donation note there).

    The caller must treat the passed ``cstate`` as consumed (rebind to
    the returned carry — ``core.session._drive_chunks`` already does).
    Trajectory contract (measured, tests/test_fused_store.py): the
    donated program is deterministic (re-runs are bitwise) and
    ``last_round`` stamping is exact, but in-place aliasing lets XLA
    reschedule the update clusters, so values drift from the non-donated
    engine at ULP — pinned at atol=1e-6 per round, the same contract the
    per-round rows path carries (an extra optimization_barrier on the
    store does NOT recover bitwise; probed empirically).
    """
    chunk = _cohort_chunk(_cohort_round_fn(pair, fcfg, approach), adaptive)
    return jax.jit(chunk, donate_argnums=(0,))


def make_spmd_cohort_engine(pair, fcfg: DistGANConfig, mesh, approach: str,
                            cohort_size: int):
    """Cohort engine with the COHORT mapped onto the mesh ``users`` axis:
    one cohort member per device slice, so the device count bounds C while
    U is just the row count of the replicated CohortStore.  The scan sits
    inside shard_map as in ``make_spmd_engine``.
    """
    from jax.sharding import PartitionSpec as PS

    from repro.core.spmd import AXIS, make_spmd_cohort_round

    axis_size = mesh.shape[AXIS]
    assert axis_size == cohort_size, (
        f"cohort must equal the '{AXIS}' mesh axis (C={cohort_size}, "
        f"axis={axis_size})")
    round_fn = make_spmd_cohort_round(pair, fcfg, approach, cohort_size)

    def chunk(cstate: CohortState, reals, idx, valid=None):
        rep = lambda tree: jax.tree.map(lambda _: PS(), tree)
        carry_specs = CohortState(
            g=rep(cstate.g), g_opt=rep(cstate.g_opt),
            store=CohortStore(PS(), PS(), PS(),
                              None if cstate.store.residual is None
                              else PS()),
            server_d=rep(cstate.server_d), step=PS(), key=PS())
        metric_specs = {"d_loss": PS(None, AXIS), "g_loss": PS(),
                        "kept_frac": PS(), "mean_age": PS()}

        if valid is None:
            def scanned(st, rs, ix):
                return jax.lax.scan(round_fn, st, (rs, ix))
            in_specs = (carry_specs, PS(None, AXIS), PS(None, AXIS))
            args = (cstate, reals, idx)
        else:
            def scanned(st, rs, ix, vs):
                return jax.lax.scan(_masked(round_fn), st, ((rs, ix), vs))
            in_specs = (carry_specs, PS(None, AXIS), PS(None, AXIS), PS())
            args = (cstate, reals, idx, valid)

        fn = jax.shard_map(scanned, mesh=mesh, in_specs=in_specs,
                           out_specs=(carry_specs, metric_specs),
                           check_vma=False)
        return fn(*args)

    return jax.jit(chunk)  # not donated — see make_cohort_engine


def make_spmd_fused_store_engine(pair, fcfg: DistGANConfig, mesh,
                                 approach: str, cohort_size: int):
    """Store-resident SPMD cohort engine over a mesh-SHARDED store: each
    of the C mesh slices holds U/C rows of the CohortStore and a round's
    gather/scatter moves exactly C rows across the axis as bitcast-int32
    one-hot psums (bit-exact — see ``make_spmd_fused_store_round``).
    Same signature as ``make_spmd_cohort_engine``; per-device store
    memory drops from U·N to (U/C)·N, so U scales with the MESH instead
    of a single device.  Requires ``U % C == 0``.
    """
    from jax.sharding import PartitionSpec as PS

    from repro.core.spmd import AXIS, make_spmd_fused_store_round

    axis_size = mesh.shape[AXIS]
    assert axis_size == cohort_size, (
        f"cohort must equal the '{AXIS}' mesh axis (C={cohort_size}, "
        f"axis={axis_size})")
    round_fn = make_spmd_fused_store_round(pair, fcfg, approach, cohort_size)

    def chunk(cstate: CohortState, reals, idx, valid=None):
        U = cstate.store.num_users
        assert U % axis_size == 0, (
            f"the sharded store needs U % C == 0 (U={U}, C={axis_size}); "
            f"use make_spmd_cohort_engine (replicated store) otherwise")
        rep = lambda tree: jax.tree.map(lambda _: PS(), tree)
        carry_specs = CohortState(
            g=rep(cstate.g), g_opt=rep(cstate.g_opt),
            store=CohortStore(PS(AXIS), PS(AXIS), PS(AXIS),
                              None if cstate.store.residual is None
                              else PS(AXIS)),
            server_d=rep(cstate.server_d), step=PS(), key=PS())
        metric_specs = {"d_loss": PS(None, AXIS), "g_loss": PS(),
                        "kept_frac": PS(), "mean_age": PS()}

        if valid is None:
            def scanned(st, rs, ix):
                return jax.lax.scan(round_fn, st, (rs, ix))
            in_specs = (carry_specs, PS(None, AXIS), PS(None, AXIS))
            args = (cstate, reals, idx)
        else:
            def scanned(st, rs, ix, vs):
                return jax.lax.scan(_masked(round_fn), st, ((rs, ix), vs))
            in_specs = (carry_specs, PS(None, AXIS), PS(None, AXIS), PS())
            args = (cstate, reals, idx, valid)

        fn = jax.shard_map(scanned, mesh=mesh, in_specs=in_specs,
                           out_specs=(carry_specs, metric_specs),
                           check_vma=False)
        return fn(*args)

    return jax.jit(chunk)  # not donated — see make_cohort_engine


# ---------------------------------------------------------------------------
# Streamed cohort engine: rows live in a UserStateBackend, not the carry
# ---------------------------------------------------------------------------
#
# The scan-fused cohort engine above keeps the full (U, N) store in its
# device carry, so U is still bounded by accelerator memory.  The rows
# engine inverts the residency: the store lives in a host (or device)
# UserStateBackend, and ONE round's dispatch consumes only the gathered
# cohort rows — (C, Nd)/(C, No) buffers that crossed the host<->device
# boundary via jax.device_put.  Only the replicated training state
# (CohortShared) chains device-side between dispatches, so the driver
# (core.session.stream_cohort_rounds) can overlap round k's compute with
# round k+1's staging, and — in async bounded-staleness mode — defer
# round k's scatter-back past round k+1's launch.

class CohortShared(NamedTuple):
    """Replicated training state carried across streamed rounds.  The
    per-user rows are NOT here — they live in a UserStateBackend and
    enter each round as explicit gathered-row arguments."""

    g: jnp.ndarray
    g_opt: jnp.ndarray
    server_d: jnp.ndarray
    step: jnp.ndarray
    key: jnp.ndarray


def make_cohort_rows_engine(pair, fcfg: DistGANConfig,
                            approach: str) -> Callable:
    """One-round engine over gathered cohort rows.

    Returns ``round(shared, d_rows, opt_rows, ages, wts, real) ->
    (shared, new_d_rows, new_opt_rows, metrics)`` with ``d_rows (C, Nd)``
    / ``opt_rows (C, No)`` the cohort's FlatLayout rows, ``ages (C,)
    int32`` participation ages, ``wts (C,) f32 | None`` the optional
    adaptive combine weights, and ``real (C, B, ...)`` the members'
    private batches.  ``d_rows`` and ``opt_rows`` are donated (they are
    per-round transfers); the shared carry is not — see the donation
    note at the jit below.

    The same optimization barriers as ``make_cohort_engine`` pin the
    body's update clusters, so a synchronous streamed run reproduces the
    store-carry engine's trajectory to within 1 ULP per round (the scan-
    embedded and standalone programs still tile a handful of reductions
    differently — pinned at atol=1e-6 in tests/test_stream.py; the PR 2
    bitwise contract binds the DEVICE backend, which is untouched).
    """
    appr = resolve_approach(approach)
    assert appr.user_axis, f"{approach} has no user axis to virtualize"
    body = appr.body_factory(pair, fcfg)
    d_layout = d_flat_layout(pair)
    o_layout = d_opt_flat_layout(pair, fcfg)

    if _wants_residual(fcfg):
        # error-feedback variant: the cohort's residual rows arrive (and
        # return) as one more donated (C, Nd) transfer, right after the
        # opt rows — ``round(shared, d_rows, opt_rows, res_rows, ages,
        # wts, real) -> (shared, nd, no, new_res, metrics)``
        def round_fn_ef(shared: CohortShared, d_rows, opt_rows, res_rows,
                        ages, wts, real):
            ds = d_layout.unflatten_stacked(d_rows)
            opts = o_layout.unflatten_stacked(opt_rows)
            ds, opts = jax.lax.optimization_barrier((ds, opts))
            state = DistGANState(shared.g, shared.g_opt, ds, opts,
                                 shared.server_d, shared.step, shared.key)
            new_state, metrics, new_res = body(state, real, ages, wts,
                                               res_rows)
            nds, nopts = jax.lax.optimization_barrier(
                (new_state.ds, new_state.d_opts))
            new_shared = CohortShared(new_state.g, new_state.g_opt,
                                      new_state.server_d, new_state.step,
                                      new_state.key)
            metrics = dict(metrics,
                           mean_age=jnp.mean(ages.astype(jnp.float32)))
            return (new_shared, d_layout.flatten_stacked(nds),
                    o_layout.flatten_stacked(nopts), new_res, metrics)

        return jax.jit(round_fn_ef, donate_argnums=(1, 2, 3))

    def round_fn(shared: CohortShared, d_rows, opt_rows, ages, wts, real):
        ds = d_layout.unflatten_stacked(d_rows)
        opts = o_layout.unflatten_stacked(opt_rows)
        ds, opts = jax.lax.optimization_barrier((ds, opts))
        state = DistGANState(shared.g, shared.g_opt, ds, opts,
                             shared.server_d, shared.step, shared.key)
        new_state, metrics = body(state, real, ages, wts)
        nds, nopts = jax.lax.optimization_barrier(
            (new_state.ds, new_state.d_opts))
        new_shared = CohortShared(new_state.g, new_state.g_opt,
                                  new_state.server_d, new_state.step,
                                  new_state.key)
        metrics = dict(metrics, mean_age=jnp.mean(ages.astype(jnp.float32)))
        return (new_shared, d_layout.flatten_stacked(nds),
                o_layout.flatten_stacked(nopts), metrics)

    # rows are donated (fresh per-round transfers; XLA updates them in
    # place).  The shared carry is NOT: donating it lets XLA reschedule
    # the G-update clusters and the trajectory drifts at ULP from the
    # store-carry cohort engine (same effect as the non-donated cohort
    # carry — see make_cohort_engine).  The per-round copy is one G/opt/
    # server-D tree, amortized noise next to the round's compute.
    return jax.jit(round_fn, donate_argnums=(1, 2))


def make_superbatch_engine(pair, fcfg: DistGANConfig, approach: str,
                           adaptive: bool = False) -> Callable:
    """Windowed superbatch engine for host-resident stores: a whole
    K-round window over ONE staged row block, dispatched once.

    The per-round rows engine pays a host gather, a dispatch, and a
    blocking scatter-back per round.  Here the driver gathers the
    window's scheduled rows as a ``(K, C, N)`` block in one host pass and
    this engine scans the K rounds over it IN-PROGRAM, so the host stalls
    once per window instead of once per round.

    Returns ``window(shared, blk_d, blk_o, fwd, ages, real, wts=None,
    valid=None) -> (shared, blk_d, blk_o, metrics)``:

    * ``blk_d (K, C, Nd)`` / ``blk_o (K, C, No)`` — the scheduled rows,
      gathered host-side BEFORE the window ran (stale for users that
      repeat inside the window).  Donated; row r is overwritten with
      round r's updated rows, so the returned block is what the host
      scatters back — in round order, last-writer-wins.
    * ``fwd (K, C) int32`` — write-after-read forwarding plan
      (``core.federated.window_forwarding``): -1 reads the staged row,
      else the flat ``r'*C + c'`` position of the SAME user's most recent
      in-window write, whose updated bytes round r reads instead.  The
      forwarding select is exact (``jnp.where``), so a forwarded row is
      bitwise the row the per-round path would have scattered to the
      host and regathered.
    * ``ages (K, C) int32`` — participation ages, exact under forwarding
      (host-computed from the pre-window ``last_round`` plus in-window
      stamps; a user repeating r' -> r carries age r - r' - 1).
    * ``valid (K,) bool`` — masks padded rounds of a remainder window
      (their block rows are never written), so every window size compiles
      ONE program, exactly as ``run_scanned`` does for data chunks.

    Per round the program between the optimization barriers is the
    per-round rows engine's body verbatim; the pin against the streamed
    per-round path is established in tests/test_fused_store.py.
    """
    appr = resolve_approach(approach)
    assert appr.user_axis, f"{approach} has no user axis to virtualize"
    body = appr.body_factory(pair, fcfg)
    d_layout = d_flat_layout(pair)
    o_layout = d_opt_flat_layout(pair, fcfg)
    ef = _wants_residual(fcfg)

    def round_fn(carry, inp):
        if ef:
            shared, blk_d, blk_o, blk_r = carry
        else:
            shared, blk_d, blk_o = carry
        r, fwd, ages, real, *rest = inp
        w = rest[0] if rest else None
        C = fwd.shape[0]
        # one gather serves both sources: a non-forwarded member reads its
        # own staged row r*C + c (untouched — earlier rounds only wrote
        # their OWN rows), a forwarded member reads the flat position of
        # its last in-window write, which already holds updated bytes
        src = jnp.where(fwd >= 0, fwd,
                        r * C + jnp.arange(C, dtype=jnp.int32))
        d_rows = blk_d.reshape(-1, blk_d.shape[-1])[src]
        o_rows = blk_o.reshape(-1, blk_o.shape[-1])[src]
        ds = d_layout.unflatten_stacked(d_rows)
        opts = o_layout.unflatten_stacked(o_rows)
        ds, opts = jax.lax.optimization_barrier((ds, opts))
        state = DistGANState(shared.g, shared.g_opt, ds, opts,
                             shared.server_d, shared.step, shared.key)
        if ef:
            # the residual block forwards through the SAME src plan: a
            # member repeating in-window reads the residual its earlier
            # round just wrote, exactly as the per-round path would have
            # scattered to the host and regathered
            res_rows = blk_r.reshape(-1, blk_r.shape[-1])[src]
            new_state, metrics, new_res = body(state, real, ages, w,
                                               res_rows)
        else:
            new_state, metrics = body(state, real, ages, w)
        nds, nopts = jax.lax.optimization_barrier(
            (new_state.ds, new_state.d_opts))
        new_shared = CohortShared(new_state.g, new_state.g_opt,
                                  new_state.server_d, new_state.step,
                                  new_state.key)
        blk_d = blk_d.at[r].set(d_layout.flatten_stacked(nds))
        blk_o = blk_o.at[r].set(o_layout.flatten_stacked(nopts))
        metrics = dict(metrics, mean_age=jnp.mean(ages.astype(jnp.float32)))
        if ef:
            blk_r = blk_r.at[r].set(new_res)
            return (new_shared, blk_d, blk_o, blk_r), metrics
        return (new_shared, blk_d, blk_o), metrics

    if ef:
        def window_ef(shared, blk_d, blk_o, blk_r, fwd, ages, real,
                      wts=None, valid=None):
            assert (wts is not None) == adaptive, \
                "wts must be supplied iff the engine was built adaptive=True"
            k = blk_d.shape[0]
            r_idx = jnp.arange(k, dtype=jnp.int32)
            xs = (r_idx, fwd, ages, real)
            if wts is not None:
                xs = xs + (wts,)
            carry = (shared, blk_d, blk_o, blk_r)
            if valid is None:
                carry, metrics = jax.lax.scan(round_fn, carry, xs)
            else:
                carry, metrics = jax.lax.scan(_masked(round_fn), carry,
                                              (xs, valid))
            shared, blk_d, blk_o, blk_r = carry
            return shared, blk_d, blk_o, blk_r, metrics

        return jax.jit(window_ef, donate_argnums=(1, 2, 3))

    def window(shared, blk_d, blk_o, fwd, ages, real, wts=None, valid=None):
        assert (wts is not None) == adaptive, \
            "wts must be supplied iff the engine was built adaptive=True"
        k = blk_d.shape[0]
        r_idx = jnp.arange(k, dtype=jnp.int32)
        xs = (r_idx, fwd, ages, real)
        if wts is not None:
            xs = xs + (wts,)
        carry = (shared, blk_d, blk_o)
        if valid is None:
            carry, metrics = jax.lax.scan(round_fn, carry, xs)
        else:
            carry, metrics = jax.lax.scan(_masked(round_fn), carry,
                                          (xs, valid))
        shared, blk_d, blk_o = carry
        return shared, blk_d, blk_o, metrics

    # the row blocks are per-window transfers (donated, updated in
    # place); the shared carry is NOT donated — see make_cohort_rows_engine
    return jax.jit(window, donate_argnums=(1, 2))


def init_host_backend(pair, fcfg: DistGANConfig, key, *,
                      sync_ds: bool = False, init_chunk: int = 256):
    """Host-resident analogue of ``init_cohort_state``: returns
    ``(CohortShared, HostStateBackend)`` with the SAME per-user values as
    the device path (bit-exact, pinned in tests/test_stream.py) while
    materializing at most ``init_chunk`` user rows on device at a time —
    U is bounded by host RAM, never by accelerator memory.

    Key splitting mirrors ``init_state`` exactly (kg -> G + server D,
    kd -> per-user Ds, kk -> the training key); optimizer rows are the
    deterministic zero-init, built once and broadcast."""
    from repro.models.common import build

    kg, kd, ks, kk = jax.random.split(key, 4)
    g_opt_def, d_opt_def = _opts(fcfg)
    g, d0 = pair.init(kg)
    dl = d_flat_layout(pair)
    ol = d_opt_flat_layout(pair, fcfg)
    U = fcfg.num_users

    d_flat = np.empty((U, dl.n), np.float32)
    if sync_ds:
        d_flat[:] = np.asarray(dl.flatten(d0))[None]
    else:
        keys = jax.random.split(kd, U)
        # eager on purpose: jit-fusing the RNG + flatten re-associates the
        # sampling transcendentals and drifts from the (eager)
        # init_user_ds values at ULP — breaking the host==device pin
        flatten_chunk = lambda ks_: dl.flatten_stacked(
            jax.vmap(lambda k: build(pair.d_decls, k, jnp.float32))(ks_))
        for i in range(0, U, init_chunk):
            d_flat[i:i + init_chunk] = np.asarray(
                flatten_chunk(keys[i:i + init_chunk]))

    # optimizer init is shape-deterministic (zero moments, step 0): one
    # row, broadcast host-side
    o_row = np.asarray(ol.flatten(d_opt_def.init(d0)), np.float32)
    opt_flat = np.broadcast_to(o_row, (U, ol.n)).copy()

    residual = (np.zeros((U, dl.n), np.float32)
                if _wants_residual(fcfg) else None)
    backend = HostStateBackend(d_flat, opt_flat,
                               np.zeros((U,), np.int32),
                               residual=residual)
    shared = CohortShared(g, g_opt_def.init(g), d0,
                          jnp.zeros((), jnp.int32), kk)
    return shared, backend


# ---------------------------------------------------------------------------
# Chunked drivers
# ---------------------------------------------------------------------------

def _pad_to(arr: np.ndarray, k: int):
    """Pad ``arr`` on the leading axis to length ``k`` by repeating the
    last entry (masked rounds never touch the carry; repeating keeps the
    padding's shapes/dtypes trivially right)."""
    short = k - arr.shape[0]
    if short <= 0:
        return arr
    fill = np.broadcast_to(arr[-1:], (short,) + arr.shape[1:])
    return np.concatenate([arr, fill], axis=0)


def run_scanned(engine: Callable, state, reals,
                rounds_per_jit: int = DEFAULT_ROUNDS_PER_JIT):
    """Drive ``engine`` over ``reals`` (leading axis = rounds) in chunks.

    Every chunk — the trailing remainder included — is padded to
    ``rounds_per_jit`` rounds with a validity mask, so ANY
    ``steps % rounds_per_jit`` compiles exactly ONE program.  Returns
    ``(state, metrics)`` with metrics np-concatenated over the real (un-
    padded) rounds.
    """
    reals = np.asarray(reals)
    k_total = reals.shape[0]
    rpj = min(rounds_per_jit, k_total)
    chunks_metrics = []
    i = 0
    while i < k_total:
        k = min(rpj, k_total - i)
        chunk_reals = _pad_to(reals[i:i + k], rpj)
        valid = jnp.asarray(np.arange(rpj) < k)
        state, m = engine(state, jnp.asarray(chunk_reals), valid)
        chunks_metrics.append(jax.tree.map(lambda x: np.asarray(x)[:k], m))
        i += k
    metrics = jax.tree.map(lambda *xs: np.concatenate(xs, axis=0),
                           *chunks_metrics)
    return state, metrics


# ---------------------------------------------------------------------------
# Static-analysis introspection (consumed by repro.analysis.tracecheck)
# ---------------------------------------------------------------------------

class TraceSpecimen(NamedTuple):
    """One jitted engine program plus the trace contract it must satisfy.

    ``donate`` is the positional argnums the factory promises to donate —
    the checker asserts every leaf of those args is ALIASED in the
    lowered program (donated-but-copied is the regression class) and
    that nothing else is.  ``min_barriers`` is the optimization_barrier
    count the engine's bitwise pin depends on (the ``_pin`` clusters from
    the approach bodies plus the cohort gather/scatter barriers);
    ``expect_scan`` marks scan-fused programs (per_step engines have no
    scan to find)."""

    name: str
    fn: Callable
    args: tuple
    donate: tuple
    min_barriers: int
    expect_scan: bool = True


def _sample_shape(pair):
    """Data sample shape, derived from the generator itself so specimens
    track any pair architecture."""
    g, _ = pair.init(jax.random.key(0))
    x = pair.g_apply(g, pair.sample_z(jax.random.key(1), 1))
    return tuple(x.shape[1:])


def trace_specimens(pair, fcfg: DistGANConfig, *, approaches=None,
                    rounds: int = 2, batch: int = 4):
    """Yield every device/host engine family for every registered
    approach (or the given subset) with tiny concrete example inputs —
    the enumeration surface ``repro.analysis.tracecheck`` lowers and
    inspects.  Donation expectations restate each factory's documented
    contract (carry donated for fused/fused-store, deliberately NOT
    donated for the cohort/spmd-cohort bitwise-pin engines, per-transfer
    rows donated for the streaming engines)."""
    from repro.core.spec import APPROACH_REGISTRY, _load_builtins
    _load_builtins()
    names = (tuple(approaches) if approaches
             else tuple(sorted(APPROACH_REGISTRY.entries)))
    K, B, U = rounds, batch, fcfg.num_users
    C = U
    shape = _sample_shape(pair)
    ef = _wants_residual(fcfg)
    dl = d_flat_layout(pair)
    ol = d_opt_flat_layout(pair, fcfg)
    valid = np.ones((K,), bool)

    for name in names:
        appr = resolve_approach(name)
        key = jax.random.key(0)
        state = init_state(pair, fcfg, key, sync_ds=appr.sync_ds)
        if appr.user_axis:
            reals = np.zeros((K, U, B) + shape, np.float32)
        else:
            reals = np.zeros((K, B) + shape, np.float32)
        if not ef:
            # the plain engines don't thread residual rows; an EF config
            # only exists for the cohort/rows/superbatch families below
            yield TraceSpecimen(
                f"{name}/fused", make_engine(pair, fcfg, name),
                (state, reals, valid), donate=(0,), min_barriers=1)
            yield TraceSpecimen(
                f"{name}/per_step", appr.step_factory(pair, fcfg),
                (state, reals[0]), donate=(0,), min_barriers=1,
                expect_scan=False)
        if not appr.user_axis:
            continue

        cstate = init_cohort_state(pair, fcfg, key, sync_ds=appr.sync_ds)
        idx = np.tile(np.arange(C, dtype=np.int32), (K, 1))
        creals = np.zeros((K, C, B) + shape, np.float32)
        # gather -> body -> scatter per round: the round's in/out barriers
        # plus at least one _pin inside the approach body
        yield TraceSpecimen(
            f"{name}/cohort", make_cohort_engine(pair, fcfg, name),
            (cstate, creals, idx, None, valid), donate=(), min_barriers=3)
        yield TraceSpecimen(
            f"{name}/fused_store",
            make_fused_store_engine(pair, fcfg, name),
            (cstate, creals, idx, None, valid), donate=(0,),
            min_barriers=3)

        ages = np.zeros((C,), np.int32)
        d_rows = np.zeros((C, dl.n), np.float32)
        o_rows = np.zeros((C, ol.n), np.float32)
        if ef:
            res = np.zeros((C, dl.n), np.float32)
            yield TraceSpecimen(
                f"{name}/rows_ef", make_cohort_rows_engine(pair, fcfg, name),
                (CohortShared(state.g, state.g_opt, state.server_d,
                              state.step, state.key),
                 d_rows, o_rows, res, ages, None, creals[0]),
                donate=(1, 2, 3), min_barriers=3, expect_scan=False)
        else:
            yield TraceSpecimen(
                f"{name}/rows", make_cohort_rows_engine(pair, fcfg, name),
                (CohortShared(state.g, state.g_opt, state.server_d,
                              state.step, state.key),
                 d_rows, o_rows, ages, None, creals[0]),
                donate=(1, 2), min_barriers=3, expect_scan=False)

        shared = CohortShared(state.g, state.g_opt, state.server_d,
                              state.step, state.key)
        blk_d = np.zeros((K, C, dl.n), np.float32)
        blk_o = np.zeros((K, C, ol.n), np.float32)
        fwd = np.full((K, C), -1, np.int32)
        wages = np.zeros((K, C), np.int32)
        if ef:
            blk_r = np.zeros((K, C, dl.n), np.float32)
            yield TraceSpecimen(
                f"{name}/superbatch_ef",
                make_superbatch_engine(pair, fcfg, name),
                (shared, blk_d, blk_o, blk_r, fwd, wages, creals, None,
                 valid), donate=(1, 2, 3), min_barriers=3)
        else:
            yield TraceSpecimen(
                f"{name}/superbatch",
                make_superbatch_engine(pair, fcfg, name),
                (shared, blk_d, blk_o, fwd, wages, creals, None, valid),
                donate=(1, 2), min_barriers=3)
