"""Store-resident fused cohort rounds (the PR 7 tentpole): the donated
device window engine, the host superbatch staging path with
write-after-read forwarding, and the mesh-sharded SPMD store.

Correctness ladder:
* device fused-store engine — the EXACT ``make_cohort_engine`` trace with
  a donated carry; donation lets XLA reschedule the update clusters, so
  the pin is atol=1e-6 per round (the same contract the per-round rows
  path carries) with exact ``last_round`` stamping, and the donated
  program itself is deterministic (re-runs are bitwise);
* host superbatch — one staged ``(K, C, N)`` block and one dispatch per
  window, forwarding in-window repeats; pinned at atol=1e-6 against the
  per-round stream with bitwise-equal ``last_round``/ages, and invariant
  to session windowing (a boundary-spanning repeat reads the same bytes
  from the host that the forward would have read in-program);
* SPMD sharded store — bitcast-int32 one-hot psums make gather/scatter
  exact selects, so the engine is BITWISE the replicated-store engine.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.approaches import (DistGANConfig, DistGANState,
                                   d_flat_layout, d_opt_flat_layout)
from repro.core.engine import (_pad_to, cohort_state_to_full,
                               init_cohort_state,
                               init_host_backend, make_cohort_engine,
                               make_cohort_rows_engine,
                               make_fused_store_engine,
                               make_superbatch_engine)
from repro.core.federated import (cohort_gather, make_schedule,
                                  window_forwarding)
from repro.core.gan import MLPGanConfig, make_mlp_pair
from repro.core.protocol import run_distgan, stream_cohort_rounds
from repro.core.session import (CohortStateView, FederationSession,
                                superbatch_cohort_rounds)
from repro.core.spec import (BackendSpec, CombineSpec, CompressionSpec,
                             EngineSpec, FederationSpec, ParticipationSpec)
from repro.data.federated import FederatedDataset
from repro.data.mixtures import make_user_domains

PAIR = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=8, g_hidden=32,
                                  d_hidden=32))


def _ds(num_users):
    users, union = make_user_domains(num_users, 2, 1.0)
    return FederatedDataset([u.sample for u in users], union.sample,
                            {"shard_sizes": [100 * (u + 1)
                                             for u in range(num_users)]})


# ---------------------------------------------------------------------------
# window_forwarding: the host-side plan the superbatch engine executes
# ---------------------------------------------------------------------------

def test_window_forwarding_plan():
    """Repeats forward to the LATEST in-window write; ages are exact under
    both the pre-window last_round and the in-window stamps (re-zeroed
    convention: trained through round r -> stamp r + 1)."""
    schedule = np.asarray([[0, 1], [2, 0], [1, 0]], np.int32)
    last_round = np.asarray([3, 0, 0], np.int32)
    fwd, ages = window_forwarding(schedule, last_round, 5)
    # u0 repeats at r1 (reads r0's write at flat 0) and r2 (reads r1's
    # write at flat 3 — last writer, not the first)
    np.testing.assert_array_equal(fwd, [[-1, -1], [-1, 0], [1, 3]])
    # first occurrences age against last_round (global rounds); repeats
    # against the in-window stamp: r - r' - 1
    np.testing.assert_array_equal(ages, [[2, 5], [6, 0], [1, 0]])


def test_window_forwarding_no_repeats_is_trivial():
    schedule = np.asarray([[0, 1], [2, 3]], np.int32)
    fwd, ages = window_forwarding(schedule, np.zeros(4, np.int32), 0)
    assert np.all(fwd == -1)
    np.testing.assert_array_equal(ages, [[0, 0], [1, 1]])


# ---------------------------------------------------------------------------
# device: donated fused-store engine vs the non-donated cohort engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("approach", ["approach1", "approach2", "approach3",
                                      "download_first"])
def test_fused_store_matches_cohort_engine(approach):
    """All four user-axis approaches, partial cohorts: same trace, donated
    carry — values pinned at 1e-6/round, last_round stamping exact."""
    U, C, K = 8, 3, 5
    fcfg = DistGANConfig(num_users=U, selection="topk", upload_frac=0.3)
    rng = np.random.default_rng(0)
    reals = rng.normal(size=(K, C, 16, 2)).astype(np.float32)
    sched = make_schedule("uniform", U, C, K, np.random.default_rng(1))
    sync = approach in ("approach1", "download_first")
    c1 = init_cohort_state(PAIR, fcfg, jax.random.key(0), sync_ds=sync)
    c2 = init_cohort_state(PAIR, fcfg, jax.random.key(0), sync_ds=sync)
    c1, m1 = make_cohort_engine(PAIR, fcfg, approach)(
        c1, jnp.asarray(reals), jnp.asarray(sched))
    c2, m2 = make_fused_store_engine(PAIR, fcfg, approach)(
        c2, jnp.asarray(reals), jnp.asarray(sched))
    np.testing.assert_allclose(np.asarray(m1["g_loss"]),
                               np.asarray(m2["g_loss"]), rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(c1.store.d_flat),
                               np.asarray(c2.store.d_flat),
                               rtol=0, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c1.store.last_round),
                                  np.asarray(c2.store.last_round))
    np.testing.assert_array_equal(np.asarray(m1["mean_age"]),
                                  np.asarray(m2["mean_age"]))


def test_fused_store_is_deterministic_and_shares_one_program():
    """The donated program re-runs bitwise, and padded remainder chunks
    reuse the ONE compiled program (the dispatch-count contract the bench
    asserts at scale)."""
    U, C, K, rpj = 8, 3, 7, 4
    fcfg = DistGANConfig(num_users=U, selection="topk", upload_frac=0.3)
    rng = np.random.default_rng(0)
    reals = rng.normal(size=(K, C, 16, 2)).astype(np.float32)
    sched = make_schedule("uniform", U, C, K, np.random.default_rng(1))
    eng = make_fused_store_engine(PAIR, fcfg, "approach1")

    def drive():
        c = init_cohort_state(PAIR, fcfg, jax.random.key(0), sync_ds=True)
        calls = 0
        for i in range(0, K, rpj):
            k = min(rpj, K - i)
            r = jnp.asarray(_pad_to(reals[i:i + k], rpj))
            s = jnp.asarray(_pad_to(sched[i:i + k], rpj))
            c, _ = eng(c, r, s, None, jnp.asarray(np.arange(rpj) < k))
            calls += 1
        return np.asarray(c.store.d_flat), calls

    a, calls_a = drive()
    b, _ = drive()
    np.testing.assert_array_equal(a, b)
    assert calls_a == 2                      # ceil(7/4) dispatches
    assert eng._cache_size() == 1            # ONE program, both chunks


def _host(tree):
    """Host copy of a carry, PRNG keys as their raw data."""
    def one(x):
        if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
            x = jax.random.key_data(x)
        return np.asarray(x)
    return jax.tree.map(one, tree)


@pytest.mark.parametrize("padding", ["repeat_last", "one_user"])
@pytest.mark.parametrize("codec", ["none", "topk_int8"])
def test_fused_store_remainder_matches_unpadded(codec, padding):
    """A masked padded chunk never touches the carry: chunked driving
    lands on the same store as one unpadded call, and a masked round is
    bitwise a no-op on every store buffer (error-feedback residual
    included) — also when a padded round repeats one user index, since
    each duplicate writes back the same unchanged row."""
    U, C, K, rpj = 8, 3, 5, 4
    fcfg = DistGANConfig(num_users=U, selection="topk", upload_frac=0.3,
                         codec=codec, error_feedback=codec != "none")
    rng = np.random.default_rng(0)
    reals = rng.normal(size=(K, C, 16, 2)).astype(np.float32)
    sched = make_schedule("round_robin", U, C, K, np.random.default_rng(1))
    eng = make_fused_store_engine(PAIR, fcfg, "approach1")
    c1 = init_cohort_state(PAIR, fcfg, jax.random.key(0), sync_ds=True)
    c1, m1 = eng(c1, jnp.asarray(reals), jnp.asarray(sched))
    g1 = np.asarray(m1["g_loss"])

    c2 = init_cohort_state(PAIR, fcfg, jax.random.key(0), sync_ds=True)
    g2 = []
    for i in range(0, K, rpj):
        k = min(rpj, K - i)
        r = jnp.asarray(_pad_to(reals[i:i + k], rpj))
        s = _pad_to(sched[i:i + k], rpj)
        trained = np.unique(sched[i:i + k])
        if padding == "one_user" and k < rpj:
            # every padded round names one user no valid round trains
            s[k:] = np.setdiff1d(np.arange(U), trained)[0]
        before = _host(c2.store)
        c2, m = eng(c2, r, jnp.asarray(s), None,
                    jnp.asarray(np.arange(rpj) < k))
        g2.append(np.asarray(m["g_loss"])[:k])
        # rows no valid round trains are bitwise as they were, whatever
        # the padded rounds name
        untouched = np.setdiff1d(np.arange(U), trained)
        for b, a in zip(jax.tree.leaves(before),
                        jax.tree.leaves(_host(c2.store))):
            np.testing.assert_array_equal(a[untouched], b[untouched])
    # chunked-vs-whole reuses the scan-tiling 1e-6 contract; last_round
    # is exact either way
    np.testing.assert_allclose(g1, np.concatenate(g2), rtol=0, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c1.store.last_round),
                                  np.asarray(c2.store.last_round))
    np.testing.assert_allclose(np.asarray(c1.store.d_flat),
                               np.asarray(c2.store.d_flat),
                               rtol=0, atol=1e-6)

    # a chunk of masked rounds only leaves the whole carry bitwise
    before = _host(c2)
    c3, _ = eng(c2, r, jnp.asarray(s), None, jnp.zeros((rpj,), bool))
    after = _host(c3)
    assert (after.store.residual is None) == (codec == "none")
    jax.tree.map(np.testing.assert_array_equal, after, before)


# ---------------------------------------------------------------------------
# host: superbatch window vs the per-round stream (repeat forwarding)
# ---------------------------------------------------------------------------

def _drive_superbatch(approach, part, U, C, steps, rpj, seed=0):
    fcfg = DistGANConfig(num_users=U, selection="topk", upload_frac=0.3)
    rng = np.random.default_rng(seed)
    reals = rng.normal(size=(steps, C, 16, 2)).astype(np.float32)
    sched = make_schedule(part, U, C, steps, np.random.default_rng(seed + 1))
    sync = approach in ("approach1", "download_first")

    sh1, be1 = init_host_backend(PAIR, fcfg, jax.random.key(0), sync_ds=sync)
    sh1, ms, _ = stream_cohort_rounds(
        make_cohort_rows_engine(PAIR, fcfg, approach), sh1, be1, sched,
        lambda r: reals[r])
    g1 = np.asarray([m["g_loss"] for m in ms])

    sh2, be2 = init_host_backend(PAIR, fcfg, jax.random.key(0), sync_ds=sync)
    sh2, ms2, _ = superbatch_cohort_rounds(
        make_superbatch_engine(PAIR, fcfg, approach), sh2, be2, sched,
        lambda r: reals[r], rounds_per_jit=rpj)
    g2 = np.asarray([m["g_loss"] for m in ms2])
    return sched, (g1, be1), (g2, be2)


@pytest.mark.parametrize("approach", ["approach1", "approach2", "approach3",
                                      "download_first"])
def test_superbatch_round_robin_repeats(approach):
    """round_robin at C close to U guarantees users repeat INSIDE a
    window: the forwarded round must see its own earlier update and end
    with the per-round path's bytes (1e-6) and exact last_round ages."""
    sched, (g1, be1), (g2, be2) = _drive_superbatch(
        approach, "round_robin", U=4, C=2, steps=10, rpj=4)
    # the premise: at least one user repeats within some window
    fwd, _ = window_forwarding(sched[:4], np.zeros(4, np.int32), 0)
    assert np.any(fwd >= 0)
    np.testing.assert_allclose(g1, g2, rtol=0, atol=1e-6)
    np.testing.assert_allclose(be1.d_flat, be2.d_flat, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(be1.last_round, be2.last_round)


def test_superbatch_uniform_collisions():
    """uniform seeds with cross-round collisions inside a window exercise
    the data-dependent forwarding plan."""
    sched, (g1, be1), (g2, be2) = _drive_superbatch(
        "approach1", "uniform", U=6, C=3, steps=11, rpj=4)
    any_fwd = False
    for i in range(0, 11, 4):
        k = min(4, 11 - i)
        fwd, _ = window_forwarding(sched[i:i + k], np.zeros(6, np.int32), i)
        any_fwd = any_fwd or bool(np.any(fwd >= 0))
    assert any_fwd, "seed produced no in-window repeat; pick another"
    np.testing.assert_allclose(g1, g2, rtol=0, atol=1e-6)
    np.testing.assert_allclose(be1.d_flat, be2.d_flat, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(be1.last_round, be2.last_round)


def test_superbatch_shares_one_program_across_windows():
    """Full and remainder windows (padded + masked) compile ONE program —
    the host-side analogue of the device dispatch contract."""
    U, C, steps, rpj = 6, 2, 7, 4
    fcfg = DistGANConfig(num_users=U, selection="topk", upload_frac=0.3)
    rng = np.random.default_rng(0)
    reals = rng.normal(size=(steps, C, 16, 2)).astype(np.float32)
    sched = make_schedule("uniform", U, C, steps, np.random.default_rng(1))
    eng = make_superbatch_engine(PAIR, fcfg, "approach1")
    sh, be = init_host_backend(PAIR, fcfg, jax.random.key(0), sync_ds=True)
    superbatch_cohort_rounds(eng, sh, be, sched, lambda r: reals[r],
                             rounds_per_jit=rpj)
    assert eng._cache_size() == 1


# ---------------------------------------------------------------------------
# session level: EngineSpec.fuse_store_rounds end to end
# ---------------------------------------------------------------------------

def test_session_device_fused_store_flag_and_pin():
    ds = _ds(8)
    fcfg = DistGANConfig(num_users=8, selection="topk", upload_frac=0.3)
    kw = dict(steps=9, batch_size=16, seed=0, eval_samples=0,
              participation="uniform", cohort_size=3, rounds_per_jit=4)
    r0 = run_distgan(PAIR, fcfg, ds, "approach1", **kw)
    r1 = run_distgan(PAIR, fcfg, ds, "approach1", fuse_store_rounds=True,
                     **kw)
    assert r0.extra["fused_store"] is False
    assert r1.extra["fused_store"] is True
    np.testing.assert_allclose(r0.g_losses, r1.g_losses, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(r0.extra["schedule"], r1.extra["schedule"])
    np.testing.assert_array_equal(r0.extra["staleness"],
                                  r1.extra["staleness"])
    np.testing.assert_array_equal(r0.extra["mean_age"], r1.extra["mean_age"])


def test_session_host_superbatch_flag_and_pin():
    ds = _ds(8)
    fcfg = DistGANConfig(num_users=8, selection="topk", upload_frac=0.3)
    kw = dict(steps=11, batch_size=16, seed=0, eval_samples=0,
              participation="round_robin", cohort_size=3,
              state_backend="host")
    r0 = run_distgan(PAIR, fcfg, ds, "approach1", **kw)
    r1 = run_distgan(PAIR, fcfg, ds, "approach1", rounds_per_jit=4,
                     fuse_store_rounds=True, **kw)
    assert r0.extra["fused_store"] is False
    assert r1.extra["fused_store"] is True
    np.testing.assert_allclose(r0.g_losses, r1.g_losses, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(r0.extra["staleness"],
                                  r1.extra["staleness"])
    np.testing.assert_array_equal(r0.extra["mean_age"], r1.extra["mean_age"])
    assert "host_stall_s_per_round" in r1.extra


def test_session_async_falls_back_to_per_round():
    """Bounded staleness is inherently per-round: the fusion request is
    honored with a fallback, reported through extra."""
    ds = _ds(8)
    fcfg = DistGANConfig(num_users=8, selection="topk", upload_frac=0.3)
    r = run_distgan(PAIR, fcfg, ds, "approach1", steps=6, batch_size=16,
                    seed=0, eval_samples=0, participation="round_robin",
                    cohort_size=2, state_backend="host", async_rounds=2,
                    fuse_store_rounds=True)
    assert r.extra["fused_store"] is False
    assert np.all(np.isfinite(r.g_losses))


def _device_cohort_session(fused_store: bool, codec: str):
    spec = FederationSpec(
        approach="approach1", batch_size=16, seed=0, eval_samples=0,
        engine=EngineSpec(kind="fused", rounds_per_jit=4,
                          fuse_store_rounds=fused_store),
        participation=ParticipationSpec("round_robin", cohort_size=3),
        backend=BackendSpec("device"),
        combine=CombineSpec(compression=CompressionSpec(
            codec=codec, error_feedback=codec != "none")))
    fcfg = DistGANConfig(num_users=8, selection="topk", upload_frac=0.3)
    return FederationSession(PAIR, fcfg, _ds(8), spec)


def _unpacked(sess):
    """Host copy of ``cohort_state_to_full`` of the driver's carry, and
    of the store's rows by the identity gather it made before the view."""
    carry = sess._driver.cstate
    full = _host(cohort_state_to_full(PAIR, sess.fcfg, carry))
    rows = _host(cohort_gather(carry.store, jnp.arange(8),
                               d_flat_layout(PAIR),
                               d_opt_flat_layout(PAIR, sess.fcfg)))
    return full, rows


def _assert_state_equal(view, full):
    for name in DistGANState._fields:
        jax.tree.map(np.testing.assert_array_equal,
                     _host(getattr(view, name)), getattr(full, name))


@pytest.mark.parametrize("codec", ["none", "topk_int8"])
@pytest.mark.parametrize("fused_store", [False, True])
def test_cohort_result_state_is_a_view_of_the_carry(fused_store, codec):
    """The device cohort path's ``RunResult.state`` reads the window's
    carry: every field bitwise what ``cohort_state_to_full`` gives, the
    shared leaves with no copy, and ``jax.block_until_ready`` unpacks
    nothing.  On the donating fused-store engine a view whose carry went
    into the next run() raises a named error; on the plain cohort engine
    it stays readable and unchanged."""
    sess = _device_cohort_session(fused_store, codec)
    r1 = sess.run(4)
    full1, _ = _unpacked(sess)
    r2 = sess.run(5)
    full2, (ds_rows, opt_rows) = _unpacked(sess)
    carry = sess._driver.cstate
    view = r2.state
    assert isinstance(view, CohortStateView)
    assert (carry.store.residual is None) == (codec == "none")
    assert jax.block_until_ready(view) is view
    assert not view._parts                   # blocking unpacked nothing
    for name in ("g", "g_opt", "server_d", "step", "key"):
        for a, b in zip(jax.tree.leaves(getattr(view, name)),
                        jax.tree.leaves(getattr(carry, name))):
            assert a is b
    _assert_state_equal(view, full2)
    _assert_state_equal(view.to_full(), full2)
    jax.tree.map(np.testing.assert_array_equal, _host(view.ds), ds_rows)
    jax.tree.map(np.testing.assert_array_equal, _host(view.d_opts), opt_rows)

    sess.run(3)
    # parts read before the next run() are kept
    jax.tree.map(np.testing.assert_array_equal, _host(view.ds), full2.ds)
    if fused_store:
        for read in (lambda s: s.ds, lambda s: s.g,
                     lambda s: s.block_until_ready()):
            with pytest.raises(RuntimeError,
                               match="before the next run"):
                read(r1.state)
    else:
        _assert_state_equal(r1.state, full1)
        _assert_state_equal(view, full2)


def _fused_host_session(ds, fcfg, rpj=4):
    spec = FederationSpec(
        approach="approach1", batch_size=16, seed=0, eval_samples=0,
        engine=EngineSpec(kind="fused", rounds_per_jit=rpj,
                          fuse_store_rounds=True),
        participation=ParticipationSpec("round_robin", cohort_size=2),
        backend=BackendSpec("host"))
    return FederationSession(PAIR, fcfg, ds, spec)


def test_session_superbatch_windowing_invariance():
    """run(5); run(6) == run(11): a repeat spanning the window boundary
    reads the scattered bytes from the host instead of the in-program
    forward — the same bytes, so the trajectory is invariant."""
    ds = _ds(4)
    fcfg = DistGANConfig(num_users=4, selection="topk", upload_frac=0.3)
    s1 = _fused_host_session(ds, fcfg)
    r_a = s1.run(5)
    r_b = s1.run(6)
    s2 = _fused_host_session(ds, fcfg)
    r_all = s2.run(11)
    np.testing.assert_array_equal(
        np.concatenate([r_a.g_losses, r_b.g_losses]), r_all.g_losses)
    np.testing.assert_array_equal(s1._driver.backend.d_flat,
                                  s2._driver.backend.d_flat)
    np.testing.assert_array_equal(s1._driver.backend.last_round,
                                  s2._driver.backend.last_round)


def test_session_superbatch_save_restore(tmp_path):
    """Checkpoint/resume through the fused host path reproduces the
    uninterrupted trajectory bitwise."""
    ds = _ds(4)
    fcfg = DistGANConfig(num_users=4, selection="topk", upload_frac=0.3)
    s1 = _fused_host_session(ds, fcfg)
    s1.run(5)
    path = str(tmp_path / "ckpt")
    s1.save(path)
    r_tail = s1.run(6)

    s2 = FederationSession.restore(path, PAIR, fcfg, ds)
    assert s2.spec.engine.fuse_store_rounds is True
    assert s2._driver.fused_store is True
    r_resumed = s2.run(6)
    np.testing.assert_array_equal(r_tail.g_losses, r_resumed.g_losses)
    np.testing.assert_array_equal(s1._driver.backend.d_flat,
                                  s2._driver.backend.d_flat)


# ---------------------------------------------------------------------------
# SPMD: mesh-sharded store-resident engine == replicated-store engine
# ---------------------------------------------------------------------------

def test_spmd_sharded_store_matches_replicated_bitwise():
    """The bitcast-int32 one-hot psums make gather/scatter exact selects:
    the sharded-store engine is BITWISE the replicated-store engine
    (store, last_round, losses), at 1/C the per-device store memory."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.gan import make_mlp_pair, MLPGanConfig
        from repro.core.approaches import DistGANConfig
        from repro.core.engine import (init_cohort_state,
                                       make_spmd_cohort_engine,
                                       make_spmd_fused_store_engine)
        from repro.core.federated import make_schedule
        from repro.launch.mesh import make_users_mesh

        C, U = 4, 8
        pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=8, g_hidden=16,
                                          d_hidden=16))
        mesh = make_users_mesh(C)
        rng = np.random.default_rng(0)
        reals = rng.normal(size=(6, C, 16, 2)).astype(np.float32)
        sched = make_schedule("round_robin", U, C, 6,
                              np.random.default_rng(1))
        for ap in ["approach1", "approach2", "approach3"]:
            fcfg = DistGANConfig(num_users=U, selection="topk",
                                 upload_frac=0.3)
            sync = ap == "approach1"
            c1 = init_cohort_state(pair, fcfg, jax.random.key(0),
                                   sync_ds=sync)
            c2 = init_cohort_state(pair, fcfg, jax.random.key(0),
                                   sync_ds=sync)
            e1 = make_spmd_cohort_engine(pair, fcfg, mesh, ap, C)
            e2 = make_spmd_fused_store_engine(pair, fcfg, mesh, ap, C)
            c1, m1 = e1(c1, jnp.asarray(reals), jnp.asarray(sched))
            c2, m2 = e2(c2, jnp.asarray(reals), jnp.asarray(sched))
            np.testing.assert_array_equal(np.asarray(c1.store.d_flat),
                                          np.asarray(c2.store.d_flat))
            np.testing.assert_array_equal(np.asarray(c1.store.opt_flat),
                                          np.asarray(c2.store.opt_flat))
            np.testing.assert_array_equal(np.asarray(c1.store.last_round),
                                          np.asarray(c2.store.last_round))
            np.testing.assert_array_equal(np.asarray(m1["g_loss"]),
                                          np.asarray(m2["g_loss"]))
            # masked remainder call works against the sharded store too
            v = jnp.asarray(np.arange(6) < 4)
            e2(c2, jnp.asarray(reals), jnp.asarray(sched), v)
            print(ap, "OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stdout + r.stderr
    for ap in ["approach1", "approach2", "approach3"]:
        assert f"{ap} OK" in r.stdout
