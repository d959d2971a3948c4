"""Multi-device SPMD tests (subprocesses set their own host-device flags;
the main pytest process keeps the single real CPU device)."""

import os
import subprocess
import sys
import textwrap


def _run(code: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=560)


def test_spmd_distgan_all_approaches_4users():
    r = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.gan import make_mlp_pair, MLPGanConfig
        from repro.core.approaches import DistGANConfig, init_state
        from repro.core.spmd import make_spmd_step
        from repro.launch.mesh import make_users_mesh
        from repro.data.mixtures import make_user_domains

        U = 4
        pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=8, g_hidden=32,
                                          d_hidden=32))
        users, _ = make_user_domains(U, 2, separation=1.0)
        mesh = make_users_mesh(U)
        rng = np.random.default_rng(0)
        for ap in ["approach1", "approach2", "approach3"]:
            fcfg = DistGANConfig(num_users=U, selection="topk",
                                 upload_frac=0.3)
            state = init_state(pair, fcfg, jax.random.key(0),
                               sync_ds=(ap == "approach1"))
            step = make_spmd_step(pair, fcfg, mesh, ap)
            for i in range(10):
                real = jnp.stack([jnp.asarray(users[u].sample(rng, 32))
                                  for u in range(U)])
                state, m = step(state, real)
            assert np.isfinite(float(m["g_loss"])), ap
            # G must stay replicated: fetch per-device copies and compare
            leaf = jax.tree.leaves(state.g)[0]
            shards = [np.asarray(s.data) for s in leaf.addressable_shards]
            for s in shards[1:]:
                np.testing.assert_array_equal(shards[0], s)
            print(ap, "OK")
    """)
    assert r.returncode == 0, r.stdout + r.stderr
    for ap in ["approach1", "approach2", "approach3"]:
        assert f"{ap} OK" in r.stdout


def test_spmd_approach2_grad_matches_host_simulation():
    """One step of the SPMD approach-2 G update == the host (vmap) version,
    given identical state and inputs: validates the psum'd gradient
    assembly against the stacked reference."""
    r = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.gan import make_mlp_pair, MLPGanConfig
        from repro.core import losses
        from repro.launch.mesh import make_users_mesh
        from jax.sharding import PartitionSpec as PS

        pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=8, g_hidden=32,
                                          d_hidden=32))
        g, _ = pair.init(jax.random.key(0))
        ds = pair.init_user_ds(jax.random.key(1), 2)
        z = pair.sample_z(jax.random.key(2), 16)

        def host_loss(gp):
            f = pair.g_apply(gp, z)
            per = jax.vmap(lambda d: pair.d_apply(d, f))(ds)
            return losses.g_loss_avg_probs(per)
        want = jax.grad(host_loss)(g)

        mesh = make_users_mesh(2)
        def body(gp, d_stack):
            d = jax.tree.map(lambda x: x[0], d_stack)
            def loss(gp):
                f = pair.g_apply(gp, z)
                p = jax.nn.sigmoid(pair.d_apply(d, f))
                pavg = jax.lax.pmean(p, "users")
                return -jnp.mean(jnp.log(pavg + 1e-7))
            grads = jax.grad(loss)(gp)
            # psum's transpose already summed the cross-user cotangents:
            # per-shard grads are complete; pmean just de-duplicates
            return jax.tree.map(lambda x: jax.lax.pmean(x, "users"), grads)

        got = jax.jit(jax.shard_map(
            body, mesh=mesh, check_vma=False,
            in_specs=(jax.tree.map(lambda _: PS(), g),
                      jax.tree.map(lambda _: PS("users"), ds)),
            out_specs=jax.tree.map(lambda _: PS(), g)))(g, ds)
        # GSPMD lowers the cotangent psum to an
        # all-reduce whose accumulation order differs from the host vmap's
        # fused reduction.  Where per-user contributions cancel, the
        # absolute error scales with the SUMMANDS' magnitude, not the
        # result's — so a fixed atol floor (the old 2e-6) flakes on leaves
        # with large cancelling terms.  Scale the floor per leaf by the
        # oracle's own magnitude instead of loosening rtol.
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            a, b = np.asarray(a), np.asarray(b)
            scale = max(1.0, float(np.max(np.abs(a))))
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6 * scale)
        print("GRAD OK")
    """)
    assert "GRAD OK" in r.stdout, r.stdout + r.stderr


def test_dryrun_single_pair_multipod():
    """The 2-pod 512-chip mesh lowers+compiles for one representative pair
    (the full sweep is run by the benchmark/experiment scripts)."""
    r = _run("""
        import repro.launch.dryrun as dr
        rec = dr.run_one("tinyllama-1.1b", "decode_32k", multi_pod=True,
                         save=False)
        assert rec["status"] == "ok", rec
        print("MP OK", rec["dominant"])
    """)
    assert "MP OK" in r.stdout, r.stdout + r.stderr
