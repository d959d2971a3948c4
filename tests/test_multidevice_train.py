"""Real multi-device SPMD training (not dry-run): the train launcher on a
forced 2x2 host mesh, and the Pallas top-k kernel inside the paper's
approach-1 step."""

import os
import subprocess
import sys
import textwrap


def _run(code: str, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


def test_sharded_training_runs_and_matches_single_device():
    """Loss trajectory on a (data=2, model=2) mesh must match the
    1-device run (same seeds; SPMD is semantics-preserving)."""
    r = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, numpy as np
        # partition-invariant threefry: jax.random under out_shardings
        # generates the same bits for sharded outputs, so both runs
        # start from the same params; the trajectories then agree to
        # ~2e-4, comfortably inside the 2e-3 assertion.
        jax.config.update("jax_threefry_partitionable", True)
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.configs.base import get_config
        from repro.data.synthetic import TokenStream
        from repro.launch.mesh import make_host_mesh
        from repro.launch.steps import make_train_step, param_pspecs
        from repro.models import model as M
        from repro.optim import adamw

        cfg = get_config("tinyllama-1.1b").reduced()
        stream = TokenStream(cfg.vocab_size, 32, 8, seed=0)

        def losses_on(mesh):
            step_fn, opt = make_train_step(cfg, adamw(1e-3))
            pspecs = param_pspecs(cfg, mesh)
            p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                                is_leaf=lambda x: isinstance(x, PartitionSpec))
            params = jax.jit(lambda k: M.init_params(cfg, k),
                             out_shardings=p_sh)(jax.random.key(0))
            opt_state = jax.jit(opt.init)(params)
            jstep = jax.jit(step_fn)
            out = []
            for i in range(5):
                params, opt_state, m = jstep(params, opt_state,
                                             stream.batch(i))
                out.append(float(m["loss"]))
            return out

        l1 = losses_on(make_host_mesh(1, 1))
        l4 = losses_on(make_host_mesh(2, 2))
        np.testing.assert_allclose(l1, l4, rtol=2e-3)
        print("SPMD_MATCH", l1[-1], l4[-1])
    """)
    assert "SPMD_MATCH" in r.stdout, r.stdout + r.stderr


def test_approach1_with_pallas_topk_kernel():
    """The paper's selective upload routed through the Pallas kernel
    (interpret mode) inside the jit'd approach-1 step: must train and
    keep ~the requested fraction."""
    r = _run("""
        import numpy as np, jax
        from repro.core.gan import make_mlp_pair, MLPGanConfig
        from repro.core.approaches import DistGANConfig
        from repro.core.protocol import run_distgan
        from repro.data.mixtures import make_user_domains
        from repro.data.federated import FederatedDataset

        # D spans multiple 8192-element kernel blocks: exercises the
        # two-pass (block maxima -> refine) global-threshold path
        pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=8, g_hidden=32,
                                          d_hidden=192))
        users, union = make_user_domains(2, 2, separation=1.0)
        ds = FederatedDataset([u.sample for u in users], union.sample, {})
        fcfg = DistGANConfig(num_users=2, selection="topk", upload_frac=0.2,
                             use_topk_kernel=True)
        r = run_distgan(pair, fcfg, ds, "approach1", steps=10, batch_size=32,
                        seed=0, eval_samples=0)
        assert np.all(np.isfinite(r.g_losses))
        # global-threshold kernel: kept == the exact requested fraction
        assert abs(r.extra["kept_frac"] - 0.2) < 0.01, r.extra
        print("KERNEL_OK", r.extra["kept_frac"])
    """)
    assert "KERNEL_OK" in r.stdout, r.stdout + r.stderr
