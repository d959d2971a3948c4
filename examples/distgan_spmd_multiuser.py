"""Production-shape Distributed-GAN: 5 users as 5 mesh slices (SPMD via
shard_map), the paper's §5.7 large-scale experiment.  Raw data is sharded
over the `users` axis and never crosses it — only selected deltas
(approach 1) / D probabilities and G gradients (approach 2) do.

A CPU demo: it sets ``JAX_PLATFORMS=cpu`` and forces 5 host devices, so
it never takes an accelerator.  On chips, the same engine runs with one
user per chip (``chip_smoke.py --chips 4`` drives the spmd backend).

  PYTHONPATH=src python examples/distgan_spmd_multiuser.py
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=5")

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.approaches import DistGANConfig, init_state  # noqa: E402
from repro.core.engine import make_spmd_engine, run_scanned  # noqa: E402
from repro.core.gan import MLPGanConfig, make_mlp_pair  # noqa: E402
from repro.data.mixtures import make_user_domains  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_users_mesh  # noqa: E402


def main():
    enable_compile_cache()
    U, steps, B = 5, 800, 64
    pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=16, g_hidden=128,
                                      d_hidden=128))
    users, union = make_user_domains(U, 2, separation=1.0)
    mesh = make_users_mesh(U)
    print(f"mesh: {mesh}")

    rng = np.random.default_rng(0)
    for approach in ["approach1", "approach2"]:
        fcfg = DistGANConfig(num_users=U, selection="topk", upload_frac=0.5)
        state = init_state(pair, fcfg, jax.random.key(0),
                           sync_ds=(approach == "approach1"))
        # scan-fused engine: 16 federation rounds per XLA dispatch, the
        # per-round collectives compiled into one program
        engine = make_spmd_engine(pair, fcfg, mesh, approach)
        reals = np.stack([
            np.stack([users[u].sample(rng, B) for u in range(U)])
            for _ in range(steps)]).astype(np.float32)
        state, m = run_scanned(engine, state, reals, rounds_per_jit=16)
        z = pair.sample_z(jax.random.key(1), 2048)
        samples = np.asarray(pair.g_apply(state.g, z))
        cov, hist = union.mode_coverage(samples)
        per_user = [int((hist[u * 2:(u + 1) * 2] > 10).any())
                    for u in range(U)]
        print(f"{approach}: g_loss={float(m['g_loss'][-1]):.3f} "
              f"modes_hit={(hist > 10).sum()}/{U * 2} "
              f"users_covered={sum(per_user)}/{U}")


if __name__ == "__main__":
    main()
