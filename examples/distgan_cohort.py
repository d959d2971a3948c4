"""Cohort-virtualized Distributed-GAN: 64 LOGICAL users, but every round
only a cohort of 8 trains — the compiled program is shaped by the cohort
width, so the same engine scales to thousands of logical users (the
MD-GAN / BGAN partial-participation regime).

The data is split non-IID with a Dirichlet(alpha) label-skew partition;
the run uses the shard-size-weighted scheduler and the staleness-aware
argmax-|.| server fold (stale uploads are age-discounted).

  PYTHONPATH=src python examples/distgan_cohort.py
"""

import numpy as np

from repro.core.approaches import DistGANConfig
from repro.core.gan import MLPGanConfig, make_mlp_pair
from repro.core.protocol import run_distgan
from repro.data.federated import dirichlet_partition
from repro.data.mixtures import GaussianMixture
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    U, C, steps, B = 64, 8, 400, 64
    modes = 8

    # labeled union data: 2-D ring, label = mode index
    mix = GaussianMixture.ring(modes)
    rng = np.random.default_rng(0)
    labels = rng.integers(0, modes, size=20_000)
    data = (mix.modes[labels]
            + rng.normal(0, mix.std, (len(labels), 2))).astype(np.float32)

    ds = dirichlet_partition(data, labels, num_users=U, alpha=0.3, seed=0)
    sizes = np.asarray(ds.meta["shard_sizes"])
    print(f"dirichlet(0.3) split over {U} users: shard sizes "
          f"min={sizes.min()} median={int(np.median(sizes))} "
          f"max={sizes.max()}")

    pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=16, g_hidden=128,
                                      d_hidden=128))
    fcfg = DistGANConfig(num_users=U, selection="topk", upload_frac=0.5,
                         combiner="staleness_max_abs", staleness_decay=0.7)
    r = run_distgan(pair, fcfg, ds, "approach1", steps=steps, batch_size=B,
                    seed=0, participation="weighted", cohort_size=C,
                    rounds_per_jit=16)

    counts = r.extra["participation_counts"]
    stale = r.extra["staleness"]
    cov, hist = mix.mode_coverage(r.samples)
    print(f"approach1 U={U} C={C} weighted: "
          f"g_loss={r.g_losses[-1]:.3f} "
          f"modes_hit={(hist > 10).sum()}/{modes} "
          f"on_mode_frac={cov:.2f}")
    print(f"participation: users_touched={(counts > 0).sum()}/{U} "
          f"rounds/user min={counts.min()} max={counts.max()}; "
          f"staleness mean={stale.mean():.1f} max={stale.max()}")
    print(f"per-round {r.extra['min_step_time_s'] * 1e6:.0f} us "
          f"(compiled width C={C}, resident users U={U})")


if __name__ == "__main__":
    main()
