"""Store-resident fused cohort rounds: the same federation run at three
dispatch granularities, same trajectory contract, very different host
traffic.

* ``per_round`` (host store, streamed): every round pays a host row
  gather, one jit dispatch, and a blocking scatter-back — K host stalls
  per K rounds.
* ``superbatch`` (host store, ``fuse_store_rounds=True``): the driver
  gathers a whole ``rounds_per_jit`` window of scheduled rows as one
  (K, C, N) block, dispatches ONE fused K-round program (users repeating
  inside the window read their in-window update through an exact
  write-after-read forward — ages stay exact), and blocks a single time
  before scattering the window back.  K host stalls become 1.
* ``device fused`` (device store, ``fuse_store_rounds=True``): the
  (U, N) store lives in the donated scan carry — gather→train→scatter
  for the whole window runs inside one compiled program with zero
  per-round host traffic and no per-window store copy.

All three are the SAME ``FederationSpec`` modulo the backend/engine
fields.  Participation bookkeeping (schedule, ages, ``last_round``) is
EXACT across all three; the training values agree to ~1 ULP per round
(the fused programs reassociate a few reductions — the measured contract
of tests/test_fused_store.py), which compounds chaotically over a long
run exactly as any ULP perturbation does in GAN training — the tail of
this script prints that divergence growth rather than hiding it.

  PYTHONPATH=src python examples/distgan_fused_store.py
"""

import numpy as np

from repro.core.approaches import DistGANConfig
from repro.core.gan import MLPGanConfig, make_mlp_pair
from repro.core.session import FederationSession
from repro.core.spec import (BackendSpec, EngineSpec, FederationSpec,
                             ParticipationSpec)
from repro.data.federated import FederatedDataset
from repro.data.mixtures import GaussianMixture
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    U, C, K, steps, B = 512, 8, 16, 192, 64

    mix = GaussianMixture.ring(8)
    rng = np.random.default_rng(0)
    pool = mix.sample(rng, 20_000)

    def sampler(rng_, n):
        return pool[rng_.integers(0, len(pool), size=n)]

    ds = FederatedDataset([sampler] * U, sampler,
                          {"shard_sizes": [len(pool)] * U})
    pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=16, g_hidden=64,
                                      d_hidden=64))
    fcfg = DistGANConfig(num_users=U, selection="topk", upload_frac=0.5)

    def spec_for(backend, fused):
        return FederationSpec(
            approach="approach1", batch_size=B, seed=0, eval_samples=0,
            engine=EngineSpec(kind="fused", rounds_per_jit=K,
                              fuse_store_rounds=fused),
            participation=ParticipationSpec("round_robin", cohort_size=C),
            backend=BackendSpec(backend))

    runs = {}
    print(f"{'mode':>14} {'us/round':>9} {'fused':>6} {'host stall us':>14}")
    for name, backend, fused in [("per_round", "host", False),
                                 ("superbatch", "host", True),
                                 ("device_fused", "device", True)]:
        r = FederationSession(pair, fcfg, ds, spec_for(backend, fused)).run(
            steps)
        runs[name] = r
        stall = r.extra.get("host_stall_s_per_round")
        print(f"{name:>14} {r.extra['min_step_time_s'] * 1e6:>9.0f} "
              f"{str(r.extra['fused_store']):>6} "
              f"{'-' if stall is None else f'{stall * 1e6:.0f}':>14}")

    # the fused paths compute the per-round trajectory, not an
    # approximation: participation bookkeeping (schedule, ages,
    # last_round) is EXACT, and a single round drifts at most ~1 ULP
    # (reassociation from donation / scan embedding — the tested
    # contract, tests/test_fused_store.py).  Over a long run that ULP
    # compounds chaotically, as any floating-point reassociation does in
    # GAN training — shown below, not papered over.
    base = runs["per_round"]
    for name in ("superbatch", "device_fused"):
        np.testing.assert_array_equal(runs[name].extra["staleness"],
                                      base.extra["staleness"])
        np.testing.assert_allclose(runs[name].g_losses[:8],
                                   base.g_losses[:8], rtol=0, atol=1e-6)
        assert np.all(np.isfinite(runs[name].g_losses))
    print("\n|g_loss - per_round| as ULP drift compounds:")
    for name in ("superbatch", "device_fused"):
        divs = [float(np.max(np.abs(runs[name].g_losses[:n]
                                    - base.g_losses[:n])))
                for n in (8, 64, steps)]
        print(f"{name:>14} " + " ".join(f"rounds<={n}: {d:.1e}"
                                        for n, d in zip((8, 64, steps),
                                                        divs)))
    print(f"\nbookkeeping exact across all three modes; superbatch turns "
          f"{K} host stalls/window into 1, the device store runs the "
          f"whole {K}-round window in one dispatch")


if __name__ == "__main__":
    main()
