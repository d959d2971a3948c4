"""Multi-process federation end to end: the MD-GAN topology as a fleet.

The launcher materializes a ``FederationSpec`` into per-worker
subprocess jobs — spawn -> health-check -> run -> collect -> teardown —
each worker a jax-free shard holder for a contiguous range of the
(U, N) host store.  The coordinator (this process) owns the generator /
server-D carry, gathers each round's scheduled cohort rows over the
length-prefixed msgpack RPC wire, runs the cohort rows engine on its
device, and scatters the updated rows back, with the D-row legs packed
as int8 + per-row scale (the PR 8 ``stage_rows`` transport) and the
measured payload bytes asserted equal to the ``upload_bytes_flat``
pricing on every call.

The script then saves the session — each worker checkpoints its own
shard, the coordinator writes the manifest — restores it at a DIFFERENT
worker count (the shard files re-slice by row range), continues
training, and verifies the continued trajectory matches a single-process
``host``-backend reference bitwise.

  PYTHONPATH=src python examples/distgan_multihost.py [--quick]
"""

import argparse
import tempfile

import numpy as np

from repro.core.approaches import (DistGANConfig, d_flat_layout,
                                   d_opt_flat_layout)
from repro.core.gan import MLPGanConfig, make_mlp_pair
from repro.core.session import FederationSession
from repro.core.spec import (BackendSpec, CombineSpec, CompressionSpec,
                             FederationSpec, ParticipationSpec)
from repro.data.federated import FederatedDataset
from repro.data.mixtures import GaussianMixture
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    U, C, W = (256, 8, 2) if args.quick else (2048, 8, 4)
    steps = 12 if args.quick else 60
    B = 32

    mix = GaussianMixture.ring(8)
    pool = mix.sample(np.random.default_rng(0), 20_000)

    def sampler(rng_, n):
        return pool[rng_.integers(0, len(pool), size=n)]

    ds = FederatedDataset([sampler] * U, sampler,
                          {"shard_sizes": [len(pool)] * U})
    pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=8, g_hidden=32,
                                      d_hidden=32))
    fcfg = DistGANConfig(num_users=U, selection="topk", upload_frac=0.5)

    def spec(kind, workers=None):
        return FederationSpec(
            approach="approach1", batch_size=B, seed=0, eval_samples=0,
            participation=ParticipationSpec(scheduler="uniform",
                                            cohort_size=C),
            backend=BackendSpec(kind=kind, workers=workers,
                                materialize_state=False),
            combine=CombineSpec(compression=CompressionSpec(
                codec="topk_int8", error_feedback=True, stage_rows=True)))

    nd = d_flat_layout(pair).n
    no = d_opt_flat_layout(pair, fcfg).n
    print(f"U={U} users over {W} workers, C={C}, rows nd={nd} no={no}")

    # -- phase 1: train on the fleet, watch the wire ----------------------
    sess = FederationSession(pair, fcfg, ds, spec("multihost", W))
    fleet = sess._driver._fleet
    print("fleet:", [(h.rank, h.lo, h.hi) for h in fleet.workers])
    r = sess.run(steps)
    mb = r.extra["host_backend"]
    print(f"ran {steps} rounds: step={r.extra['min_step_time_s']*1e6:.0f}us "
          f"g_loss[-1]={r.g_losses[-1]:.3f}")
    print(f"wire: payload={mb.round_payload_bytes}B over {mb.rpc_calls} "
          f"RPCs (socket incl envelope: {mb.socket_bytes}B) — every call "
          f"asserted == upload_bytes_flat pricing")

    # -- phase 2: sharded save, re-partitioned restore --------------------
    path = tempfile.mkdtemp(prefix="distgan-multihost-")
    sess.save(path)
    sess.close()
    W2 = W + 1
    restored = FederationSession.restore(path, pair, fcfg, ds, workers=W2)
    print(f"restored at {W2} workers (was {W}) from {path}")
    r2 = restored.run(steps)
    restored.close()

    # -- phase 3: the single-process reference ----------------------------
    ref = FederationSession(pair, fcfg, ds, spec("host"))
    ref.run(steps)
    r_ref = ref.run(steps)
    match = np.array_equal(r_ref.g_losses, r2.g_losses)
    print(f"continued trajectory vs single-process host backend: "
          f"{'BITWISE MATCH' if match else 'MISMATCH'}")
    if not match:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
