"""Multi-tenant generation serving over a live FederationSession.

The paper's closing argument (§7) is that the platform should "provide
model for users who lack computing power": after federated training,
the server-held generator is a *service*.  This example trains a small
approach-1 federation on the host-store backend, then stands up a
``repro.serve.GenerationService`` over the live session and shows the
full serving story:

* a mixed-size request workload (1..17 samples per request, many
  tenants) coalesced by the micro-batcher into padded power-of-two
  bucket dispatches — throughput vs one-jit-call-per-request, with the
  compiled-program count bounded by the bucket ladder;
* **determinism**: a served request is byte-identical to its
  ``replay(seed, request_id, n)`` — batching is invisible in the bytes;
* **hot-swap**: training continues (``session.run``) and
  ``service.refresh()`` atomically publishes the newer generator
  between batches;
* **per-user rejection filtering**: a tenant's samples filtered by its
  OWN discriminator row from the host store;
* per-user accounting (requests / samples / bytes served).

  PYTHONPATH=src python examples/distgan_serve.py [--quick]
"""

import argparse
import time

import numpy as np

from repro.core.approaches import DistGANConfig
from repro.core.gan import MLPGanConfig, make_mlp_pair
from repro.core.session import FederationSession
from repro.core.spec import (BackendSpec, FederationSpec,
                             ParticipationSpec, ServeSpec)
from repro.data.federated import FederatedDataset
from repro.data.mixtures import make_user_domains
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import GenerationService


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    U, C = (16, 4) if args.quick else (64, 8)
    rounds = 8 if args.quick else 24
    n_requests = 80 if args.quick else 240

    pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=8, g_hidden=32,
                                      d_hidden=32))
    users, union = make_user_domains(U, 2, 1.0)
    ds = FederatedDataset([u.sample for u in users], union.sample,
                          {"shard_sizes": [1000] * U})
    fcfg = DistGANConfig(num_users=U, selection="topk", upload_frac=0.3)
    spec = FederationSpec(
        approach="approach1", batch_size=32, eval_samples=0,
        participation=ParticipationSpec("uniform", cohort_size=C),
        backend=BackendSpec("host"),
        serve=ServeSpec(max_batch=32, flush_ms=1.0))

    print(f"[train] U={U} C={C}: {rounds} rounds on the host store...")
    sess = FederationSession(pair, fcfg, ds, spec)
    sess.run(rounds)

    svc = GenerationService.from_session(sess)

    # mixed-size multi-tenant workload, micro-batched
    rng = np.random.default_rng(0)
    sizes = rng.integers(1, 18, size=n_requests)
    tenants = rng.integers(0, U, size=n_requests)
    futs = [svc.submit(int(u), int(n), seed=int(u))
            for u, n in zip(tenants, sizes)]
    svc.drain()  # warm the bucket programs outside the timed pass

    futs = [svc.submit(int(u), int(n), seed=int(u))
            for u, n in zip(tenants, sizes)]
    t0 = time.perf_counter()
    svc.drain()
    dt = time.perf_counter() - t0
    total = int(sizes.sum())
    st = svc.stats()
    bat = st["batcher"]
    print(f"[serve] {n_requests} requests / {total} samples in {dt:.3f}s "
          f"({total / dt:,.0f} samples/s)")
    print(f"[serve] flushes={bat['flushes']} "
          f"(~{total / max(bat['flushes'] // 2, 1):.1f} samples/dispatch), "
          f"padding={bat['padded_slots'] / max(bat['dispatched_slots'], 1):.2f}, "
          f"compiled request programs={st['programs']['request']} "
          f"<= buckets={len(svc.serve.buckets())}")

    # determinism: served bytes == replay bytes, batching invisible
    probe = futs[0].result()
    rep = svc.replay(seed=int(tenants[0]), request_id=int(n_requests),
                     n=int(sizes[0]))
    assert np.array_equal(probe, rep), "served != replay"
    print("[serve] determinism: request bytes == replay bytes "
          f"(request_id={n_requests}, n={sizes[0]})")

    # hot-swap: train on, publish the newer generator between batches
    sess.run(rounds // 2)
    gen = svc.refresh()
    rep2 = svc.replay(seed=int(tenants[0]), request_id=int(n_requests),
                      n=int(sizes[0]))
    print(f"[serve] hot-swap: generation={gen}, same request now serves "
          f"{'new' if not np.array_equal(rep, rep2) else 'IDENTICAL (bug)'}"
          " bytes from the refreshed generator")

    # per-user rejection filter: tenant 0's own D row scores candidates
    plain = svc.sample(0, 64, seed=123)
    filt = svc.sample_filtered(0, 64, seed=123)
    d0 = svc.user_d_params(0)
    s_plain = float(svc.engine.score_bucket(d0, plain).mean())
    s_filt = float(svc.engine.score_bucket(d0, filt).mean())
    print(f"[serve] rejection filter (user 0, x{svc.serve.oversample} "
          f"oversample): own-D score {s_plain:+.3f} -> {s_filt:+.3f}")

    top = sorted(st["per_user"].items(),
                 key=lambda kv: -kv[1]["samples"])[:3]
    for u, acc in top:
        print(f"[account] user {u:3d}: {acc['requests']} requests, "
              f"{acc['samples']} samples, {acc['bytes']} bytes")
    print(f"[account] total: {st['total_samples']} samples, "
          f"{st['total_bytes']} bytes served")


if __name__ == "__main__":
    main()
