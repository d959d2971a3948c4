"""Benchmark harness — one function per paper table/figure, plus the
roofline table derived from the dry-run artifacts and kernel micro-bench.

Prints ``name,us_per_call,derived`` CSV rows (derived = the figure's
headline quantity) and writes the same results machine-readably to
``BENCH_distgan.json`` (repo root): flat ``name -> us_per_call`` plus
``_derived``/``_quick`` side-channels.  Full experiment narratives live
in EXPERIMENTS.md.

  PYTHONPATH=src python -m benchmarks.run                    # all
  PYTHONPATH=src python -m benchmarks.run paper_time         # one
  PYTHONPATH=src python -m benchmarks.run --quick            # <60s smoke
  PYTHONPATH=src python -m benchmarks.run paper_time --quick
"""

from __future__ import annotations

import glob
import json
import os
import sys
import time

import numpy as np

SEED = 0
OUT = []
RESULTS = {}   # name -> us_per_call (written to BENCH_distgan.json)
DERIVED = {}   # name -> derived string
QUICK = False  # set by --quick: small configs, <60 s total

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_distgan.json")


def emit(name: str, us_per_call: float, derived: str):
    row = f"{name},{us_per_call:.1f},{derived}"
    OUT.append(row)
    RESULTS[name] = round(float(us_per_call), 1)
    DERIVED[name] = derived
    print(row, flush=True)


def _mlp_pair():
    from repro.core.gan import MLPGanConfig, make_mlp_pair
    return make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=16, g_hidden=128,
                                      d_hidden=128))


def _ring(num_users=2, modes=4, separation=1.0):
    from repro.data.federated import FederatedDataset
    from repro.data.mixtures import make_user_domains
    users, union = make_user_domains(num_users, modes, separation)
    return FederatedDataset([u.sample for u in users], union.sample,
                            {}), union


# ---------------------------------------------------------------------------
# Paper fig 14/15: training time, distributed vs normal GAN
# ---------------------------------------------------------------------------

def _fused_vs_per_step(approaches, reps, batch):
    """Scan-fused engine vs legacy per-step loop on the MLP pair, same
    body, same shapes (bit-identical trajectories — tests/test_engine.py).

    The per-step side replays exactly what the legacy harness pays every
    round: per-user device staging, one jit dispatch of the full state
    pytree, two host syncs for metrics.  The fused side drives the K=16
    scan-compiled chunk over pre-staged device data with one dispatch and
    one sync per chunk.  Both are timed as best-of-``reps`` interleaved
    windows (min is the steady-state estimator — this box is 2 shared
    cores and the mean is dominated by background load)."""
    import jax
    import jax.numpy as jnp

    from repro.core.approaches import (DistGANConfig, STEP_FACTORIES,
                                       init_state)
    from repro.core.engine import DEFAULT_ROUNDS_PER_JIT, make_engine
    from repro.core.gan import MLPGanConfig, make_mlp_pair

    pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=8, g_hidden=16,
                                      d_hidden=16))
    ds, _ = _ring()
    K = DEFAULT_ROUNDS_PER_JIT
    W = 24            # rounds per per-step timing window
    U = 2
    rng = np.random.default_rng(SEED)
    speedups = {}
    for ap in approaches:
        fcfg = DistGANConfig(num_users=U, selection="topk", upload_frac=0.5)
        if ap == "baseline":
            pool = [ds.union_sampler(rng, batch).astype(np.float32)
                    for _ in range(K)]
        else:
            pool = [np.stack([ds.user_batch(u, rng, batch)
                              for u in range(U)]).astype(np.float32)
                    for _ in range(K)]
        staged = jnp.asarray(np.stack(pool))          # (K, [U,] B, 2)

        def stage_one(j):  # the legacy loop's per-round staging
            if ap == "baseline":
                return jnp.asarray(pool[j % K])
            return jnp.stack([jnp.asarray(pool[j % K][u])
                              for u in range(U)])

        s_loop = init_state(pair, fcfg, jax.random.key(SEED),
                            sync_ds=(ap == "approach1"))
        s_fused = init_state(pair, fcfg, jax.random.key(SEED),
                             sync_ds=(ap == "approach1"))
        step_fn = STEP_FACTORIES[ap](pair, fcfg)
        eng = make_engine(pair, fcfg, ap)

        # compile both programs outside the timed windows
        s_loop, m = step_fn(s_loop, stage_one(0))
        jax.block_until_ready(m["g_loss"])
        s_fused, mf = eng(s_fused, staged)
        jax.block_until_ready(mf["g_loss"])

        t_loop = t_fused = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for j in range(W):
                s_loop, m = step_fn(s_loop, stage_one(j))
                float(m["g_loss"]); np.asarray(m["d_loss"])
            t_loop = min(t_loop, (time.perf_counter() - t0) / W)

            t0 = time.perf_counter()
            s_fused, mf = eng(s_fused, staged)
            jax.tree.map(np.asarray, mf)              # one sync per chunk
            t_fused = min(t_fused, (time.perf_counter() - t0) / K)

        sp = t_loop / t_fused
        speedups[ap] = sp
        emit(f"paper_time/{ap}_per_step_loop", t_loop * 1e6,
             "engine=per_step;best_of_windows=1")
        emit(f"paper_time/{ap}_fused_engine", t_fused * 1e6,
             f"rounds_per_jit={K};speedup=x{sp:.2f}")
    worst = min(speedups, key=speedups.get)
    emit("paper_time/fused_speedup", 0.0,
         f"min_x{speedups[worst]:.2f}({worst});" +
         ";".join(f"{a}=x{s:.2f}" for a, s in speedups.items()) +
         f";pass={int(speedups[worst] >= 3.0)}")


def paper_time():
    """Paper §5.5 (figs 14/15): wall-clock to train over N samples,
    distributed (users' local-D phases in parallel) vs the serial union
    baseline.  Components (t_base, t_d) are measured; the D-phase
    parallelism is modeled (one host core here).  Uses the paper-scale
    784-dim MLP pair so the D update dominates, as in the paper.

    Also reports the harness-level fused-vs-per-step comparison (us per
    round of the scan-compiled engine vs the legacy jit loop); in
    ``--quick`` mode only that comparison runs (<60 s)."""
    _fused_vs_per_step(["approach1", "approach2", "approach3", "baseline"],
                       reps=6 if QUICK else 10, batch=64)
    if QUICK:
        return

    from repro.core.approaches import DistGANConfig
    from repro.core.gan import MLPGanConfig, make_mlp_pair
    from repro.core.protocol import (effective_epoch_time,
                                     measure_component_times, run_distgan)
    from repro.data.federated import FederatedDataset
    from repro.data.mixtures import digits_like_mixture

    _, s1 = digits_like_mixture([0, 1, 2, 3, 4])
    _, s2 = digits_like_mixture([5, 6, 7, 8, 9])
    flat = lambda s: (lambda rng, n: s(rng, n).reshape(n, -1))
    union = lambda rng, n: np.concatenate(
        [flat(s1)(rng, n // 2), flat(s2)(rng, n - n // 2)])
    ds = FederatedDataset([flat(s1), flat(s2)], union, {})
    pair = make_mlp_pair(MLPGanConfig(data_dim=784, z_dim=64, g_hidden=256,
                                      d_hidden=1024))
    U, B, N = 2, 128, 10_000
    fcfg = DistGANConfig(num_users=U, selection="topk", upload_frac=0.5)
    t_base, t_d = measure_component_times(pair, fcfg, ds, B, seed=SEED)
    emit("paper_time/components", t_base * 1e6,
         f"t_d_us={t_d*1e6:.0f};d_share={t_d/t_base:.2f}")
    base_epoch = effective_epoch_time(None, U, "baseline", t_base=t_base,
                                      t_d=t_d, per_samples=N, batch_size=B)
    emit("paper_time/baseline", t_base * 1e6,
         f"epoch_{N}samples_s={base_epoch:.4f}")
    best = None
    for ap in ["approach1", "approach2", "approach3"]:
        # per-step on purpose: the §5.5 model decomposes ONE round against
        # per-step-measured t_base/t_d; a fused step time would clamp the
        # server-overhead term to zero and misattribute the epoch cost
        r = run_distgan(pair, fcfg, ds, ap, steps=48, batch_size=B,
                        seed=SEED, eval_samples=0, engine="per_step")
        eff = effective_epoch_time(r, U, ap, t_base=t_base, t_d=t_d,
                                   per_samples=N, batch_size=B)
        best = min(best, eff) if best else eff
        emit(f"paper_time/{ap}", r.step_time_s * 1e6,
             f"epoch_{N}samples_s={eff:.4f};speedup=x{base_epoch/eff:.2f}")
    emit("paper_time/speedup_vs_baseline", 0.0, f"x{base_epoch/best:.2f}")


# ---------------------------------------------------------------------------
# Paper fig 8-13: generator loss trend per approach
# ---------------------------------------------------------------------------

def paper_loss():
    from repro.core.approaches import DistGANConfig
    from repro.core.protocol import loss_trend, run_distgan
    pair = _mlp_pair()
    ds, _ = _ring()
    for ap, fcfg, steps in [
        ("approach1", DistGANConfig(selection="topk", upload_frac=0.5), 800),
        ("approach2", DistGANConfig(), 600),
        ("approach3", DistGANConfig(), 600),
    ]:
        r = run_distgan(pair, fcfg, ds, ap, steps=steps, batch_size=128,
                        seed=SEED, eval_samples=0)
        tr = loss_trend(r.g_losses)
        emit(f"paper_loss/{ap}", r.step_time_s * 1e6,
             f"g_loss_first={r.g_losses[0]:.3f};last={r.g_losses[-1]:.3f};"
             f"trend={tr:+.3f};finite={int(np.all(np.isfinite(r.g_losses)))}")


# ---------------------------------------------------------------------------
# Paper fig 2/6/7: mode coverage without data sharing (the 0-4/5-9 split)
# ---------------------------------------------------------------------------

def paper_mode_coverage():
    from repro.core.approaches import DistGANConfig
    from repro.core.protocol import run_distgan
    pair = _mlp_pair()
    ds, union = _ring()
    for ap, fcfg, steps in [
        ("approach1", DistGANConfig(selection="topk", upload_frac=0.5), 2000),
        ("approach2", DistGANConfig(), 1500),
        ("approach3", DistGANConfig(), 1500),
        ("baseline", DistGANConfig(), 1500),
    ]:
        r = run_distgan(pair, fcfg, ds, ap, steps=steps, batch_size=128,
                        seed=SEED)
        cov, hist = union.mode_coverage(r.samples)
        hit = hist > 10
        emit(f"paper_coverage/{ap}", r.step_time_s * 1e6,
             f"sample_frac_on_modes={cov:.2f};modes_hit={hit.sum()}/8;"
             f"user1_arc={int(hit[:4].any())};user2_arc={int(hit[4:].any())}")


# ---------------------------------------------------------------------------
# Paper §5.3.2 fig 4/5: approach 2 vs domain separation
# ---------------------------------------------------------------------------

def paper_domain_similarity():
    """Paper §5.3.2 (figs 4/5): approach 2 trained on '6 and 8' (similar
    classes) beats '4 and 7' (dissimilar).  Image-space analogue: pick the
    most- and least-correlated template pairs; each user holds one class;
    metric = the generator's worst per-template correlation (how well the
    harder class is represented).  NOTE: a 2-D Gaussian version of this
    experiment FAILED to show the effect (approach 2 covered arbitrarily
    distant modes) — the paper's phenomenon needs image-manifold structure;
    both results are reported."""
    import numpy as np
    from repro.core.approaches import DistGANConfig
    from repro.core.gan import MLPGanConfig, make_mlp_pair
    from repro.core.protocol import run_distgan
    from repro.data.federated import FederatedDataset
    from repro.data.mixtures import digits_like_mixture, template_coverage

    templates, _ = digits_like_mixture(list(range(10)))
    t = templates.reshape(10, -1)
    t = t / np.linalg.norm(t, axis=1, keepdims=True)
    corr = t @ t.T
    pairs = [(i, j, corr[i, j]) for i in range(10) for j in range(i + 1, 10)]
    pairs.sort(key=lambda p: p[2])
    gan = make_mlp_pair(MLPGanConfig(data_dim=784, z_dim=64, g_hidden=256,
                                     d_hidden=256))
    scores = {}
    for name, (a, b, c) in [("similar", pairs[-1]), ("dissimilar", pairs[0])]:
        ta, sa = digits_like_mixture([int(a)])
        tb, sb = digits_like_mixture([int(b)])
        tmpl = np.concatenate([ta, tb])
        fa = lambda rng, n, s=sa: s(rng, n).reshape(n, -1)
        fb = lambda rng, n, s=sb: s(rng, n).reshape(n, -1)
        union = lambda rng, n: np.concatenate(
            [fa(rng, n // 2), fb(rng, n - n // 2)])
        ds = FederatedDataset([fa, fb], union, {})
        r = run_distgan(gan, DistGANConfig(), ds, "approach2", steps=2000,
                        batch_size=64, seed=SEED, eval_samples=512)
        cov, best = template_coverage(r.samples.reshape(-1, 28, 28), tmpl,
                                      thresh=0.35)
        scores[name] = float(best.min())
        emit(f"paper_domain/approach2_{name}_{a}{b}", r.step_time_s * 1e6,
             f"pair_corr={c:.2f};both_covered={cov:.2f};"
             f"worst_template_corr={best.min():.2f}")
    emit("paper_domain/similar_domains_better", 0.0,
         f"worst_corr_similar={scores['similar']:.2f}>="
         f"dissimilar={scores['dissimilar']:.2f}:"
         f"{int(scores['similar'] >= scores['dissimilar'])}")


# ---------------------------------------------------------------------------
# Paper §5.7 fig 22/23: large-scale multi-user
# ---------------------------------------------------------------------------

def paper_multiuser():
    from repro.core.approaches import DistGANConfig
    from repro.core.protocol import run_distgan
    pair = _mlp_pair()
    for U in (5,):
        ds, union = _ring(num_users=U, modes=2)
        for ap in ("approach1", "approach3"):
            fcfg = DistGANConfig(num_users=U, selection="topk",
                                 upload_frac=0.5)
            r = run_distgan(pair, fcfg, ds, ap, steps=1500, batch_size=96,
                            seed=SEED)
            cov, hist = union.mode_coverage(r.samples)
            arcs = [int((hist[u * 2:(u + 1) * 2] > 10).any())
                    for u in range(U)]
            emit(f"paper_multiuser/{ap}_{U}users", r.step_time_s * 1e6,
                 f"modes_hit={(hist > 10).sum()}/{U * 2};"
                 f"users_covered={sum(arcs)}/{U}")


# ---------------------------------------------------------------------------
# Paper tables 3-4 config (conv/DCGAN pair) on image-shaped data
# ---------------------------------------------------------------------------

def paper_conv_gan():
    from repro.core.approaches import DistGANConfig
    from repro.core.gan import ConvGanConfig, make_conv_pair
    from repro.core.protocol import run_distgan
    from repro.data.federated import FederatedDataset
    from repro.data.mixtures import digits_like_mixture, template_coverage

    t1, s1 = digits_like_mixture([0, 1, 2, 3, 4], size=32)
    t2, s2 = digits_like_mixture([5, 6, 7, 8, 9], size=32)
    templates = np.concatenate([t1, t2])

    def u1(rng, n):
        return s1(rng, n)[..., None]

    def u2(rng, n):
        return s2(rng, n)[..., None]

    def union(rng, n):
        h = n // 2
        return np.concatenate([u1(rng, h), u2(rng, n - h)])

    ds = FederatedDataset([u1, u2], union, {})
    pair = make_conv_pair(ConvGanConfig(image_size=32, channels=1, z_dim=64,
                                        base_filters=32))
    r = run_distgan(pair, DistGANConfig(num_users=2), ds, "approach3",
                    steps=250, batch_size=32, seed=SEED, eval_samples=256)
    cov, best = template_coverage(r.samples[..., 0], templates, thresh=0.35)
    emit("paper_conv/approach3_dcgan", r.step_time_s * 1e6,
         f"template_coverage={cov:.2f};g_loss_last={r.g_losses[-1]:.2f};"
         f"finite={int(np.all(np.isfinite(r.g_losses)))}")


# ---------------------------------------------------------------------------
# Paper §10 (open problem): mode collapse in the distributed setting.
# Beyond-paper: swap the BCE objective for W-GAN (the paper's ref [1]).
# ---------------------------------------------------------------------------

def paper_collapse():
    from repro.core.approaches import DistGANConfig
    from repro.core.protocol import run_distgan
    pair = _mlp_pair()
    ds, union = _ring()
    for name, fcfg in [
        ("bce", DistGANConfig()),
        ("wgan", DistGANConfig(loss_type="wgan", d_lr=5e-4, g_lr=1e-4,
                               b1=0.0)),
    ]:
        r = run_distgan(pair, fcfg, ds, "approach3", steps=1500,
                        batch_size=128, seed=SEED)
        cov, hist = union.mode_coverage(r.samples)
        emit(f"paper_collapse/approach3_{name}", r.step_time_s * 1e6,
             f"sample_frac_on_modes={cov:.2f};modes_hit={(hist > 10).sum()}/8;"
             f"g_loss_last={r.g_losses[-1]:.2f}")


# ---------------------------------------------------------------------------
# Cohort-virtualized federation: U logical users, C-wide compiled program
# ---------------------------------------------------------------------------

def paper_cohort():
    """U=256 logical users, cohort C=8 per round (uniform scheduler): the
    compiled program is shaped by C only, so us/round must be independent
    of U — measured as the U=256 / U=32 per-round ratio at fixed C.  Host
    data sampling also scales with C (only cohort members are drawn)."""
    import jax
    from repro.core.approaches import DistGANConfig
    from repro.core.gan import MLPGanConfig, make_mlp_pair
    from repro.core.protocol import run_distgan
    from repro.data.federated import FederatedDataset
    from repro.data.mixtures import make_user_domains

    pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=8, g_hidden=16,
                                      d_hidden=16))
    C = 8
    steps = 48 if QUICK else 96
    times = {}
    for U in (32, 256):
        users, union = make_user_domains(U, 1, 1.0)
        ds = FederatedDataset([u.sample for u in users], union.sample,
                              {"shard_sizes": [1000] * U})
        fcfg = DistGANConfig(num_users=U, selection="topk",
                             upload_frac=0.5)
        r = run_distgan(pair, fcfg, ds, "approach1", steps=steps,
                        batch_size=32, seed=SEED, eval_samples=0,
                        rounds_per_jit=16, participation="uniform",
                        cohort_size=C)
        t_us = r.extra["min_step_time_s"] * 1e6
        times[U] = t_us
        counts = r.extra["participation_counts"]
        emit(f"paper_cohort/U{U}_C{C}_approach1", t_us,
             f"steps={steps};users_touched={int((counts > 0).sum())}/{U};"
             f"max_staleness={int(r.extra['staleness'].max())};"
             f"finite={int(np.all(np.isfinite(r.g_losses)))}")
    ratio = times[256] / times[32]
    emit("paper_cohort/u_independence", 0.0,
         f"t_U256/t_U32=x{ratio:.2f};compiled_width=C={C};"
         f"pass={int(ratio < 1.5)}")


# ---------------------------------------------------------------------------
# Host-resident user store + streamed cohort rounds (PR 3 tentpole)
# ---------------------------------------------------------------------------

def _stream_ds(U, dim, pool=8192):
    """O(1)-in-U federated dataset: every user samples the same host pool
    (the store scaling under test is per-user STATE, not data)."""
    from repro.data.federated import FederatedDataset
    base = np.random.default_rng(0).normal(size=(pool, dim)) \
        .astype(np.float32)

    def sampler(rng, n):
        return base[rng.integers(0, len(base), size=n)]

    return FederatedDataset([sampler] * U, sampler,
                            {"shard_sizes": [pool] * U})


def paper_stream():
    """Host-resident user store: (1) per-round time must be FLAT in U —
    the compiled program, the host gather/scatter, and the transfers all
    touch only the C scheduled rows, so U=4096 must cost the same per
    round as U=512 (gate: ratio < 1.5); (2) the double-buffered driver
    (data prefetch + async_rounds=1 bounded staleness) must beat fully
    synchronous staging, gated on the HOST STALL per round (seconds the
    host spends blocked on the device): gate stall_db < 0.5 *
    stall_sync.  Wall-clock speedup is reported but not gated — see the
    comment at the measurement below."""
    from repro.core.approaches import DistGANConfig
    from repro.core.gan import MLPGanConfig, make_mlp_pair
    from repro.core.protocol import run_distgan

    C = 8
    # (1) U-independence on the tiny pair (per-round cost is pure harness)
    steps = 32 if QUICK else 64
    pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=8, g_hidden=16,
                                      d_hidden=16))
    times = {}
    for U in (512, 4096):
        ds = _stream_ds(U, 2)
        fcfg = DistGANConfig(num_users=U, selection="topk",
                             upload_frac=0.5)
        r = run_distgan(pair, fcfg, ds, "approach1", steps=steps,
                        batch_size=32, seed=SEED, eval_samples=0,
                        participation="uniform", cohort_size=C,
                        state_backend="host", materialize_state=False)
        t_us = r.extra["min_step_time_s"] * 1e6
        times[U] = t_us
        counts = r.extra["participation_counts"]
        emit(f"paper_stream/host_U{U}_C{C}", t_us,
             f"steps={steps};users_touched={int((counts > 0).sum())}/{U};"
             f"upload_bytes_per_round={r.extra['upload_bytes_per_round']};"
             f"finite={int(np.all(np.isfinite(r.g_losses)))}")
    ratio = times[4096] / times[512]
    emit("paper_stream/u_flatness", 0.0,
         f"t_U4096/t_U512=x{ratio:.2f};resident=host_ram;"
         f"pass={int(ratio < 1.5)}")

    # (2) double-buffering vs synchronous staging, on a pair whose
    # staging leg (rows + C*B*dim data sampling/device_put) is comparable
    # to its compute leg — the regime the overlap is for.  The GATED
    # metric is the host STALL per round (seconds blocked on the device
    # fetching a round's outputs): synchronous staging must stall for
    # ~the whole device compute every round because the host has nothing
    # else to do, while the double-buffered driver stages round k+1
    # under round k's compute and retires long-finished rounds — its
    # stall collapses toward zero.  Wall-clock speedup is reported but
    # NOT gated: on a 2-core CPU container the host staging thread and
    # the XLA compute threads contend for the same cores, so the wall
    # margin is real-but-noisy (x0.9-1.2 observed); the stall ratio is
    # load-robust because it measures WHERE the host spends the round,
    # not how long the round takes.
    pair2 = make_mlp_pair(MLPGanConfig(data_dim=256, z_dim=32,
                                       g_hidden=256, d_hidden=256))
    ds2 = _stream_ds(1024, 256)
    fcfg2 = DistGANConfig(num_users=1024, selection="topk",
                          upload_frac=0.1)
    steps2 = 20 if QUICK else 32
    reps = 3
    modes = [("sync_staging", dict(prefetch=False)),
             ("double_buffered", dict(prefetch=True, async_rounds=1))]
    best = {name: float("inf") for name, _ in modes}
    stall = {name: float("inf") for name, _ in modes}
    # reps INTERLEAVED so a background-load swing hits both sides alike
    # (min is the steady-state estimator, as everywhere in this harness)
    for _ in range(reps):
        for name, kw in modes:
            r = run_distgan(pair2, fcfg2, ds2, "approach1", steps=steps2,
                            batch_size=128, seed=SEED, eval_samples=0,
                            participation="uniform", cohort_size=C,
                            state_backend="host", **kw)
            best[name] = min(best[name], r.extra["min_step_time_s"])
            stall[name] = min(stall[name],
                              r.extra["host_stall_s_per_round"])
    for name, _ in modes:
        emit(f"paper_stream/{name}", best[name] * 1e6,
             f"U=1024;C={C};B=128;dim=256;best_of={reps};"
             f"host_stall_us={stall[name] * 1e6:.0f}")
    sp = best["sync_staging"] / best["double_buffered"]
    ratio = stall["double_buffered"] / max(stall["sync_staging"], 1e-9)
    emit("paper_stream/overlap_speedup", 0.0,
         f"stall_db/stall_sync=x{ratio:.3f};wall=x{sp:.2f};"
         f"async_rounds=1;prefetch=1;pass={int(ratio < 0.5)}")


# ---------------------------------------------------------------------------
# Store-resident fused cohort rounds (PR 7 tentpole)
# ---------------------------------------------------------------------------

def paper_fused_store():
    """Store-resident fused cohort rounds: gather→train→scatter for a
    whole K-round window in ONE compiled dispatch.

    Device leg (U=4096, C=8, K=16): ``make_fused_store_engine`` scans the
    window over the resident (U, N) store with the carry donated, vs the
    per-round rows engine streamed over a ``DeviceStateBackend`` — K
    dispatches + K row gathers/scatters + K metric syncs per window.
    GATED: the fused side must run the whole run (full windows AND the
    masked remainder) out of ONE compiled program with exactly one engine
    call per window.  The wall speedup is reported but NOT gated — on
    this 2-core container the dispatch overhead being removed is real but
    its wall margin is background-load noisy (same policy as
    paper_stream's wall number).

    Host leg (host-resident store): windowed superbatch staging — gather
    the window's rows as one (K, C, N) block, one fused K-round program
    with write-after-read forwarding for in-window repeats, ONE blocking
    fetch per window — vs the synchronous per-round stream over the SAME
    backend.  GATED on the host stall per round (seconds the host spends
    blocked on the device): superbatch must stall < 0.5x the per-round
    stream (it collapses ~K-fold: K stalls become 1).  Stall, not wall,
    for the same load-robustness reason as paper_stream.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.approaches import DistGANConfig
    from repro.core.engine import (CohortShared, init_cohort_state,
                                   make_cohort_rows_engine,
                                   make_fused_store_engine)
    from repro.core.federated import DeviceStateBackend, make_schedule
    from repro.core.gan import MLPGanConfig, make_mlp_pair
    from repro.core.protocol import run_distgan
    from repro.core.session import stream_cohort_rounds

    # --- device leg: dispatch-count contract + wall comparison ---------
    U, C, K, B = 4096, 8, 16, 32
    windows = 2 if QUICK else 4
    steps = K * windows
    pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=8, g_hidden=16,
                                      d_hidden=16))
    fcfg = DistGANConfig(num_users=U, selection="topk", upload_frac=0.5)
    sched = make_schedule("uniform", U, C, steps, np.random.default_rng(1))
    data = np.random.default_rng(SEED).normal(
        size=(steps, C, B, 2)).astype(np.float32)

    rows_eng = make_cohort_rows_engine(pair, fcfg, "approach1")
    fs_eng = make_fused_store_engine(pair, fcfg, "approach1")
    calls = {"rows": 0, "fused": 0}

    def rows_counted(*a):
        calls["rows"] += 1
        return rows_eng(*a)

    def fused_counted(*a, **kw):
        calls["fused"] += 1
        return fs_eng(*a, **kw)

    def init():
        cs = init_cohort_state(pair, fcfg, jax.random.key(SEED),
                               sync_ds=True)
        return cs, CohortShared(cs.g, cs.g_opt, cs.server_d, cs.step,
                                cs.key), DeviceStateBackend(cs.store)

    def run_rows(shared, backend, i):
        shared, _, _ = stream_cohort_rounds(
            rows_counted, shared, backend, sched[i:i + K],
            lambda r: data[i + r])
        return shared

    # every window — full or remainder — passes a (K,) valid mask, so one
    # compiled program serves them all (valid=None would trace a second,
    # maskless program)
    full = jnp.ones((K,), bool)

    def run_fused(cstate, i):
        cstate, m = fused_counted(cstate, jnp.asarray(data[i:i + K]),
                                  jnp.asarray(sched[i:i + K]), valid=full)
        jax.block_until_ready(m["g_loss"])
        return cstate

    cstate, shared, backend = init()
    shared = run_rows(shared, backend, 0)       # compile both programs
    cstate = run_fused(cstate, 0)
    t_rows = t_fused = float("inf")
    reps = 2 if QUICK else 3
    for _ in range(reps):                        # interleaved, best-of
        for i in range(K, steps, K):
            t0 = time.perf_counter()
            shared = run_rows(shared, backend, i)
            t_rows = min(t_rows, (time.perf_counter() - t0) / K)
            t0 = time.perf_counter()
            cstate = run_fused(cstate, i)
            t_fused = min(t_fused, (time.perf_counter() - t0) / K)
    n_windows = 1 + reps * (windows - 1)
    one_dispatch = calls["fused"] == n_windows
    # a masked remainder window must reuse the SAME compiled program
    k_rem = 3
    pad = np.concatenate([sched[:k_rem]] * (K // k_rem + 1))[:K]
    dpad = np.concatenate([data[:k_rem]] * (K // k_rem + 1))[:K]
    cstate, _ = fs_eng(cstate, jnp.asarray(dpad), jnp.asarray(pad),
                       valid=jnp.asarray(np.arange(K) < k_rem))
    one_program = fs_eng._cache_size() == 1

    emit(f"paper_fused_store/device_rows_U{U}_C{C}", t_rows * 1e6,
         f"dispatches_per_window={K};rows_roundtrips_per_window={K}")
    emit(f"paper_fused_store/device_fused_U{U}_C{C}", t_fused * 1e6,
         f"rounds_per_jit={K};dispatches_per_window=1;"
         f"programs={fs_eng._cache_size()};store_donated=1")
    sp = t_rows / t_fused
    emit("paper_fused_store/device_dispatch_bound", 0.0,
         f"engine_calls={calls['fused']}/windows={n_windows};"
         f"one_program_incl_remainder={int(one_program)};wall=x{sp:.2f};"
         f"pass={int(one_dispatch and one_program)}")

    # --- host leg: superbatch staging vs per-round streaming -----------
    # dim/width chosen so the per-round D2H fetch + scatter is a visible
    # share of the round (the regime the superbatch collapses); the
    # per-round side keeps prefetch=True — it loses ONLY its K-per-window
    # blocking output fetches, not its data staging overlap
    pair2 = make_mlp_pair(MLPGanConfig(data_dim=256, z_dim=32,
                                       g_hidden=256, d_hidden=256))
    U2, rpj = 1024, 8
    ds2 = _stream_ds(U2, 256)
    fcfg2 = DistGANConfig(num_users=U2, selection="topk", upload_frac=0.1)
    steps2 = 24 if QUICK else 48
    kw = dict(steps=steps2, batch_size=128, seed=SEED, eval_samples=0,
              participation="uniform", cohort_size=8, state_backend="host")
    modes = [("per_round", dict()),
             ("superbatch", dict(rounds_per_jit=rpj,
                                 fuse_store_rounds=True))]
    stall = {name: float("inf") for name, _ in modes}
    best = {name: float("inf") for name, _ in modes}
    fused_flag = {}
    for _ in range(3):                           # interleaved, best-of
        for name, extra_kw in modes:
            r = run_distgan(pair2, fcfg2, ds2, "approach1", **kw,
                            **extra_kw)
            stall[name] = min(stall[name],
                              r.extra["host_stall_s_per_round"])
            best[name] = min(best[name], r.extra["min_step_time_s"])
            fused_flag[name] = r.extra["fused_store"]
    for name, _ in modes:
        emit(f"paper_fused_store/host_{name}", best[name] * 1e6,
             f"U={U2};C=8;dim=256;host_stall_us={stall[name] * 1e6:.0f};"
             f"fused_store={int(fused_flag[name])}")
    ratio = stall["superbatch"] / max(stall["per_round"], 1e-9)
    sp2 = best["per_round"] / best["superbatch"]
    emit("paper_fused_store/host_stall_collapse", 0.0,
         f"stall_super/stall_round=x{ratio:.3f};wall=x{sp2:.2f};"
         f"rounds_per_jit={rpj};stalls_per_window=1_vs_{rpj};"
         f"pass={int(ratio < 0.5 and fused_flag['superbatch'])}")


# ---------------------------------------------------------------------------
# Compressed delta transport (PR 8 tentpole)
# ---------------------------------------------------------------------------

def paper_compress():
    """Quantized, error-fed uploads: ``topk+int8`` vs the dense float32
    row on the 8-Gaussian two-user pair.

    Three matched-rounds runs: the dense f32 baseline (selection
    ``none`` — the full row ships, as in the unmodified paper
    protocol), ``topk`` at frac 0.1 still in f32 (isolates the
    selection from the codec), and ``topk+int8`` with error feedback
    (the PR's transport).  Gated (floor=x3.5 vs a priced-table margin
    of ~x7.9 at frac 0.1):

      * PRICED bytes/round reduction >= 3.5x — `upload_bytes_flat`
        via ``extra["upload_bytes_per_round"]``;
      * MEASURED reduction >= 3.5x from real packed wire buffers
        (``packed_payload_nbytes``: int32 indices + int8 codes + f32
        scale vs the dense f32 row) on a transported-shape row;
      * mode coverage of the compressed run within 1 mode of the dense
        baseline at matched rounds — error feedback is what keeps the
        lossy path tracking the dense one (EF-SGD residual).
    """
    import jax.numpy as jnp

    from repro.core.approaches import DistGANConfig
    from repro.core.federated import (packed_payload_nbytes,
                                      select_delta_flat)
    from repro.core.protocol import run_distgan

    pair = _mlp_pair()
    ds, union = _ring()
    # 600 is the quick floor: the EF residual needs a few hundred rounds
    # to re-inject early quantization error (400 leaves the lossy run 3
    # modes short; 600 reaches 8/8 like the dense baseline)
    steps = 600 if QUICK else 2000
    C = 2
    modes_hit, priced = {}, {}
    for name, sel, codec in [("dense_f32", "none", "none"),
                             ("topk_f32", "topk", "none"),
                             ("topk_int8_ef", "topk", "topk_int8")]:
        fcfg = DistGANConfig(num_users=2, selection=sel, upload_frac=0.1)
        r = run_distgan(pair, fcfg, ds, "approach1", steps=steps,
                        batch_size=128, seed=SEED, participation="uniform",
                        cohort_size=C, codec=codec)
        _, hist = union.mode_coverage(r.samples)
        modes_hit[name] = int((hist > 10).sum())
        priced[name] = int(r.extra["upload_bytes_per_round"])
        comp = r.extra["compression"]
        emit(f"paper_compress/{name}", r.step_time_s * 1e6,
             f"steps={steps};priced_bytes_per_round={priced[name]};"
             f"modes={modes_hit[name]}/8;codec={comp['codec']};"
             f"ef={int(comp['error_feedback'])}")

    # measured ground truth: pack ONE transported row's real buffers at
    # the exact flat width the runs shipped (priced = C rows/round, so
    # the per-row ratio is the per-round ratio)
    n = priced["dense_f32"] // (C * 4)           # dense f32 row width
    rng = np.random.default_rng(SEED)
    row = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    masked, _ = select_delta_flat(row, "topk", frac=0.1)
    meas_dense = packed_payload_nbytes(np.asarray(row), "none", "none")
    meas_comp = packed_payload_nbytes(np.asarray(masked), "topk",
                                      "topk_int8")
    priced_ratio = priced["dense_f32"] / priced["topk_int8_ef"]
    meas_ratio = meas_dense / meas_comp
    md, mc = modes_hit["dense_f32"], modes_hit["topk_int8_ef"]
    emit("paper_compress/upload_reduction", 0.0,
         f"priced=x{priced_ratio:.2f};measured=x{meas_ratio:.2f};"
         f"floor=x3.5;modes_dense={md};modes_topk_f32="
         f"{modes_hit['topk_f32']};modes_topk_int8={mc};"
         f"pass={int(priced_ratio >= 3.5 and meas_ratio >= 3.5 and mc >= md - 1)}")


# ---------------------------------------------------------------------------
# Multi-process federation control plane (PR 10 tentpole)
# ---------------------------------------------------------------------------

def paper_multihost():
    """The ``multihost`` backend: U logical users sharded over 2 local
    worker processes, coordinator-driven rounds over the RPC wire.

    Gates:

    * per-round time FLAT in U (t_U4096 / t_U512 < 1.5) — per round only
      the C scheduled rows cross the wire, so the store size U prices
      nothing on the round path (only worker RAM);
    * measured wire payload bytes per run EXACTLY equal the
      ``upload_bytes_flat``-composed pricing (``wire.priced_round_nbytes``)
      for the configured transport — codec=topk_int8 with
      ``stage_rows``: D-row legs cross as int8 + per-row f32 scale, opt
      and EF-residual legs as exact f32 (the ledger is never quantized).
      The backend also hard-asserts this per RPC call.

    The in-graph DELTA upload (what each user ships to the server
    combine, codec topk_int8) is priced separately via
    ``extra["upload_bytes_per_round"]`` and reported for comparison —
    the store wire and the delta upload are different legs of the same
    PR 8 pricing table."""
    from repro.core.approaches import (DistGANConfig, d_flat_layout,
                                       d_opt_flat_layout)
    from repro.core.gan import MLPGanConfig, make_mlp_pair
    from repro.core.session import FederationSession
    from repro.core.spec import (BackendSpec, CombineSpec, CompressionSpec,
                                 FederationSpec, ParticipationSpec)
    from repro.multihost import wire

    pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=8, g_hidden=16,
                                      d_hidden=16))
    C, W = 8, 2
    steps = 24 if QUICK else 64
    fcfg0 = DistGANConfig(num_users=8, selection="topk", upload_frac=0.5)
    nd = d_flat_layout(pair).n
    no = d_opt_flat_layout(pair, fcfg0).n
    times, stats = {}, {}
    for U in (512, 4096):
        ds = _stream_ds(U, 2)
        fcfg = DistGANConfig(num_users=U, selection="topk",
                             upload_frac=0.5)
        spec = FederationSpec(
            approach="approach1", batch_size=32, seed=SEED,
            eval_samples=0,
            participation=ParticipationSpec(scheduler="uniform",
                                            cohort_size=C),
            backend=BackendSpec(kind="multihost", workers=W,
                                materialize_state=False),
            combine=CombineSpec(compression=CompressionSpec(
                codec="topk_int8", error_feedback=True,
                stage_rows=True)))
        sess = FederationSession(pair, fcfg, ds, spec)
        try:
            r = sess.run(steps)
            mb = r.extra["host_backend"]
            times[U] = r.extra["min_step_time_s"] * 1e6
            stats[U] = {"measured": mb.round_payload_bytes,
                        "socket": mb.socket_bytes,
                        "rpc_calls": mb.rpc_calls,
                        "delta_priced": int(
                            r.extra["upload_bytes_per_round"])}
        finally:
            sess.close()
        emit(f"paper_multihost/U{U}_W{W}_C{C}", times[U],
             f"steps={steps};workers={W};"
             f"wire_payload_bytes={stats[U]['measured']};"
             f"rpc_calls={stats[U]['rpc_calls']};"
             f"delta_upload_priced_bytes_per_round="
             f"{stats[U]['delta_priced']};"
             f"finite={int(np.all(np.isfinite(r.g_losses)))}")
    ratio = times[4096] / times[512]
    priced = steps * wire.priced_round_nbytes(C, nd, no,
                                              stage_codec="int8",
                                              has_residual=True)
    measured = stats[4096]["measured"]
    envelope = stats[4096]["socket"] / max(measured, 1)
    emit("paper_multihost/u_independence", 0.0,
         f"t_U4096/t_U512=x{ratio:.2f};workers={W};"
         f"pass={int(ratio < 1.5)}")
    emit("paper_multihost/wire_priced_vs_measured", 0.0,
         f"priced={priced};measured={measured};codec=topk_int8;"
         f"stage_rows=int8+scale;socket/payload=x{envelope:.2f};"
         f"pass={int(measured == priced)}")


# ---------------------------------------------------------------------------
# Multi-tenant generation serving (PR 5 tentpole)
# ---------------------------------------------------------------------------

def paper_serve():
    """Serving the trained generator (paper §7: "provide model for users
    who lack computing power") at a mixed request-size workload.

    Gates: (1) the bucketed micro-batched service must deliver >= 1.5x
    the samples/s of the naive one-jit-dispatch-per-request loop (which
    gets a per-size program cache, so the comparison is pure dispatch/
    sync/coalescing — not compile time; the margin is machine-dependent:
    x5.9 on the 2-core box that calibrated the original 3x floor, x1.9
    on a 1-core box where per-dispatch overhead is much lower — the
    floor is set to hold on both); (2) the service's compiled request
    programs are bounded by the bucket ladder, NOT by the number of
    requests or distinct sizes; (3) a served request's bytes equal its
    solo replay — batch composition is invisible (per-request RNG
    isolation).  Both sides timed as best-of-``reps`` interleaved passes
    (min = the steady-state estimator on this 2-core box)."""
    from repro.core.approaches import DistGANConfig
    from repro.core.gan import MLPGanConfig, make_mlp_pair
    from repro.core.session import FederationSession
    from repro.core.spec import FederationSpec, ServeSpec
    from repro.serve import GenerationService
    from repro.serve.sampler import SamplerEngine

    pair = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=8, g_hidden=16,
                                      d_hidden=16))
    ds, _ = _ring()
    fcfg = DistGANConfig(num_users=2, selection="topk", upload_frac=0.5)
    spec = FederationSpec(approach="approach1", batch_size=32,
                          eval_samples=0,
                          serve=ServeSpec(max_batch=128, flush_ms=0.5))
    sess = FederationSession(pair, fcfg, ds, spec)
    sess.run(4)
    g = sess.generator_params()

    n_req = 200 if QUICK else 600
    reps = 3 if QUICK else 5
    rng = np.random.default_rng(SEED)
    sizes = rng.integers(1, 13, n_req)
    seeds = rng.integers(0, 2**31, n_req)
    total = int(sizes.sum())

    svc = GenerationService.from_session(sess)
    # the naive side still gets a program per DISTINCT size (fair: no
    # recompiles in the timed loop) — it pays one dispatch + one host
    # sync per request
    naive = SamplerEngine(pair, sorted(set(int(s) for s in sizes)))

    def run_naive():
        for i, (n, s) in enumerate(zip(sizes, seeds)):
            n = int(n)
            np.asarray(naive.sample_bucket(
                g, n, [int(s)] * n, [i] * n, np.arange(n)))

    def run_bucketed(base_rid):
        futs = [svc.submit(int(i % 8), int(n), seed=int(s),
                           request_id=base_rid + i)
                for i, (n, s) in enumerate(zip(sizes, seeds))]
        svc.drain()
        return futs

    run_naive()                      # compile the per-size programs
    futs = run_bucketed(0)           # compile the bucket programs
    t_naive = t_buck = float("inf")
    for r in range(reps):            # interleaved, best-of
        t0 = time.perf_counter()
        run_naive()
        t_naive = min(t_naive, time.perf_counter() - t0)
        t0 = time.perf_counter()
        futs = run_bucketed((r + 1) * n_req)
        t_buck = min(t_buck, time.perf_counter() - t0)

    # determinism: served bytes == solo replay bytes for a mid-workload
    # request, regardless of who shared its buckets
    j = n_req // 2
    served = futs[j].result()
    rep_rid = reps * n_req + j
    det = np.array_equal(served,
                         svc.replay(int(seeds[j]), rep_rid, int(sizes[j])))
    n_buckets = len(svc.serve.buckets())
    compile_ok = svc.engine.compile_count <= n_buckets
    bat = svc.batcher.stats

    emit("paper_serve/naive_per_request", t_naive / total * 1e6,
         f"requests={n_req};samples={total};"
         f"programs={len(naive._request_progs)};dispatches={n_req}")
    emit("paper_serve/bucketed_microbatch", t_buck / total * 1e6,
         f"requests={n_req};samples={total};"
         f"programs={svc.engine.compile_count};buckets={n_buckets};"
         f"pad_frac={bat['padded_slots'] / max(bat['dispatched_slots'], 1):.3f}")
    sp = t_naive / t_buck
    emit("paper_serve/serve_speedup", 0.0,
         f"x{sp:.2f};floor=x1.5;samples_per_s={total / t_buck:,.0f};"
         f"compile_le_buckets={int(compile_ok)};deterministic={int(det)};"
         f"pass={int(sp >= 1.5 and compile_ok and det)}")


# ---------------------------------------------------------------------------
# Continuous-batching LM decode (PR 6 tentpole)
# ---------------------------------------------------------------------------

def paper_decode():
    """Slot-based continuous-batching decode vs sequential per-request
    greedy decode, at mixed prompt/generation lengths on the reduced
    tinyllama config.

    Gates: (1) continuous-batching tokens/s >= 3x the sequential loop
    (which shares ONE precompiled step program and a fixed-size cache, so
    the comparison is batching/dispatch — not compile time); (2) compiled
    programs bounded by the prefill bucket ladder + 1 decode program;
    (3) byte determinism — engine tokens equal the sequential loop's,
    equal their solo ``replay``, and invariant to submission order (slot
    assignment and batch-mates are invisible in the bytes)."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.core.spec import DecodeSpec
    from repro.models import model as M
    from repro.serve.decode import DecodeEngine, DecodeRequest

    cfg = get_config("tinyllama-1.1b").reduced()
    params = M.init_params(cfg, jax.random.key(SEED))
    rng = np.random.default_rng(SEED)
    n_req = 24 if QUICK else 64
    reps = 3 if QUICK else 5
    T = 64
    plens = rng.integers(4, 25, n_req)
    gens = rng.integers(8, 33, n_req)
    prompts = [rng.integers(1, cfg.vocab_size, p).astype(np.int32)
               for p in plens]
    total = int(gens.sum())

    spec = DecodeSpec(slots=8, max_seq=T, flush_ms=0.0)
    eng = DecodeEngine(cfg, params, spec)
    step = jax.jit(lambda p, c, t, i: M.decode_step(p, c, t, i, cfg))

    def run_sequential():
        # what a per-request server pays: one cache + one dispatch and
        # host sync per token, requests strictly one after another.  The
        # cache is allocated at the same fixed T for every request, so
        # the whole loop runs ONE compiled program (index masking makes
        # the allocated size invisible in the bytes).
        outs = []
        for prompt, g in zip(prompts, gens):
            cache = M.init_cache(cfg, 1, T)
            out = []
            tok = jnp.full((1, 1), int(prompt[0]), jnp.int32)
            for i in range(len(prompt) + int(g) - 1):
                logits, cache = step(params, cache, tok, jnp.int32(i))
                nxt = int(jnp.argmax(logits[0, -1]))
                if i + 1 < len(prompt):
                    tok = jnp.full((1, 1), int(prompt[i + 1]), jnp.int32)
                else:
                    out.append(nxt)
                    tok = jnp.full((1, 1), nxt, jnp.int32)
            outs.append(np.asarray(out, np.int32))
        return outs

    def run_engine(order):
        futs = {int(i): eng.submit(
            DecodeRequest(user_id=int(i) % 4, prompt=prompts[i],
                          max_new=int(gens[i])), request_id=int(i))
            for i in order}
        eng.drain()
        return {i: f.result() for i, f in futs.items()}

    outs_seq = run_sequential()          # compile the step program
    outs_a = run_engine(range(n_req))    # compile bucket + decode programs
    t_seq = t_cont = float("inf")
    for _ in range(reps):                # interleaved, best-of
        t0 = time.perf_counter()
        run_sequential()
        t_seq = min(t_seq, time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_engine(range(n_req))
        t_cont = min(t_cont, time.perf_counter() - t0)

    # determinism: same rids resubmitted in REVERSE order — different
    # slot assignment and batch-mates, identical bytes; plus solo replay
    outs_b = run_engine(range(n_req - 1, -1, -1))
    mix_ok = all(np.array_equal(outs_a[i], outs_b[i])
                 for i in range(n_req))
    seq_ok = all(np.array_equal(outs_a[i], outs_seq[i])
                 for i in range(n_req))
    j = n_req // 2
    rep_ok = np.array_equal(
        outs_a[j], eng.replay(prompts[j], int(gens[j]), request_id=j))
    pc = eng.program_counts
    prog_ok = (pc["prefill"] <= len(spec.buckets()) and pc["decode"] == 1)
    st = eng.engine_stats()

    emit("paper_decode/sequential_greedy", t_seq / total * 1e6,
         f"requests={n_req};tokens={total};programs=1;cache_per_req=1x{T}")
    emit("paper_decode/continuous_batching", t_cont / total * 1e6,
         f"slots={spec.slots};buckets={len(spec.buckets())};"
         f"prefill_programs={pc['prefill']};decode_programs={pc['decode']};"
         f"pool_mb={st['pool_nbytes'] / 1e6:.2f};"
         f"mean_occupancy={st.get('mean_occupancy', 0):.2f}")
    sp = t_seq / t_cont
    emit("paper_decode/decode_speedup", 0.0,
         f"x{sp:.2f};floor=x3.0;tokens_per_s={total / t_cont:,.0f};"
         f"programs_bounded={int(prog_ok)};match_sequential={int(seq_ok)};"
         f"replay={int(rep_ok)};mix_invariant={int(mix_ok)};"
         f"pass={int(sp >= 3.0 and prog_ok and seq_ok and rep_ok and mix_ok)}")


# ---------------------------------------------------------------------------
# Cross-user bandwidth: the paper's selective upload, bandwidth-true
# (EXPERIMENTS.md §Perf pair C iter 5)
# ---------------------------------------------------------------------------

def paper_bandwidth():
    """Bytes crossing the user boundary per round, from the compiled HLO
    of the SPMD approach-1 step (2 users, a 20M-param 'CelebA-class' D).
    The paper's dense masked fold moves full-size tensors regardless of
    selection; the shared-mask random-k variant moves frac*N."""
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, jax.numpy as jnp
        from repro.core.gan import make_mlp_pair, MLPGanConfig
        from repro.core.approaches import DistGANConfig, init_state
        from repro.core.spmd import make_spmd_step
        from repro.launch.mesh import make_users_mesh
        from repro.roofline.analysis import collective_bytes_from_hlo
        pair = make_mlp_pair(MLPGanConfig(data_dim=784, z_dim=64,
                                          g_hidden=512, d_hidden=4096))
        mesh = make_users_mesh(2)
        for name, fcfg in [
            ("dense_maxabs", DistGANConfig(num_users=2, selection="topk",
                                           upload_frac=0.1)),
            ("shared_random_f0.1", DistGANConfig(
                num_users=2, selection="shared_random", upload_frac=0.1)),
            ("shared_random_f0.01", DistGANConfig(
                num_users=2, selection="shared_random", upload_frac=0.01)),
        ]:
            state = init_state(pair, fcfg, jax.random.key(0), sync_ds=True)
            step = make_spmd_step(pair, fcfg, mesh, "approach1")
            hlo = step.lower(state, jnp.zeros((2, 64, 784))).compile().as_text()
            print(name, collective_bytes_from_hlo(hlo)["total"])
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=_cpu_child_env(), timeout=560)
    if r.returncode != 0:
        raise RuntimeError(f"paper_bandwidth child rc={r.returncode}: "
                           f"{r.stderr[-400:]}")
    rows = dict(line.split() for line in r.stdout.strip().splitlines()
                if line.strip())
    dense = float(rows["dense_maxabs"])
    for name, v in rows.items():
        emit(f"paper_bandwidth/{name}", 0.0,
             f"bytes_per_round={float(v):.3e};reduction=x{dense/float(v):.1f}")


# ---------------------------------------------------------------------------
# Kernel micro-bench (interpret mode: correctness-path timing only)
# ---------------------------------------------------------------------------

def kernels_micro():
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    def bench(fn, *args, n=3):
        fn(*args)  # compile
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / n * 1e6

    x = jax.random.normal(jax.random.key(0), (65536,))
    us = bench(lambda a, f: ops.topk_mask(a, f, mode="global"), x, 0.1)
    emit("kernels/topk_mask_global_65536", us,
         "interpret_mode=1;exact_fullvector=1")
    us = bench(lambda a, f: ops.topk_mask(a, f, mode="block"), x, 0.1)
    emit("kernels/topk_mask_block_65536", us, "interpret_mode=1")

    q = jax.random.normal(jax.random.key(1), (1, 256, 4, 64))
    k = jax.random.normal(jax.random.key(2), (1, 256, 2, 64))
    v = jax.random.normal(jax.random.key(3), (1, 256, 2, 64))
    us = bench(lambda a, b, c: ops.flash_attention(a, b, c, causal=True),
               q, k, v)
    emit("kernels/flash_attn_256", us, "interpret_mode=1")

    xs = jax.random.normal(jax.random.key(4), (1, 256, 4, 32)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(5), (1, 256, 4)))
    A = -jnp.ones((4,))
    Bm = jax.random.normal(jax.random.key(6), (1, 256, 1, 16)) * 0.3
    us = bench(lambda a, b, c, d, e: ops.ssd_scan(a, b, c, d, e, chunk=64),
               xs, dt, A, Bm, Bm)
    emit("kernels/ssd_scan_256", us, "interpret_mode=1")


# ---------------------------------------------------------------------------
# Roofline table (deliverable g) from the dry-run artifacts
# ---------------------------------------------------------------------------

# combos the quick path self-generates when the artifact dir is empty:
# one attention arch (train + decode shapes) and one SSM arch — enough to
# populate the roofline row classes without the full 10-arch sweep
_QUICK_DRYRUN = [("tinyllama-1.1b", "train_4k"),
                 ("tinyllama-1.1b", "decode_32k"),
                 ("mamba2-780m", "train_4k")]


def _cpu_child_env() -> dict:
    """Environment for the benchmark's child processes.  They are CPU
    cost-model jobs (forced host devices, 512-device dry-run compiles):
    ``JAX_PLATFORMS=cpu`` keeps them off the accelerator, which belongs
    to this process once it has touched JAX."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return env


def _gen_dryrun_artifacts():
    """Produce experiments/dryrun/*.json in a SUBPROCESS — dryrun pins
    XLA_FLAGS (512 fake host devices) at import, which must not leak into
    this process's already-initialized JAX runtime."""
    import subprocess
    env = _cpu_child_env()
    cmds = ([[sys.executable, "-m", "repro.launch.dryrun",
              "--arch", a, "--shape", s] for a, s in _QUICK_DRYRUN]
            if QUICK else
            [[sys.executable, "-m", "repro.launch.dryrun", "--all"]])
    for cmd in cmds:
        r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           timeout=300 if QUICK else 3600)
        if r.returncode != 0:
            raise RuntimeError(f"dryrun {' '.join(cmd[3:])} "
                               f"rc={r.returncode}: {r.stderr[-400:]}")


def roofline_table():
    art = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "dryrun", "*.json")
    files = sorted(glob.glob(art))
    if not files:
        _gen_dryrun_artifacts()      # empty dir -> seed it, don't punt
        files = sorted(glob.glob(art))
    if not files:
        emit("roofline/NO_ARTIFACTS", 0.0,
             "run: python -m repro.launch.dryrun --all")
        return
    n_ok = n_skip = n_fail = 0
    for f in files:
        with open(f) as fh:
            rec = json.load(fh)
        tagpart = f"__{rec['tag']}" if rec.get("tag") else ""
        name = f"roofline/{rec['arch']}__{rec['shape']}__{rec['mesh']}{tagpart}"
        if rec["status"] == "ok":
            n_ok += 1
            emit(name, 0.0,
                 f"dom={rec['dominant']};comp={rec['compute_s']:.3e};"
                 f"mem={rec['memory_s']:.3e};coll={rec['collective_s']:.3e};"
                 f"useful={rec['useful_flops_ratio']:.3f};"
                 f"bytes/dev={rec['bytes_per_device']:.3e}")
        elif rec["status"].startswith("skipped"):
            n_skip += 1
        else:
            n_fail += 1
            emit(name, 0.0, f"FAIL:{rec.get('error', '')[:80]}")
    emit("roofline/summary", 0.0,
         f"ok={n_ok};skipped={n_skip};failed={n_fail};"
         f"pass={int(n_ok > 0 and n_fail == 0)}")


BENCHES = {
    "paper_time": paper_time,
    "paper_loss": paper_loss,
    "paper_mode_coverage": paper_mode_coverage,
    "paper_domain_similarity": paper_domain_similarity,
    "paper_multiuser": paper_multiuser,
    "paper_conv_gan": paper_conv_gan,
    "paper_collapse": paper_collapse,
    "paper_cohort": paper_cohort,
    "paper_stream": paper_stream,
    "paper_fused_store": paper_fused_store,
    "paper_compress": paper_compress,
    "paper_multihost": paper_multihost,
    "paper_serve": paper_serve,
    "paper_decode": paper_decode,
    "paper_bandwidth": paper_bandwidth,
    "kernels_micro": kernels_micro,
    "roofline_table": roofline_table,
}

# --quick smoke gate (<~5 min): fused-engine comparison, kernel micro,
# the cohort U-independence check, the host-store streaming gates, the
# fused store-resident window gates, the serving micro-batching gate,
# the continuous-batching decode gate, and the (self-seeding) roofline
# table.
#
# Gate thresholds under --quick are FLOORS calibrated to hold on the
# weakest CI box (1-2 shared cores), not the margins a full run on a
# quiet machine shows — e.g. serve_speedup gates at x1.5 although the
# 2-core box that calibrated it measured x5.9 (a 1-core box, where
# per-dispatch overhead is much lower, measures x1.9), and
# decode_speedup gates at x3.0 against typical full-run margins of
# x5-8.  Each speedup row names its floor in ``_derived``
# (``floor=x..``) so the artifact is self-describing: a recorded
# x1.82 next to a x1.5 floor is a pass, not a near-miss of some
# undocumented full-run target.
QUICK_BENCHES = ["paper_time", "kernels_micro", "paper_cohort",
                 "paper_stream", "paper_fused_store", "paper_compress",
                 "paper_serve", "paper_decode", "roofline_table"]


def _env_info() -> dict:
    """Provenance block for the artifact: a recorded number is only
    comparable across runs with the runtime/machine context it was
    measured under (a 1-core CI box and a 16-core workstation disagree
    x3+ on every dispatch-bound row)."""
    import jax

    from repro.kernels.ops import _interpret

    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "cpu_count": os.cpu_count(),
        "kernels_interpret_mode": bool(_interpret()),
    }


def write_bench_json(path: str = BENCH_JSON) -> None:
    """Merge this run's rows into the existing artifact (a subset run —
    one bench name, or --quick — must not clobber full-run results).
    ``_env`` is NOT merged: it describes THIS run's machine/runtime and
    is overwritten wholesale."""
    payload, derived = {}, {}
    if os.path.exists(path):
        try:
            with open(path) as fh:
                payload = json.load(fh)
            derived = payload.pop("_derived", {})
            payload.pop("_quick", None)
            payload.pop("_env", None)
        except (json.JSONDecodeError, OSError):
            payload, derived = {}, {}
    payload.update(RESULTS)
    derived.update(DERIVED)
    payload["_derived"] = derived
    payload["_quick"] = QUICK
    payload["_env"] = _env_info()
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")


def main() -> None:
    global QUICK
    args = sys.argv[1:]
    QUICK = "--quick" in args
    names = [a for a in args if not a.startswith("--")]
    if not names:
        names = QUICK_BENCHES if QUICK else list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        sys.exit(f"unknown benchmark(s) {unknown}; "
                 f"choose from: {', '.join(BENCHES)}")
    from repro.launch.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()
    write_bench_json()
    print(f"# wrote {os.path.abspath(BENCH_JSON)}", file=sys.stderr)
    # rows carrying an explicit pass flag ARE the smoke gate: a quick CI
    # run must fail visibly, not just record pass=0 in the artifact
    failed = [n for n, d in DERIVED.items() if "pass=0" in d]
    if failed:
        sys.exit(f"gate failure in: {', '.join(failed)}")


if __name__ == "__main__":
    main()
