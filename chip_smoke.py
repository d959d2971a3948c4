"""Smoke test of the federation trainer and the GAN sampler on a TPU.

Runs the system's main path once, in this one process, at the paper's
widths, through the entry points a user calls (``FederationSession``,
``GenerationService``), and checks what comes out:

1. ``mlp_federation`` -- the paper's MLP pair (784-dim, 256 hidden),
   approach 1 with the Pallas top-k selection and the ``topk_int8``
   codec with error feedback, on the ``device`` backend with
   store-resident fused windows: U=1024 logical users, cohorts of 8.
2. ``conv_federation`` -- the paper's DCGAN pair at 64x64, approach 1
   with the Pallas top-k, U=C=4 silos.
3. ``kernels`` -- the Pallas top-k mask against ``federated.topk_mask``
   on real delta rows from phase 1, the int8 quantize/dequantize kernels
   against the ``kernels/ref.py`` oracles, and a ``tpu_custom_call`` in
   the compiled round program.
4. ``serve`` -- ``GenerationService.from_session`` answers mixed-size
   sample requests from phase 1's generator.

``--chips 4`` runs only ``spmd_federation``: the MLP federation over
``BackendSpec(kind="spmd")`` on a ``users`` mesh of the four chips,
against the same spec and seed on the one-device ``host`` backend.

The script needs a TPU: without one it exits non-zero and prints no
result.  Weights are random, made from a seed; the data is synthetic.
The last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Run:  python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.approaches import (DistGANConfig, d_flat_layout,  # noqa: E402
                                   d_opt_flat_layout)
from repro.core.federated import topk_mask  # noqa: E402
from repro.core.gan import (ConvGanConfig, MLPGanConfig,  # noqa: E402
                            make_conv_pair, make_mlp_pair)
from repro.core.session import FederationSession  # noqa: E402
from repro.core.spec import (BackendSpec, CombineSpec,  # noqa: E402
                             CompressionSpec, EngineSpec, FederationSpec,
                             ParticipationSpec)
from repro.data.federated import (dirichlet_partition,  # noqa: E402
                                  federated_split)
from repro.data.mixtures import digits_like_mixture  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_users_mesh  # noqa: E402
from repro.serve.service import GenerationService  # noqa: E402

# The platform the smoke must run on.  Only the CPU test of the phases
# sets it to "cpu"; there the kernels run in interpret mode and the
# compiled programs hold no tpu_custom_call.
PLATFORM = "tpu"

# Served samples lie in [-1, 1].  Replay runs the request in another
# bucket program than the one that served it.  On the TPU an f32 matmul
# at default precision rounds its inputs to bfloat16 (8 significant
# bits), so a last-bit difference in one program's f32 accumulation can
# flip a hidden activation's rounding by 2^-8 relative, and the
# generator's hidden layers carry that to ~1e-2 at the output (8.0e-3
# measured on v5e).  The gate, 1/32, is 8 steps of an 8-bit pixel and
# four times that measurement.  The bitwise result is printed beside it.
SERVE_REPLAY_ATOL = 1.0 / 32

# spmd (4 chips) against host (1 device), both traced at "highest"
# matmul precision.  The two backends run different round bodies
# (per-shard vs vmapped users), so reductions reassociate.  Adam's first
# steps move every coordinate by about +-d_lr, so a rounding difference
# in a near-zero gradient flips a sign, top-k near-ties flip, and error
# feedback releases what one side held back: the stores cannot agree
# bitwise.  What must agree: last_round exactly, rows of untrained users
# untouched on both sides, the losses (BCE values of order 1) within
# 1e-2, and the direction of the store's update -- the cosine between
# the two backends' (final - initial) stores at least 0.9.  A misrouted
# row or a wrong fold leaves that cosine near 0.
SPMD_LOSS_ATOL = 1e-2
SPMD_UPDATE_MIN_COSINE = 0.9


@dataclasses.dataclass(frozen=True)
class MlpCell:
    """Phase 1 (and the four-chip phase): the paper's MNIST MLP pair."""

    mlp: MLPGanConfig = MLPGanConfig(data_dim=784, z_dim=64, g_hidden=256,
                                     d_hidden=256)
    users: int = 1024
    cohort: int = 8
    rounds_per_jit: int = 16
    windows: int = 3
    batch: int = 64
    samples_per_class: int = 1024
    requests: tuple = ((0, 1), (1, 7), (2, 64), (3, 33), (4, 5))


@dataclasses.dataclass(frozen=True)
class ConvCell:
    """Phase 2: the paper's DCGAN pair at 64x64, cross-silo U=C=4."""

    conv: ConvGanConfig = ConvGanConfig(image_size=64, channels=1, z_dim=100,
                                        base_filters=64)
    users: int = 4
    rounds_per_jit: int = 4
    windows: int = 2
    batch: int = 64
    samples_per_class: int = 256


@dataclasses.dataclass(frozen=True)
class SpmdCell:
    """Four-chip phase: one cohort member per chip."""

    mlp: MLPGanConfig = MlpCell.mlp
    users: int = 1024
    cohort: int = 4
    rounds: int = 16
    windows: int = 2
    batch: int = 64
    samples_per_class: int = 1024


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_platform(count: int) -> dict:
    """Fail unless JAX sees ``count`` devices of ``PLATFORM``."""
    info = device_info()
    if info["platform"] != PLATFORM:
        raise SystemExit(
            f"chip_smoke needs a {PLATFORM.upper()}, but JAX found "
            f"{info['count']} {info['platform']!r} device(s) "
            f"({info['kind']}); nothing was run")
    if info["count"] < count:
        raise SystemExit(f"chip_smoke --chips {count} needs {count} "
                         f"devices, JAX found {info['count']}")
    return info


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def digit_images(size: int, per_class: int, seed: int = 0):
    """(10 * per_class, size, size) synthetic digit-like images + labels."""
    rng = np.random.default_rng(seed)
    data, labels = [], []
    for c in range(10):
        _, sample = digits_like_mixture([c], size=size)
        data.append(sample(rng, per_class))
        labels.append(np.full(per_class, c))
    return np.concatenate(data), np.concatenate(labels)


def mlp_dataset(users: int, per_class: int):
    data, labels = digit_images(28, per_class)
    return dirichlet_partition(data.reshape(len(data), -1), labels, users,
                               alpha=1.0, seed=0)


def mlp_spec(cell, backend: str, fused: bool, rounds_per_jit: int):
    """Approach 1, top-k selection, topk_int8 codec with error feedback."""
    return FederationSpec(
        approach="approach1", batch_size=cell.batch, seed=0, eval_samples=0,
        engine=EngineSpec("fused", rounds_per_jit=rounds_per_jit,
                          fuse_store_rounds=fused),
        participation=ParticipationSpec("round_robin",
                                        cohort_size=cell.cohort),
        backend=(BackendSpec(backend) if backend == "device" else
                 BackendSpec(backend, materialize_state=False)),
        combine=CombineSpec(compression=CompressionSpec(
            codec="topk_int8", error_feedback=True)))


def mlp_fcfg(cell) -> DistGANConfig:
    return DistGANConfig(num_users=cell.users, selection="topk",
                         upload_frac=0.1, use_topk_kernel=True)


def store_nbytes(pair, fcfg: DistGANConfig, users: int, ef: bool) -> int:
    nd = d_flat_layout(pair).n
    no = d_opt_flat_layout(pair, fcfg).n
    return users * 4 * (nd * (2 if ef else 1) + no + 1)


def expected_staleness(schedules, users: int) -> np.ndarray:
    """Rounds since each user last trained, from the schedule alone."""
    sched = np.concatenate(schedules)
    last = np.zeros(users, np.int64)
    for r, row in enumerate(sched):
        last[row] = r + 1
    return len(sched) - last


def check_finite(phase: str, name: str, arr) -> None:
    arr = np.asarray(arr)
    if not np.all(np.isfinite(arr)):
        raise AssertionError(f"{phase}: {name} has non-finite values")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def mlp_federation(cell: MlpCell = MlpCell()) -> dict:
    """Phase 1.  Returns the live session and real per-user delta rows."""
    pair = make_mlp_pair(cell.mlp)
    fcfg = mlp_fcfg(cell)
    ds = mlp_dataset(cell.users, cell.samples_per_class)
    sess = FederationSession(pair, fcfg, ds, mlp_spec(
        cell, "device", True, cell.rounds_per_jit))
    first = np.arange(cell.cohort)     # round_robin trains users 0..C-1 first
    before = np.stack([sess.user_d_flat(int(u)) for u in first])

    t0 = time.perf_counter()
    res = sess.run(cell.rounds_per_jit)
    compile_s = res.extra["compile_s"]
    first_window_s = time.perf_counter() - t0
    after = np.stack([sess.user_d_flat(int(u)) for u in first])
    g_losses, d_losses = [res.g_losses], [res.d_losses]
    schedules = [res.extra["schedule"]]
    staleness = res.extra["staleness"]
    del res                            # holds a full (U, ...) state copy

    t1 = time.perf_counter()
    for _ in range(cell.windows - 1):
        res = sess.run(cell.rounds_per_jit)
        g_losses.append(res.g_losses)
        d_losses.append(res.d_losses)
        schedules.append(res.extra["schedule"])
        staleness = res.extra["staleness"]
        del res
    run_s = time.perf_counter() - t1

    g_losses, d_losses = np.concatenate(g_losses), np.concatenate(d_losses)
    check_finite("mlp_federation", "g_loss", g_losses)
    check_finite("mlp_federation", "d_loss", d_losses)
    want = expected_staleness(schedules, cell.users)
    if not np.array_equal(np.asarray(staleness), want):
        raise AssertionError("mlp_federation: last_round advanced for other "
                             "users than the schedule trained")
    trained = int(np.sum(want < len(g_losses)))
    log("mlp_federation", users=cell.users, cohort=cell.cohort,
        rounds=len(g_losses), trained_users=trained,
        store_bytes=store_nbytes(pair, sess.fcfg, cell.users, True),
        compile_s=f"{compile_s:.3f}",
        first_window_s=f"{first_window_s:.3f}",
        run_s=f"{run_s:.3f}",
        g_loss_first=f"{g_losses[0]:.4f}", g_loss_last=f"{g_losses[-1]:.4f}",
        peak_bytes=peak_bytes())
    return {"session": sess, "deltas": after - before}


def conv_federation(cell: ConvCell = ConvCell()) -> None:
    """Phase 2: the DCGAN pair, full participation of U=C silos."""
    pair = make_conv_pair(cell.conv)
    size = cell.conv.image_size
    data, labels = digit_images(size, cell.samples_per_class, seed=1)
    data = data.reshape(len(data), size, size, 1)
    classes = np.array_split(np.arange(10), cell.users)
    ds = federated_split(data, labels, [list(c) for c in classes])
    fcfg = DistGANConfig(num_users=cell.users, selection="topk",
                         upload_frac=0.1, use_topk_kernel=True)
    spec = FederationSpec(
        approach="approach1", batch_size=cell.batch, seed=0, eval_samples=0,
        engine=EngineSpec("fused", rounds_per_jit=cell.rounds_per_jit))
    sess = FederationSession(pair, fcfg, ds, spec)

    res = sess.run(cell.rounds_per_jit)
    compile_s = res.extra["compile_s"]
    g_losses, d_losses = [res.g_losses], [res.d_losses]
    t1 = time.perf_counter()
    for _ in range(cell.windows - 1):
        res = sess.run(cell.rounds_per_jit)
        g_losses.append(res.g_losses)
        d_losses.append(res.d_losses)
    run_s = time.perf_counter() - t1
    g_losses, d_losses = np.concatenate(g_losses), np.concatenate(d_losses)
    check_finite("conv_federation", "g_loss", g_losses)
    check_finite("conv_federation", "d_loss", d_losses)
    nd = d_flat_layout(pair).n
    log("conv_federation", image=f"{size}x{size}", users=cell.users,
        d_params=nd, rounds=len(g_losses),
        state_bytes=store_nbytes(pair, sess.fcfg, cell.users, False),
        compile_s=f"{compile_s:.3f}", run_s=f"{run_s:.3f}",
        g_loss_first=f"{g_losses[0]:.4f}", g_loss_last=f"{g_losses[-1]:.4f}",
        peak_bytes=peak_bytes())


def kernels(sess: FederationSession, deltas: np.ndarray,
            cell: MlpCell = MlpCell()) -> None:
    """Phase 3: kernels against their plain references, on the device."""
    frac = sess.fcfg.upload_frac
    rows = jnp.asarray(deltas)
    t0 = time.perf_counter()
    for r in range(rows.shape[0]):
        got = np.asarray(kops.topk_mask(rows[r], frac))
        want = np.asarray(jax.jit(topk_mask, static_argnums=1)(rows[r],
                                                               frac))
        if not np.array_equal(got, want):
            raise AssertionError(
                f"kernels: Pallas top-k mask differs from federated."
                f"topk_mask on delta row {r} "
                f"({int(np.sum(got != want))} entries)")
    log("kernels", check="topk_mask==federated.topk_mask",
        rows=rows.shape[0], n=rows.shape[1], frac=frac, equal=True)

    # int8 codec: jitted kernels vs the jnp oracles.  Under jit XLA may
    # rewrite the scale's division, so the scale agrees to 1e-6 relative,
    # codes to one step, and dequantized rows to one scale step.
    for stochastic in (False, True):
        seed = jnp.int32(7) if stochastic else None
        q, s = kops.quantize_rows(rows, stochastic=stochastic, seed=seed)
        qr, sr = jax.jit(kref.quantize_rows_ref,
                         static_argnames="stochastic")(
            rows, stochastic=stochastic, seed=seed)
        deq = np.asarray(kops.dequantize_rows(q, s))
        deq_ref = np.asarray(kref.dequantize_rows_ref(qr, sr))
        s, sr = np.asarray(s), np.asarray(sr)
        code_err = int(np.max(np.abs(np.asarray(q, np.int32)
                                     - np.asarray(qr, np.int32))))
        scale_rel = float(np.max(np.abs(s - sr) / np.maximum(sr, 1e-30)))
        deq_err = float(np.max(np.abs(deq - deq_ref) / sr[:, None]))
        ok = scale_rel <= 1e-6 and code_err <= 1 and deq_err <= 1.0 + 1e-3
        log("kernels", check="quantize_rows~quantize_rows_ref",
            stochastic=stochastic, scale_rel_err=f"{scale_rel:.3e}",
            max_code_diff=code_err, max_dequant_diff_in_scales=
            f"{deq_err:.4f}", within_bound=ok)
        if not ok:
            raise AssertionError("kernels: int8 codec outside its bound")

    # the compiled round program holds the Mosaic kernels
    drv = sess._driver
    K, C, B = cell.rounds_per_jit, cell.cohort, cell.batch
    reals = jax.ShapeDtypeStruct((K, C, B, cell.mlp.data_dim), jnp.float32)
    idx = jax.ShapeDtypeStruct((K, C), jnp.int32)
    valid = jax.ShapeDtypeStruct((K,), jnp.bool_)
    hlo = drv.eng.lower(drv.cstate, reals, idx, None, valid).compile()
    n_custom = hlo.as_text().count("tpu_custom_call")
    log("kernels", check="tpu_custom_call in compiled round program",
        count=n_custom, seconds=f"{time.perf_counter() - t0:.3f}",
        peak_bytes=peak_bytes())
    if PLATFORM == "tpu" and n_custom == 0:
        raise AssertionError("kernels: the compiled round program has no "
                             "tpu_custom_call -- the Pallas kernels did "
                             "not compile to Mosaic")


def serve(sess: FederationSession, cell: MlpCell = MlpCell()) -> None:
    """Phase 4: mixed-size requests against the trained generator."""
    svc = GenerationService.from_session(sess)
    t0 = time.perf_counter()
    futs = [svc.submit(u, n, seed=100 + u) for u, n in cell.requests]
    svc.drain()
    served = [f.result(timeout=60) for f in futs]
    drain_s = time.perf_counter() - t0
    bitwise, worst = True, 0.0
    for rid, ((u, n), out) in enumerate(zip(cell.requests, served)):
        if out.shape != (n, cell.mlp.data_dim):
            raise AssertionError(f"serve: request {rid} got {out.shape}, "
                                 f"want {(n, cell.mlp.data_dim)}")
        check_finite("serve", f"request {rid}", out)
        if np.max(np.abs(out)) > 1.0:
            raise AssertionError(f"serve: request {rid} leaves [-1, 1]")
        again = svc.replay(100 + u, rid, n)
        bitwise &= bool(np.array_equal(out, again))
        worst = max(worst, float(np.max(np.abs(out - again))))
    stats = svc.stats()
    log("serve", requests=len(served), samples=stats["total_samples"],
        programs=stats["programs"], drain_s=f"{drain_s:.3f}",
        replay_bitwise=bitwise, replay_max_abs_diff=f"{worst:.3e}",
        replay_atol=SERVE_REPLAY_ATOL, peak_bytes=peak_bytes())
    if worst > SERVE_REPLAY_ATOL:
        raise AssertionError("serve: replay differs from the served bytes "
                             "beyond the stated tolerance")


def _federate(cell: SpmdCell, backend: str, pair, fcfg, ds, mesh):
    """One run of the four-chip phase's spec on ``backend``; returns
    losses, the initial and final host stores, last_round, and the
    engine's first output rows (to see where they landed)."""
    per_window = cell.rounds // cell.windows
    sess = FederationSession(
        pair, fcfg, ds, mlp_spec(cell, backend, False, per_window),
        mesh=mesh if backend == "spmd" else None)
    init = np.array(sess._driver.backend.d_flat)
    first_rows = []
    eng = sess._driver.eng

    def recording_eng(*args):
        res = eng(*args)
        if not first_rows:
            first_rows.append(res[1])
        return res
    sess._driver.eng = recording_eng

    t0 = time.perf_counter()
    g, d = [], []
    for _ in range(cell.windows):
        res = sess.run(per_window)
        g.append(res.g_losses)
        d.append(res.d_losses)
    wall = time.perf_counter() - t0
    store = res.extra["host_backend"]
    log("spmd_federation", backend=backend, rounds=cell.rounds,
        wall_s=f"{wall:.3f}", peak_bytes=peak_bytes())
    out = (np.concatenate(g), np.concatenate(d), init,
           np.array(store.d_flat), np.array(store.last_round), first_rows[0])
    sess.close()
    return out


def spmd_federation(cell: SpmdCell = SpmdCell()) -> None:
    """Four-chip phase: spmd over a users mesh vs the one-device host
    backend, same spec and seed."""
    pair = make_mlp_pair(cell.mlp)
    fcfg = mlp_fcfg(cell)
    ds = mlp_dataset(cell.users, cell.samples_per_class)
    mesh = make_users_mesh(cell.cohort)
    with jax.default_matmul_precision("highest"):
        gs, ds_, init, st_s, lr_s, rows = _federate(cell, "spmd", pair, fcfg,
                                                    ds, mesh)
        gh, dh, init_h, st_h, lr_h, _ = _federate(cell, "host", pair, fcfg,
                                                  ds, mesh)

    per_dev = {s.device.id: s.data.shape[0] for s in rows.addressable_shards}
    log("spmd_federation", mesh=dict(mesh.shape),
        rows_devices=len(rows.sharding.device_set), rows_per_device=per_dev)
    if (len(rows.sharding.device_set) != cell.cohort
            or set(per_dev.values()) != {1}):
        raise AssertionError("spmd_federation: the cohort rows do not land "
                             "one per device on the users mesh")

    for name, a in (("g_loss", gs), ("d_loss", ds_), ("store", st_s)):
        check_finite("spmd_federation", name, a)
    if not np.array_equal(init, init_h):
        raise AssertionError("spmd_federation: the backends start from "
                             "different stores")
    loss_err = float(max(np.max(np.abs(gs - gh)), np.max(np.abs(ds_ - dh))))
    up_s, up_h = (st_s - init).ravel(), (st_h - init).ravel()
    cosine = float(up_s @ up_h / (np.linalg.norm(up_s)
                                  * np.linalg.norm(up_h)))
    untrained = lr_h == 0
    untouched = bool(np.array_equal(st_s[untrained], init[untrained])
                     and np.array_equal(st_h[untrained], init[untrained]))
    same_rounds = bool(np.array_equal(lr_s, lr_h))
    ok = (same_rounds and untouched and loss_err <= SPMD_LOSS_ATOL
          and cosine >= SPMD_UPDATE_MIN_COSINE)
    log("spmd_federation", check="spmd(4 devices)~host(1 device)",
        last_round_equal=same_rounds, untrained_rows_untouched=untouched,
        max_loss_diff=f"{loss_err:.3e}", loss_atol=SPMD_LOSS_ATOL,
        max_store_diff=f"{np.max(np.abs(st_s - st_h)):.3e}",
        update_cosine=f"{cosine:.4f}", min_cosine=SPMD_UPDATE_MIN_COSINE,
        within_tolerance=ok)
    if not ok:
        raise AssertionError("spmd_federation: spmd and host disagree "
                             "beyond the stated tolerance")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def run_phases(phases) -> list:
    """Run ``(name, fn, needs)`` phases in order; a phase whose input
    phase failed is skipped.  Returns the names that failed or were
    skipped."""
    results, failed = {}, []
    for name, fn, needs in phases:
        if any(n in failed for n in needs):
            print(f"[{name}] SKIPPED: needs {needs}", flush=True)
            failed.append(name)
            continue
        t0 = time.perf_counter()
        try:
            results[name] = fn(*[results[n] for n in needs])
        except Exception:
            traceback.print_exc()
            print(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s",
                  flush=True)
            failed.append(name)
            continue
        print(f"[{name}] ok in {time.perf_counter() - t0:.1f}s", flush=True)
    return failed


def one_chip_phases(mlp: MlpCell = MlpCell(), conv: ConvCell = ConvCell()):
    return [
        ("mlp_federation", lambda: mlp_federation(mlp), ()),
        ("conv_federation", lambda: conv_federation(conv), ()),
        ("kernels", lambda r: kernels(r["session"], r["deltas"], mlp),
         ("mlp_federation",)),
        ("serve", lambda r: serve(r["session"], mlp), ("mlp_federation",)),
    ]


def four_chip_phases(cell: SpmdCell = SpmdCell()):
    return [("spmd_federation", lambda: spmd_federation(cell), ())]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the spmd phase on a four-chip mesh")
    args = ap.parse_args(argv)
    info = require_platform(args.chips)
    print(f"cache_dir={enable_compile_cache()} jax={jax.__version__}",
          flush=True)
    phases = four_chip_phases() if args.chips == 4 else one_chip_phases()
    failed = run_phases(phases)
    if failed:
        print(f"chip_smoke FAILED: {failed}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
