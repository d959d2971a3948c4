"""FederationSession: the registry-driven executor behind FederationSpec.

A session binds a :class:`repro.core.spec.FederationSpec` to the runtime
objects a spec cannot serialize (the G/D ``pair``, the model
``DistGANConfig``, the ``FederatedDataset``) and owns every piece of
mutable run state: the training carry, the user-state backend, the data
and scheduler RNG streams, the participation counts, and the global
round counter.  On top of that it offers what the one-shot
``run_distgan`` driver never could:

* **incremental execution** — ``run(rounds)`` advances the federation by
  a window of rounds and returns that window's :class:`RunResult`.
  With a synchronous pipeline (``async_rounds == 0``, any backend)
  trajectories are invariant to how a run is windowed — every window
  reuses the one spec-sized compiled chunk program and the streaming
  path dispatches per round — so ``run(5); run(5)`` is ``run(10)``
  bitwise.  With ``async_rounds > 0`` each window drains its in-flight
  rounds before returning (their metrics are part of the window's
  result and un-landed device work cannot be checkpointed), so a window
  boundary is a pipeline sync point: the rounds just after it see a
  caught-up store, where the uninterrupted run would still be lagging.
  Both interleavings satisfy the bounded-staleness contract (lag <= S
  always); they are different schedules, not a correctness bug;
* **fault tolerance** — ``save(path)`` checkpoints the whole session
  (host store / device carry, server state, RNG streams, round counter)
  through the msgpack machinery and ``FederationSession.restore``
  rebuilds it in a fresh process, reproducing the uninterrupted
  trajectory (bitwise on the device backend — pinned in
  tests/test_spec.py; async sessions resume with the window-boundary
  drain semantics above).

Execution is dispatched through the backend registry
(``repro.core.spec.register_backend``): ``device`` and ``host`` drivers
live here, the ``spmd`` driver in ``repro.core.spmd`` — a new residency
plugs in without touching this module's driver loop.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import time
import typing
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint.msgpack_ckpt import (latest_step, restore_checkpoint,
                                           save_checkpoint)
from repro.core.approaches import (DistGANConfig, DistGANState,
                                   d_flat_layout, init_state)
from repro.core.engine import (CohortShared, CohortState, _pad_to,
                               _wants_residual, cohort_state_to_full,
                               init_cohort_state, init_host_backend,
                               make_cohort_engine, make_cohort_rows_engine,
                               make_engine, make_fused_store_engine,
                               make_superbatch_engine, store_d_opts,
                               store_ds)
from repro.core.federated import (make_schedule_source,
                                  participation_weights, upload_bytes_flat,
                                  window_forwarding)
from repro.core.spec import (FederationSpec, register_backend,
                             resolve_approach, resolve_backend)

# pre-stage a whole window's batches on device when below this (else the
# fused engine samples/transfers chunk by chunk)
_STAGE_CAP_BYTES = 256 * 1024 * 1024

_SESSION_META = "session.json"

# JAX's own duration event for one backend compilation (or a persistent
# cache read standing in for one)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class RunResult:
    """One window's result.  ``state`` is the training state after the
    window: a ``DistGANState`` on the plain device engines and the
    streaming backends (``None`` there under ``materialize_state=False``),
    a :class:`CohortStateView` on the device cohort path, which reads the
    window's carry and unpacks the (U, N) store only when ``ds`` or
    ``d_opts`` is read.  On the donating engines (``make_engine``,
    ``fuse_store_rounds``) the state is only readable until the next
    ``run()``."""

    g_losses: np.ndarray           # (steps,)
    d_losses: np.ndarray           # (steps, U) — (steps, C) under cohorting
    step_time_s: float             # steady-state per-step (post-compile)
    samples: np.ndarray | None
    state: typing.Any              # DistGANState | CohortStateView | None
    extra: dict


class CohortStateView:
    """Read-only ``DistGANState`` view over a cohort window's final carry.

    ``g``, ``g_opt``, ``server_d``, ``step`` and ``key`` are the carry's
    own arrays.  ``ds`` and ``d_opts`` unpack the store into the stacked
    (U, ...) trees on first read, each on its own and under a
    ``fed.unpack`` span tagged with the window and the ``part``, and are
    cached; ``to_full()`` gives the whole ``DistGANState``.
    ``block_until_ready()`` (which ``jax.block_until_ready`` calls on this
    leaf) waits for the carry and unpacks nothing.  A carry donated to a
    later ``run()`` raises on any read not yet cached."""

    def __init__(self, pair, fcfg: DistGANConfig, carry: CohortState,
                 window: int):
        self._pair, self._fcfg = pair, fcfg
        self._carry_state, self._window = carry, window
        self._parts = {}

    def _carry(self) -> CohortState:
        if any(isinstance(leaf, jax.Array) and leaf.is_deleted()
               for leaf in jax.tree.leaves(self._carry_state)):
            raise RuntimeError(
                f"RunResult.state of the window at round {self._window} "
                f"was donated to a later run(): read RunResult.state "
                f"before the next run()")
        return self._carry_state

    def _part(self, part: str, unpack: Callable):
        if part not in self._parts:
            store = self._carry().store
            with jax.profiler.TraceAnnotation("fed.unpack",
                                              window=self._window,
                                              part=part):
                self._parts[part] = unpack(store)
        return self._parts[part]

    @property
    def g(self):
        return self._carry().g

    @property
    def g_opt(self):
        return self._carry().g_opt

    @property
    def server_d(self):
        return self._carry().server_d

    @property
    def step(self):
        return self._carry().step

    @property
    def key(self):
        return self._carry().key

    @property
    def ds(self):
        return self._part("ds", lambda s: store_ds(self._pair, s))

    @property
    def d_opts(self):
        return self._part("d_opts",
                          lambda s: store_d_opts(self._pair, self._fcfg, s))

    def to_full(self) -> DistGANState:
        return DistGANState(self.g, self.g_opt, self.ds, self.d_opts,
                            self.server_d, self.step, self.key)

    def block_until_ready(self) -> "CohortStateView":
        jax.block_until_ready(self._carry())
        return self


def _merge_results(parts: list) -> RunResult:
    """Merge consecutive sub-window RunResults (the autosave path) into
    one window-shaped result: time series concatenate, counts sum, and
    point-in-time fields (final state/samples/staleness) come from the
    last sub-window."""
    if len(parts) == 1:
        return parts[0]
    extra = dict(parts[-1].extra)
    for key in ("mean_age", "schedule", "participation_weights"):
        if all(key in p.extra for p in parts):
            extra[key] = np.concatenate([p.extra[key] for p in parts])
    if all("participation_counts" in p.extra for p in parts):
        extra["participation_counts"] = np.sum(
            [p.extra["participation_counts"] for p in parts], axis=0)
    if all("compile_s" in p.extra for p in parts):
        extra["compile_s"] = float(sum(p.extra["compile_s"]
                                       for p in parts))
    if all("min_step_time_s" in p.extra for p in parts):
        extra["min_step_time_s"] = min(p.extra["min_step_time_s"]
                                       for p in parts)
    return RunResult(
        g_losses=np.concatenate([p.g_losses for p in parts]),
        d_losses=np.concatenate([p.d_losses for p in parts]),
        step_time_s=parts[-1].step_time_s,
        samples=parts[-1].samples,
        state=parts[-1].state,
        extra=extra)


# ---------------------------------------------------------------------------
# Chunk helpers shared by the scan-fused drivers
# ---------------------------------------------------------------------------

def _chunk_slice(staged, start: int, k: int, rpj: int):
    """Device-side chunk ``[start, start+k)`` of a pre-staged round stack,
    padded to ``rpj`` rounds by repeating the final round (padded rounds
    are masked out and never touch the carry)."""
    out = jax.lax.slice_in_dim(staged, start, start + k)
    if k < rpj:
        fill = jnp.broadcast_to(staged[-1:], (rpj - k,) + staged.shape[1:])
        out = jnp.concatenate([out, fill], axis=0)
    return out


def _chunk_stack(batch_fn, start: int, k: int, rpj: int, window: int):
    """Host-side chunk: sample rounds ``[start, start+k)``, pad to rpj
    (same repeat-the-last-round convention as engine._pad_to), and copy
    it to the device.  ``window`` (the session round at the window's
    start) tags the ``fed.sample`` and ``fed.h2d`` spans."""
    with jax.profiler.TraceAnnotation("fed.sample", window=window) as span:
        block = _pad_to(np.stack([batch_fn(j)
                                  for j in range(start, start + k)]), rpj)
        span.set_metadata(bytes=block.nbytes)
    with jax.profiler.TraceAnnotation("fed.h2d", window=window,
                                      bytes=block.nbytes):
        return jnp.asarray(block)


def _valid_mask(k: int, rpj: int):
    return jnp.asarray(np.arange(rpj) < k)


def _poison_donated(tree) -> None:
    """Delete every jax.Array leaf of a carry that was just donated.

    When donation is honored XLA already invalidated these buffers, but
    on a backend (or program variant) where XLA declined to alias, the
    stale python reference would keep READING the pre-window copy —
    silently, with no error.  Deleting the leaves turns any such read
    into an immediate "Array has been deleted" error at the use site
    (the runtime twin of lint rule RPR003)."""
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def _drive_chunks(run_chunk, carry, steps: int, rpj: int, window: int,
                  donating: bool = False):
    """Warmup + timed chunk loop shared by the fused and cohort drivers.

    Every chunk is rpj rounds (padded + masked), so the whole run shares
    ONE compiled program — and because rpj comes from the spec rather
    than the window length, every window of a session shares that
    program too, which is what makes trajectories structurally invariant
    to windowing (XLA fuses e.g. a length-1 scan differently from a
    length-K one at metric-ULP level, so equal-program is the only safe
    contract).  ``donating=True`` declares that ``run_chunk`` DONATES
    the carry to its engine: each consumed carry is then poisoned
    (:func:`_poison_donated`) so any stale reference held elsewhere —
    the driver's own ``_state`` mid-run included — raises immediately
    instead of reading a pre-window copy.  ``run_chunk`` returns the
    chunk's metrics on the device; each chunk ends in one host sync on
    them (the ``fed.sync`` span, tagged with ``window``), with the padded
    rounds sliced off.  Returns ``(carry, chunks, steady_s,
    window_rates)``; ``window_rates`` holds per-round seconds of each
    FULL post-warmup window — the remainder window is excluded because
    its rate would over-count the masked padding rounds it still
    computes."""

    def chunk(start: int, k: int, carry):
        prev = carry
        carry, m = run_chunk(start, k, carry)
        with jax.profiler.TraceAnnotation("fed.sync", window=window):
            m = jax.tree.map(lambda x: np.asarray(x)[:k], m)
        if donating:
            _poison_donated(prev)
        return carry, m

    k0 = min(rpj, steps)
    carry, m0 = chunk(0, k0, carry)
    chunks = [m0]

    t1 = time.perf_counter()
    i = k0
    window_rates = []
    while i < steps:
        k = min(rpj, steps - i)
        tc = time.perf_counter()
        carry, m = chunk(i, k, carry)
        if k == rpj:
            window_rates.append((time.perf_counter() - tc) / k)
        chunks.append(m)
        i += k
    jax.block_until_ready(carry.g)
    steady = time.perf_counter() - t1
    return carry, chunks, steady, window_rates


def _upload_accounting(pair, fcfg: DistGANConfig, approach, C: int,
                       kept_frac: float, *,
                       stage_rows: bool = False) -> dict:
    """Cohort-aware per-round upload bytes: C members upload per round —
    NOT the full population U.  Only delta-uploading approaches
    (``ApproachDef.uploads``) ship parameters across the privacy
    boundary; approaches 2/3 exchange logits/gradients and the baseline
    nothing, so the key is absent there.  For the data-dependent
    ``threshold`` policy, pass the RUN-MEAN measured kept fraction (a
    single round's value misprices a drifting threshold).

    The transport codec reprices the payload (``upload_bytes_flat``):
    value bytes shrink to the codec width and int8 codecs add the
    per-row scale.  ``extra["compression"]`` records the full transport
    configuration alongside the priced bytes."""
    if not resolve_approach(approach).uploads:
        return {}
    n = d_flat_layout(pair).n
    kf = kept_frac if fcfg.selection == "threshold" else None
    per_user = upload_bytes_flat(n, fcfg.selection, fcfg.upload_frac,
                                 kept_frac=kf, codec=fcfg.codec)
    lossy = fcfg.codec != "none"
    return {"upload_bytes_per_user": per_user,
            "upload_bytes_per_round": C * per_user,
            "compression": {
                "codec": fcfg.codec,
                "error_feedback": bool(lossy and fcfg.error_feedback),
                "stochastic": bool(lossy and fcfg.codec_stochastic),
                "stage_rows": bool(stage_rows)}}


# ---------------------------------------------------------------------------
# Streaming driver (rows engines over a UserStateBackend)
# ---------------------------------------------------------------------------

def _np_quantize_rows(x: np.ndarray):
    """Host-side per-row absmax int8 — the numpy mirror of
    ``kernels.ref.quantize_rows_ref`` (deterministic path), used by the
    ``stage_rows`` transport to shrink H2D staging to 1 byte/element."""
    x = np.asarray(x, np.float32)
    scale = (np.abs(x).max(axis=1) / np.float32(127.0)).astype(np.float32)
    inv = np.where(scale > 0, np.float32(1.0) / scale,
                   np.float32(0.0)).astype(np.float32)
    q = np.clip(np.rint(x * inv[:, None]), -127, 127).astype(np.int8)
    return q, scale


def _np_dequantize_rows(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    return q.astype(np.float32) * scale[:, None].astype(np.float32)


class StreamStats(typing.NamedTuple):
    retire_t: list    # perf_counter stamp when round r's scatter landed
    stall_s: list     # host seconds blocked on the device for round r


def stream_cohort_rounds(eng, shared, backend, schedule: np.ndarray,
                         batch_fn: Callable, *, async_rounds: int = 0,
                         prefetch: bool = True, wts: np.ndarray | None = None,
                         round_base: int = 0, stage_codec: str = "none"):
    """Double-buffered streaming driver over a rows engine.

    ``eng(shared, d_rows, opt_rows, ages, wts_row, real)`` is dispatched
    once per round (``make_cohort_rows_engine`` or the SPMD
    ``make_spmd_cohort_rows_engine`` — same signature); the per-user rows
    live in ``backend`` (a UserStateBackend) and only the scheduled
    cohort's C rows cross the host<->device boundary.

    ``round_base`` is the GLOBAL index of ``schedule[0]``'s round: ages
    are computed and ``last_round`` stamped against global rounds, so a
    resumable session can drive the stream window by window.  Stamps
    follow the re-zeroed age convention — a member that trained through
    global round r has ``last_round == r + 1`` (0 = never trained), so a
    member drawn again next round carries age 0.

    Pipeline structure per round k (JAX dispatch is asynchronous, so the
    engine call returns immediately and the device computes in the
    background):

    * ``prefetch=True``: round k+1's data chunk is sampled and
      ``jax.device_put`` while round k computes — the PR 1 "overlap host
      staging with device compute" item extended to the streamed store.
    * ``async_rounds == 0`` (synchronous): round k's updated rows are
      fetched and scattered back BEFORE round k+1's rows are gathered, so
      every gather sees a fully up-to-date store.
    * ``async_rounds == S > 0`` (bounded staleness): up to S rounds may
      be in flight — round k+1's rows are gathered from the store as-is
      (round k's scatter may not have landed), so a member's row can be
      at most S rounds stale.  Scatter is last-writer-wins and
      ``last_round`` reflects LANDED rounds only, so the ages the
      staleness-aware combiners see automatically include the pipeline
      lag.

    Returns ``(shared, metrics, stats)``: per-round metric dicts (host
    numpy) and a ``StreamStats`` — ``retire_t[r]`` is the perf_counter
    stamp at which round r's scatter-back landed, ``stall_s[r]`` the
    host time spent BLOCKED on the device fetching round r's outputs.
    The stall is the pipeline's figure of merit: synchronous staging
    must stall for ~the whole device compute every round (the host has
    nothing else to do), while the double-buffered/async modes stage
    round k+1 under round k's compute and retire long-finished rounds —
    stalls collapse toward zero (gated in benchmarks paper_stream).

    ``stage_codec="int8"`` (CompressionSpec.stage_rows on a host store)
    moves the cohort's D rows across the PCIe boundary quantized: H2D
    ships int8 + per-row scale (host-side numpy quantizer) and
    dequantizes on device; D2H quantizes on device and dequantizes back
    into the host store — 4x fewer staged bytes each way.  This is a
    LOSSY store transport (the row rounds through int8 every round);
    optimizer rows and EF residuals stay exact f32 — the residual is the
    error-feedback ledger and quantizing it would break the
    compensation invariant.
    """
    steps = len(schedule)
    metrics_out: list = [None] * steps
    stats = StreamStats([0.0] * steps, [0.0] * steps)
    inflight: collections.deque = collections.deque()
    has_res = getattr(backend, "has_residual", False)
    stage_q = stage_codec != "none"
    if stage_q:
        assert stage_codec == "int8", stage_codec
        from repro.kernels import ops as kops
        if getattr(backend, "device_resident", False):
            stage_q = False   # rows never cross the boundary — nothing to save

    def stage_rows(r):
        d_rows, o_rows, last = backend.gather_rows(schedule[r])
        if isinstance(last, jax.Array):
            # device-resident last_round: compute ages on device too —
            # int32 subtraction is bitwise the same either side of the
            # boundary, and staying on device avoids a blocking sync on
            # the store every round
            ages = (jnp.int32(round_base + r) - last).astype(jnp.int32)
        else:
            ages = jax.device_put(
                np.asarray(round_base + r - np.asarray(last), np.int32))

        def put(a):
            # DeviceStateBackend hands back device-resident rows — pass
            # them through untouched (forcing them through numpy would
            # cost a D2H+H2D round-trip and a sync every round)
            if isinstance(a, jax.Array):
                return a
            return jax.device_put(np.ascontiguousarray(a))

        if stage_q:
            q, s = _np_quantize_rows(np.asarray(d_rows))
            d_dev = kops.dequantize_rows(jax.device_put(q),
                                         jax.device_put(s))
        else:
            d_dev = put(d_rows)
        out = (d_dev, put(o_rows))
        if has_res:
            out = out + (put(backend.gather_residual(schedule[r])),)
        return out + (ages,)

    def stage_data(r):
        return jax.device_put(np.asarray(batch_fn(r)))

    def retire(keep: int):
        while len(inflight) > keep:
            rr, ii, nd, no, nres, m = inflight.popleft()
            t0 = time.perf_counter()
            if getattr(backend, "device_resident", False):
                # device-resident store: the updated rows never leave the
                # device — scatter is a functional .at[].set on device
                # arrays, and the only host block is the metrics fetch
                backend.scatter_rows(ii, nd, no, round_base + rr + 1,
                                     residual=nres)
                metrics_out[rr] = jax.tree.map(np.asarray, m)
                stats.stall_s[rr] = time.perf_counter() - t0
            else:
                if stage_q:
                    # nd arrived as (q, scale) — the D2H fetch moves int8
                    # + one f32 per row instead of the dense f32 row
                    q, s = np.asarray(nd[0]), np.asarray(nd[1])
                    no = np.asarray(no)    # blocks on rr
                    nd = _np_dequantize_rows(q, s)
                else:
                    nd, no = np.asarray(nd), np.asarray(no)  # blocks on rr
                if nres is not None:
                    nres = np.asarray(nres)
                stats.stall_s[rr] = time.perf_counter() - t0
                backend.scatter_rows(ii, nd, no, round_base + rr + 1,
                                     residual=nres)
                metrics_out[rr] = jax.tree.map(np.asarray, m)
            stats.retire_t[rr] = time.perf_counter()

    rows = stage_rows(0)
    data = stage_data(0)
    for r in range(steps):
        w = None if wts is None else jnp.asarray(np.asarray(wts[r],
                                                            np.float32))
        if has_res:
            shared, nd, no, nres, m = eng(shared, rows[0], rows[1], rows[2],
                                          rows[3], w, data)
        else:
            shared, nd, no, m = eng(shared, rows[0], rows[1], rows[2],
                                    w, data)
            nres = None
        if stage_q:
            nd = kops.quantize_rows(nd)    # D2H payload: (int8, scale)
        inflight.append((r, np.asarray(schedule[r]), nd, no, nres, m))
        last = r + 1 == steps
        if prefetch and not last:
            data = stage_data(r + 1)       # overlaps round r's compute
        # sync (async_rounds=0): blocks on round r itself, so the gather
        # below sees a fully up-to-date store.  async (S>0): blocks only
        # on rounds <= r-S (long since done) — round r stays in flight
        # while r+1's rows are gathered from the bounded-stale store and
        # its dispatch goes out without the device ever idling.
        retire(async_rounds)
        if not last:
            rows = stage_rows(r + 1)
        if not prefetch and not last:
            data = stage_data(r + 1)       # serialized staging (no overlap)
    retire(0)
    return shared, metrics_out, stats


class SuperbatchStats(typing.NamedTuple):
    win_retire_t: list   # perf_counter stamp when window w's scatter landed
    win_stall_s: list    # host seconds blocked on the device for window w
    win_rounds: list     # real (unpadded) rounds in window w


def superbatch_cohort_rounds(eng, shared, backend, schedule: np.ndarray,
                             batch_fn: Callable, *, rounds_per_jit: int,
                             wts: np.ndarray | None = None,
                             round_base: int = 0, prefetch: bool = True):
    """Windowed superbatch driver over a ``make_superbatch_engine``.

    Where ``stream_cohort_rounds`` pays a host gather, a dispatch, and a
    blocking scatter-back PER ROUND, this driver handles a whole
    ``rounds_per_jit`` window per iteration: gather the window's
    scheduled rows as one ``(K, C, N)`` block, compute the
    write-after-read forwarding plan for users repeating inside the
    window (``core.federated.window_forwarding`` — ages exact), dispatch
    the fused K-round program ONCE, and block a single time on the
    returned block before scattering it back in round order
    (last-writer-wins; ``last_round`` stamped per real round).  K host
    stalls per window become 1 — PR 3's double-buffering extended to
    window granularity: while the device runs window w, the host samples
    window w+1's batches (``prefetch``); only the ROW gather for w+1
    must wait for w's scatter.

    Every window — the trailing remainder included — is padded to
    ``rounds_per_jit`` with masked rounds, so any steps count and any
    session windowing reuse ONE compiled program; a repeat that spans a
    window boundary reads the scattered bytes from the host instead of
    the in-program forward, which are the same bytes (the forwarding
    select is exact), so trajectories stay invariant to windowing.

    Returns ``(shared, metrics, stats)`` like ``stream_cohort_rounds``
    but with per-WINDOW :class:`SuperbatchStats` (the stall is the
    single block on the window's output rows — the gated figure of
    merit in benchmarks ``paper_fused_store``).
    """
    steps = len(schedule)
    rpj = rounds_per_jit
    metrics_out: list = [None] * steps
    stats = SuperbatchStats([], [], [])
    has_res = getattr(backend, "has_residual", False)
    data = None
    i = 0
    while i < steps:
        k = min(rpj, steps - i)
        s_pad = _pad_to(np.asarray(schedule[i:i + k]), rpj)
        # forwarding/ages need the CURRENT last_round — every prior
        # window's scatter has landed (the one inter-window sync point)
        fwd, ages = window_forwarding(s_pad, backend.last_round,
                                      round_base + i)
        rows = [backend.gather_rows(schedule[i + r]) for r in range(k)]
        d_blk = _pad_to(np.stack([np.asarray(r_[0]) for r_ in rows]), rpj)
        o_blk = _pad_to(np.stack([np.asarray(r_[1]) for r_ in rows]), rpj)
        r_blk = None
        if has_res:
            # the residual block rides the same forwarding plan as the
            # d/o rows — an in-window repeat reads the residual its
            # earlier round wrote (see make_superbatch_engine)
            r_blk = _pad_to(np.stack(
                [np.asarray(backend.gather_residual(schedule[i + r]))
                 for r in range(k)]), rpj)
        if data is None:
            data = _chunk_stack(batch_fn, i, k, rpj, round_base)
        w = None
        if wts is not None:
            w = jnp.asarray(_pad_to(np.asarray(wts[i:i + k], np.float32),
                                    rpj))
        if has_res:
            shared, out_d, out_o, out_r, m = eng(
                shared, jax.device_put(d_blk), jax.device_put(o_blk),
                jax.device_put(r_blk), jnp.asarray(fwd), jnp.asarray(ages),
                data, w, _valid_mask(k, rpj))
        else:
            shared, out_d, out_o, m = eng(
                shared, jax.device_put(d_blk), jax.device_put(o_blk),
                jnp.asarray(fwd), jnp.asarray(ages), data, w,
                _valid_mask(k, rpj))
            out_r = None
        # sample the NEXT window's batches while this one computes (rng
        # order stays strictly sequential, so trajectories are
        # prefetch-neutral exactly as in the per-round stream)
        data = None
        if prefetch and i + k < steps:
            kn = min(rpj, steps - i - k)
            data = _chunk_stack(batch_fn, i + k, kn, rpj, round_base)
        t0 = time.perf_counter()
        out_d, out_o = np.asarray(out_d), np.asarray(out_o)  # THE stall
        if out_r is not None:
            out_r = np.asarray(out_r)
        stats.win_stall_s.append(time.perf_counter() - t0)
        mets = jax.tree.map(np.asarray, m)
        for r in range(k):
            backend.scatter_rows(s_pad[r], out_d[r], out_o[r],
                                 round_base + i + r + 1,
                                 residual=(None if out_r is None
                                           else out_r[r]))
            metrics_out[i + r] = jax.tree.map(lambda x: x[r], mets)
        stats.win_retire_t.append(time.perf_counter())
        stats.win_rounds.append(k)
        i += k
    return shared, metrics_out, stats


# ---------------------------------------------------------------------------
# Backend drivers
# ---------------------------------------------------------------------------

class BackendDriver:
    """Per-backend execution strategy bound to one session.

    ``run(rounds)`` advances the session's training state by a window of
    rounds; ``arrays()`` returns the checkpointable pytree of the
    mutable state (pure arrays — PRNG keys as key_data) and
    ``load_arrays(tree)`` installs a restored one.

    ``defer_state=True`` (the restore path) skips materializing the
    initial training state: ``arrays()`` then returns an ABSTRACT
    ``jax.ShapeDtypeStruct`` template — exactly what
    ``restore_checkpoint`` needs from its target — and the driver is
    unusable until ``load_arrays`` installs concrete state.  This keeps
    resume cost at one state materialization instead of two (the
    full-init-then-overwrite cost grows linearly with U, the regime
    checkpointing exists for)."""

    def __init__(self, sess: "FederationSession", defer_state: bool = False):
        self.sess = sess

    def run(self, rounds: int) -> RunResult:
        raise NotImplementedError

    def arrays(self):
        raise NotImplementedError

    def load_arrays(self, tree) -> None:
        raise NotImplementedError

    # -- out-of-tree checkpoint state + lifecycle --------------------------

    def save_aux(self, path: str, step: int) -> None:
        """Persist state ``arrays()`` does not carry (the multihost
        driver's workers each checkpoint their own store shard here);
        in-process drivers have none."""

    def load_aux(self, path: str, step: int) -> None:
        """Restore the ``save_aux`` state in a fresh process."""

    def close(self) -> None:
        """Release out-of-process resources (worker fleets); in-process
        drivers hold none."""

    # -- serve handles (repro.serve reads live training state) -------------

    def generator_params(self):
        """The current generator parameter tree — the artifact the serve
        layer publishes (paper §7: the platform 'provide[s] model for
        users who lack computing power')."""
        raise NotImplementedError

    def user_d_flat(self, user_id: int) -> np.ndarray:
        """One user's flat (Nd,) discriminator row (FlatLayout order) —
        the serve layer's per-user rejection filter scores with it."""
        raise NotImplementedError


def _pack_key(state):
    return state._replace(key=jax.random.key_data(state.key))


def _unpack_key(state):
    return state._replace(
        key=jax.random.wrap_key_data(jnp.asarray(state.key)))


class DeviceBackendDriver(BackendDriver):
    """Device-resident state: the plain fused engine or per-step loop for
    full participation, the scan-fused cohort engine (store in the scan
    carry) when the run is cohort-virtualized."""

    def __init__(self, sess, defer_state: bool = False):
        super().__init__(sess)
        pair, fcfg, sp = sess.pair, sess.fcfg, sess.spec
        if sess.cohort_virtual:
            self.mode = "cohort"
            # fuse_store_rounds: same trace, donated carry — the (U, N)
            # store updates in place across the window instead of being
            # copied once per chunk (see make_fused_store_engine for the
            # ULP contract that donation trades for)
            self.fused_store = sp.engine.fuse_store_rounds
            mk = (make_fused_store_engine if self.fused_store
                  else make_cohort_engine)
            self.eng = mk(pair, fcfg, sp.approach,
                          adaptive=sp.combine.adaptive_server_scale)
        elif sp.engine.kind == "fused":
            self.mode = "fused"
            self.eng = make_engine(pair, fcfg, sp.approach)
        else:
            self.mode = "per_step"
            self.step_fn = sess.approach.step_factory(pair, fcfg)

        init = init_cohort_state if self.mode == "cohort" else init_state

        def make():
            return init(pair, fcfg, jax.random.key(sp.seed),
                        sync_ds=sess.approach.sync_ds)

        self._template = None
        if defer_state:
            # abstract template only (restore_checkpoint needs shapes/
            # dtypes/treedef; the real state arrives via load_arrays)
            self._template = jax.eval_shape(lambda: _pack_key(make()))
            self._state = None
        else:
            self._state = make()

    # cohort/plain state under one attribute; the mode-specific drivers
    # below read whichever name matches their layout
    @property
    def cstate(self):
        return self._state

    @cstate.setter
    def cstate(self, v):
        self._state = v

    @property
    def state(self):
        return self._state

    @state.setter
    def state(self, v):
        self._state = v

    # -- checkpoint state --------------------------------------------------

    def arrays(self):
        if self._state is None:
            return self._template
        return _pack_key(self._state)

    def load_arrays(self, tree) -> None:
        self._state = _unpack_key(jax.tree.map(jnp.asarray, tree))

    # -- serve handles -----------------------------------------------------

    def generator_params(self):
        if self._state is None:
            raise RuntimeError("driver state not materialized (restore in "
                               "progress) — nothing to serve yet")
        return self._state.g

    def user_d_flat(self, user_id: int) -> np.ndarray:
        if self._state is None:
            raise RuntimeError("driver state not materialized (restore in "
                               "progress) — nothing to serve yet")
        if self.mode == "cohort":
            return np.asarray(self._state.store.d_flat[user_id])
        row = jax.tree.map(lambda x: x[user_id], self._state.ds)
        return np.asarray(d_flat_layout(self.sess.pair).flatten(row))

    # -- execution ---------------------------------------------------------

    def run(self, rounds: int) -> RunResult:
        if self.mode == "per_step":
            return self._run_per_step(rounds)
        with jax.profiler.TraceAnnotation("fed.run", window=self.sess.round,
                                          rounds=rounds):
            if self.mode == "cohort":
                return self._run_cohort(rounds)
            return self._run_fused(rounds)

    def _window_rpj(self, rounds: int) -> int:
        # ALWAYS the spec's chunk length, independent of the window size
        # (short windows pad the tail with masked rounds): every window
        # then runs the one compiled scan program, which is what makes
        # run(a); run(b) bitwise-equal to run(a+b) — see _drive_chunks.
        # The cost is masked-padding waste when rounds << rounds_per_jit.
        del rounds
        return self.sess.spec.engine.rounds_per_jit

    def _run_fused(self, rounds: int) -> RunResult:
        sess = self.sess
        window = sess.round
        rpj = self._window_rpj(rounds)
        batch_np = lambda j: sess._batch_full()
        prestage = rounds * sess._probe_nbytes_full() <= _STAGE_CAP_BYTES
        if prestage:
            staged = _chunk_stack(batch_np, 0, rounds, rounds, window)

        def run_chunk(start: int, k: int, state):
            reals = (_chunk_slice(staged, start, k, rpj) if prestage
                     else _chunk_stack(batch_np, start, k, rpj, window))
            with jax.profiler.TraceAnnotation("fed.dispatch", window=window):
                return self.eng(state, reals, _valid_mask(k, rpj))

        # make_engine donates the state carry (argnum 0): poison each
        # consumed window carry so a stale self._state read fails fast
        state, chunks, steady, window_rates = _drive_chunks(
            run_chunk, self.state, rounds, rpj, window, donating=True)
        self.state = state

        g_losses = np.concatenate([c["g_loss"] for c in chunks])
        d_losses = np.concatenate([c["d_loss"] for c in chunks])
        kept_frac = float(chunks[-1]["kept_frac"][-1])
        kept_mean = float(np.mean(np.concatenate([c["kept_frac"]
                                                  for c in chunks])))
        step_denom = max(rounds - rpj, 1)
        min_step_s = min(window_rates) if window_rates else steady / step_denom

        return RunResult(
            g_losses=g_losses,
            d_losses=d_losses,
            step_time_s=steady / step_denom,
            samples=sess._eval_samples(state.g),
            state=state,
            extra={"kept_frac": kept_frac,
                   "engine": "fused",
                   # best post-warmup window: steady-state per-round
                   # time, robust to background load spikes (benchmarks
                   # use this)
                   "min_step_time_s": min_step_s,
                   # full participation: the per-round cohort is all U
                   **_upload_accounting(sess.pair, sess.fcfg,
                                        sess.spec.approach,
                                        sess.fcfg.num_users, kept_mean)},
        )

    def _run_per_step(self, rounds: int) -> RunResult:
        # legacy loop, kept verbatim as the comparison target: per-round
        # device staging, one jit dispatch and two host syncs per round.
        sess = self.sess
        state = self.state
        g_list, d_list = [], []

        def batch():
            b = sess._batch_full(stage=jnp)
            return b

        # warmup/compile on the window's first shapes
        state, metrics = self.step_fn(state, batch())
        jax.block_until_ready(metrics["g_loss"])

        g_list.append(float(metrics["g_loss"]))
        d_list.append(np.asarray(metrics["d_loss"]))

        t1 = time.perf_counter()
        round_times = []
        for _ in range(1, rounds):
            tr = time.perf_counter()
            state, metrics = self.step_fn(state, batch())
            g_list.append(float(metrics["g_loss"]))
            d_list.append(np.asarray(metrics["d_loss"]))
            round_times.append(time.perf_counter() - tr)
        jax.block_until_ready(state.g)
        steady = time.perf_counter() - t1
        self.state = state

        kept_frac = float(metrics["kept_frac"])
        kept_mean = kept_frac  # per-step loop tracks only the final round
        step_denom = max(rounds - 1, 1)
        min_step_s = min(round_times) if round_times else steady

        return RunResult(
            g_losses=np.asarray(g_list),
            d_losses=np.stack(d_list),
            step_time_s=steady / step_denom,
            samples=sess._eval_samples(state.g),
            state=state,
            extra={"kept_frac": kept_frac,
                   "engine": "per_step",
                   "min_step_time_s": min_step_s,
                   **_upload_accounting(sess.pair, sess.fcfg,
                                        sess.spec.approach,
                                        sess.fcfg.num_users, kept_mean)},
        )

    def _run_cohort(self, rounds: int) -> RunResult:
        """Cohort-virtualized window: U logical users, a C-wide compiled
        program (see FederationSession._next_schedule for the rng-stream
        discipline)."""
        sess = self.sess
        window = sess.round
        U, C = sess.fcfg.num_users, sess.cohort_size
        schedule = sess._next_schedule(rounds)
        wts = sess._next_weights(schedule)
        rpj = self._window_rpj(rounds)

        def batch_round(r: int):
            return np.stack([np.asarray(
                sess.dataset.user_batch(int(u), sess.data_rng,
                                        sess.spec.batch_size))
                for u in schedule[r]])

        nbytes = sess._probe_nbytes_cohort(schedule)
        prestage = rounds * nbytes <= _STAGE_CAP_BYTES
        if prestage:
            staged = _chunk_stack(batch_round, 0, rounds, rounds, window)
        sched_dev = jnp.asarray(schedule)
        wts_dev = None if wts is None else jnp.asarray(wts)

        def run_chunk(start: int, k: int, cstate):
            reals = (_chunk_slice(staged, start, k, rpj) if prestage
                     else _chunk_stack(batch_round, start, k, rpj, window))
            idx = _chunk_slice(sched_dev, start, k, rpj)
            w = (None if wts_dev is None
                 else _chunk_slice(wts_dev, start, k, rpj))
            with jax.profiler.TraceAnnotation("fed.dispatch", window=window,
                                              masked=rpj - k):
                return self.eng(cstate, reals, idx, wts=w,
                                valid=_valid_mask(k, rpj))

        # only the fused-store engine donates the carry (the plain cohort
        # engine keeps the bitwise-pin copy — its carry stays readable)
        cstate, chunks, steady, window_rates = _drive_chunks(
            run_chunk, self.cstate, rounds, rpj, window,
            donating=self.fused_store)
        self.cstate = cstate

        g_losses = np.concatenate([c["g_loss"] for c in chunks])
        d_losses = np.concatenate([c["d_loss"] for c in chunks])
        mean_age = np.concatenate([c["mean_age"] for c in chunks])
        kept_frac = float(chunks[-1]["kept_frac"][-1])
        kept_mean = float(np.mean(np.concatenate([c["kept_frac"]
                                                  for c in chunks])))
        step_denom = max(rounds - rpj, 1)
        min_step_s = min(window_rates) if window_rates else steady / step_denom

        counts = np.bincount(schedule.ravel(), minlength=U)
        total = sess.round + rounds
        staleness = total - np.asarray(cstate.store.last_round)
        return RunResult(
            g_losses=g_losses,
            d_losses=d_losses,
            step_time_s=steady / step_denom,
            samples=sess._eval_samples(cstate.g),
            state=CohortStateView(sess.pair, sess.fcfg, cstate, window),
            extra={"kept_frac": kept_frac,
                   "engine": "fused", "min_step_time_s": min_step_s,
                   "participation": sess.spec.participation.scheduler,
                   "cohort_size": C,
                   "schedule": schedule,
                   "participation_counts": counts,
                   "staleness": staleness,
                   "mean_age": mean_age,
                   "state_backend": "device",
                   "fused_store": self.fused_store,
                   "adaptive_server_scale":
                       sess.spec.combine.adaptive_server_scale,
                   **({"participation_weights": wts}
                      if wts is not None else {}),
                   **_upload_accounting(sess.pair, sess.fcfg,
                                        sess.spec.approach, C, kept_mean)},
        )


class HostStreamDriver(BackendDriver):
    """Host-resident streamed state: the (U, N) store lives in pinned
    host NumPy buffers (HostStateBackend) and every round moves exactly C
    rows each way — per-round cost is independent of U, which is bounded
    by host RAM instead of accelerator memory."""

    backend_name = "host"

    def __init__(self, sess, defer_state: bool = False):
        super().__init__(sess)
        pair, fcfg, sp = sess.pair, sess.fcfg, sess.spec
        self._template = None
        if defer_state:
            # shapes only: skip the chunked (U, N) host-store RNG init
            # that load_arrays would immediately overwrite — resume cost
            # must not pay a second full-store materialization
            self.shared, self.backend = None, None
            self._template = self._shape_template()
        else:
            self.shared, self.backend = init_host_backend(
                pair, fcfg, jax.random.key(sp.seed),
                sync_ds=sess.approach.sync_ds)
        self.eng = self._make_engine()
        # store-resident fusion request: legal only for the synchronous
        # host stream.  Async bounded staleness is inherently per-round
        # (an in-flight scatter would invalidate a window's pre-gathered
        # rows), the spmd driver maps each round's rows onto the mesh,
        # and quantized row staging (stage_rows) is a per-round PCIe
        # transport — all FALL BACK to the per-round stream and report
        # extra["fused_store"] = False.
        self.stage_rows = (sp.combine.compression.stage_rows
                           and self.backend_name in ("host", "multihost"))
        self.fused_store = (sp.engine.fuse_store_rounds
                            and self.backend_name == "host"
                            and sp.backend.async_rounds == 0
                            and not self.stage_rows)
        self.win_eng = None
        if self.fused_store:
            self.win_eng = make_superbatch_engine(
                pair, fcfg, sp.approach,
                adaptive=sp.combine.adaptive_server_scale)

    def _make_engine(self):
        return make_cohort_rows_engine(self.sess.pair, self.sess.fcfg,
                                       self.sess.spec.approach)

    def _shape_template(self):
        from repro.core.approaches import (_opts, d_opt_flat_layout)
        pair, fcfg, sp = self.sess.pair, self.sess.fcfg, self.sess.spec
        U = fcfg.num_users

        def shared_shape():
            # mirrors init_host_backend's CohortShared construction
            # (shapes only — never materialized)
            kg, kd, ks, kk = jax.random.split(jax.random.key(sp.seed), 4)
            g_opt_def, _ = _opts(fcfg)
            g, d0 = pair.init(kg)
            return _pack_key(CohortShared(g, g_opt_def.init(g), d0,
                                          jnp.zeros((), jnp.int32), kk))

        nd = d_flat_layout(pair).n
        no = d_opt_flat_layout(pair, fcfg).n
        tmpl = {"shared": jax.eval_shape(shared_shape),
                "d_flat": jax.ShapeDtypeStruct((U, nd), np.float32),
                "opt_flat": jax.ShapeDtypeStruct((U, no), np.float32),
                "last_round": jax.ShapeDtypeStruct((U,), np.int32)}
        if _wants_residual(fcfg):
            # the EF residual is part of the trajectory — dropping it on
            # restore would silently re-zero the compensation ledger.
            # codec="none" specs keep the pre-PR 4-key layout, so old
            # checkpoints stay restorable.
            tmpl["residual"] = jax.ShapeDtypeStruct((U, nd), np.float32)
        return tmpl

    # -- checkpoint state --------------------------------------------------

    def arrays(self):
        if self.backend is None:
            return self._template
        out = {"shared": _pack_key(self.shared),
               "d_flat": self.backend.d_flat,
               "opt_flat": self.backend.opt_flat,
               "last_round": self.backend.last_round}
        if self.backend.has_residual:
            out["residual"] = self.backend.residual
        return out

    def load_arrays(self, tree) -> None:
        from repro.core.federated import HostStateBackend
        self.shared = _unpack_key(
            jax.tree.map(jnp.asarray, tree["shared"]))
        self.backend = HostStateBackend(
            np.asarray(tree["d_flat"]),
            np.asarray(tree["opt_flat"]),
            np.asarray(tree["last_round"]),
            residual=(np.asarray(tree["residual"])
                      if "residual" in tree else None))

    # -- serve handles -----------------------------------------------------

    def generator_params(self):
        if self.shared is None:
            raise RuntimeError("driver state not materialized (restore in "
                               "progress) — nothing to serve yet")
        return self.shared.g

    def user_d_flat(self, user_id: int) -> np.ndarray:
        if self.backend is None:
            raise RuntimeError("driver state not materialized (restore in "
                               "progress) — nothing to serve yet")
        d_rows, _, _ = self.backend.gather_rows(np.asarray([user_id]))
        return np.asarray(d_rows[0])

    # -- execution ---------------------------------------------------------

    def run(self, rounds: int) -> RunResult:
        sess = self.sess
        sp = sess.spec
        U, C = sess.fcfg.num_users, sess.cohort_size
        schedule = sess._next_schedule(rounds)
        wts = sess._next_weights(schedule)

        def batch_round(r: int):
            return np.stack([np.asarray(
                sess.dataset.user_batch(int(u), sess.data_rng,
                                        sp.batch_size))
                for u in schedule[r]])

        if self.fused_store:
            rpj = sp.engine.rounds_per_jit
            self.shared, mets, wstats = superbatch_cohort_rounds(
                self.win_eng, self.shared, self.backend, schedule,
                batch_round, rounds_per_jit=rpj, wts=wts,
                round_base=sess.round, prefetch=sp.backend.prefetch)
            # timing at window granularity: the first window carries the
            # compile, full post-warmup windows give the steady rate, and
            # the per-round stall is the window's single block divided by
            # its real rounds
            wr = wstats.win_retire_t
            steady = wr[-1] - wr[0] if len(wr) > 1 else 0.0
            step_denom = max(rounds - wstats.win_rounds[0], 1)
            rates = [(wr[j] - wr[j - 1]) / wstats.win_rounds[j]
                     for j in range(1, len(wr))
                     if wstats.win_rounds[j] == rpj]
            min_step_s = min(rates) if rates else steady / step_denom
            post = [s / k for s, k in zip(wstats.win_stall_s[1:],
                                          wstats.win_rounds[1:])]
            host_stall = (float(np.mean(post)) if post
                          else wstats.win_stall_s[0] / wstats.win_rounds[0])
        else:
            self.shared, mets, stats = stream_cohort_rounds(
                self.eng, self.shared, self.backend, schedule, batch_round,
                async_rounds=sp.backend.async_rounds,
                prefetch=sp.backend.prefetch, wts=wts,
                round_base=sess.round,
                stage_codec="int8" if self.stage_rows else "none")

            retire_t = stats.retire_t
            steady = retire_t[-1] - retire_t[0] if rounds > 1 else 0.0
            step_denom = max(rounds - 1, 1)
            # steady-state per-round estimate: min over sliding windows
            # of retire stamps (robust to the compile round and
            # background-load spikes)
            W = max(1, min(8, (rounds - 1) // 2))
            rates = [(retire_t[i + W] - retire_t[i]) / W
                     for i in range(1, rounds - W)]
            min_step_s = min(rates) if rates else steady / step_denom
            # mean host-blocked-on-device seconds per steady round: the
            # pipeline's figure of merit.  The compile round AND the
            # end-of-run drain (the final async_rounds retires block on
            # still-running rounds by construction) are excluded — with
            # them, an async run's "steady" stall would just be
            # drain/steps and shrink with run length
            host_stall = (float(np.mean(
                stats.stall_s[1:max(rounds - sp.backend.async_rounds, 2)]))
                if rounds > 1 else 0.0)

        g_losses = np.asarray([float(m["g_loss"]) for m in mets])
        d_losses = np.stack([np.asarray(m["d_loss"]) for m in mets])
        mean_age = np.asarray([float(m["mean_age"]) for m in mets])
        kept_frac = float(mets[-1]["kept_frac"])
        kept_mean = float(np.mean([float(m["kept_frac"]) for m in mets]))

        # unpacking the store into the stacked interop layout puts (U, N)
        # buffers on DEVICE — opt out for U beyond accelerator memory
        # (the regime this backend exists for); the host store stays
        # reachable via extra["host_backend"]
        state = None
        if sp.backend.materialize_state:
            cstate = CohortState(self.shared.g, self.shared.g_opt,
                                 self.backend.snapshot(),
                                 self.shared.server_d, self.shared.step,
                                 self.shared.key)
            state = cohort_state_to_full(sess.pair, sess.fcfg, cstate)
        counts = np.bincount(schedule.ravel(), minlength=U)
        total = sess.round + rounds
        staleness = total - self.backend.last_round
        async_rounds = sp.backend.async_rounds
        return RunResult(
            g_losses=g_losses,
            d_losses=d_losses,
            step_time_s=steady / step_denom,
            samples=sess._eval_samples(self.shared.g),
            state=state,
            extra={"kept_frac": kept_frac,
                   "engine": "fused", "min_step_time_s": min_step_s,
                   "participation": sp.participation.scheduler,
                   "cohort_size": C,
                   "schedule": schedule,
                   "participation_counts": counts,
                   "staleness": staleness,
                   "mean_age": mean_age,
                   "state_backend": self.backend_name,
                   "host_backend": self.backend,
                   "async_rounds": async_rounds,
                   "prefetch": sp.backend.prefetch,
                   "fused_store": self.fused_store,
                   "host_stall_s_per_round": host_stall,
                   "adaptive_server_scale":
                       sp.combine.adaptive_server_scale,
                   **({"participation_weights": wts}
                      if wts is not None else {}),
                   **_upload_accounting(
                       sess.pair, sess.fcfg, sp.approach, C, kept_mean,
                       stage_rows=sp.combine.compression.stage_rows)},
        )


register_backend("device", DeviceBackendDriver, streams=False)
register_backend("host", HostStreamDriver, streams=True)


# ---------------------------------------------------------------------------
# The session
# ---------------------------------------------------------------------------

class _CompileClock:
    """Seconds JAX spends in backend compilation while the clock is open,
    from JAX's own monitoring events: 0.0 for a window that compiled
    nothing.  The listener is process-wide, so a compile on another
    thread in the same stretch counts too."""

    def __init__(self):
        self.seconds = 0.0

    def _listen(self, event: str, duration_secs: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.seconds += duration_secs

    def __enter__(self) -> "_CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._listen)


class FederationSession:
    """Resumable, incrementally-driven federation run described by a
    :class:`FederationSpec`.

    ``run(rounds)`` advances the session and returns the window's
    :class:`RunResult`; ``save(path)`` / ``restore(path, ...)``
    checkpoint and rebuild the full session state (training carry / host
    store, RNG streams, participation counts, round counter) through the
    msgpack machinery.  ``fcfg.combiner`` / ``fcfg.staleness_decay`` are
    overridden by the spec's :class:`CombineSpec` (the spec is the run
    description; the model config keeps only model-side fields).

    ``mesh`` is required by mesh-mapped backends (``spmd``) and ignored
    otherwise."""

    def __init__(self, pair, fcfg: DistGANConfig, dataset,
                 spec: FederationSpec, *, mesh=None, _defer_state=False):
        spec.validate_against(fcfg.num_users)
        self.pair = pair
        self.dataset = dataset
        self.spec = spec
        self.mesh = mesh
        comp = spec.combine.compression
        if comp.codec == "topk_int8" and fcfg.selection not in (
                "topk", "threshold"):
            raise ValueError(
                f"codec='topk_int8' composes int8 transport with a sparse "
                f"selection, but fcfg.selection={fcfg.selection!r} keeps a "
                f"dense/random payload — use codec='int8' instead")
        self.fcfg = dataclasses.replace(
            fcfg, combiner=spec.combine.combiner,
            staleness_decay=spec.combine.staleness_decay,
            codec=comp.codec, error_feedback=comp.error_feedback,
            codec_stochastic=comp.stochastic, stage_rows=comp.stage_rows)
        self.approach = resolve_approach(spec.approach)
        self.round = 0
        self.data_rng = np.random.default_rng(spec.seed)
        # SEPARATE rng stream for the scheduler so that data sampling
        # consumes ``data_rng`` exactly as the full-participation path
        # does — with participation="full" and C == U the cohort
        # trajectory is therefore bit-identical to the plain fused
        # engine (pinned in tests/test_engine.py)
        self.sched_rng = np.random.default_rng([spec.seed, 0x5EED])
        # the scheduler's static parameters, bound ONCE (dedup: every
        # schedule consumer goes through this source — see
        # core.federated.make_schedule_source)
        shard_sizes = None
        if dataset is not None and isinstance(dataset.meta, dict):
            shard_sizes = dataset.meta.get("shard_sizes")
        self._schedule_window = make_schedule_source(
            spec.participation.scheduler, fcfg.num_users,
            spec.cohort_size_for(fcfg.num_users), shard_sizes)
        self._part_counts = (np.zeros(fcfg.num_users, np.float64)
                             if spec.combine.adaptive_server_scale else None)
        self._probe_nbytes: int | None = None
        self._eval_override: int | None = None
        self._mid_window = False
        self._driver = resolve_backend(spec.backend.kind).driver_cls(
            self, defer_state=_defer_state)

    # -- derived properties ------------------------------------------------

    @property
    def cohort_virtual(self) -> bool:
        return self.spec.cohort_virtual

    @property
    def cohort_size(self) -> int:
        return self.spec.cohort_size_for(self.fcfg.num_users)

    # -- host-side sampling helpers (shared rng discipline) ----------------

    def _batch_full(self, stage=np):
        """One full-participation round of data: (U, B, ...) per-user
        batches, or a (B, ...) union batch for approaches without a user
        axis.  ``stage=jnp`` reproduces the legacy per-step loop's
        per-round device staging."""
        B = self.spec.batch_size
        if not self.approach.user_axis:
            return stage.asarray(self.dataset.union_sampler(self.data_rng,
                                                            B))
        return stage.stack([stage.asarray(
            self.dataset.user_batch(u, self.data_rng, B))
            for u in range(self.fcfg.num_users)])

    def _probe(self, sample) -> int:
        """nbytes of one round's batch, sampled from a THROWAWAY rng so
        the real data stream is untouched (cached — shapes are fixed)."""
        if self._probe_nbytes is None:
            saved = self.data_rng
            self.data_rng = np.random.default_rng(self.spec.seed)
            try:
                self._probe_nbytes = int(sample().nbytes)
            finally:
                self.data_rng = saved
        return self._probe_nbytes

    def _probe_nbytes_full(self) -> int:
        return self._probe(self._batch_full)

    def _probe_nbytes_cohort(self, schedule) -> int:
        B = self.spec.batch_size
        return self._probe(lambda: np.stack([
            np.asarray(self.dataset.user_batch(int(u), self.data_rng, B))
            for u in schedule[0]]))

    # -- schedule / weights windows ----------------------------------------

    def _next_schedule(self, rounds: int) -> np.ndarray:
        """The next ``rounds`` rows of the cohort membership schedule,
        drawn from the persisted scheduler rng at the session's global
        round offset — window-by-window generation reproduces the
        single-shot full-run schedule exactly."""
        return self._schedule_window(self.sched_rng, self.round, rounds)

    def _next_weights(self, schedule) -> np.ndarray | None:
        if self._part_counts is None:
            return None
        return participation_weights(schedule, self.fcfg.num_users,
                                     counts=self._part_counts,
                                     start_round=self.round)

    def _eval_samples(self, g_params) -> np.ndarray | None:
        n = (self.spec.eval_samples if self._eval_override is None
             else self._eval_override)
        if not n:
            return None
        z = self.pair.sample_z(jax.random.key(self.spec.seed + 1), n)
        return np.asarray(self.pair.g_apply(g_params, z))

    # -- serve handles -----------------------------------------------------

    def generator_params(self):
        """The live generator parameter tree — what
        ``repro.serve.GenerationService`` publishes (and re-publishes on
        ``refresh``) to sample requests."""
        return self._driver.generator_params()

    def user_d_flat(self, user_id: int) -> np.ndarray:
        """User ``user_id``'s flat (Nd,) discriminator row, gathered from
        whichever backend holds the store (device carry, host NumPy
        buffers, or the streamed SPMD store).  The serve layer's
        per-user rejection filter scores candidate samples with it;
        approaches without a per-user axis have no rows to gather."""
        if not self.approach.user_axis:
            raise ValueError(
                f"approach {self.spec.approach!r} keeps no per-user "
                f"discriminator rows (no user axis)")
        if not 0 <= int(user_id) < self.fcfg.num_users:
            raise ValueError(f"user_id {user_id} out of range "
                             f"[0, {self.fcfg.num_users})")
        return np.asarray(self._driver.user_d_flat(int(user_id)))

    # -- execution ---------------------------------------------------------

    def run(self, rounds: int, *, eval_samples: int | None = None,
            autosave_every: int | None = None,
            autosave_path: str | None = None) -> RunResult:
        """Advance the federation by ``rounds`` rounds; returns the
        window's RunResult (schedule/counts/metrics are window-local,
        ``staleness`` is against the post-window global round).

        Windowing is trajectory-neutral for synchronous pipelines; an
        ``async_rounds > 0`` stream drains at the window boundary (see
        the module docstring).  Windows shorter than
        ``EngineSpec.rounds_per_jit`` still compute a full masked chunk
        on the scan backends and report degenerate step timing — pick
        the spec's ``rounds_per_jit`` to fit the window sizes you plan
        to run.

        ``eval_samples`` overrides the spec's value for THIS window only
        (eval runs at the end of every window; pass 0 for intermediate
        windows of a long drive to skip the generator sampling, or set
        the spec's ``eval_samples=0`` and request samples only on the
        final window).

        ``autosave_every=N`` (with ``autosave_path``) checkpoints the
        session via :meth:`save` every N rounds at internal window
        boundaries — a long ``run()`` killed mid-way resumes from the
        last autosave and, because windowing is trajectory-neutral for
        synchronous pipelines, reproduces the uninterrupted trajectory
        (async streams re-sync at each autosave boundary, same drain
        semantics as manual windowing).  Generator eval runs only on the
        final sub-window; the returned RunResult is the merged whole
        window."""
        assert isinstance(rounds, int) and rounds >= 1, rounds
        if autosave_every is None:
            return self._run_window(rounds, eval_samples)
        if not isinstance(autosave_every, int) or autosave_every < 1:
            raise ValueError(f"autosave_every must be a positive int, got "
                             f"{autosave_every!r}")
        if not autosave_path:
            raise ValueError("autosave_every needs an autosave_path to "
                             "save into")
        parts = []
        done = 0
        while done < rounds:
            k = min(autosave_every, rounds - done)
            last = done + k == rounds
            parts.append(self._run_window(
                k, eval_samples if last else 0))
            done += k
            self.save(autosave_path)
        return _merge_results(parts)

    def _run_window(self, rounds: int,
                    eval_samples: int | None) -> RunResult:
        self._eval_override = eval_samples
        self._mid_window = True
        with _CompileClock() as compiles:
            result = self._driver.run(rounds)
        result.extra["compile_s"] = compiles.seconds
        # only on success: a mid-window failure leaves rng streams /
        # counts / carry partially advanced, and save() must refuse
        self._mid_window = False
        self._eval_override = None
        self.round += rounds
        return result

    # -- checkpoint / restore ----------------------------------------------

    def save(self, path: str) -> str:
        """Checkpoint the whole session under directory ``path``: the
        array state via the msgpack machinery plus a ``session.json``
        with the spec manifest, RNG streams, and round counter.  In
        async streaming mode every in-flight round has retired by the
        time ``run`` returns, so a save between windows is always
        consistent (the resumed pipeline restarts empty — the
        window-boundary drain semantics in the module docstring).

        Refuses to save after a ``run()`` that raised mid-window: the
        rng streams, participation counts, and carry are then partially
        advanced relative to the round counter, and a checkpoint of that
        state would restore a silently wrong trajectory — restore from
        the previous checkpoint instead."""
        if self._mid_window:
            raise RuntimeError(
                "session state is inconsistent: the last run() raised "
                "mid-window (rng streams/carry advanced past the round "
                "counter).  Saving would checkpoint a silently wrong "
                "trajectory; restore from the last good checkpoint.")
        os.makedirs(path, exist_ok=True)
        ckpt = save_checkpoint(path, self.round, self._driver.arrays())
        self._driver.save_aux(path, self.round)
        meta = {
            "format": 1,
            "spec": self.spec.to_dict(),
            "round": self.round,
            "num_users": self.fcfg.num_users,
            "data_rng": self.data_rng.bit_generator.state,
            "sched_rng": self.sched_rng.bit_generator.state,
            "part_counts": (None if self._part_counts is None
                            else self._part_counts.tolist()),
        }
        tmp = os.path.join(path, _SESSION_META + ".tmp")
        with open(tmp, "w") as f:
            json.dump(meta, f, indent=1, sort_keys=True)
        os.replace(tmp, os.path.join(path, _SESSION_META))
        return ckpt

    def close(self) -> None:
        """Release the driver's out-of-process resources (the multihost
        backend's worker fleet); a no-op for in-process backends.  The
        session is unusable afterwards."""
        self._driver.close()

    @classmethod
    def restore(cls, path: str, pair, fcfg: DistGANConfig, dataset, *,
                mesh=None, workers: int | None = None) -> "FederationSession":
        """Rebuild a session from ``save(path)`` in a (possibly fresh)
        process.  ``pair`` / ``fcfg`` / ``dataset`` are the runtime
        objects the manifest cannot serialize and must match the saving
        run; the spec itself comes from the checkpoint.  ``dataset=None``
        restores a serve-only session (repro.serve reads the generator
        and store rows; ``run`` needs a real dataset).

        ``workers`` overrides a multihost checkpoint's worker count —
        the sharded store re-partitions on restore (each worker loads
        the overlapping slices of the saved shard files), so a run saved
        at W workers resumes bit-identically at any other W'."""
        with open(os.path.join(path, _SESSION_META)) as f:
            meta = json.load(f)
        if meta["num_users"] != fcfg.num_users:
            raise ValueError(
                f"checkpoint was saved with num_users={meta['num_users']}, "
                f"got fcfg.num_users={fcfg.num_users}")
        spec = FederationSpec.from_dict(meta["spec"])
        if workers is not None:
            if spec.backend.kind != "multihost":
                raise ValueError(
                    f"workers= re-partitions a multihost checkpoint; this "
                    f"one was saved with backend {spec.backend.kind!r}")
            spec = dataclasses.replace(
                spec, backend=dataclasses.replace(spec.backend,
                                                  workers=workers))
        # defer state materialization: the fresh-init values would be
        # discarded by load_arrays anyway, and at large U the double
        # (U, N) store materialization dominates resume cost
        sess = cls(pair, fcfg, dataset, spec, mesh=mesh, _defer_state=True)
        step = meta["round"]
        assert latest_step(path) == step, (latest_step(path), step)
        sess._driver.load_arrays(
            restore_checkpoint(path, step, sess._driver.arrays()))
        sess._driver.load_aux(path, step)
        sess.round = step
        sess.data_rng.bit_generator.state = meta["data_rng"]
        sess.sched_rng.bit_generator.state = meta["sched_rng"]
        if meta["part_counts"] is not None:
            sess._part_counts = np.asarray(meta["part_counts"], np.float64)
        return sess
