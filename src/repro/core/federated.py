"""Selective parameter sharing (Shokri & Shmatikov 2015), the mechanism
behind the paper's first approach.

Users compute local weight deltas; only a *selected subset* crosses the
user boundary.  Selection policies (paper §3.1):

* ``topk``      — largest-|delta| fraction theta (the paper's default),
* ``threshold`` — |delta| > tau,
* ``random``    — random fraction theta (Shokri's baseline).

The server folds the uploaded deltas with the paper's rule (algorithm 1
line 4: "selects the biggest dw_i as max(dw_i)") — an elementwise
argmax-|.| across users — or with FedAvg-style mean (our baseline for
comparison).

Two execution modes:
* host-simulated: deltas stacked on a leading user axis (vmap-style);
* SPMD: one user per mesh slice, combine via jax.lax collectives inside
  shard_map (``combine_max_abs_spmd``).  Raw data never crosses the user
  axis — only these masked deltas do, which is the paper's privacy
  boundary.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Literal, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.core.spec import (COMBINER_REGISTRY, SCHEDULER_REGISTRY,
                             register_combiner, register_scheduler,
                             resolve_scheduler)

Selection = Literal["topk", "threshold", "random", "none"]


# ---------------------------------------------------------------------------
# Flat-buffer discriminator layout
# ---------------------------------------------------------------------------
#
# The fused round engine keeps D deltas as ONE contiguous (N,) buffer with a
# *static* unflatten spec, so per-round delta = one subtract, selection = one
# masked op, and the SPMD fold psums a single buffer instead of a tree of
# small leaves.  ``ravel_pytree`` rebuilds this spec on every call; FlatLayout
# builds it once at trace time from the parameter template.

@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """Static flatten/unflatten spec for one parameter pytree.

    ``flatten``/``unflatten`` move between the tree and a single (N,)
    buffer; the ``_stacked`` variants handle (U, ...)-stacked trees and
    (U, N) buffers (user axis leading).  Leaf order is jax.tree order —
    identical to ravel_pytree's, so flat indices are interchangeable.
    """

    treedef: Any
    shapes: tuple
    dtypes: tuple
    sizes: tuple
    n: int

    def flatten(self, tree) -> jnp.ndarray:
        leaves = jax.tree.leaves(tree)
        return jnp.concatenate([jnp.ravel(l) for l in leaves])

    def flatten_stacked(self, tree) -> jnp.ndarray:
        leaves = jax.tree.leaves(tree)
        u = leaves[0].shape[0]
        return jnp.concatenate(
            [jnp.reshape(l, (u, -1)) for l in leaves], axis=1)

    def _split(self, flat, axis):
        idx = 0
        parts = []
        for size, shape, dt in zip(self.sizes, self.shapes, self.dtypes):
            sl = jax.lax.slice_in_dim(flat, idx, idx + size, axis=axis)
            lead = flat.shape[:axis]
            parts.append(jnp.reshape(sl, lead + shape).astype(dt))
            idx += size
        return parts

    def unflatten(self, flat: jnp.ndarray):
        return jax.tree.unflatten(self.treedef, self._split(flat, 0))

    def unflatten_stacked(self, flat: jnp.ndarray):
        return jax.tree.unflatten(self.treedef, self._split(flat, 1))


def make_flat_layout(example_tree) -> FlatLayout:
    """Build the static layout from a tree of arrays / ShapeDtypeStructs."""
    leaves, treedef = jax.tree.flatten(example_tree)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    sizes = tuple(math.prod(s) for s in shapes)
    return FlatLayout(treedef, shapes, dtypes, sizes, sum(sizes))


# ---------------------------------------------------------------------------
# Cohort-virtualized per-user state
# ---------------------------------------------------------------------------
#
# The compiled program no longer has to be shaped by the number of LOGICAL
# users U: the (U, ...) per-user discriminator/optimizer state lives in flat
# (U, N) buffers, and each round a cohort of C <= U rows is gathered into the
# scan body and scattered back.  U only sizes the resident buffers; every
# traced shape is C.  ``last_round`` records each user's most recent
# participation so stale deltas can be aged by the staleness-aware combiners.

class CohortStore(NamedTuple):
    """Resident per-user state as flat buffers (one row per logical user).

    ``d_flat``     (U, Nd)  discriminator params, FlatLayout row layout
    ``opt_flat``   (U, No)  optimizer state (int leaves are stored as f32
                            and cast back on unflatten — exact below 2**24,
                            far beyond any round count here)
    ``last_round`` (U,) i32 round at which the user last participated
    ``residual``   (U, Nd) f32 error-feedback residual (what upload
                            compression dropped from the user's last
                            delta, re-added to its next one) — or None
                            when no lossy codec is configured.  ``None``
                            is not a pytree leaf, so codec-free stores
                            keep the exact pre-compression structure.
    """

    d_flat: jnp.ndarray
    opt_flat: jnp.ndarray
    last_round: jnp.ndarray
    residual: Any = None

    @property
    def num_users(self) -> int:
        return self.d_flat.shape[0]


def make_cohort_store(ds, d_opts, d_layout: FlatLayout,
                      opt_layout: FlatLayout, *,
                      error_feedback: bool = False) -> CohortStore:
    """Pack (U, ...)-stacked D/optimizer trees into resident flat buffers.
    ``error_feedback`` allocates the zero-initialized (U, Nd) residual."""
    u = jax.tree.leaves(ds)[0].shape[0]
    d_flat = d_layout.flatten_stacked(ds)
    return CohortStore(
        d_flat=d_flat,
        opt_flat=opt_layout.flatten_stacked(d_opts),
        last_round=jnp.zeros((u,), jnp.int32),
        residual=jnp.zeros_like(d_flat) if error_feedback else None)


def cohort_gather(store: CohortStore, idx, d_layout: FlatLayout,
                  opt_layout: FlatLayout):
    """Pull cohort rows ``idx`` (C,) out of the store as stacked (C, ...)
    D/optimizer trees — the exact layout the round bodies consume."""
    ds = d_layout.unflatten_stacked(store.d_flat[idx])
    opts = opt_layout.unflatten_stacked(store.opt_flat[idx])
    return ds, opts


def take_rows(buf, idx):
    """Rows ``idx`` (C,) of ``buf`` (U, ...) as one (C, ...) array, read as
    C dynamic row slices.  ``buf[idx]`` is an XLA gather, which the TPU
    compiler may lower as slices of the whole (U, N) buffer; C is a
    trace-time constant, so C row slices touch only the C rows.  Indices
    must lie in [0, U)."""
    return jnp.concatenate([jax.lax.dynamic_index_in_dim(buf, idx[i], 0)
                            for i in range(idx.shape[0])])


def cohort_scatter(store: CohortStore, idx, ds, d_opts, round_idx,
                   d_layout: FlatLayout, opt_layout: FlatLayout,
                   residual=None) -> CohortStore:
    """Write updated cohort slices back into the store (row replacement —
    values land bit-exactly) and stamp the members' ``last_round`` with
    ``round_idx`` (a scalar, or one stamp a member).
    ``residual`` scatters the cohort's updated error-feedback rows when
    the store carries them (required iff ``store.residual`` exists)."""
    assert (residual is None) == (store.residual is None), \
        "residual rows must be scattered iff the store carries them"
    return CohortStore(
        d_flat=store.d_flat.at[idx].set(d_layout.flatten_stacked(ds)),
        opt_flat=store.opt_flat.at[idx].set(
            opt_layout.flatten_stacked(d_opts)),
        last_round=store.last_round.at[idx].set(
            jnp.asarray(round_idx, jnp.int32)),
        residual=(None if store.residual is None
                  else store.residual.at[idx].set(residual)))


# ---------------------------------------------------------------------------
# User-state backends: where the (U, N) rows LIVE between rounds
# ---------------------------------------------------------------------------
#
# The CohortStore above is a *representation* (flat rows + last_round); a
# UserStateBackend decides its residency.  The device backend keeps the
# buffers in accelerator memory (the PR 2 regime — U bounded by HBM); the
# host backend keeps them as process-resident NumPy arrays and moves only
# the scheduled cohort's C rows across the host<->device boundary per
# round, so U is bounded by host RAM.  Both expose the same contract:
#
#   gather_rows(idx)  -> (d_rows (C, Nd), opt_rows (C, No),
#                         last_round (C,) i32 — host or device array)
#   scatter_rows(idx, d_rows, opt_rows, round_idx) -> None  (mutates)
#   snapshot()        -> CohortStore (device-resident, for eval/interop)
#
# ``last_round`` comes back as host ints from the host backend (the
# drivers compute ages host-side there); a ``device_resident`` backend
# may instead hand back device arrays for ALL THREE returns, and the
# streaming driver then computes ages on device and scatters device
# arrays straight back — no host sync anywhere on the round path.
# Scatter is last-writer-wins: under the
# async bounded-staleness driver (core.session.stream_cohort_rounds) a
# round's scatter may land AFTER later rounds launched — the classic
# async parameter-server semantics, with staleness bounded by the
# driver's ``async_rounds`` and surfaced through ``last_round`` ages.

class UserStateBackend:
    """Abstract residency contract for per-user D/optimizer rows.

    ``gather_rows`` stays a 3-tuple regardless of compression; backends
    that hold an error-feedback residual expose it through
    ``gather_residual`` and take the updated rows back through
    ``scatter_rows(..., residual=...)`` — drivers probe ``has_residual``.
    """

    num_users: int

    # True when gather_rows/scatter_rows exchange device-resident arrays:
    # the streaming driver then keeps the whole round path on device
    # (device-side ages, no D2H fetch before scatter) and only blocks the
    # host on the metrics fetch.
    device_resident: bool = False

    def gather_rows(self, idx):
        raise NotImplementedError

    def scatter_rows(self, idx, d_rows, opt_rows, round_idx, *,
                     residual=None) -> None:
        raise NotImplementedError

    @property
    def has_residual(self) -> bool:
        return False

    def gather_residual(self, idx):
        raise NotImplementedError

    def snapshot(self) -> CohortStore:
        raise NotImplementedError


class DeviceStateBackend(UserStateBackend):
    """Device-resident rows: a functional CohortStore behind the mutable
    backend API.  The scan-fused cohort engine keeps the store in its
    carry instead (faster — no per-round host round-trip); this wrapper
    exists so the streaming driver can run against either residency."""

    device_resident = True

    def __init__(self, store: CohortStore):
        self.store = store

    @property
    def num_users(self) -> int:
        return self.store.num_users

    def gather_rows(self, idx):
        idx = jnp.asarray(idx)
        # everything stays on DEVICE — including last_round, so the
        # streaming driver's age computation doesn't force a blocking
        # host sync on the store every round
        return (self.store.d_flat[idx], self.store.opt_flat[idx],
                self.store.last_round[idx])

    def scatter_rows(self, idx, d_rows, opt_rows, round_idx, *,
                     residual=None) -> None:
        idx = jnp.asarray(idx)
        store = self.store
        assert (residual is None) == (store.residual is None)
        self.store = CohortStore(
            d_flat=store.d_flat.at[idx].set(jnp.asarray(d_rows)),
            opt_flat=store.opt_flat.at[idx].set(jnp.asarray(opt_rows)),
            last_round=store.last_round.at[idx].set(
                jnp.asarray(round_idx, jnp.int32)),
            residual=(None if store.residual is None
                      else store.residual.at[idx].set(
                          jnp.asarray(residual))))

    @property
    def has_residual(self) -> bool:
        return self.store.residual is not None

    def gather_residual(self, idx):
        return self.store.residual[jnp.asarray(idx)]

    def snapshot(self) -> CohortStore:
        return self.store


class HostStateBackend(UserStateBackend):
    """Host-resident rows: pinned process-memory NumPy buffers.  U sizes
    nothing on the accelerator — per round only C rows are gathered
    (fancy-index copy) for ``jax.device_put`` and scattered back, so the
    logical population is bounded by host RAM, not HBM."""

    def __init__(self, d_flat: np.ndarray, opt_flat: np.ndarray,
                 last_round: np.ndarray, residual: np.ndarray | None = None):
        u = d_flat.shape[0]
        assert opt_flat.shape[0] == u and last_round.shape == (u,)

        def own(a, dt):
            # jax buffers arrive as read-only views; the store must own
            # writable memory (scatter mutates in place)
            a = np.ascontiguousarray(a, dtype=dt)
            return a if a.flags.writeable else a.copy()

        self.d_flat = own(d_flat, np.float32)
        self.opt_flat = own(opt_flat, np.float32)
        self.last_round = own(last_round, np.int32)
        self.residual = None if residual is None else own(residual,
                                                          np.float32)

    @property
    def num_users(self) -> int:
        return self.d_flat.shape[0]

    @classmethod
    def from_store(cls, store: CohortStore) -> "HostStateBackend":
        return cls(np.asarray(store.d_flat), np.asarray(store.opt_flat),
                   np.asarray(store.last_round),
                   None if store.residual is None
                   else np.asarray(store.residual))

    def gather_rows(self, idx):
        idx = np.asarray(idx)
        return (self.d_flat[idx], self.opt_flat[idx], self.last_round[idx])

    def scatter_rows(self, idx, d_rows, opt_rows, round_idx, *,
                     residual=None) -> None:
        idx = np.asarray(idx)
        self.d_flat[idx] = np.asarray(d_rows)
        self.opt_flat[idx] = np.asarray(opt_rows)
        self.last_round[idx] = np.int32(round_idx)
        assert (residual is None) == (self.residual is None)
        if residual is not None:
            self.residual[idx] = np.asarray(residual)

    @property
    def has_residual(self) -> bool:
        return self.residual is not None

    def gather_residual(self, idx):
        return self.residual[np.asarray(idx)]

    def snapshot(self) -> CohortStore:
        # jnp.asarray may zero-copy a large aligned host buffer on the
        # CPU backend — a snapshot aliasing the live store would then be
        # silently corrupted by later in-place scatters.  Force copies.
        return CohortStore(jnp.array(self.d_flat),
                           jnp.array(self.opt_flat),
                           jnp.array(self.last_round),
                           None if self.residual is None
                           else jnp.array(self.residual))


# ---------------------------------------------------------------------------
# Participation schedulers (host-side: they drive which users' data is
# sampled, so they must run before device dispatch)
# ---------------------------------------------------------------------------

def _sched_full(rng, num_users, cohort, rounds, shard_sizes=None, start=0):
    assert cohort == num_users, (
        f"'full' participation needs cohort == num_users "
        f"(got C={cohort}, U={num_users})")
    return np.tile(np.arange(num_users, dtype=np.int32), (rounds, 1))


def _sched_uniform(rng, num_users, cohort, rounds, shard_sizes=None,
                   start=0):
    return np.stack([rng.choice(num_users, size=cohort, replace=False)
                     for _ in range(rounds)]).astype(np.int32)


def _sched_round_robin(rng, num_users, cohort, rounds, shard_sizes=None,
                       start=0):
    # keyed off the GLOBAL round index so a window generated at
    # start=k continues the rotation exactly where round k-1 left it
    first = np.arange(start, start + rounds, dtype=np.int64)[:, None] * cohort
    return ((first + np.arange(cohort)) % num_users).astype(np.int32)


def _sched_weighted(rng, num_users, cohort, rounds, shard_sizes=None,
                    start=0):
    assert shard_sizes is not None and len(shard_sizes) == num_users, (
        "'weighted' participation needs per-user shard sizes "
        "(dataset.meta['shard_sizes'])")
    p = np.asarray(shard_sizes, np.float64)
    p = p / p.sum()
    return np.stack([rng.choice(num_users, size=cohort, replace=False, p=p)
                     for _ in range(rounds)]).astype(np.int32)


register_scheduler("full", _sched_full)
register_scheduler("uniform", _sched_uniform)
register_scheduler("round_robin", _sched_round_robin)
register_scheduler("weighted", _sched_weighted)

# legacy alias: the live registry mapping (same dict object — entries
# registered later through repro.core.spec.register_scheduler show up)
SCHEDULERS = SCHEDULER_REGISTRY.entries


def make_schedule(participation: str, num_users: int, cohort: int,
                  rounds: int, rng: np.random.Generator,
                  shard_sizes=None, start: int = 0) -> np.ndarray:
    """(rounds, C) int32 cohort membership; every row is replacement-free
    (a user appears at most once per round, so scatter rows never
    collide).  ``start`` is the global index of the first generated
    round: rng-driven schedulers consume their stream sequentially, so a
    window generated at ``start=k`` from a generator that already
    produced rounds [0, k) continues the full-run schedule exactly —
    the property resumable sessions rely on."""
    assert 1 <= cohort <= num_users, (cohort, num_users)
    sched = resolve_scheduler(participation)(
        rng, num_users, cohort, rounds, shard_sizes, start=start)
    assert sched.shape == (rounds, cohort)
    return sched


def make_schedule_source(participation: str, num_users: int, cohort: int,
                         shard_sizes=None) -> Callable:
    """Bind a scheduler's static parameters once; returns
    ``schedule_window(rng, start, K) -> (K, C) int32``.

    Every schedule consumer (the session's ``_next_schedule``, the
    store-resident fused engines, the fused-store bench) used to re-spell
    the same ``make_schedule(participation, num_users, cohort, ...)``
    call with its static arguments re-derived at each site; this factory
    is the ONE place that binding happens.  The returned window function
    keeps ``make_schedule``'s resume contract: rng-driven schedulers
    consume their stream sequentially, so windows generated at
    ``start=0, K`` then ``start=K, K'`` concatenate to the single-shot
    ``start=0, K+K'`` schedule exactly."""

    def schedule_window(rng: np.random.Generator, start: int,
                        rounds: int) -> np.ndarray:
        return make_schedule(participation, num_users, cohort, rounds, rng,
                             shard_sizes, start=start)

    return schedule_window


def window_forwarding(schedule: np.ndarray, last_round: np.ndarray,
                      round_base: int):
    """Host-side precompute for the fused K-round superbatch program:
    write-after-read forwarding indices and exact participation ages for
    one ``(K, C)`` schedule window.

    A user scheduled twice inside one fused window must see its own
    earlier update in the later round — but the staged ``(K, C, N)`` row
    block was gathered from the store BEFORE the window ran, so the later
    round's staged row is stale.  ``fwd[r, c]`` is the flat position
    ``r' * C + c'`` of user ``schedule[r, c]``'s most recent EARLIER
    occurrence within the window (the row the fused program must read
    from its output block instead of the staged input), or -1 when the
    staged row is current.  Rows within a round are replacement-free
    (make_schedule), so a forward source is always from a strictly
    earlier round — the scan reads only already-written output rows.

    ``ages[r, c]`` is the exact age the per-round path would compute,
    including in-window re-participation: a member drawn again sees
    ``last_round == round_base + r' + 1`` (the re-zeroed age convention),
    so its age is ``r - r' - 1``.  ``last_round`` is NOT mutated.

    Returns ``(fwd (K, C) int32, ages (K, C) int32)``."""
    K, C = schedule.shape
    fwd = np.full((K, C), -1, np.int32)
    ages = np.empty((K, C), np.int32)
    seen: dict = {}          # user -> (flat position, stamped last_round)
    for r in range(K):
        for c in range(C):
            u = int(schedule[r, c])
            if u in seen:
                pos, stamp = seen[u]
                fwd[r, c] = pos
                ages[r, c] = round_base + r - stamp
            else:
                ages[r, c] = round_base + r - int(last_round[u])
        for c in range(C):
            u = int(schedule[r, c])
            seen[u] = (r * C + c, round_base + r + 1)
    return fwd, ages


def participation_weights(schedule: np.ndarray, num_users: int, *,
                          counts: np.ndarray | None = None,
                          start_round: int = 0) -> np.ndarray:
    """(rounds, C) f32 adaptive combine weights from participation counts.

    Opt-in fairness knob (``CombineSpec(adaptive_server_scale=True)``):
    under partial participation a user drawn rarely contributes rarely,
    so its shard is under-represented in the server fold.  Each round,
    member u's raw weight is ``(expected + 1) / (count_u + 1)`` where
    ``count_u`` is u's prior participation count and ``expected = r*C/U``
    is the uniform-scheduler expectation at global round r —
    under-participating users get proportionally LARGER combine weight.
    Weights are normalized to mean 1 over the cohort, so the
    server_scale of the fold is preserved (the knob redistributes, it
    does not amplify).  Deterministic: derived purely from the host-side
    schedule, so it costs nothing on device beyond a (C,) multiply.

    ``counts`` / ``start_round`` window the computation for resumable
    sessions: pass the (U,) f64 participation counts accumulated over
    rounds [0, start_round) and they are UPDATED IN PLACE as this
    window's rounds are processed — weights for a run generated window
    by window equal the single-shot full-run weights."""
    rounds, cohort = schedule.shape
    if counts is None:
        counts = np.zeros(num_users, np.float64)
    out = np.empty((rounds, cohort), np.float32)
    for r in range(rounds):
        idx = schedule[r]
        expected = (start_round + r) * cohort / num_users
        w = (expected + 1.0) / (counts[idx] + 1.0)
        out[r] = (w / w.mean()).astype(np.float32)
        counts[idx] += 1.0
    return out


# ---------------------------------------------------------------------------
# Selection masks (flat)
# ---------------------------------------------------------------------------

def topk_mask(flat: jnp.ndarray, frac: float) -> jnp.ndarray:
    """Boolean mask keeping the largest-|.| ``frac`` of entries."""
    n = flat.shape[0]
    k = max(int(n * frac), 1)
    thresh = jax.lax.top_k(jnp.abs(flat), k)[0][-1]
    return jnp.abs(flat) >= thresh


def threshold_mask(flat: jnp.ndarray, tau: float) -> jnp.ndarray:
    return jnp.abs(flat) > tau


def random_mask(flat: jnp.ndarray, frac: float, key) -> jnp.ndarray:
    return jax.random.uniform(key, flat.shape) < frac


@jax.named_scope("fed.select")
def select_delta_flat(flat: jnp.ndarray, policy: Selection, *, frac=0.1,
                      tau=0.0, key=None, use_kernel: bool = False):
    """Apply a selection policy to one flat (N,) delta buffer.

    Returns (masked_flat, kept_fraction).  ``use_kernel`` routes the top-k
    masking through the Pallas global-threshold kernel
    (repro.kernels.topk_select) — exact full-vector semantics, same mask
    as ``topk_mask``.
    """
    if policy == "none":
        return flat, jnp.float32(1.0)
    if policy == "topk":
        if use_kernel:
            from repro.kernels import ops as kops
            mask = kops.topk_mask(flat, frac)
        else:
            mask = topk_mask(flat, frac)
    elif policy == "threshold":
        mask = threshold_mask(flat, tau)
    elif policy == "random":
        assert key is not None
        mask = random_mask(flat, frac, key)
    else:
        raise ValueError(policy)
    kept = jnp.mean(mask.astype(jnp.float32))
    return flat * mask, kept


def select_delta(delta_tree, policy: Selection, *, frac=0.1, tau=0.0,
                 key=None, use_kernel: bool = False):
    """Tree-shaped wrapper over ``select_delta_flat`` (re-flattens per call;
    the fused engine uses FlatLayout + select_delta_flat instead).
    """
    if policy == "none":
        return delta_tree, jnp.float32(1.0)
    flat, unravel = ravel_pytree(delta_tree)
    masked, kept = select_delta_flat(flat, policy, frac=frac, tau=tau,
                                     key=key, use_kernel=use_kernel)
    return unravel(masked), kept


# ---------------------------------------------------------------------------
# Transport codecs (wire encoding of the selected delta rows)
# ---------------------------------------------------------------------------
#
# A codec is applied AFTER the selection policy masks a row: the server
# sees dequantize(quantize(masked)) — exactly what a receiver could
# reconstruct from the packed wire payload.  ``codec_transport`` is that
# round-trip as one in-graph map over stacked (R, N) rows; the error-
# feedback residual (compensated - transported) is computed by the
# callers (approaches/spmd), because only they know the compensation.

@jax.named_scope("fed.codec")
def codec_transport(rows: jnp.ndarray, codec: str, *,
                    stochastic: bool = False, seed=None,
                    use_kernel: bool = False) -> jnp.ndarray:
    """Stacked (R, N) rows -> what the receiver reconstructs after the
    lossy wire round-trip.  ``none`` is the identity (and callers gate it
    out structurally, keeping codec-free programs bitwise-pinned);
    ``bf16`` is a double cast; the int8 codecs quantize per row with one
    absmax scale — through the Pallas kernels when ``use_kernel`` (same
    flag that routes top-k selection), else the jnp oracle.  ``seed``
    (traced int32) drives stochastic rounding."""
    if codec == "none":
        return rows
    if codec == "bf16":
        return rows.astype(jnp.bfloat16).astype(jnp.float32)
    if codec in ("int8", "topk_int8"):
        if use_kernel:
            from repro.kernels import ops as kops
            q, scale = kops.quantize_rows(rows, stochastic=stochastic,
                                          seed=seed)
            return kops.dequantize_rows(q, scale)
        from repro.kernels.ref import dequantize_rows_ref, quantize_rows_ref
        q, scale = quantize_rows_ref(rows, stochastic=stochastic, seed=seed)
        return dequantize_rows_ref(q, scale)
    raise ValueError(f"unknown codec {codec!r}")


def packed_payload_nbytes(row, policy: Selection | str,
                          codec: str = "none") -> int:
    """Materialize ONE transported (already-masked) row's wire payload as
    real packed buffers — int32 indices, codec-encoded values, per-row
    scale — and return their total nbytes.  This is the ground truth the
    ``upload_bytes_flat`` pricing table is asserted against in tests and
    measured against in the compression bench."""
    row = np.asarray(row, np.float32)
    assert row.ndim == 1, f"one row at a time, got {row.shape}"
    nbytes = 0
    if policy == "none":
        vals = row
    elif policy == "shared_random":
        vals = row[np.nonzero(row)[0]]       # indices derive from the
    else:                                    # shared key: values only
        idx = np.nonzero(row)[0].astype(np.int32)
        vals = row[idx]
        nbytes += idx.nbytes
    if codec == "none":
        nbytes += vals.nbytes
    elif codec == "bf16":
        nbytes += np.asarray(
            jnp.asarray(vals).astype(jnp.bfloat16)).nbytes
    elif codec in ("int8", "topk_int8"):
        from repro.kernels.ref import quantize_rows_ref
        q, scale = quantize_rows_ref(jnp.asarray(vals)[None])
        nbytes += np.asarray(q).nbytes + np.asarray(scale).nbytes
    else:
        raise ValueError(f"unknown codec {codec!r}")
    return nbytes


# ---------------------------------------------------------------------------
# Server combination rules
# ---------------------------------------------------------------------------

def combine_max_abs(deltas_stacked):
    """Paper's rule on a stacked (U, ...) delta tree: per coordinate, keep
    the single user's delta with the largest magnitude."""

    def one(d):  # d: (U, ...)
        idx = jnp.argmax(jnp.abs(d), axis=0, keepdims=True)
        return jnp.take_along_axis(d, idx, axis=0)[0]

    return jax.tree.map(one, deltas_stacked)


def combine_mean(deltas_stacked):
    """FedAvg baseline: mean over users (ignores zeros' sparsity)."""
    return jax.tree.map(lambda d: jnp.mean(d, axis=0), deltas_stacked)


def combine_masked_mean(deltas_stacked):
    """Mean over the users that actually uploaded each coordinate
    (zeros from the selection mask don't dilute)."""

    def one(d):
        nz = (d != 0).astype(d.dtype)
        cnt = jnp.maximum(jnp.sum(nz, axis=0), 1)
        return jnp.sum(d, axis=0) / cnt

    return jax.tree.map(one, deltas_stacked)


def _age_weights(ages, decay: float, lead_shape):
    """(C,) participation ages -> broadcastable decay weights.

    age 0 (the user trained on the current server point) weighs 1; each
    round of staleness multiplies by ``decay``.  Under partial
    participation a cohort member may not have trained since round
    ``last_round``, so its delta is w.r.t. an old server point — aging it
    down is the classic staleness correction for async/partial FL."""
    w = jnp.asarray(decay, jnp.float32) ** ages.astype(jnp.float32)
    return jnp.reshape(w, w.shape + (1,) * (len(lead_shape) - 1))


def combine_staleness_mean(deltas_stacked, ages=None, decay: float = 0.5):
    """Staleness-weighted mean: each user's delta is discounted by
    ``decay**age`` and the weights are renormalized.  With ``ages=None``
    (or all-zero ages) this is exactly ``combine_mean``.

    The weights are normalized, so they are computed relative to the
    YOUNGEST cohort member (``decay**(age - min(age))``) — mathematically
    identical, but immune to ``decay**age`` underflowing to f32 zero for
    uniformly old cohorts (ages of hundreds of rounds are routine at
    large U/C ratios), which would otherwise yield 0/0 = NaN."""

    if ages is not None:
        ages = ages - jnp.min(ages)

    def one(d):
        if ages is None:
            return jnp.mean(d, axis=0)
        w = _age_weights(ages, decay, d.shape)
        return jnp.sum(w * d, axis=0) / jnp.sum(w, axis=0)

    return jax.tree.map(one, deltas_stacked)


def combine_staleness_max_abs(deltas_stacked, ages=None, decay: float = 0.5):
    """Paper's argmax-|.| fold with stale users handicapped: deltas are
    scaled by ``decay**age`` BEFORE the magnitude competition, so a fresh
    small delta can beat a stale large one.  ``ages=None`` degenerates to
    ``combine_max_abs`` on the scaled==unscaled deltas."""

    def one(d):
        scaled = d if ages is None else _age_weights(ages, decay, d.shape) * d
        idx = jnp.argmax(jnp.abs(scaled), axis=0, keepdims=True)
        return jnp.take_along_axis(scaled, idx, axis=0)[0]

    return jax.tree.map(one, deltas_stacked)


combine_staleness_mean.needs_ages = True
combine_staleness_max_abs.needs_ages = True

register_combiner("max_abs", combine_max_abs)
register_combiner("mean", combine_mean)
register_combiner("masked_mean", combine_masked_mean)
register_combiner("staleness_mean", combine_staleness_mean)
register_combiner("staleness_max_abs", combine_staleness_max_abs)

# legacy alias: the live registry mapping (same dict object — entries
# registered later through repro.core.spec.register_combiner show up)
COMBINERS = COMBINER_REGISTRY.entries


# ---------------------------------------------------------------------------
# SPMD combination (inside shard_map, one user per 'users' axis slice)
# ---------------------------------------------------------------------------

@jax.named_scope("fed.fold")
def combine_max_abs_spmd(delta_tree, axis: str = "users"):
    """Paper's max-|.| rule as collectives: pmax of |delta|, then each user
    contributes its delta only where it attains the max; psum-normalized
    for ties.  Only masked deltas cross the axis — never raw data."""

    def one(d):
        mag = jnp.abs(d)
        mx = jax.lax.pmax(mag, axis)
        mine = (mag == mx).astype(d.dtype)
        ties = jax.lax.psum(mine, axis)
        return jax.lax.psum(d * mine / jnp.maximum(ties, 1), axis)

    return jax.tree.map(one, delta_tree)


@jax.named_scope("fed.fold")
def combine_mean_spmd(delta_tree, axis: str = "users"):
    return jax.tree.map(lambda d: jax.lax.pmean(d, axis), delta_tree)


def combine_shared_random_spmd(delta_tree, frac: float, key,
                               axis: str = "users"):
    """Shokri's *random-subset* upload policy as a bandwidth-true SPMD
    collective: all users derive the SAME mask from a shared per-round
    key, gather the selected coordinates into a dense (frac*N,) buffer,
    psum only that, and scatter back.  Unlike masking (zeros still cross
    the wire), the collective bytes here genuinely scale with ``frac`` —
    this is the paper's "improve the efficiency of information
    transmission" knob made real (EXPERIMENTS.md §Perf pair C, iter 5).

    Returns (combined_tree, uploaded_fraction)."""
    flat, unravel = ravel_pytree(delta_tree)
    out, kept = combine_shared_random_flat_spmd(flat, frac, key, axis)
    return unravel(out), kept


@jax.named_scope("fed.fold")
def combine_shared_random_flat_spmd(flat: jnp.ndarray, frac: float, key,
                                    axis: str = "users"):
    """Flat-buffer core of ``combine_shared_random_spmd``: the engine calls
    this directly on the FlatLayout buffer (no per-round re-flattening)."""
    n = flat.shape[0]
    k = max(int(n * frac), 1)
    # shared mask: same key on every shard => identical permutation
    perm = jax.random.permutation(key, n)
    idx = perm[:k]
    vals = flat[idx]
    summed = jax.lax.pmean(vals, axis)        # only k values cross the axis
    out = jnp.zeros_like(flat).at[idx].set(summed)
    return out, jnp.float32(k / n)


# ---------------------------------------------------------------------------
# Communication accounting (feeds the roofline's collective term)
# ---------------------------------------------------------------------------

def upload_bytes(delta_tree, policy: Selection, frac: float = 0.1, *,
                 tau: float = 0.0, kept_frac: float | None = None,
                 codec: str = "none") -> int:
    """Bytes per user per round crossing the privacy boundary.  Sparse
    uploads ship (index, value) pairs: 4B idx + codec value bytes per
    kept entry.

    ``topk``/``random`` keep a deterministic/expected ``frac`` of entries.
    ``threshold`` does NOT use ``frac`` — its kept count is data-dependent,
    so it is accounted from the actual kept fraction: pass ``kept_frac``
    (e.g. the trained run's measured value), else it is computed from
    ``delta_tree`` and ``tau`` directly.
    """
    n = sum(int(jnp.size(l)) for l in jax.tree.leaves(delta_tree))
    if policy == "threshold" and kept_frac is None:
        kept = sum(int(jnp.sum(jnp.abs(l) > tau))
                   for l in jax.tree.leaves(delta_tree))
        kept_frac = kept / n
    return upload_bytes_flat(n, policy, frac, kept_frac=kept_frac,
                             codec=codec)


# bytes per transported value on the wire, by codec
_CODEC_VALUE_BYTES = {"none": 4, "bf16": 2, "int8": 1, "topk_int8": 1}


def upload_bytes_flat(n: int, policy: Selection | str, frac: float = 0.1, *,
                      kept_frac: float | None = None,
                      codec: str = "none") -> int:
    """Per-user upload bytes from the flat buffer size alone (no delta
    tree needed — the cohort drivers know only ``FlatLayout.n``).  The
    ONE pricing table: ``upload_bytes`` delegates here after computing
    ``n`` (and, for ``threshold``, the kept count) from its delta tree,
    and the priced numbers equal ``packed_payload_nbytes`` on the real
    packed buffers (asserted in tests/test_cohort.py).

    Dense ``none`` ships one value per entry; sparse ``topk``/``random``/
    ``threshold`` ship (4B index, value) pairs per kept entry
    (``threshold`` MUST be given the measured ``kept_frac`` — its kept
    count is data-dependent); ``shared_random`` ships values only (the
    mask is derived from a shared per-round key, so no indices cross the
    wire).  The ``codec`` sets the value width — 4B float32 (``none``),
    2B ``bf16``, 1B for the int8 codecs plus one 4B float32 scale per
    row."""
    vb = _CODEC_VALUE_BYTES[codec]
    sb = 4 if codec in ("int8", "topk_int8") else 0   # per-row f32 scale
    if policy == "none":
        return n * vb + sb
    if policy == "threshold":
        assert kept_frac is not None, \
            "threshold accounting needs the measured kept_frac"
        kept = int(round(n * float(kept_frac)))
    elif policy == "shared_random":
        return max(int(n * frac), 1) * vb + sb
    else:
        kept = int(n * frac)
    return kept * (4 + vb) + sb
