"""Pallas TPU kernels: magnitude top-k masking — the compute hot-spot of
the paper's selective gradient sharing (approach 1 uploads the largest-
|delta| fraction of millions of discriminator weights every round).

GPU systems do this with a radix-select; the TPU adaptation replaces
data-movement-heavy selection with a *bisection threshold search* — pure
vector compares + reductions on 8x128 lanes, no sorting network.

Two variants:

* ``topk_mask_pallas`` (block-local, the original): each grid cell selects
  k_block = ceil(frac * BLOCK) of its own slice via an in-kernel f32
  bisection.  Locality trade, approximate at the full-vector level.

* ``topk_mask_pallas_global`` (two-pass, the fused engine's default): the
  threshold is GLOBAL, so the mask is exactly the full-vector oracle
  (``jax.lax.top_k`` semantics, ties included):

    pass 1 (Pallas)  — per-block maxima of the bit-cast magnitudes;
    refine (XLA)     — integer bisection on the IEEE-754 bit patterns
                       (non-negative f32 order == int32 order, so 31
                       halvings pin the k-th magnitude EXACTLY — no
                       epsilon slop, tie-exact);
    pass 2 (Pallas)  — one vector compare ``bits >= t*`` per block.

  The refine step touches only scalar counts; a production TPU build
  would histogram per block in pass 1 to avoid the re-reads, but the
  kernel/oracle contract (exact global threshold) is the same.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES, LANES = 8, 1024
BLOCK = SUBLANES * LANES  # 8192 elements per grid cell: one (8, 1024) tile
_BISECT_ITERS = 32
_BIT_ITERS = 31      # int32 magnitude patterns are < 2^31: exact in 31


# Each grid cell reads one (8, 1024) tile: the TPU lowering wants the last
# two block dims divisible by (8, 128), so a BLOCK-long slice of the flat
# vector is viewed as 8 sublane rows of 1024 lanes.  Slice i of the flat
# vector is rows 8i..8i+7 of the (nblocks * 8, 1024) view.
_TILE = pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))
# Per-call scalars live whole in SMEM, indexed by grid position.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def to_tiles(x: jnp.ndarray):
    """(..., N) -> zero-padded (..., nblocks * 8, 1024) tile view, nblocks."""
    pad = (-x.shape[-1]) % BLOCK
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    nblocks = xp.shape[-1] // BLOCK
    return xp.reshape(*x.shape[:-1], nblocks * SUBLANES, LANES), nblocks


def from_tiles(xp: jnp.ndarray, n: int) -> jnp.ndarray:
    """Inverse of :func:`to_tiles`: (..., nblocks * 8, 1024) -> (..., n)."""
    return xp.reshape(*xp.shape[:-2], -1)[..., :n]


def _topk_mask_kernel(x_ref, o_ref, *, k: int):
    x = x_ref[...]
    mag = jnp.abs(x.astype(jnp.float32))

    hi0 = jnp.max(mag)
    lo0 = jnp.zeros_like(hi0)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        count = jnp.sum((mag >= mid).astype(jnp.int32))
        # keep the invariant count(>=lo) >= k >= count(>=hi)
        new_lo = jnp.where(count >= k, mid, lo)
        new_hi = jnp.where(count >= k, hi, mid)
        return new_lo, new_hi

    lo, hi = jax.lax.fori_loop(0, _BISECT_ITERS, body, (lo0, hi0))
    o_ref[...] = mag >= lo


def topk_mask_pallas(x: jnp.ndarray, frac: float, *,
                     interpret: bool = True) -> jnp.ndarray:
    """x: flat (N,) -> bool mask keeping ~frac by block-local magnitude.

    N is padded to a BLOCK multiple with zeros (zeros never win a
    magnitude threshold > 0).
    """
    n = x.shape[0]
    xp, nblocks = to_tiles(x)
    k = max(int(BLOCK * frac), 1)

    out = pl.pallas_call(
        functools.partial(_topk_mask_kernel, k=k),
        grid=(nblocks,),
        in_specs=[_TILE],
        out_specs=_TILE,
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.bool_),
        interpret=interpret,
    )(xp)
    return from_tiles(out, n)


# ---------------------------------------------------------------------------
# Global-threshold two-pass variant (exact full-vector semantics)
# ---------------------------------------------------------------------------

def _mag_bits(x: jnp.ndarray) -> jnp.ndarray:
    """|x| as int32 bit patterns: for non-negative finite f32, value order
    and bit-pattern order coincide, so magnitude selection is integer
    selection — exact, no float-epsilon convergence issues."""
    mag = jnp.abs(x.astype(jnp.float32))
    return jax.lax.bitcast_convert_type(mag, jnp.int32)


def _block_max_bits_kernel(x_ref, o_ref):
    o_ref[pl.program_id(0)] = jnp.max(_mag_bits(x_ref[...]))


def _mask_ge_bits_kernel(t_ref, x_ref, o_ref):
    o_ref[...] = _mag_bits(x_ref[...]) >= t_ref[0]


def topk_mask_pallas_global(x: jnp.ndarray, frac: float, *,
                            interpret: bool = True) -> jnp.ndarray:
    """x: flat (N,) -> bool mask with EXACT global top-k semantics: keeps
    every entry whose |x| >= the k-th largest magnitude (ties included),
    k = max(int(N * frac), 1) — bit-identical to the jax.lax.top_k oracle.
    """
    n = x.shape[0]
    # zero padding: bits == 0, and the bisection only counts bits >= mid
    # with mid >= 1, so pads never count
    xp, nblocks = to_tiles(x)
    k = max(int(n * frac), 1)

    # pass 1: per-block maxima of the bit-cast magnitudes
    bmax = pl.pallas_call(
        _block_max_bits_kernel,
        grid=(nblocks,),
        in_specs=[_TILE],
        out_specs=_SMEM,
        out_shape=jax.ShapeDtypeStruct((nblocks,), jnp.int32),
        interpret=interpret,
    )(xp)

    # refine: integer bisection for the largest t with count(bits >= t) >= k.
    # That t is exactly the k-th largest magnitude's bit pattern, so the
    # final mask reproduces the oracle including all ties.
    bits = _mag_bits(xp)
    lo0 = jnp.int32(0)                 # count(>= 0) == N >= k always
    hi0 = jnp.max(bmax) + 1            # count(>= max+1) == 0 < k

    def body(_, carry):
        lo, hi = carry
        mid = lo + (hi - lo) // 2      # >= 1 once hi > lo >= 0
        count = jnp.sum((bits >= mid).astype(jnp.int32))
        new_lo = jnp.where(count >= k, mid, lo)
        new_hi = jnp.where(count >= k, hi, mid)
        return new_lo, new_hi

    t, _ = jax.lax.fori_loop(0, _BIT_ITERS, body, (lo0, hi0))

    # pass 2: one masked compare per block against the global threshold
    out = pl.pallas_call(
        _mask_ge_bits_kernel,
        grid=(nblocks,),
        in_specs=[_SMEM, _TILE],
        out_specs=_TILE,
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.bool_),
        interpret=interpret,
    )(t.reshape(1), xp)
    return from_tiles(out, n)
