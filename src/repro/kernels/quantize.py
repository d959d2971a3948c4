"""Pallas TPU kernels: per-row absmax int8 quantization — the transport
codec for the federation's flat D-delta rows.

A transported cohort block is a stack of per-user rows ``(R, N)``; each
row gets ONE float32 scale (``absmax / 127``) and its values travel as
int8.  That is the standard communication-compression shape (QSGD-style
uniform quantization with a per-row scale): 4 bytes/coordinate -> 1, at
a quantization error the error-feedback residual re-injects next round.

Two passes, mirroring ``topk_select``'s reduce-then-map structure:

  pass 1 (Pallas) — per-(row, block) absmax partials;
  reduce (XLA)    — per-row absmax -> ``scale`` and its safe reciprocal
                    (touches only ``(R, nblocks)`` scalars);
  pass 2 (Pallas) — ``clip(round(x * inv), -127, 127)`` per block, int8.

Rounding is deterministic (``jnp.round``) by default; the stochastic
variant replaces it with ``floor(y) + (u < frac(y))`` where ``u`` is a
counter-based uniform hash of (row, column, seed) — unbiased
(E[q] = y) and bit-reproducible across kernel and oracle, which share
``_hash_u01``.  Zero padding is safe end to end: a zero block absmax
never wins the row reduce, quantizes to 0, and dequantizes to 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk_select import (BLOCK, LANES, SUBLANES, from_tiles,
                                       to_tiles)

# A row's BLOCK-long column slice is one (8, 1024) tile of the
# (R, nblocks * 8, 1024) view, so every block meets the TPU's (8, 128)
# rule; the row axis is squeezed out of the kernel's view.
_TILE = pl.BlockSpec((None, SUBLANES, LANES), lambda i, j: (i, j, 0))
# Per-row scalars (scale, reciprocal, block maxima) and the seed live
# whole in SMEM and are indexed by grid position.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _hash_u01(row, col, seed):
    """Counter-based uniform hash -> [0, 1): xorshift-multiply mix of the
    (row, column, seed) triple.  Pure uint32 lane arithmetic (no PRNG
    state), so the kernel and the jnp oracle produce identical streams."""
    h = (col.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         + row.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         + seed.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    # top 24 bits -> exact f32 in [0, 1); Mosaic has no uint32 -> f32 cast
    top = jax.lax.bitcast_convert_type(h >> 8, jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(1.0 / 16777216.0)


def _row_block_absmax_kernel(x_ref, o_ref, *, nblocks: int):
    r, b = pl.program_id(0), pl.program_id(1)
    o_ref[r * nblocks + b] = jnp.max(jnp.abs(x_ref[...]))


def _quantize_kernel(inv_ref, x_ref, o_ref):
    y = x_ref[...] * inv_ref[pl.program_id(0)]
    o_ref[...] = jnp.clip(jnp.round(y), -127.0, 127.0).astype(jnp.int8)


def _quantize_sr_kernel(inv_ref, seed_ref, x_ref, o_ref):
    r, b = pl.program_id(0), pl.program_id(1)
    y = jnp.clip(x_ref[...] * inv_ref[r], -127.0, 127.0)
    f = jnp.floor(y)
    col = (b * BLOCK
           + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, y.shape, 1))
    row = jnp.full(y.shape, r, jnp.int32)
    u = _hash_u01(row, col, seed_ref[0])
    q = f + (u < (y - f)).astype(jnp.float32)
    o_ref[...] = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)


def _dequantize_kernel(scale_ref, q_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * scale_ref[pl.program_id(0)]


def quantize_rows_pallas(x: jnp.ndarray, *, stochastic: bool = False,
                         seed=None, interpret: bool = True):
    """x: (R, N) f32 -> (q int8 (R, N), scale f32 (R,)) with
    ``scale[r] = max|x[r]| / 127`` and ``q = clip(round(x / scale))``.
    ``seed`` (int32 scalar, traced) drives the stochastic rounding hash
    and is required iff ``stochastic``."""
    assert x.ndim == 2, f"quantize_rows wants stacked rows, got {x.shape}"
    r, n = x.shape
    xp, nblocks = to_tiles(x.astype(jnp.float32))

    bmax = pl.pallas_call(
        functools.partial(_row_block_absmax_kernel, nblocks=nblocks),
        grid=(r, nblocks),
        in_specs=[_TILE],
        out_specs=_SMEM,
        out_shape=jax.ShapeDtypeStruct((r * nblocks,), jnp.float32),
        interpret=interpret,
    )(xp)

    scale = jnp.max(bmax.reshape(r, nblocks), axis=1) / jnp.float32(127.0)
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0).astype(jnp.float32)

    if stochastic:
        assert seed is not None, "stochastic rounding needs a seed"
        q = pl.pallas_call(
            _quantize_sr_kernel,
            grid=(r, nblocks),
            in_specs=[_SMEM, _SMEM, _TILE],
            out_specs=_TILE,
            out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.int8),
            interpret=interpret,
        )(inv, jnp.asarray(seed, jnp.int32).reshape(1), xp)
    else:
        q = pl.pallas_call(
            _quantize_kernel,
            grid=(r, nblocks),
            in_specs=[_SMEM, _TILE],
            out_specs=_TILE,
            out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.int8),
            interpret=interpret,
        )(inv, xp)
    return from_tiles(q, n), scale


def dequantize_rows_pallas(q: jnp.ndarray, scale: jnp.ndarray, *,
                           interpret: bool = True) -> jnp.ndarray:
    """(q int8 (R, N), scale f32 (R,)) -> f32 (R, N): ``q * scale[r]``."""
    assert q.ndim == 2, f"dequantize_rows wants stacked rows, got {q.shape}"
    r, n = q.shape
    qp, nblocks = to_tiles(q)
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(r, nblocks),
        in_specs=[_SMEM, _TILE],
        out_specs=_TILE,
        out_shape=jax.ShapeDtypeStruct(qp.shape, jnp.float32),
        interpret=interpret,
    )(scale.astype(jnp.float32), qp)
    return from_tiles(out, n)
