"""Pallas TPU kernel: Mamba-2 SSD chunked scan.

The GPU reference (mamba_ssm) is a warp-level associative scan; the TPU
adaptation keeps SSD's *chunked dual form* so nearly all work is dense
matmuls on the MXU:

  per (batch*head, chunk) grid cell, with the chunk tile in VMEM:
    intra-chunk:  (C B^T ∘ L) @ (x·dt)       — (cl x cl) @ (cl x P)
    state update: S += B^T-decay-weighted x  — (N x cl) @ (cl x P)
    inter-chunk:  C @ S_prev                 — (cl x N) @ (N x P)

The inter-chunk recurrence is carried in VMEM scratch across the
sequential last grid dimension (chunks), exactly where a GPU would
round-trip to HBM between kernel launches.

Layout: inputs are pre-arranged to (BH, S, *) head-major in ops.py; the
B/C group expansion happens there too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, state_scr, *,
                chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)          # (cl, P)
    dt = dt_ref[0].astype(jnp.float32)        # (cl, 1)
    a = a_ref[pl.program_id(0)]               # scalar decay rate (negative)
    bmat = b_ref[0].astype(jnp.float32)       # (cl, N)
    cmat = c_ref[0].astype(jnp.float32)       # (cl, N)

    dA = dt * a                               # (cl, 1), negative
    xdt = x * dt                              # (cl, P)

    # prefix sums as masked reductions (Mosaic has no cumsum):
    # cum[i] = sum_{j<=i} dA[j], as a column (cl, 1) and a row (1, cl)
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dA_i = jnp.broadcast_to(dA, (chunk, chunk))       # [i, j] = dA[i]
    cum = jnp.sum(jnp.where(li >= lj, dA_i.T, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(li <= lj, dA_i, 0.0), axis=0, keepdims=True)
    total = jnp.sum(dA)

    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for i >= j
    L = jnp.where(li >= lj, jnp.exp(cum - cum_row), 0.0)
    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(scores * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: C_i exp(cum_i) @ S_prev
    state = state_scr[...]                    # (N, P)
    y += jax.lax.dot_general(cmat * jnp.exp(cum), state,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)

    # state update: S_new = exp(total) S_prev + B^T-weighted inputs
    decay_to_end = jnp.exp(total - cum)       # (cl, 1)
    bw = bmat * decay_to_end
    s_chunk = jax.lax.dot_general(bw, xdt, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    state_scr[...] = jnp.exp(total) * state + s_chunk

    o_ref[0] = y.astype(o_ref.dtype)


def ssd_scan_pallas(x, dt, A, Bm, Cm, *, chunk: int = 256,
                    interpret: bool = True):
    """x: (B,S,H,P), dt: (B,S,H), A: (H,), Bm/Cm: (B,S,G,N) -> y (B,S,H,P).

    Head-major re-layout + group->head expansion happen here (the ops.py
    wrapper jit-fuses them with neighbours).
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    rep = H // G

    BH = Bsz * H
    xt = jnp.moveaxis(x, 2, 1).reshape(BH, S, P)
    dtt = jnp.moveaxis(dt, 2, 1).reshape(BH, S, 1)
    bh = jnp.moveaxis(jnp.repeat(Bm, rep, axis=2), 2, 1).reshape(BH, S, N)
    ch = jnp.moveaxis(jnp.repeat(Cm, rep, axis=2), 2, 1).reshape(BH, S, N)
    a_rates = jnp.tile(A.astype(jnp.float32), (Bsz,))   # (BH,), in SMEM

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, c: (i, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, N), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda i, c: (i, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda i, c: (i, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, a_rates, bh, ch)

    return jnp.moveaxis(out.reshape(Bsz, H, S, P), 1, 2)
