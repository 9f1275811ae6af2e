"""Mamba-2 SSD intra-chunk kernel (matmul form) for TPU via Pallas.

Computes, for one (batch, chunk, head) grid cell with chunk length Q,
state size N, head dim P:

  y_diag[q]    = sum_{j<=q} (C_q . B_j) * exp(cumsum dA (j, q]) * xdt_j
  chunk_state  = sum_j B_j ^T (xdt_j * exp(total - cum_j))   -> (N, P)
  chunk_decay  = exp(total dA)

The inter-chunk state recurrence (tiny: (H,N,P) per step) stays in a
lax.scan outside the kernel — it is latency-bound, not compute-bound,
while everything here is MXU matmuls over (Q x N)/(Q x Q)/(Q x P) tiles.

TPU adaptation notes: Mosaic has no cumsum, so the in-chunk prefix sums
of dA are lower-triangular masked reductions over a (Q, Q) broadcast of
dA, taken once per layout (column for rows, row for columns) so no
in-kernel transpose is needed; masks are 2D broadcasted_iota (no 1D iota
on TPU); all accumulation in f32; tiles sized so Q, N, P are 128-ish
multiples (mamba2-780m: Q=256, N=128, P=64 -> all MXU-aligned).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_chunk_kernel(cb_ref, x_ref, dt_ref, da_ref, da_row_ref, y_ref,
                      state_ref, decay_ref, *, chunk: int):
    """Refs (blocks for one (b, c, h) cell):
      cb:    C (chunk, N), B (chunk, N) stacked -> (2, chunk, N)
      x:     (chunk, P)
      dt:    (chunk, 1) f32
      da:    (chunk, 1) f32   (dt * A, log-decay per step)
      da_row: (1, chunk) f32  (the same values laid out as a row)
      out y: (chunk, P)
      out state: (N, P)
      out decay: (1, 1)
    """
    C = cb_ref[0, 0, 0].astype(jnp.float32)            # (Q, N)
    B = cb_ref[0, 0, 1].astype(jnp.float32)            # (Q, N)
    x = x_ref[0, 0, 0].astype(jnp.float32)             # (Q, P)
    dt = dt_ref[0, 0, 0]                               # (Q, 1)
    da = da_ref[0, 0, 0]                               # (Q, 1)
    da_row = da_row_ref[0, 0, 0]                       # (1, Q)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = ii >= jj
    # inclusive prefix sums of dA: cum_i = sum_{j <= i} da_j
    cum = jnp.sum(jnp.where(lower, da_row, 0.0), axis=1, keepdims=True)  # (Q, 1)
    cum_row = jnp.sum(jnp.where(ii <= jj, da, 0.0), axis=0,
                      keepdims=True)                                     # (1, Q)
    total = jnp.sum(da, axis=0, keepdims=True)                           # (1, 1)

    xdt = x * dt                                       # (Q, P)
    # L[i, j] = exp(cum_i - cum_j) for i >= j else 0
    L = jnp.where(lower, jnp.exp(cum - cum_row), 0.0)  # (Q, Q)

    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q, Q)
    y = jax.lax.dot_general(scores * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # (Q, P)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    decay_to_end = jnp.exp(total - cum)                # (Q, 1)
    state = jax.lax.dot_general(B, xdt * decay_to_end,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)   # (N, P)
    state_ref[0, 0, 0] = state.astype(state_ref.dtype)
    decay_ref[0, 0, 0] = jnp.exp(total).astype(decay_ref.dtype)


def ssd_chunk_fwd(C: jax.Array, B: jax.Array, x: jax.Array, dt: jax.Array,
                  da: jax.Array, *, interpret: bool = False):
    """Intra-chunk SSD via Pallas.

    C, B: (b, nc, Q, N); x: (b, nc, Q, H, P); dt, da: (b, nc, Q, H)
    Returns y_diag (b, nc, Q, H, P), states (b, nc, H, N, P),
            decays (b, nc, H).
    """
    b, nc, Q, N = C.shape
    H, P = x.shape[3], x.shape[4]

    cb = jnp.stack([C, B], axis=2)                    # (b, nc, 2, Q, N)
    xt = x.transpose(0, 1, 3, 2, 4)                   # (b, nc, H, Q, P)
    dtt = dt.transpose(0, 1, 3, 2)[..., None].astype(jnp.float32)
    dat = da.transpose(0, 1, 3, 2)[..., None].astype(jnp.float32)
    dar = da.transpose(0, 1, 3, 2)[..., None, :].astype(jnp.float32)

    grid = (b * nc, H)
    kernel = functools.partial(_ssd_chunk_kernel, chunk=Q)
    y, states, decays = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 2, Q, N), lambda bc, h: (bc // nc, bc % nc, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, P), lambda bc, h: (bc // nc, bc % nc, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda bc, h: (bc // nc, bc % nc, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda bc, h: (bc // nc, bc % nc, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q), lambda bc, h: (bc // nc, bc % nc, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda bc, h: (bc // nc, bc % nc, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, N, P), lambda bc, h: (bc // nc, bc % nc, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, 1), lambda bc, h: (bc // nc, bc % nc, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, H, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, H, N, P), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, H, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(cb, xt, dtt, dat, dar)
    return (y.transpose(0, 1, 3, 2, 4),               # (b, nc, Q, H, P)
            states,                                   # (b, nc, H, N, P)
            decays[..., 0, 0])                        # (b, nc, H)
