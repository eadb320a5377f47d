"""Pallas TPU Mamba2 SSD chunk-scan kernel (long-context hot spot for the
hybrid/ssm architectures).

TPU-native structure: the inter-chunk recurrence is carried in VMEM scratch
across the *sequential* chunk axis of the grid — the TPU grid IS the scan.
Each grid step does three MXU matmuls on one chunk:

  G      = (C B^T) ⊙ exp(segsum(a))          (chunk x chunk, lower-tri)
  y      = G x  +  exp(cumsum a) · (C state^T)
  state' = exp(total) state + x^T (B ⊙ w)    w_j = exp(total - cum_j)

with chunk=128 (MXU-aligned).  No CUDA-style warp tricks are needed: the
parallel-prefix structure maps onto the systolic array as dense per-chunk
matmuls plus an O(1)-state carry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, st_ref, *,
                chunk: int, n_chunks: int):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    x = x_ref[0].astype(jnp.float32)          # (chunk, P)
    a = a_ref[0].astype(jnp.float32)          # (1, chunk)
    B = b_ref[0].astype(jnp.float32)          # (chunk, N)
    C = c_ref[0].astype(jnp.float32)          # (chunk, N)

    # cumulative log-decay as a column and a row, by masked reductions
    # (Mosaic lowers no cumsum and no 1-D <-> 2-D relayout)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = row >= col
    cs_col = jnp.sum(jnp.where(tri, a, 0.0), axis=1, keepdims=True)
    a_col = jnp.sum(jnp.where(row == col, a, 0.0), axis=1, keepdims=True)
    cs_row = jnp.sum(jnp.where(row <= col, a_col, 0.0), axis=0,
                     keepdims=True)           # (1, chunk)
    total = jnp.sum(a, axis=1, keepdims=True)  # (1, 1)
    # intra-chunk: G[i,j] = C_i·B_j * exp(cs_i - cs_j) for j <= i
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    G = jnp.where(tri, scores * jnp.exp(cs_col - cs_row), 0.0)
    y = jax.lax.dot_general(G, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk: contribution of the carried state
    st = st_ref[...]                          # (P, N)
    y += jnp.exp(cs_col) * jax.lax.dot_general(
        C, st, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)
    # state update
    w = jnp.exp(total - cs_col) * B           # (chunk, N)
    st_ref[...] = (jnp.exp(total) * st
                   + jax.lax.dot_general(x, w, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32))


def ssd_scan_kernel(x: jax.Array, a: jax.Array, B: jax.Array,
                    C: jax.Array, *, chunk: int = 128,
                    interpret: bool = False
                    ) -> tuple[jax.Array, None]:
    """x: (b, l, h, p); a: (b, l, h); B/C: (b, l, n) -> y: (b, l, h, p).

    The (batch, head) pairs become grid rows; B/C are shared across heads
    via the index_map (no H-fold duplication in HBM).
    """
    b, l, h, p = x.shape
    n = B.shape[-1]
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    lp = l + pad
    n_chunks = lp // chunk

    xr = x.transpose(0, 2, 1, 3).reshape(b * h, lp, p)
    # (b*h, 1, L): a (1, chunk) block is legal on the TPU (the last two
    # block dims must be divisible by (8, 128) or span the array)
    ar = a.transpose(0, 2, 1).reshape(b * h, 1, lp)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    y = pl.pallas_call(
        kernel,
        grid=(b * h, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, c: (bh, 0, c)),
            pl.BlockSpec((1, chunk, n), lambda bh, c, h=h: (bh // h, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, c, h=h: (bh // h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda bh, c: (bh, c, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, lp, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xr, ar, B, C)
    y = y.reshape(b, h, lp, p).transpose(0, 2, 1, 3)[:, :l]
    return y, None
