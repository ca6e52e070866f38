"""SSD intra-chunk Pallas TPU kernel (Mamba-2 diagonal-block term).

Per (batch, chunk, head) grid cell, entirely in VMEM:

    cum    = cumsum(dA)                       (L,)
    L_mat  = tril(exp(cum_l - cum_s))         (L, L)
    scores = (C B^T) * L_mat                  (L, L)   — one MXU matmul
    Y      = scores @ X                       (L, P)   — one MXU matmul

The head axis sits ahead of (L, P) and dA carries a unit axis ahead of L, so
the last two dims of every block are either (8, 128)-tileable or whole array
dims, as Mosaic requires.  Mosaic has no cumsum, so both orientations of the
running sum come from triangular matmuls at full f32 precision.

With the default chunk L=128 and head dim P=64/128, all five tiles
(C: LxN, B: LxN, X: LxP, scores: LxL, Y: LxP) fit comfortably in VMEM
(< 512 KB at N=P=128 fp32) and both matmuls are 128-aligned for the MXU.
This is the compute-dense half of SSD; the inter-chunk recurrence stays in
XLA as a lax.scan (bandwidth-trivial: one (H,P,N) state per chunk).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HIGHEST = jax.lax.Precision.HIGHEST


def _ssd_kernel(x_ref, da_ref, b_ref, c_ref, o_ref):
    da = da_ref[0, 0, 0].astype(jnp.float32)                # (1, L)
    x = x_ref[0, 0, 0].astype(jnp.float32)                  # (L, P)
    bm = b_ref[0, 0].astype(jnp.float32)                    # (L, N)
    cm = c_ref[0, 0].astype(jnp.float32)                    # (L, N)
    l = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    tri = row >= col
    # cum_l as a column (L, 1) and cum_s as a row (1, L)
    cum_col = jax.lax.dot_general(
        tri.astype(jnp.float32), da, (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )
    cum_row = jax.lax.dot_general(
        da, (row <= col).astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )
    decay = jnp.where(tri, jnp.exp(cum_col - cum_row), 0.0)
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * decay
    y = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[0, 0, 0] = y.astype(o_ref.dtype)


def ssd_intra_bchlp(xc, dac, bc, cc, *, interpret: bool):
    """xc: (B,nc,H,L,P); dac: (B,H,nc,1,L); bc/cc: (B,nc,L,N) -> (B,nc,H,L,P)."""
    bsz, nc, h, l, p = xc.shape
    n = bc.shape[-1]
    return pl.pallas_call(
        _ssd_kernel,
        grid=(bsz, nc, h),
        in_specs=[
            pl.BlockSpec((1, 1, 1, l, p), lambda b, c, hh: (b, c, hh, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, l), lambda b, c, hh: (b, hh, c, 0, 0)),
            pl.BlockSpec((1, 1, l, n), lambda b, c, hh: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, l, n), lambda b, c, hh: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, l, p), lambda b, c, hh: (b, c, hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, nc, h, l, p), jnp.float32),
        interpret=interpret,
    )(xc, dac, bc, cc)
