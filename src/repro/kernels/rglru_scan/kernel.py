"""RG-LRU linear-scan Pallas TPU kernel.

The recurrence h_t = a_t h_{t-1} + b_t is bandwidth-bound (3 streams in, one
out, O(1) FLOPs/byte), so the kernel's job on TPU is purely to keep the
recurrent state resident in VMEM while streaming (a, b) tiles HBM->VMEM:

  * grid = (B, n_w_blocks, n_s_blocks); the sequence dimension is the
    innermost (sequential) grid axis, so the (1, block_w) state row carries
    across sequence tiles in VMEM scratch.
  * within a tile, a ``fori_loop`` walks block_s steps of the recurrence on
    the VPU; each step is an (8,128)-lane fused multiply-add.
  * channel blocks (block_w = 128 lanes by default) are independent, giving
    the second parallel grid axis.
  * ``h0`` is (B, 1, W), so its block's last two dims are (1, block_w): a
    whole unit axis and a lane-aligned width, as Mosaic requires.

Contrast with the GPU formulation (warp-parallel Blelloch scan): on TPU the
sequential-grid + VMEM-carry pattern is both simpler and optimal once the
kernel is bandwidth-bound; the log-depth tree adds no speedup when a single
pass already saturates HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(a_ref, b_ref, h0_ref, o_ref, h_scr, *, block_s: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    def step(t, h):
        a_t = a_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        b_t = b_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        h = a_t * h + b_t
        o_ref[0, pl.ds(t, 1), :] = h.astype(o_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_s, step, h_scr[...])


def linear_scan_bsw(
    a: jax.Array,   # (B, S, W) fp32
    b: jax.Array,   # (B, S, W)
    h0: jax.Array,  # (B, 1, W)
    *,
    block_s: int = 256,
    block_w: int = 128,
    interpret: bool,
) -> jax.Array:
    bsz, s, w = a.shape
    block_s = min(block_s, s)
    block_w = min(block_w, w)
    assert s % block_s == 0 and w % block_w == 0, (s, w, block_s, block_w)
    grid = (bsz, w // block_w, s // block_s)
    return pl.pallas_call(
        functools.partial(_scan_kernel, block_s=block_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_w), lambda b, wi, si: (b, si, wi)),
            pl.BlockSpec((1, block_s, block_w), lambda b, wi, si: (b, si, wi)),
            pl.BlockSpec((1, 1, block_w), lambda b, wi, si: (b, 0, wi)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_w), lambda b, wi, si: (b, si, wi)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
    )(a, b, h0)
