"""Wrapper for the whole-tile KV write: the (…, B, S, kv, hd) cache the model
keeps, seen in the (L, B, kv, hd, S) order of its TPU layout (on the chip
both transposes are bitcasts), and the kernel's layer block sized."""

from __future__ import annotations

import jax.numpy as jnp

from .. import interpret_mode
from .kernel import TILE, write_blocks_lbkds

BLOCK_BYTES = 2 << 20   # cache bytes per grid step (in and out double-buffered)


def write_kv_blocks(cache, new, slot):
    """``cache`` (…, B, S, kv, hd) with ``new`` (…, B, 1, kv, hd), in the
    cache's dtype, written at per-lane ``slot`` (B,), each in [0, S), by
    rewriting whole aligned 128-position blocks.  Bitwise what a
    one-position write gives."""
    shape = cache.shape
    n_b, _, n_kv, hd = shape[-4:]
    c = jnp.transpose(cache.reshape((-1,) + shape[-4:]), (0, 1, 3, 4, 2))
    n = jnp.transpose(new.reshape((-1, n_b, n_kv, hd)), (0, 1, 3, 2))
    per_layer = n_kv * hd * TILE * cache.dtype.itemsize
    block_l = max(
        d for d in range(1, c.shape[0] + 1)
        if c.shape[0] % d == 0 and (d == 1 or d * per_layer <= BLOCK_BYTES)
    )
    out = write_blocks_lbkds(
        c, n, slot.astype(jnp.int32),
        block_l=block_l, interpret=interpret_mode(),
    )
    return jnp.transpose(out, (0, 1, 4, 2, 3)).reshape(shape)
