"""Decode KV write of whole tiles: the Pallas TPU kernel.

Where the head size does not fill a tile's 128 lanes, the TPU compiler lays
a (L, B, S, kv, hd) cache out position-minor: its bytes are those of a
row-major (L, B, kv, hd, S) array tiled (16, 128) over (hd, S).  A decode
step's one new position per lane is then one lane column of every tile, and
XLA writes those columns one partial tile at a time.  This kernel takes the
cache in that (L, B, kv, hd, S) order, aliased to its output, and for each
lane rewrites the aligned 128-position block that holds the lane's slot:
every HBM access is a whole tile, and the block comes back unchanged but
for the slot's column.

  * grid = (B, L / block_l): lane, then a run of layers.  The cache block
    (block_l, 1, kv, hd, 128) sits at block ``slot // 128`` of the lane,
    from the scalar-prefetched slots.
  * the new values come in as (L, B, hd, kv): a head's hd values run down
    the sublanes, as the cache's do, so one lane of them broadcast along
    the 128 lanes is that head's new column.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128  # positions in a tile's lane dimension


def _write_kernel(slot_ref, new_ref, cache_ref, out_ref, *, block_l: int, n_kv: int):
    col = slot_ref[pl.program_id(0)] % TILE
    hd = cache_ref.shape[-2]
    at = jax.lax.broadcasted_iota(jnp.int32, (hd, TILE), 1) == col
    for l in range(block_l):
        new = new_ref[l, 0]                                       # (hd, kv)
        for h in range(n_kv):
            out_ref[l, 0, h] = jnp.where(at, new[:, h:h + 1], cache_ref[l, 0, h])


def write_blocks_lbkds(
    cache: jax.Array,   # (L, B, kv, hd, S), S a multiple of 128
    new: jax.Array,     # (L, B, hd, kv), the cache's dtype
    slot: jax.Array,    # (B,) int32, each in [0, S)
    *,
    block_l: int,
    interpret: bool,
) -> jax.Array:
    n_l, n_b, n_kv, hd, s = cache.shape
    assert s % TILE == 0 and n_l % block_l == 0, (cache.shape, block_l)
    block = (block_l, 1, n_kv, hd, TILE)
    cache_spec = pl.BlockSpec(block, lambda b, l, slot: (l, b, 0, 0, slot[b] // TILE))
    return pl.pallas_call(
        functools.partial(_write_kernel, block_l=block_l, n_kv=n_kv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_b, n_l // block_l),
            in_specs=[
                pl.BlockSpec((block_l, 1, hd, n_kv), lambda b, l, slot: (l, b, 0, 0)),
                cache_spec,
            ],
            out_specs=cache_spec,
        ),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},
        name="kv_block_write",
        interpret=interpret,
    )(slot, new, cache)
