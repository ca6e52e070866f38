"""jit wrapper for the RG-LRU linear-scan kernel (padding + backend select)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .kernel import linear_scan_bsw


@functools.partial(jax.jit, static_argnames=("block_s", "block_w"))
def linear_scan(
    a: jax.Array,
    b: jax.Array,
    h0: jax.Array,
    *,
    block_s: int = 256,
    block_w: int = 128,
) -> jax.Array:
    """h_t = a_t h_{t-1} + b_t.  a/b: (B, S, W); h0: (B, W) -> (B, S, W) f32."""
    bsz, s, w = a.shape
    bs = min(block_s, s)
    bw = min(block_w, w)
    pad_s = (-s) % bs
    pad_w = (-w) % bw
    if pad_s or pad_w:
        # a=1, b=0 padding keeps the recurrence identity on padded steps
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_w)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad_s), (0, pad_w)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_w)))
    out = linear_scan_bsw(
        a.astype(jnp.float32), b.astype(jnp.float32),
        h0.astype(jnp.float32)[:, None, :],
        block_s=bs, block_w=bw, interpret=interpret_mode(),
    )
    return out[:, :s, :w]
