"""jit-able wrapper around the flash-attention Pallas kernel.

Handles layout (B,S,H,hd) <-> (B*H,S,hd), GQA head-group index mapping,
padding to block multiples, and backend selection (``repro.kernels.interpret_mode``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .kernel import flash_attention_bhsd


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x
    spec = [(0, 0)] * x.ndim
    spec[axis] = (0, pad)
    return jnp.pad(x, spec)


def _forward(q, k, v, causal, window, block_q, block_k):
    b, sq, h, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    group = h // hkv

    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(8, skv))
    qp = _pad_to(jnp.transpose(q, (0, 2, 1, 3)).reshape(b * h, sq, hd), 1, bq)
    kp = _pad_to(jnp.transpose(k, (0, 2, 1, 3)).reshape(b * hkv, skv, hd), 1, bk)
    vp = _pad_to(jnp.transpose(v, (0, 2, 1, 3)).reshape(b * hkv, skv, hd), 1, bk)

    out = flash_attention_bhsd(
        qp, kp, vp,
        group=group, causal=causal, window=window,
        block_q=bq, block_k=bk,
        sq_valid=sq, skv_valid=skv,
        interpret=interpret_mode(),
    )
    out = out[:, :sq].reshape(b, h, sq, hd)
    return jnp.transpose(out, (0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fa(q, k, v, causal, window, block_q, block_k):
    return _forward(q, k, v, causal, window, block_q, block_k)


def _fa_fwd(q, k, v, causal, window, block_q, block_k):
    return _forward(q, k, v, causal, window, block_q, block_k), (q, k, v)


def _fa_bwd(causal, window, block_q, block_k, res, g):
    """Backward via the pure-jnp oracle's VJP (recompute-from-inputs, the
    flash strategy).  A dedicated backward Pallas kernel is the TPU hot-path
    extension; on the training path this keeps grads exact and memory-safe."""
    from .ref import attention_ref

    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: attention_ref(q, k, v, causal=causal, window=window), q, k, v)
    return vjp(g)


_fa.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k"),
)
def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Skv, Hkv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    return _fa(q, k, v, causal, window, block_q, block_k)
