"""Flash-attention Pallas TPU kernel: causal/windowed GQA with online softmax.

TPU adaptation of the Flash-Attention recurrence (the paper's algorithm is a
GPU shared-memory design; here the blocking is driven by VMEM and the MXU):

  * grid = (B*H, n_q_blocks, n_kv_blocks); the *last* grid dim is the
    innermost sequential loop on TPU, so the running (m, l, acc) softmax state
    for one (head, q-block) lives in VMEM scratch across kv steps — the role
    a GPU kernel gives to registers/shared memory.
  * BlockSpecs tile q/out as (1, block_q, hd) and k/v as (1, block_k, hd) —
    block_q/block_k default to 128, matching the 128x128 MXU systolic tile
    and the (8,128) VREG lane layout.
  * GQA is handled by *index maps*: the kv BlockSpec maps q-head bh to kv head
    bh // group — no materialised repeat of K/V in HBM.
  * Fully-masked blocks are skipped with pl.when (the index space is still
    visited; on real hardware the skipped iterations cost only the grid
    bookkeeping since their DMAs are elided by Mosaic when the block is
    unused... conservatively we still fetch; a production variant would prune
    the grid).

Validated in interpret mode against ref.attention_ref over a shape/dtype
sweep (tests/test_kernels.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(
    q_ref, k_ref, v_ref,  # inputs
    o_ref,                # output
    m_scr, l_scr, acc_scr,  # scratch: (block_q,), (block_q,), (block_q, hd)
    *,
    scale: float,
    block_q: int,
    block_k: int,
    n_kv_blocks: int,
    causal: bool,
    window: int,
    sq_valid: int,
    skv_valid: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level reachability (static per grid point at trace time via when)
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1

    def body():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                  # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = (q_pos < sq_valid) & (k_pos < skv_valid)
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(jnp.where(m_prev > NEG_INF / 2, m_prev - m_new, NEG_INF))
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    if causal:
        # causal reachability depends only on static block ids when the grid
        # is not pruned — use a dynamic predicate (works in both modes)
        pl.when(k_start <= q_start + block_q - 1)(body)
    else:
        body()

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,  # (BH, Sq, hd)  — q heads flattened
    k: jax.Array,  # (BHkv, Skv, hd)
    v: jax.Array,
    *,
    group: int,
    causal: bool,
    window: int,
    block_q: int = 128,
    block_k: int = 128,
    sq_valid: int | None = None,
    skv_valid: int | None = None,
    interpret: bool,
) -> jax.Array:
    bh, sq, hd = q.shape
    skv = k.shape[1]
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv, block_q, block_k)
    n_q = sq // block_q
    n_kv = skv // block_k
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _fa_kernel,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        n_kv_blocks=n_kv,
        causal=causal,
        window=window,
        sq_valid=sq if sq_valid is None else sq_valid,
        skv_valid=skv if skv_valid is None else skv_valid,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki, _g=group: (b // _g, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, qi, ki, _g=group: (b // _g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
