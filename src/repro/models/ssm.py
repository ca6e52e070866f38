"""Mamba-2 SSD (state-space duality) blocks, arXiv:2405.21060.

The SSD layer computes, per head h with state size N and head dim P:

    s_t = exp(dt_t * A_h) * s_{t-1} + dt_t * B_t x_t^T        (s: (N, P))
    y_t = C_t^T s_t + D_h x_t

The chunked algorithm (paper Listing 1) splits the sequence into chunks of
length L: a quadratic *intra-chunk* term (masked decay matmul — MXU-friendly)
plus a linear *inter-chunk* state recurrence.  The paper's listing makes the
inter-chunk pass a (nc x nc) matmul, quadratic in chunk count — unusable at
500k tokens; we replace it with a ``lax.scan`` over chunks (linear, and the
natural TPU formulation).  The intra-chunk term is also available as a Pallas
kernel (repro/kernels/ssd_scan).

Shapes follow the Mamba-2 convention: X (B,S,H,P), dt (B,S,H), A (H,) < 0,
B/C (B,S,G,N) with G head-groups broadcast over H (G=1 here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import ParamBuilder, rmsnorm
from .sharding import shard


def declare_ssd(pb: ParamBuilder, prefix: str, cfg, stack: int = 0):
    lead = (stack,) if stack else ()
    lax = ("layers",) if stack else ()
    d, di, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_ch = di + 2 * n  # conv over [x, B, C]
    pb.declare(f"{prefix}/in_proj", lead + (d, 2 * di + 2 * n + h), lax + ("fsdp", "mlp"))
    pb.declare(f"{prefix}/conv_w", lead + (cfg.conv_width, conv_ch), lax + (None, None))
    pb.declare(f"{prefix}/conv_b", lead + (conv_ch,), lax + (None,), init="zeros")
    pb.declare(f"{prefix}/a_log", lead + (h,), lax + (None,), init="ssm_a")
    pb.declare(f"{prefix}/d_skip", lead + (h,), lax + (None,), init="ones")
    pb.declare(f"{prefix}/dt_bias", lead + (h,), lax + (None,), init="dt_bias")
    pb.declare(f"{prefix}/norm_w", lead + (di,), lax + (None,), init="zeros")
    pb.declare(f"{prefix}/out_proj", lead + (di, d), lax + ("mlp", "fsdp"))


def _segsum(x: jax.Array) -> jax.Array:
    """x: (..., L) -> (..., L, L) cumulative segment sums, -inf above diag."""
    l = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    chunk: int = 128,
    s0: jax.Array | None = None,
    intra_impl: str = "jnp",
):
    """SSD scan.  x: (B,S,H,P) already dt-weighted is NOT expected — raw x.

    dt: (B,S,H) post-softplus; a: (H,) negative; b/c: (B,S,N) (G=1, broadcast
    over heads).  Returns (y (B,S,H,P), s_last (B,H,P,N))."""
    bs, s, h, p = x.shape
    n = b.shape[-1]
    l = min(chunk, s)
    if s % l:
        pad = l - s % l
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    s_pad = x.shape[1]
    nc = s_pad // l

    xd = (x * dt[..., None]).astype(jnp.float32)                    # dt-weighted input
    da = (dt.astype(jnp.float32) * a.astype(jnp.float32))           # (B,S,H)

    # chunk views
    xc = xd.reshape(bs, nc, l, h, p)
    dac = jnp.transpose(da.reshape(bs, nc, l, h), (0, 3, 1, 2))     # (B,H,nc,L)
    bc = b.reshape(bs, nc, l, n).astype(jnp.float32)
    cc = c.reshape(bs, nc, l, n).astype(jnp.float32)
    da_cs = jnp.cumsum(dac, axis=-1)                                 # (B,H,nc,L)

    # 1) intra-chunk (diagonal blocks) — the SSD Pallas kernel region: the
    # (L,L) decay matrix and chunk-local scores stay in VMEM on TPU
    with jax.named_scope("ssd_kernel_region"):
        if intra_impl == "pallas":
            from repro.kernels.ssd_scan import ops as ssd_ops

            y_diag = ssd_ops.ssd_intra(xc, dac, bc, cc)
        else:
            lmat = jnp.exp(_segsum(dac))                            # (B,H,nc,L,L)
            y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", cc, bc, lmat, xc)

        # 2) per-chunk input->state contribution
        decay_states = jnp.exp(da_cs[..., -1:] - da_cs)              # (B,H,nc,L)
        states = jnp.einsum("bcln,bhcl,bclhp->bchpn", bc, decay_states, xc)

    # 3) inter-chunk recurrence — lax.scan (linear in nc, vs paper's nc^2 matmul)
    chunk_decay = jnp.exp(da_cs[..., -1])                            # (B,H,nc)

    def step(s_prev, inp):
        st, dec = inp                                                # (B,H,P,N), (B,H)
        s_in = s_prev
        s_new = dec[..., None, None] * s_prev + st
        return s_new, s_in

    init = jnp.zeros((bs, h, p, n), jnp.float32) if s0 is None else s0.astype(jnp.float32)
    s_last, s_in = jax.lax.scan(
        step,
        init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 2, 0)),
    )
    s_enter = jnp.moveaxis(s_in, 0, 1)                               # (B,nc,H,P,N)

    # 4) state -> output within each chunk (same kernel family)
    with jax.named_scope("ssd_kernel_region"):
        out_decay = jnp.exp(da_cs)                                   # (B,H,nc,L)
        y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", cc, s_enter, out_decay)

    y = (y_diag + y_off).reshape(bs, s_pad, h, p)[:, :s]
    return y.astype(x.dtype), s_last


def ssd_step(x_t, dt_t, a, b_t, c_t, s_prev, put=None):
    """One decode step.  x_t: (B,H,P); dt_t: (B,H); b_t/c_t: (B,N);
    s_prev: (B,H,P,N) fp32 -> (y (B,H,P), s_new).

    ``put``, where given, stores ``s_new`` and returns it read back from
    where it was stored, and the readout ``C·s_new`` reads that.  A caller
    that keeps the state in a stacked buffer updated in place (the decode
    layer scan) then writes the new state straight into it: read from
    ``s_new`` itself, the compiler computes the state beside the readout
    into a temporary and copies that into the buffer."""
    da = jnp.exp(dt_t.astype(jnp.float32) * a.astype(jnp.float32))   # (B,H)
    inp = jnp.einsum("bhp,bn->bhpn", (x_t * dt_t[..., None]).astype(jnp.float32), b_t.astype(jnp.float32))
    s_new = da[..., None, None] * s_prev + inp
    if put is not None:
        s_new = put(s_new)
    y = jnp.einsum("bhpn,bn->bhp", s_new, c_t.astype(jnp.float32))
    return y.astype(x_t.dtype), s_new


def _split_proj(cfg, proj):
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    z, xin, b, c, dt = jnp.split(proj, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    return z, xin, b, c, dt


def ssd_block(params: dict, x: jax.Array, cfg, *, intra_impl: str = "jnp", lengths=None):
    """Full Mamba-2 block, train/prefill.  x: (B,S,D) -> (y, state).

    ``lengths`` (B,) marks right-padded rows (packed prefill): ``dt`` is 0
    at each row's pad positions, so ``exp(0)`` decay and zero input carry
    the state through them unchanged, and the conv tail is gathered at the
    row's true length.  The state returned is then each row's at its true
    length; a row of length 0 keeps zero state."""
    bsz, s, _ = x.shape
    di, n, h, p = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    proj = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    proj = shard(proj, "batch", None, "mlp")
    z, xin, b, c, dt_raw = _split_proj(cfg, proj)

    conv_in = jnp.concatenate([xin, b, c], axis=-1)
    from .rglru import causal_conv1d  # same depthwise causal conv

    conv = jax.nn.silu(
        causal_conv1d(conv_in, params["conv_w"], params["conv_b"]).astype(jnp.float32)
    ).astype(x.dtype)
    xin, b, c = conv[..., :di], conv[..., di : di + n], conv[..., di + n :]

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    if lengths is not None:
        valid = jnp.arange(s)[None, :] < lengths[:, None]                # (B, S)
        dt = jnp.where(valid[..., None], dt, 0.0)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    xh = xin.reshape(bsz, s, h, p)
    y, s_last = ssd_chunked(xh, dt, a, b, c, chunk=cfg.ssm_chunk, intra_impl=intra_impl)
    y = y + params["d_skip"].astype(jnp.float32)[None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(bsz, s, di).astype(x.dtype)

    # gated RMSNorm then out-projection
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(z.dtype), params["norm_w"],
                cfg.norm_eps or 1e-6)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"])
    k = params["conv_w"].shape[0]
    if lengths is None:
        conv_tail = conv_in[:, -(k - 1) :, :] if s >= k - 1 else jnp.pad(conv_in, ((0, 0), (k - 1 - s, 0), (0, 0)))
    else:
        # the last k-1 inputs before each row's true length; padded position
        # j of ``lead`` is input j - (k-1), zeros before the row starts
        lead = jnp.pad(conv_in, ((0, 0), (k - 1, 0), (0, 0)))
        idx = lengths[:, None] + jnp.arange(k - 1)[None, :]              # (B, K-1)
        conv_tail = jnp.take_along_axis(lead, idx[..., None], axis=1)
    return shard(out, "batch", "seq", "embed"), (s_last, conv_tail)


def ssd_block_step(params: dict, x_t: jax.Array, state, cfg, put=None):
    """Decode step.  x_t: (B,1,D); state = (s (B,H,P,N) fp32, conv (B,K-1,C));
    ``put`` as in ``ssd_step``."""
    s_prev, conv_state = state
    di, n, h, p = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    xt = x_t[:, 0, :]
    proj = xt @ params["in_proj"]
    z, xin, b, c, dt_raw = _split_proj(cfg, proj)

    conv_in = jnp.concatenate([xin, b, c], axis=-1)
    from .rglru import conv1d_step

    conv, conv_state = conv1d_step(conv_in, conv_state.astype(conv_in.dtype), params["conv_w"], params["conv_b"])
    conv = jax.nn.silu(conv.astype(jnp.float32)).astype(x_t.dtype)
    xin, b, c = conv[..., :di], conv[..., di : di + n], conv[..., di + n :]

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    xh = xin.reshape(-1, h, p)
    y, s_new = ssd_step(xh, dt, a, b, c, s_prev, put)
    y = y + params["d_skip"].astype(jnp.float32)[None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(-1, di).astype(x_t.dtype)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(z.dtype), params["norm_w"],
                cfg.norm_eps or 1e-6)
    out = y @ params["out_proj"]
    return out[:, None, :], (s_new, conv_state)
