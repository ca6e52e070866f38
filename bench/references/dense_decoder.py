"""Plain float32 reference of a dense decoder: a full forward pass with no
cache and no batching, in straightforward ``jax.numpy`` at the highest
matmul precision.  It imports nothing of the program; it reads the param
layout the benchmark draws (``bench/programs/dense_decoder.py``).

It computes the configuration as run, whose departures from the published
model the configuration file lists: norm weights stored as (weight - 1),
rotary over the whole head (NeoX halves), untied output head, no scalar
multipliers.  Pre-norm blocks: x += attn(norm(x)); x += mlp(norm(x)), causal
softmax attention with grouped KV heads, SwiGLU MLP, then a final norm and
the output head over the real vocabulary.

``quant="fp8"`` is the control: every weight rounded to float8 e4m3 (scaled
per tensor, per layer) before use, the next precision below the bfloat16 the
configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0   # largest finite float8 e4m3fn


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HI, preferred_element_type=jnp.float32)


def _weight(w, quant):
    w = w.astype(jnp.float32)
    if quant is None:
        return w
    if quant != "fp8":
        raise ValueError(f"unknown quantization {quant!r}")
    s = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / F8_MAX
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _norm(x, w, b, eps):
    w = 1.0 + w.astype(jnp.float32)
    if b is None:                                             # RMSNorm
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w
    mu = jnp.mean(x, -1, keepdims=True)                       # LayerNorm
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b.astype(jnp.float32)


def _rope(x, theta):
    """x (B, S, H, hd) at positions 0..S-1, rotating halves of each head."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs    # (S, half)
    c, si = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * si, x2 * c + x1 * si], axis=-1)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _logits_at(params, tokens, positions, *, dims, quant):
    heads, kv, hd, eps, theta, vocab = dims
    b = tokens.shape[0]
    x = _weight(params["embed"], quant)[tokens]                # (B, S, d)
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        h = _norm(x, p["ln1"], p.get("ln1_b"), eps)
        q = _rope(_mm("bsd,dhk->bshk", h, _weight(p["wq"], quant)), theta)
        k = _rope(_mm("bsd,dhk->bshk", h, _weight(p["wk"], quant)), theta)
        v = _mm("bsd,dhk->bshk", h, _weight(p["wv"], quant))
        k = jnp.repeat(k, heads // kv, axis=2)                 # head h reads kv head h // g
        v = jnp.repeat(v, heads // kv, axis=2)
        sc = _mm("bqhk,bshk->bhqs", q, k) / math.sqrt(hd)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        o = _mm("bhqs,bshk->bqhk", jax.nn.softmax(sc, axis=-1), v)
        x = x + _mm("bqhk,hkd->bqd", o, _weight(p["wo"], quant))
        h = _norm(x, p["ln2"], p.get("ln2_b"), eps)
        m = p["mlp"]
        g = jax.nn.silu(_mm("bsd,df->bsf", h, _weight(m["wg"], quant)))
        u = _mm("bsd,df->bsf", h, _weight(m["wi"], quant))
        return x + _mm("bsf,fd->bsd", g * u, _weight(m["wo"], quant)), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _norm(x, params["final_norm"], params.get("final_norm_b"), eps)
    xs = jnp.take_along_axis(x, positions[..., None], axis=1)   # (B, T, d)
    head = _weight(params["lm_head"], quant)[:, :vocab]
    return _mm("btd,dv->btv", xs, head).reshape(b, positions.shape[1], vocab)


def logits_at(params, cfg: dict, tokens, positions, quant=None):
    """Float32 logits (B, T, vocab) after ``tokens`` (B, S) at the
    ``positions`` (B, T) of each row."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    eps = cfg.get("rms_norm_eps", cfg.get("layer_norm_eps"))
    dims = (h, cfg["num_key_value_heads"], cfg.get("head_dim") or d // h,
            float(eps), float(cfg["rope_theta"]), cfg["vocab_size"])
    return _logits_at(params, tokens, positions, dims=dims, quant=quant)
