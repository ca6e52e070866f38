"""Decoder-LM assembly: dense / MoE / hybrid (RG-LRU or SSD) / SSM / VLM families.

The layer stack is compiled as a list of *segments*:

  * ``("scan", name, kinds, n_rep)`` — ``n_rep`` repetitions of the block-kind
    cycle ``kinds`` (usually a single kind), stacked params scanned with
    ``lax.scan`` (+ remat) so HLO size is O(1) in depth — 96-layer nemotron
    compiles as fast as 2-layer smoke configs.
  * ``("unroll", name, kind)`` — a single materialised layer (hybrid pattern
    remainders, deepseek's first dense layer).

Block kinds: ``attn`` (attention + dense FFN), ``moe`` (attention + MoE FFN),
``rec`` (RG-LRU recurrent block + dense FFN), ``ssd`` (Mamba-2 block, plus a
dense FFN where ``d_ff > 0``, as in granite-4.0-h's hybrid stack).  The
token mixers of ``attn``/``moe`` and ``ssd`` blocks run under
``jax.named_scope("mixer.attn")`` and ``("mixer.ssd")``, so a profile
splits device time by layer kind.

Decode reads the KV cache as scan xs and writes each step's one new
position after the scan (``DecoderLM._merge_kv``; where the TPU lays the
cache out position-minor, the whole aligned tile block around it,
``kv_write_block``).  SSD and RG-LRU state, which each step rewrites
whole, rides in the scan's carry and is updated at its layer index, so no
stacked copy of it comes out of the scan.  With
the cache donated both writes are in place, so one cache stays resident.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from .attention import attn_decode, attention
from .common import ParamBuilder, apply_rope, cross_entropy, embed_lookup, norm, rope_angles
from .mlp import declare_mlp, mlp_apply
from .moe import declare_moe, moe_apply
from .rglru import declare_rglru, rglru_block, rglru_block_step
from .sharding import axis_shards, current_ctx, shard
from .ssm import declare_ssd, ssd_block, ssd_block_step


# ---------------------------------------------------------------------------
# segments
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Segment:
    mode: str              # "scan" | "unroll"
    name: str
    kinds: tuple[str, ...]  # block kind per position in the cycle
    n_rep: int = 1


def layer_kinds(cfg) -> list[str]:
    if cfg.family == "ssm":
        return ["ssd"] * cfg.n_layers
    kinds = []
    for i, k in enumerate(cfg.blocks):
        if k in ("rec", "ssd"):
            kinds.append(k)
        elif cfg.n_experts and i >= cfg.first_k_dense:
            kinds.append("moe")
        else:
            kinds.append("attn")
    return kinds


def build_segments(cfg) -> list[Segment]:
    kinds = layer_kinds(cfg)
    segs: list[Segment] = []
    i = 0
    # leading unrolled layers (deepseek first-k-dense)
    while i < len(kinds) and cfg.first_k_dense and i < cfg.first_k_dense:
        segs.append(Segment("unroll", f"layer{i}", (kinds[i],)))
        i += 1
    rest = kinds[i:]
    if not rest:
        return segs
    if len(set(rest)) == 1:
        segs.append(Segment("scan", "blocks", (rest[0],), len(rest)))
        return segs
    p = len(cfg.block_pattern)
    n_full = len(rest) // p
    if n_full:
        segs.append(Segment("scan", "cyc", tuple(rest[:p]), n_full))
    for j in range(n_full * p, len(rest)):
        segs.append(Segment("unroll", f"tail{j}", (rest[j],)))
    return segs


# ---------------------------------------------------------------------------
# per-block param declaration
# ---------------------------------------------------------------------------

def declare_block(pb: ParamBuilder, prefix: str, cfg, kind: str, stack: int = 0):
    lead = (stack,) if stack else ()
    lax_ = ("layers",) if stack else ()
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.hd
    ln_bias = cfg.norm == "layernorm"

    def decl_norm(n):
        pb.declare(f"{prefix}/{n}", lead + (d,), lax_ + (None,), init="zeros")
        if ln_bias:
            pb.declare(f"{prefix}/{n}_b", lead + (d,), lax_ + (None,), init="zeros")

    decl_norm("ln1")
    if kind in ("attn", "moe"):
        pb.declare(f"{prefix}/wq", lead + (d, h, hd), lax_ + ("fsdp", "heads", None))
        pb.declare(f"{prefix}/wk", lead + (d, kv, hd), lax_ + ("fsdp", "kv_heads", None))
        pb.declare(f"{prefix}/wv", lead + (d, kv, hd), lax_ + ("fsdp", "kv_heads", None))
        pb.declare(f"{prefix}/wo", lead + (h, hd, d), lax_ + ("heads", None, "fsdp"))
        decl_norm("ln2")
        if kind == "moe":
            declare_moe(pb, f"{prefix}/moe", cfg, stack)
        else:
            declare_mlp(pb, f"{prefix}/mlp", d, cfg.d_ff, cfg.mlp, stack)
    elif kind == "rec":
        declare_rglru(pb, f"{prefix}/rec", d, cfg.lru_width or d, cfg.conv_width, stack)
        decl_norm("ln2")
        declare_mlp(pb, f"{prefix}/mlp", d, cfg.d_ff, cfg.mlp, stack)
    elif kind == "ssd":
        declare_ssd(pb, f"{prefix}/ssd", cfg, stack)
        if cfg.d_ff:
            decl_norm("ln2")
            declare_mlp(pb, f"{prefix}/mlp", d, cfg.d_ff, cfg.mlp, stack)
    else:
        raise ValueError(kind)


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _norm(params, name, x, cfg):
    return norm(cfg.norm, x, params[name], params.get(f"{name}_b"), cfg.norm_eps)


def _residual(x, y, cfg):
    """x + y, the sublayer output ``y`` scaled by the residual multiplier
    (Granite's; the default 1.0 adds it as it is)."""
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


def _attn_scale(cfg):
    """The score scale: Granite's attention multiplier where set, else None
    (the attention functions' 1/sqrt(head_dim))."""
    return cfg.attention_multiplier or None


def _attn_full(params, x, cfg, rope_cs, *, causal=True, window=None, cross_kv=None):
    """Attention sublayer, full-sequence mode.  Returns (x_out, (k, v))."""
    h = _norm(params, "ln1" if cross_kv is None else "lnx", x, cfg)
    q = jnp.einsum("bsd,dhk->bshk", h, params["wq" if cross_kv is None else "wxq"])
    if cross_kv is None:
        k = jnp.einsum("bsd,dhk->bshk", h, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, params["wv"])
        if rope_cs is not None:
            cos, sin = rope_cs
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
    else:
        k, v = cross_kv
    w = cfg.window if window is None else window
    o = attention(
        q, k, v,
        impl=cfg.attn_impl, causal=causal, window=w, chunk=cfg.attn_chunk,
        scale=_attn_scale(cfg),
    )
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo" if cross_kv is None else "wxo"])
    return _residual(x, shard(out, "batch", "seq", "embed"), cfg), (k, v)


def _rope_pos(pos):
    """pos: () or (B,) -> positions shaped for rope_angles broadcasting."""
    p = jnp.asarray(pos)
    return p[None, None] if p.ndim == 0 else p[:, None]


def _write_kv(cache: jax.Array, new: jax.Array, slot) -> jax.Array:
    """Write (B, 1, kv, hd) into (B, S, kv, hd) at ``slot`` (scalar or (B,))."""
    slot = jnp.asarray(slot)
    if slot.ndim == 0:
        return jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype), (0, slot, 0, 0))
    return jax.vmap(
        lambda c, n, s: jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (s, 0, 0))
    )(cache, new, slot)


def _attn_step(params, x_t, cfg, pos, cache, *, ring: bool, cross_kv=None):
    """Attention sublayer, one-token decode.  cache = (k_cache, v_cache),
    READ-ONLY here: the new token's (k, v) slice is returned for the caller
    to write into the cache once, outside the layer scan — keeping the big
    cache an xs input the partitioner never copies or rewrites per layer.

    ``pos`` is () for lockstep decode (dry-run shapes) or (B,) for the
    continuous-batching engine (per-slot positions)."""
    h = _norm(params, "ln1" if cross_kv is None else "lnx", x_t, cfg)
    q = jnp.einsum("bsd,dhk->bshk", h, params["wq" if cross_kv is None else "wxq"])
    if cross_kv is None:
        k_cache, v_cache = cache
        k = jnp.einsum("bsd,dhk->bshk", h, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, params["wv"])
        if cfg.pos == "rope":
            cos, sin = rope_angles(_rope_pos(pos), cfg.hd, cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        o = attn_decode(
            q, k_cache, v_cache, jnp.asarray(pos), window=cfg.window, ring=ring,
            extra_kv=(k.astype(k_cache.dtype), v.astype(v_cache.dtype)),
            scale=_attn_scale(cfg),
        )
        new_kv = (k.astype(k_cache.dtype), v.astype(v_cache.dtype))
    else:
        k_cache, v_cache = cross_kv
        o = attn_decode(q, k_cache, v_cache, k_cache.shape[1], ring=False,
                        scale=_attn_scale(cfg))
        new_kv = None
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo" if cross_kv is None else "wxo"])
    return _residual(x_t, out, cfg), new_kv


def _to_ring(k: jax.Array, window: int) -> jax.Array:
    """Convert a full-sequence KV (B,S,kv,hd) into the ring layout decode
    expects for sliding-window archs: slot i%window holds token i, keeping the
    last ``window`` tokens.  Without this, continuing decode from a prefill
    whose prompt length != window mis-places cache entries (caught by the
    decode-matches-prefill tests)."""
    b, s, kv, hd = k.shape
    if s <= window:
        return jnp.pad(k, ((0, 0), (0, window - s), (0, 0), (0, 0)))
    tail = k[:, -window:]                                # tokens s-window..s-1
    slots = jnp.mod(jnp.arange(s - window, s), window)
    return jnp.zeros((b, window, kv, hd), k.dtype).at[:, slots].set(tail)


def _mlp_sublayer(params, x, cfg):
    """x + mlp(norm(x)), the FFN half of ``attn``, ``rec`` and ``ssd`` blocks."""
    h = _norm(params, "ln2", x, cfg)
    return _residual(x, mlp_apply(params["mlp"], h, cfg.mlp), cfg)


def block_full(params, x, cfg, kind, rope_cs, *, causal=True, lengths=None):
    """Full-sequence block.  Returns (x, aux, cache).  ``lengths`` (B,)
    marks right-padded packed rows; only state layers read it (their
    state must stand at each row's true length)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "moe"):
        with jax.named_scope("mixer.attn"):
            x, (k, v) = _attn_full(params, x, cfg, rope_cs, causal=causal)
        h = _norm(params, "ln2", x, cfg)
        if kind == "moe":
            if cfg.moe_impl == "ep":
                from .moe_ep import moe_apply_ep

                y, aux = moe_apply_ep(params["moe"], h, cfg)
            else:
                y, aux = moe_apply(params["moe"], h, cfg, n_domains=cfg.cna_domains)
        else:
            y = mlp_apply(params["mlp"], h, cfg.mlp)
        x = _residual(x, y, cfg)
        cdt = cfg_cache_dtype(cfg)
        if cfg.window > 0:
            k, v = _to_ring(k, cfg.window), _to_ring(v, cfg.window)
        cache = (k.astype(cdt), v.astype(cdt))
    elif kind == "rec":
        h = _norm(params, "ln1", x, cfg)
        y, state = rglru_block(params["rec"], h, scan_impl=cfg.rec_impl)
        x = _mlp_sublayer(params, _residual(x, y, cfg), cfg)
        cache = state
    elif kind == "ssd":
        h = _norm(params, "ln1", x, cfg)
        with jax.named_scope("mixer.ssd"):
            y, state = ssd_block(params["ssd"], h, cfg, intra_impl=cfg.ssd_impl,
                                 lengths=lengths)
        x = _residual(x, y, cfg)
        if cfg.d_ff:
            x = _mlp_sublayer(params, x, cfg)
        cache = state
    else:
        raise ValueError(kind)
    return shard(x, "batch", "seq", "embed"), aux, cache


def block_step(params, x_t, cfg, kind, pos, cache, put=None):
    """One-token decode block.  Returns (x_t, new_cache).  ``put`` goes to
    an ``ssd`` block's ``ssd_step``."""
    if kind in ("attn", "moe"):
        ring = cfg.window > 0
        with jax.named_scope("mixer.attn"):
            x_t, new_attn = _attn_step(params, x_t, cfg, pos, cache, ring=ring)
        if kind == "moe":
            h = _norm(params, "ln2", x_t, cfg)
            y, _ = moe_apply(params["moe"], h, cfg, n_domains=cfg.cna_domains)
            return _residual(x_t, y, cfg), new_attn
        return _mlp_sublayer(params, x_t, cfg), new_attn
    if kind == "rec":
        h = _norm(params, "ln1", x_t, cfg)
        y, new_state = rglru_block_step(params["rec"], h, cache)
        return _mlp_sublayer(params, _residual(x_t, y, cfg), cfg), new_state
    if kind == "ssd":
        h = _norm(params, "ln1", x_t, cfg)
        with jax.named_scope("mixer.ssd"):
            y, new_state = ssd_block_step(params["ssd"], h, cache, cfg, put)
        x_t = _residual(x_t, y, cfg)
        if cfg.d_ff:
            x_t = _mlp_sublayer(params, x_t, cfg)
        return x_t, new_state
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# packed / continuation prefill (continuous batching)
# ---------------------------------------------------------------------------

def _scatter_rows(cache: jax.Array, new: jax.Array, start, length) -> jax.Array:
    """Write ``new`` (B, T, kv, hd) into ``cache`` (B, S, kv, hd) at per-row
    column offsets: token t of row b lands at column ``start[b] + t``, and
    only ``t < length[b]`` commits (right-padded rows never touch the cache).
    Gather-then-select keeps this one fused ``where`` over the cache — the
    masked-select idiom ``DecoderLM._merge_kv`` keeps for seq-sharded
    caches — so no per-row dynamic slices fan out under the layer scan."""
    idx = jnp.arange(cache.shape[1])[None, :] - start[:, None]          # (B, S)
    valid = (idx >= 0) & (idx < length[:, None])
    take = jnp.clip(idx, 0, new.shape[1] - 1)[:, :, None, None]
    take = jnp.broadcast_to(take, idx.shape + new.shape[2:])
    g = jnp.take_along_axis(new, take, axis=1)
    return jnp.where(valid[:, :, None, None], g.astype(cache.dtype), cache)


def _attn_rows(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, start,
               scale: float | None = None) -> jax.Array:
    """``attn_xla`` with a *per-row* query offset: query t of row b sits at
    position ``start[b] + t`` and attends causally over the position-ordered
    cache columns.  Op-for-op the same graph as ``attn_xla`` (grouped einsum,
    NEG_INF mask, ``jax.nn.softmax``, grouped PV einsum) — masked columns
    contribute exact zeros, which is what makes continuation prefill
    bitwise-equal to the from-scratch path (regression-tested)."""
    b, sq, h, hd = q.shape
    skv, hkv = k_cache.shape[1], k_cache.shape[2]
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    qg = attn_mod._group_q(q * jnp.asarray(scale, q.dtype), hkv)
    s = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg.astype(k_cache.dtype), k_cache,
        preferred_element_type=jnp.float32,
    )
    q_pos = start[:, None] + jnp.arange(sq)[None, :]                    # (B, Sq)
    mask = q_pos[:, :, None] - jnp.arange(skv)[None, None, :] >= 0      # (B, Sq, Skv)
    s = jnp.where(mask[:, None, None], s, attn_mod.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def _attn_cont(params, x, cfg, rope_cs, kv_cache, start, length):
    """Attention sublayer, suffix-continuation mode: the suffix K/V land in
    the (seeded) cache at per-row offsets first, then the suffix queries
    attend over the whole cache.  Returns (x_out, (k_cache, v_cache))."""
    h = _norm(params, "ln1", x, cfg)
    q = jnp.einsum("bsd,dhk->bshk", h, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, params["wv"])
    if rope_cs is not None:
        cos, sin = rope_cs
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    kc, vc = kv_cache
    kc = _scatter_rows(kc, k, start, length)
    vc = _scatter_rows(vc, v, start, length)
    o = _attn_rows(q, kc, vc, start, _attn_scale(cfg))
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    return _residual(x, shard(out, "batch", "seq", "embed"), cfg), (kc, vc)


def block_cont(params, x, cfg, kind, rope_cs, kv_cache, start, length):
    """Suffix-continuation block (attention kinds only — recurrent/SSM state
    absorbs padded positions, so those families never take this path).
    Returns (x, (k_cache, v_cache))."""
    if kind != "attn":
        raise ValueError(f"continuation prefill supports 'attn' blocks, got {kind!r}")
    x, kv = _attn_cont(params, x, cfg, rope_cs, kv_cache, start, length)
    x = _mlp_sublayer(params, x, cfg)
    return shard(x, "batch", "seq", "embed"), kv


def cfg_cache_dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# cache shape declarations
# ---------------------------------------------------------------------------

KV_AXES = ("batch", "kv_seq", "kv_heads", None)
KV_TILE = 128   # positions in one TPU tile's lane dimension


def kv_write_block(seq_len: int, head_dim: int) -> int:
    """Positions one lane's decode KV write spans in a (…, S, kv, hd) cache.

    Where the head size does not fill the 128 lanes of a TPU tile (stablelm's
    80, granite-4.0-h's 64) the TPU compiler lays the cache out
    position-minor: positions along the lanes, the head dim along the
    sublanes.  A one-position write there touches one lane column of every
    tile; the whole aligned ``KV_TILE`` block around it writes whole tiles.
    A head size that is a multiple of 128 keeps the natural layout, where
    one position is a run of whole rows: it is written alone."""
    return KV_TILE if head_dim % KV_TILE and seq_len % KV_TILE == 0 else 1


def block_cache_shape(cfg, kind: str, batch: int, cache_len: int):
    """One block's cache leaves (no leading stack dim) and the logical axes
    of each, declared beside its shape: ``((sds, ...), (axes, ...))``.
    Attention keeps (k, v); recurrent and SSD blocks keep their float32
    state and the conv tail of their last ``conv_width - 1`` inputs, which
    have no sequence axis."""
    cdt = cfg_cache_dtype(cfg)
    if kind in ("attn", "moe"):
        s = min(cache_len, cfg.window) if cfg.window > 0 else cache_len
        kv = jax.ShapeDtypeStruct((batch, s, cfg.n_kv, cfg.hd), cdt)
        return (kv, kv), (KV_AXES, KV_AXES)
    if kind == "rec":
        w = cfg.lru_width or cfg.d_model
        return (
            jax.ShapeDtypeStruct((batch, w), jnp.float32),
            jax.ShapeDtypeStruct((batch, cfg.conv_width - 1, w), cdt),
        ), (("batch", "mlp"), ("batch", None, "mlp"))
    if kind == "ssd":
        conv_ch = cfg.d_inner + 2 * cfg.ssm_state
        return (
            jax.ShapeDtypeStruct((batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
            jax.ShapeDtypeStruct((batch, cfg.conv_width - 1, conv_ch), cdt),
        ), (("batch", None, None, None), ("batch", None, "mlp"))
    raise ValueError(kind)


def _stack_sds(sds: jax.ShapeDtypeStruct, n: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((n,) + sds.shape, sds.dtype)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class DecoderLM:
    """Decoder-only LM over the segment stack.  Also carries the VLM variant
    (pixtral): precomputed patch embeddings (assignment stub) are projected
    and overwrite the leading ``n_patches`` positions of the token stream."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.segments = build_segments(cfg)
        self.pb = ParamBuilder(dtype=jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
        self._declare()
        self._logical_cache = self.pb.logical_tree()

    # -- params --------------------------------------------------------------
    def _declare(self):
        cfg, pb = self.cfg, self.pb
        pb.declare("embed", (cfg.padded_vocab, cfg.d_model), ("vocab", "fsdp"), init="normal", scale=0.02)
        if cfg.pos == "learned":
            pb.declare("pos_emb", (cfg.max_pos, cfg.d_model), (None, "fsdp"), init="normal", scale=0.02)
        if cfg.n_patches:
            pb.declare("patch_proj", (cfg.d_model, cfg.d_model), ("fsdp", None), init="normal")
        for seg in self.segments:
            if seg.mode == "scan":
                for j, kind in enumerate(seg.kinds):
                    name = seg.name if len(seg.kinds) == 1 else f"{seg.name}{j}"
                    declare_block(pb, name, cfg, kind, stack=seg.n_rep)
            else:
                declare_block(pb, seg.name, cfg, seg.kinds[0], stack=0)
        pb.declare("final_norm", (cfg.d_model,), (None,), init="zeros")
        if cfg.norm == "layernorm":
            pb.declare("final_norm_b", (cfg.d_model,), (None,), init="zeros")
        if not cfg.tie_embeddings:
            pb.declare("lm_head", (cfg.d_model, cfg.padded_vocab), ("fsdp", "vocab"), init="normal", scale=0.02)

    def init(self, key):
        return self.pb.init(key)

    def abstract_params(self):
        return self.pb.abstract()

    def logical_tree(self):
        return self.pb.logical_tree()

    def _seg_params(self, params, seg: Segment):
        if seg.mode == "scan":
            if len(seg.kinds) == 1:
                return (params[seg.name],)
            return tuple(params[f"{seg.name}{j}"] for j in range(len(seg.kinds)))
        return (params[seg.name],)

    def _seg_logical(self, seg: Segment):
        log = self._logical_cache
        if seg.mode == "scan":
            if len(seg.kinds) == 1:
                return (log[seg.name],)
            return tuple(log[f"{seg.name}{j}"] for j in range(len(seg.kinds)))
        return (log[seg.name],)

    @staticmethod
    def _constrain_sliced(p_layer, logical):
        """Re-pin a scan-sliced layer's params to their (fsdp x model) layout.

        Without this the partitioner hoists the FSDP all-gather of the whole
        stacked (L, ...) parameter out of the layer loop — materialising every
        layer's gathered weights at once (nemotron train_4k: 106 GB/device;
        EXPERIMENTS.md §Perf).  Constraining the *sliced* leaf keeps the
        gather inside the loop and lets the backward choose reduce-scatter
        for the per-layer grad."""
        return jax.tree.map(
            lambda a, l: shard(a, *l[1:]),
            p_layer,
            logical,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(i, (str, type(None))) for i in x),
        )

    # -- embedding / logits ----------------------------------------------------
    def _embed(self, params, tokens, patches=None, pos_offset=0):
        cfg = self.cfg
        # the residual stream runs in the config's dtype whatever the params
        # are stored in: a float32 model given bf16 params computes in f32
        # (each matmul promotes its weight), which is how a float32
        # reference runs on weights too large to hold in f32
        x = embed_lookup(params["embed"], tokens).astype(self.pb.dtype)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        x = shard(x, "batch", "seq", "embed")
        if cfg.n_patches and patches is not None:
            pe = jnp.einsum("bpd,de->bpe", patches.astype(x.dtype), params["patch_proj"])
            n = min(cfg.n_patches, x.shape[1])
            x = jnp.concatenate([pe[:, :n], x[:, n:]], axis=1)
        if cfg.pos == "learned":
            off = jnp.asarray(pos_offset)
            pos = jnp.arange(x.shape[1]) + (off[:, None] if off.ndim else off)
            pe = jnp.take(params["pos_emb"], jnp.clip(pos, 0, cfg.max_pos - 1), axis=0)
            x = x + (pe if pe.ndim == 3 else pe[None])
        return x

    def _logits(self, params, x):
        cfg = self.cfg
        x = norm(cfg.norm, x, params["final_norm"], params.get("final_norm_b"), cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
        if cfg.logits_scaling != 1.0:
            logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
        vmask = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab, 0.0, attn_mod.NEG_INF)
        logits = logits + vmask.astype(logits.dtype)
        # vocab-parallel logits: 'seq' must NOT claim the model axis here, or
        # vocab falls back to replicated and the partitioner materialises an
        # unsharded fp32 lm_head copy in the accum-loop carry (18.8 GiB on
        # nemotron-340b; EXPERIMENTS.md §Perf)
        return shard(logits, "batch", None, "vocab")

    def _rope(self, seq_len, offset=0):
        if self.cfg.pos != "rope":
            return None
        return rope_angles(jnp.arange(seq_len) + offset, self.cfg.hd, self.cfg.rope_theta)

    # -- full pass -------------------------------------------------------------
    def _run_full(self, params, x, want_cache: bool, lengths=None):
        cfg = self.cfg
        rope_cs = self._rope(x.shape[1])
        aux_total = jnp.zeros((), jnp.float32)
        caches = {}

        for seg in self.segments:
            p = self._seg_params(params, seg)
            if seg.mode == "unroll":
                x, aux, cache = block_full(p[0], x, cfg, seg.kinds[0], rope_cs,
                                           lengths=lengths)
                aux_total += aux
                if want_cache:
                    caches[seg.name] = cache
                continue

            seg_log = self._seg_logical(seg)

            def body(carry, xs, _kinds=seg.kinds, _log=seg_log):
                xx = carry
                aux_sum = jnp.zeros((), jnp.float32)
                cs = []
                for j, kind in enumerate(_kinds):
                    p_j = self._constrain_sliced(xs[j], _log[j])
                    xx, aux, cache = block_full(p_j, xx, cfg, kind, rope_cs,
                                                lengths=lengths)
                    aux_sum += aux
                    cs.append(cache)
                return xx, (aux_sum, tuple(cs))

            fn = body
            if cfg.remat:
                fn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
            x, (auxs, cs) = jax.lax.scan(fn, x, p)
            aux_total += jnp.sum(auxs)
            if want_cache:
                caches[seg.name] = cs
        return x, aux_total, caches if want_cache else None

    # -- public API --------------------------------------------------------------
    def loss(self, params, batch):
        """batch: {tokens (B,S), labels (B,S), [patches]} -> scalar loss."""
        x = self._embed(params, batch["tokens"], batch.get("patches"))
        x, aux, _ = self._run_full(params, x, want_cache=False)
        logits = self._logits(params, x)
        ce = cross_entropy(logits, batch["labels"], self.cfg.vocab, batch.get("mask"))
        return ce + aux

    def prefill(self, params, batch, *, cache_headroom: int = 8):
        """-> (last-token logits (B, Vpad), cache dict).

        Full-attention KV caches are emitted with ``cache_headroom`` spare
        slots: ``dynamic_update_slice`` silently *clamps* out-of-bounds
        writes, so a zero-headroom cache would corrupt its last entry on the
        first decode step (regression-tested).  Ring (sliding-window) and
        recurrent caches have fixed capacity and never need headroom."""
        x = self._embed(params, batch["tokens"], batch.get("patches"))
        x, _, caches = self._run_full(params, x, want_cache=True)
        if cache_headroom:
            caches = self._pad_caches(caches, cache_headroom)
        logits = self._logits(params, x[:, -1:])
        caches["pos"] = jnp.full((), x.shape[1], jnp.int32)
        return logits[:, 0], caches

    def _pad_caches(self, caches, headroom: int):
        if self.cfg.window > 0:
            return caches  # ring caches: slot = pos % window, always in bounds
        out = {}
        for seg in self.segments:
            per = caches[seg.name]
            if seg.mode == "unroll":
                per = (per,)
            new = []
            for j, kind in enumerate(seg.kinds):
                c = per[j]
                if kind in ("attn", "moe"):
                    ax = 2 if seg.mode == "scan" else 1  # (L,B,S,kv,hd) | (B,S,kv,hd)
                    c = tuple(
                        jnp.pad(t, [(0, headroom if d == ax else 0) for d in range(t.ndim)])
                        for t in c
                    )
                new.append(c)
            out[seg.name] = tuple(new) if seg.mode == "scan" else new[0]
        return out

    # -- packed / continuation prefill (continuous batching) --------------------
    @property
    def has_recurrent_state(self) -> bool:
        """Whether some block keeps recurrent state (``rec``/``ssd``) in
        place of KV: such a cache cannot be paged or resumed from a stored
        prefix until state snapshots exist."""
        return any(k in ("rec", "ssd") for seg in self.segments for k in seg.kinds)

    def supports_packed_prefill(self, cache_len: int | None = None) -> bool:
        """Whether right-padded packed prefill is correct for this arch.

        For a stack of plain dense attention a packed row is *bitwise* the
        per-request ``prefill``: masked pad columns add exact zeros.  SSD
        layers (alone or between attention layers) are admitted too: pad
        positions get ``dt = 0``, so the state passes them unchanged, and
        each row's conv tail is gathered at its true length.  There the
        contract is agreement with the float32 reference within the stated
        tolerance (the chunked scan's split of a row differs with the
        bucket; ``tests/test_hybrid.py``).  RG-LRU state and MoE capacity
        routing absorb padded positions, sliding-window ring caches place
        entries by absolute slot, and patch rows overwrite leading
        positions, so those stay refused.  When ``cache_len`` is given, also
        require that every bucket the engine would use dispatches to the
        same ``attn_xla`` path as the per-request reference (a bucket above
        ``attn_chunk`` would stream while the reference doesn't)."""
        cfg = self.cfg
        ok = (
            cfg.window == 0
            and cfg.n_patches == 0
            and all(k in ("attn", "ssd") for seg in self.segments for k in seg.kinds)
        )
        if ok and cache_len is not None and cfg.attn_impl != "xla":
            ok = cache_len <= cfg.attn_chunk
        return ok

    def _mask_packed(self, caches, lengths):
        """Zero every KV position >= the row's true length.  Right-padded
        rows compute garbage K/V past the prompt; zeroing them matches the
        zero-padding of ``SlotCache._fit`` so a packed row is bitwise the
        per-request cache, not just equal on the valid span.  State leaves
        already stand at the true length (``ssd_block``) and pass as they
        are."""
        out = {}
        for seg in self.segments:
            per = caches[seg.name]
            if seg.mode == "unroll":
                per = (per,)
            new = []
            for kind, c in zip(seg.kinds, per):
                if kind not in ("attn", "moe"):
                    new.append(c)
                    continue

                def z(t):  # (L, B, S, kv, hd) scanned | (B, S, kv, hd)
                    s = t.shape[2 if t.ndim == 5 else 1]
                    keep = jnp.arange(s)[None, :] < lengths[:, None]     # (B, S)
                    keep = keep[..., None, None]
                    if t.ndim == 5:
                        keep = keep[None]
                    return jnp.where(keep, t, jnp.zeros((), t.dtype))
                new.append(tuple(z(t) for t in c))
            out[seg.name] = tuple(new) if seg.mode == "scan" else new[0]
        return out

    def prefill_packed(self, params, tokens, lengths, *, cache_headroom: int = 8):
        """Packed prefill: ``tokens`` (B, S) right-padded prompt rows,
        ``lengths`` (B,) true lengths -> (per-row last-*real*-token logits
        (B, Vpad), cache with per-row ``pos``).  One trace serves every
        workload sharing (B, S): the batching layer buckets S to powers of
        two so trace count stays O(log cache_len).  On the ``attn_xla`` path
        each row is bitwise what ``prefill`` returns for that prompt alone —
        masked pad columns add exact zeros (regression-tested).  Rows with
        ``length == 0`` are dummies (pack remainder): their logits are
        garbage by contract and their KV/pos stay zero."""
        lengths = jnp.asarray(lengths, jnp.int32)
        x = self._embed(params, tokens)
        x, _, caches = self._run_full(params, x, want_cache=True, lengths=lengths)
        if cache_headroom:
            caches = self._pad_caches(caches, cache_headroom)
        caches = self._mask_packed(caches, lengths)
        idx = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        logits = self._logits(params, x_last)
        caches["pos"] = lengths
        return logits[:, 0], caches

    def prefill_cont(self, params, cache, tokens, lengths):
        """Continuation prefill: extend per-row seeded caches by whole
        right-padded suffixes in one call.  ``cache`` is a batched cache
        whose ``pos`` (B,) marks each row's seeded length (KV for positions
        < pos already written, zeros past it); ``tokens`` (B, T) are the
        suffixes, ``lengths`` (B,) their true lengths.  Replaces the
        one-``decode_step``-per-suffix-token resume loop — and unlike that
        loop it stays *bitwise-equal* to the from-scratch ``prefill`` of the
        full prompt (the decode path's two-part online softmax only agrees
        to cache-dtype resolution; this path replays ``attn_xla``'s exact op
        order over the position-ordered cache).  Rows with ``length == 0``
        pass through untouched."""
        cfg = self.cfg
        start = jnp.asarray(cache["pos"], jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        x = self._embed(params, tokens, pos_offset=start)
        rope_cs = None
        if cfg.pos == "rope":
            pos = start[:, None] + jnp.arange(tokens.shape[1])[None, :]
            rope_cs = rope_angles(pos, cfg.hd, cfg.rope_theta)

        new_cache = dict(cache)
        for seg in self.segments:
            p = self._seg_params(params, seg)
            if seg.mode == "unroll":
                x, kv = block_cont(
                    p[0], x, cfg, seg.kinds[0], rope_cs, cache[seg.name],
                    start, lengths,
                )
                new_cache[seg.name] = kv
                continue

            seg_log = self._seg_logical(seg)

            def body(xx, xs, _kinds=seg.kinds, _log=seg_log):
                ps, cs = xs
                kvs = []
                for j, kind in enumerate(_kinds):
                    p_j = self._constrain_sliced(ps[j], _log[j])
                    xx, kv = block_cont(p_j, xx, cfg, kind, rope_cs, cs[j], start, lengths)
                    kvs.append(kv)
                return xx, tuple(kvs)

            x, ys = jax.lax.scan(body, x, (p, cache[seg.name]))
            new_cache[seg.name] = ys

        idx = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        logits = self._logits(params, x_last)
        new_cache["pos"] = start + lengths
        return logits[:, 0], new_cache

    def _merge_kv(self, old, new, pos):
        """Write the (…, B, 1, kv, hd) new-token slices into the cache at
        ``pos`` (the ring slot for window configs), once per step.

        The write is a ``dynamic_update_slice``: once for a scalar ``pos``,
        once per lane for per-lane ``pos``.  On a donated cache XLA applies
        it in place.  A position past the cache's end clamps onto its last
        entry; only an idle engine lane gets there (a live sequence retires
        first), and its lane is overwritten whole when it is claimed again.
        (A scatter over the lanes measured twice the step time on
        stablelm-3b; PERF.md.)

        Where the TPU lays the cache out position-minor
        (``kv_write_block``), that one position is one lane column in every
        (16, 128) tile of the cache's layers and heads, and those
        partial-tile writes cost about 50 ns a tile (4 ms of stablelm-3b's
        16 ms step).  There the ``kv_block_write`` kernel rewrites instead
        the aligned 128-position block that holds each lane's slot, whole
        tiles only, in place; the cache is bitwise what the one-position
        write leaves.  It runs only with no mesh active: GSPMD cannot split
        a kernel, and would gather the cache to run it.  (XLA's own
        slice-select-update of that block measured slower than the
        columns: 5.89 against 4.37 ms for a stablelm step's 16 writes;
        PERF.md.)

        Under a mesh that shards the cache's ``kv_seq`` axis it stays a
        masked select over that axis: a dynamic-index write on a sharded dim
        makes GSPMD all-gather the whole cache to update it (measured +0.42 s
        collective on granite decode), while the ``iota == slot`` select
        stays shard-local.  The select reads and rewrites every byte of the
        cache, which is why it is kept only there."""
        s_max = old.shape[-3]
        slot = jnp.mod(pos, s_max) if self.cfg.window > 0 else jnp.asarray(pos)
        new = new.astype(old.dtype)
        if axis_shards("kv_seq", s_max) > 1:
            seq_iota = jnp.arange(s_max)
            if slot.ndim == 0:
                mask = seq_iota == slot                          # (S,)
                mask = mask[:, None, None]                       # (S, 1, 1)
            else:
                mask = seq_iota[None, :] == slot[:, None]        # (B, S)
                mask = mask[..., None, None]                     # (B, S, 1, 1)
                if old.ndim == 5:
                    mask = mask[None]                            # (1, B, S, 1, 1)
            return jnp.where(mask, new, old)
        if kv_write_block(s_max, old.shape[-1]) > 1 and current_ctx() is None:
            from repro.kernels.kv_block_write import ops as kvw_ops

            at = jnp.broadcast_to(jnp.clip(slot, 0, s_max - 1), old.shape[-4:-3])  # the clamp
            return kvw_ops.write_kv_blocks(old, new, at)
        if slot.ndim == 0:
            start = (0,) * (old.ndim - 3) + (slot, 0, 0)
            return jax.lax.dynamic_update_slice(old, new, start)
        lane_ax = old.ndim - 4
        for b in range(slot.shape[0]):
            start = (0,) * lane_ax + (b, slot[b], 0, 0)
            lane = jax.lax.slice_in_dim(new, b, b + 1, axis=lane_ax)
            old = jax.lax.dynamic_update_slice(old, lane, start)
        return old

    def decode_step(self, params, cache, tokens):
        """tokens: (B, 1) -> (logits (B, Vpad), new cache).

        KV is read-only inside the layer scan (pure xs): each attention
        layer emits only its new-token (k, v) slice as ys, and after the
        scan ``_merge_kv`` writes that one position per lane into each
        stacked cache.  State layers (``ssd``, ``rec``) rewrite their whole
        state each step, so a scanned segment that holds one carries its
        stacked state leaves in the scan's carry with the layer index: each
        layer reads its slice and writes the new state back at that index,
        and an SSD layer's readout reads its new state back from there
        (``put``), so the compiler keeps no temporary of it.  As scan ys the
        new state would come out as a fresh stacked buffer that XLA then
        copies into the cache.  A caller that donates
        ``cache`` (the engine's tick) gets both writes in place: one cache
        resident and no copy of it (PERF.md, the model decode layer and its
        findings)."""
        cfg = self.cfg
        pos = cache["pos"]
        x = self._embed(params, tokens, pos_offset=pos)
        new_cache = dict(cache)

        for seg in self.segments:
            p = self._seg_params(params, seg)
            if seg.mode == "unroll":
                kind = seg.kinds[0]
                x, out = block_step(p[0], x, cfg, kind, pos, cache[seg.name])
                if kind in ("attn", "moe"):
                    new_cache[seg.name] = tuple(
                        self._merge_kv(c, n, pos) for c, n in zip(cache[seg.name], out)
                    )
                else:
                    new_cache[seg.name] = jax.tree.map(
                        lambda n, c: n.astype(c.dtype), out, cache[seg.name]
                    )
                continue

            # State leaves ride in the carry and are updated in place at the
            # layer index; KV rides as xs (see the docstring).  A segment with
            # no state kind carries only x, as it always did.
            cs = cache[seg.name]
            state = {j: c for j, (k, c) in enumerate(zip(seg.kinds, cs)) if k not in ("attn", "moe")}
            kv_xs = tuple(None if j in state else c for j, c in enumerate(cs))
            i0 = jnp.int32(0) if state else None

            def body(carry, xs, _kinds=seg.kinds):
                xx, i, state = carry
                state = dict(state)
                ps, kvs = xs
                outs = []
                for j, kind in enumerate(_kinds):
                    if j not in state:
                        xx, out = block_step(ps[j], xx, cfg, kind, pos, kvs[j])
                        outs.append(out)
                        continue
                    leaves = list(state[j])
                    layer = tuple(jax.lax.dynamic_index_in_dim(s, i, keepdims=False) for s in leaves)

                    def put(s_new, leaves=leaves):
                        # the SSD state goes into its stack before the readout
                        # reads it back (ssm.ssd_step)
                        leaves[0] = jax.lax.dynamic_update_index_in_dim(leaves[0], s_new, i, 0)
                        return jax.lax.dynamic_index_in_dim(leaves[0], i, keepdims=False)

                    xx, out = block_step(ps[j], xx, cfg, kind, pos, layer, put)
                    state[j] = tuple(
                        new if new is not old  # already written by put
                        else jax.lax.dynamic_update_index_in_dim(old, n.astype(old.dtype), i, 0)
                        for old, new, n in zip(state[j], leaves, out))
                    outs.append(None)
                return (xx, None if i is None else i + 1, state), tuple(outs)

            (x, _, state), ys = jax.lax.scan(body, (x, i0, state), (p, kv_xs))
            new_cache[seg.name] = tuple(
                state[j] if j in state
                else tuple(self._merge_kv(c, n, pos) for c, n in zip(cs[j], ys[j]))
                for j in range(len(seg.kinds))
            )

        logits = self._logits(params, x)
        new_cache["pos"] = pos + 1
        return logits[:, 0], new_cache

    # -- abstract cache / inputs -----------------------------------------------
    def _cache_tree(self, batch: int, cache_len: int, part: int):
        """Per segment, each block's ``block_cache_shape`` half ``part`` (0:
        shapes, 1: logical axes), with the scan's leading layers axis."""
        caches = {}
        for seg in self.segments:
            per_pos = []
            for k in seg.kinds:
                leaves = block_cache_shape(self.cfg, k, batch, cache_len)[part]
                if seg.mode == "scan":
                    leaves = tuple(_stack_sds(s, seg.n_rep) if part == 0 else ("layers",) + s
                                   for s in leaves)
                per_pos.append(leaves)
            caches[seg.name] = tuple(per_pos) if seg.mode == "scan" else per_pos[0]
        return caches

    def cache_abstract(self, batch: int, cache_len: int):
        caches = self._cache_tree(batch, cache_len, 0)
        caches["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
        return caches

    def cache_logical(self, cache_abstract=None):
        """Logical axes of every cache leaf, as ``block_cache_shape``
        declares them (``"layers"`` leading a scanned segment's leaves).
        The tree matches ``cache_abstract``'s; it does not depend on its
        batch or length."""
        axes = self._cache_tree(1, 1, 1)
        axes["pos"] = ()
        return axes
