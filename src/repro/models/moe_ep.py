"""CNA expert parallelism: the paper's two-queue discipline as an MoE layer.

Mapping (DESIGN.md §2): an EP shard is a NUMA socket; a token is a thread; the
expert it wants is the lock.  The standard EP layer sends *every* routed token
through one uniform all-to-all.  CNA-EP splits the dispatch exactly like the
paper splits waiters:

  main queue      tokens routed to experts resident on their own shard are
                  dispatched *locally* — no collective at all (the same-socket
                  handover);
  secondary queue tokens routed to remote experts go through an all-to-all
                  whose per-destination capacity ``C_rem`` is provisioned for
                  the *residual* (post-bias) remote traffic — the wire bytes
                  shrink with the achieved locality;
  fairness        the router's load-balancing aux loss plus the bounded bias
                  keep remote experts fed (no expert starves) — the
                  keep_lock_local threshold analogue.

With ``cna_routing`` on, the router adds a bounded bias toward same-shard
experts, so the locality fraction λ rises from ~1/n_ep to ~0.5-0.9 and
``remote_capacity_factor`` can be provisioned ~4x smaller at the same drop
rate: all-to-all wire bytes fall proportionally (benchmarks/moe_ep_bench.py,
EXPERIMENTS.md §Perf deepseek hillclimb).

Implemented with ``jax.shard_map`` manual over the EP axes; the 'model' axis
stays auto (GSPMD).  Expert weights are sharded over the EP axes on the
expert dim; e.g. deepseek's 64 experts over 16 data shards = 4 experts/shard
(x 2 pods = 2/shard on the multi-pod mesh, experts contiguous per shard so a
pod is a super-domain).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .moe import _positions, moe_capacity
from .mlp import mlp_apply
from .sharding import current_ctx, shard


def ep_axes_for(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _swiglu(buf, wi, wg, wo):
    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    return jnp.einsum("ecf,efd->ecd", h, wo)


def moe_apply_ep(params: dict, x: jax.Array, cfg):
    """x: (B, S, D) -> (out, aux).  Falls back to the TP layer without a mesh
    context or when the expert count does not divide the EP shards."""
    ctx = current_ctx()
    if ctx is None:
        from .moe import moe_apply

        return moe_apply(params, x, cfg)
    mesh = ctx.mesh
    axes = ep_axes_for(mesh)
    n_ep = 1
    for a in axes:
        n_ep *= mesh.shape[a]
    e = cfg.n_experts
    if n_ep <= 1 or e % n_ep or x.shape[0] % n_ep:
        from .moe import moe_apply

        return moe_apply(params, x, cfg)

    e_loc = e // n_ep
    k = cfg.top_k
    b, s, d = x.shape
    g_l = (b // n_ep) * s                      # tokens per EP shard
    c_loc = moe_capacity(g_l, k, e_loc, cfg.capacity_factor)
    r = cfg.ep_remote_capacity_factor
    c_rem = max(4, int(math.ceil(g_l * k * r / n_ep / 4)) * 4)
    c_rin = max(4, int(math.ceil(n_ep * c_rem * cfg.capacity_factor / e_loc / 4)) * 4)

    local_fn = partial(
        _ep_local, cfg=cfg, axes=axes, n_ep=n_ep, e_loc=e_loc,
        c_loc=c_loc, c_rem=c_rem, c_rin=c_rin,
    )
    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(axes, None, None),       # x: batch over EP shards
            P(None, None),             # router weights replicated
            P(axes, None, None),       # wi: experts over EP shards
            P(axes, None, None),
            P(axes, None, None),
        ),
        out_specs=(P(axes, None, None), P()),
        axis_names=set(axes),
        check_vma=False,
    )(x, params["router"], params["wi"], params["wg"], params["wo"])

    if cfg.n_shared_experts:
        out = out + mlp_apply(params["shared"], x, "swiglu")
    return shard(out, "batch", "seq", "embed"), aux


def _ep_local(x_l, router, wi, wg, wo, *, cfg, axes, n_ep, e_loc, c_loc, c_rem, c_rin):
    """Per-EP-shard body.  x_l: (Bl, S, D); wi/wg/wo: (e_loc, D, ff)."""
    e, k = cfg.n_experts, cfg.top_k
    bl, s, d = x_l.shape
    g = bl * s
    my = jax.lax.axis_index(axes)

    # -- routing (with the CNA main-queue bias toward resident experts) ------
    xt = x_l.reshape(g, d)
    logits = jnp.einsum("gd,de->ge", xt.astype(jnp.float32), router.astype(jnp.float32))
    exp_shard = jnp.arange(e, dtype=jnp.int32) // e_loc          # home shard per expert
    if cfg.cna_routing:
        logits = logits + cfg.cna_routing_bias * (exp_shard == my).astype(jnp.float32)[None, :]
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = (w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)).astype(x_l.dtype)
    # load-balance aux (global mean via psum — the fairness threshold)
    f = jnp.mean(jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=1), axis=0)
    p = jnp.mean(probs, axis=0)
    f = jax.lax.pmean(f, axes)
    p = jax.lax.pmean(p, axes)
    aux = e * jnp.sum(f * p) * cfg.router_aux_coef

    e_all = idx.reshape(-1)                                       # (g*k,)
    w_all = w.reshape(-1)
    tok = jnp.repeat(jnp.arange(g, dtype=jnp.int32), k)
    dest = e_all // e_loc
    is_local = dest == my

    # -- main queue: same-shard dispatch, no collective ----------------------
    e_main = jnp.where(is_local, e_all % e_loc, e_loc)            # e_loc = dummy row
    pos_m, keep_m = _positions(e_main, e_loc + 1, c_loc)
    keep_m &= is_local
    buf_m = jnp.zeros((e_loc + 1, c_loc, d), x_l.dtype)
    buf_m = buf_m.at[e_main, pos_m].add(jnp.where(keep_m[:, None], xt[tok], 0))

    # -- secondary queue: remote tokens through the provisioned all-to-all ---
    d_sec = jnp.where(is_local, n_ep, dest)                       # n_ep = dummy row
    pos_s, keep_s = _positions(d_sec, n_ep + 1, c_rem)
    keep_s &= ~is_local
    send_x = jnp.zeros((n_ep + 1, c_rem, d), x_l.dtype)
    send_x = send_x.at[d_sec, pos_s].add(jnp.where(keep_s[:, None], xt[tok], 0))
    send_e = jnp.full((n_ep + 1, c_rem), e_loc, jnp.int32)        # dummy expert
    send_e = send_e.at[d_sec, pos_s].set(jnp.where(keep_s, e_all % e_loc, e_loc))
    recv_x = jax.lax.all_to_all(send_x[:n_ep], axes, split_axis=0, concat_axis=0, tiled=True)
    recv_e = jax.lax.all_to_all(send_e[:n_ep], axes, split_axis=0, concat_axis=0, tiled=True)

    flat_e = recv_e.reshape(-1)
    pos_r, keep_r = _positions(flat_e, e_loc + 1, c_rin)
    keep_r &= flat_e < e_loc
    buf_r = jnp.zeros((e_loc + 1, c_rin, d), x_l.dtype)
    buf_r = buf_r.at[flat_e, pos_r].add(jnp.where(keep_r[:, None], recv_x.reshape(-1, d), 0))

    # -- expert FFN over [main | remote] capacity regions --------------------
    buf = jnp.concatenate([buf_m[:e_loc], buf_r[:e_loc]], axis=1)  # (e_loc, c_loc+c_rin, D)
    out_buf = _swiglu(buf, wi, wg, wo)
    out_m, out_r = out_buf[:, :c_loc], out_buf[:, c_loc:]

    # -- combine: main directly; secondary back through the all-to-all -------
    y = jnp.zeros((g, d), x_l.dtype)
    y_m = out_m[jnp.minimum(e_main, e_loc - 1), jnp.minimum(pos_m, c_loc - 1)]
    y = y.at[tok].add(jnp.where(keep_m[:, None], y_m * w_all[:, None], 0))

    back = jnp.zeros((n_ep * c_rem, d), x_l.dtype)
    y_r = out_r[jnp.minimum(flat_e, e_loc - 1), jnp.minimum(pos_r, c_rin - 1)]
    back = jnp.where(keep_r[:, None], y_r, 0)
    back = jax.lax.all_to_all(back.reshape(n_ep, c_rem, d), axes, split_axis=0, concat_axis=0, tiled=True)
    back = jnp.concatenate([back, jnp.zeros((1, c_rem, d), x_l.dtype)], axis=0)
    y_s = back[jnp.minimum(d_sec, n_ep), jnp.minimum(pos_s, c_rem - 1)]
    y = y.at[tok].add(jnp.where(keep_s[:, None], y_s * w_all[:, None], 0))

    return y.reshape(bl, s, d), aux


def ep_routing_stats(params, x, cfg, n_ep: int):
    """Offline routing statistics (numpy-friendly): locality fraction and the
    drop rates at the provisioned capacities — used by the benchmark to pick
    remote_capacity_factor (no mesh needed)."""
    e, k = cfg.n_experts, cfg.top_k
    e_loc = e // n_ep
    b, s, d = x.shape
    bl = b // n_ep
    g = bl * s
    c_rem = max(4, int(math.ceil(g * k * cfg.ep_remote_capacity_factor / n_ep / 4)) * 4)
    stats = {"local": 0.0, "dropped": 0.0, "total": 0.0,
             "a2a_bytes": 2.0 * n_ep * c_rem * d * x.dtype.itemsize, "c_rem": c_rem}
    for shard_i in range(n_ep):
        x_l = x[shard_i * bl : (shard_i + 1) * bl].reshape(g, d)
        logits = jnp.einsum("gd,de->ge", x_l.astype(jnp.float32), params["router"].astype(jnp.float32))
        if cfg.cna_routing:
            exp_shard = jnp.arange(e) // e_loc
            logits = logits + cfg.cna_routing_bias * (exp_shard == shard_i).astype(jnp.float32)[None, :]
        _, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
        e_all = idx.reshape(-1)
        dest = e_all // e_loc
        is_local = dest == shard_i
        stats["local"] += float(jnp.sum(is_local))
        stats["total"] += float(e_all.shape[0])
        d_sec = jnp.where(is_local, n_ep, dest)
        pos, keep = _positions(d_sec, n_ep + 1, c_rem)
        keep &= ~is_local
        stats["dropped"] += float(jnp.sum(~is_local) - jnp.sum(keep))
    stats["locality"] = stats["local"] / stats["total"]
    stats["drop_rate"] = stats["dropped"] / max(1.0, stats["total"] - stats["local"])
    return stats
