"""Pod-local training with deferred cross-pod sync (the keep_lock_local
analogue for the optimizer, DiLoCo-style).

Each pod trains *independently* on its own batch shard — all per-step
collectives stay on ICI — and parameters are averaged across pods only every
``sync_every`` steps (the secondary-queue flush: one DCN crossing amortised
over K local handovers).  DCN bytes drop by K× versus per-step sync, at the
cost of K steps of inter-pod parameter drift (bounded by the sync period —
the same throughput↔staleness dial as the paper's fairness threshold).

Implementation: the pod axis is realised as a *leading array axis* of size
n_pods on the whole train state, sharded over the mesh's ``pod`` axis; the
train step is vmapped over it (so each pod's update sees only its slice) and
the periodic sync is a mean over that axis — which GSPMD lowers to exactly
one all-reduce over the pod axis (the DCN collective we are rationing).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.sharding import current_ctx, spec_for
from repro.training.step import make_train_step


def replicate_for_pods(state, n_pods: int):
    """state -> per-pod stacked state (leading axis n_pods, sharded 'pod')."""
    stacked = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n_pods,) + x.shape), state)
    ctx = current_ctx()
    if ctx is not None and "pod" in ctx.mesh.axis_names:
        from jax.sharding import NamedSharding, PartitionSpec as P

        def shard_leaf(x):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(ctx.mesh, P("pod", *([None] * (x.ndim - 1))))
            )

        stacked = jax.tree.map(shard_leaf, stacked)
    return stacked


def pod_average(state):
    """Average params/opt across the pod axis (ONE all-reduce over 'pod')."""
    return jax.tree.map(lambda x: jnp.broadcast_to(jnp.mean(x, 0, keepdims=True), x.shape)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, state)


def make_local_train_step(model, cfg, *, sync_every: int, lr_fn=None, **kw):
    """-> step(state_stacked, batch_stacked) with deferred pod sync.

    ``batch_stacked`` leaves have shape (n_pods, per_pod_batch, ...).  The
    sync fires when (step % sync_every == 0); between syncs there is no
    cross-pod communication at all."""
    base_step = make_train_step(model, cfg, lr_fn=lr_fn, **kw)
    vstep = jax.vmap(base_step)

    def step(state, batch):
        state, metrics = vstep(state, batch)
        do_sync = jnp.max(state["step"]) % sync_every == 0
        state = jax.lax.cond(do_sync, pod_average, lambda s: s, state)
        metrics = jax.tree.map(lambda m: jnp.mean(m, 0), metrics)
        metrics["synced"] = do_sync
        return state, metrics

    return step


@jax.jit
def pod_drift(state) -> jax.Array:
    """Max parameter divergence across pods (monitoring the staleness dial).
    One program: run op by op, its per-leaf pod reductions were dozens of
    small multi-device programs in flight at once, and on the CPU backend
    those can starve each other's collective rendezvous."""
    def leaf_drift(x):
        if not jnp.issubdtype(x.dtype, jnp.floating) or x.shape[0] < 2:
            return jnp.zeros(())
        x = x.astype(jnp.float32)
        return jnp.max(jnp.abs(x - jnp.mean(x, 0, keepdims=True)))
    return jax.tree.reduce(jnp.maximum, jax.tree.map(leaf_drift, state["params"]))
