"""CNA-inspired collective schedules for multi-pod gradient synchronisation.

The paper's locality principle, lifted to collectives: intra-pod ICI is the
"same socket" (cheap handover), inter-pod DCN is the "remote socket".  The
gradient-sync schedules below keep per-step traffic on ICI and treat the DCN
crossing the way CNA treats the secondary queue — make it rarer (deferred
sync every K steps = ``keep_lock_local`` threshold) and make each crossing
cheaper (int8 compression = a smaller cache line).

All functions are written to run *inside* ``shard_map`` over the production
mesh (axis names ``pod``, ``data``, ``model``), and are exercised on CPU in
tests via subprocess-spawned multi-device meshes.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# int8 gradient compression (the "smaller remote cache line")
# ---------------------------------------------------------------------------

def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-tensor symmetric int8 quantisation.  Deterministic round-to-nearest
    (tests bound the dequantisation error at scale/2 per element)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30).astype(jnp.float32)
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# shard_map-level collective schedules
# ---------------------------------------------------------------------------

def hierarchical_grad_sync(g: jax.Array, *, intra_axes=("data",), pod_axis="pod"):
    """Baseline-but-better schedule: reduce-scatter on ICI, all-reduce the
    (1/N-sized) shards over DCN, all-gather on ICI.  Equivalent to a flat
    psum over (intra+pod) but moves 2x less data over the slow axis than a
    flat ring that includes the pod hop.

    Shapes: ``g`` is a per-device gradient shard; the first dim must divide
    by the intra-axis size.
    """
    g = jax.lax.psum_scatter(g, intra_axes, scatter_dimension=0, tiled=True)
    g = jax.lax.psum(g, pod_axis)
    g = jax.lax.all_gather(g, intra_axes, axis=0, tiled=True)
    return g


def compressed_pod_sum(g: jax.Array, *, pod_axis="pod"):
    """All-reduce over the pod axis with int8 payload on the wire.

    Ring exchange via ``ppermute``: each step sends the int8-quantised
    accumulator to the next pod and dequantises into a float accumulator.
    Exact for n_pods=2 up to one quantisation; for larger rings each hop
    requantises (error grows linearly with hops — documented, bounded in
    tests)."""
    n = jax.lax.axis_size(pod_axis)
    acc = g.astype(jnp.float32)
    send = g.astype(jnp.float32)
    idx = jax.lax.axis_index(pod_axis)
    del idx
    perm = None

    def body(i, carry):
        acc, send = carry
        q, scale = quantize_int8(send)
        q = jax.lax.ppermute(q, pod_axis, perm)
        scale = jax.lax.ppermute(scale, pod_axis, perm)
        recv = dequantize_int8(q, scale)
        return acc + recv, recv

    perm = [(i, (i + 1) % n) for i in range(n)]
    acc, _ = jax.lax.fori_loop(0, n - 1, body, (acc, send))
    return acc.astype(g.dtype)


def cna_grad_sync(
    g: jax.Array,
    *,
    intra_axes=("data",),
    pod_axis="pod",
    compress: bool = False,
):
    """The full CNA schedule: local reduce-scatter, (optionally compressed)
    pod crossing, local all-gather."""
    g = jax.lax.psum_scatter(g, intra_axes, scatter_dimension=0, tiled=True)
    if compress:
        g = compressed_pod_sum(g, pod_axis=pod_axis)
    else:
        g = jax.lax.psum(g, pod_axis)
    g = jax.lax.all_gather(g, intra_axes, axis=0, tiled=True)
    return g


def make_pod_average(mesh: Mesh, specs: Any):
    """Build a jitted ``params -> params`` that averages parameters over the
    pod axis — the deferred-sync "secondary queue flush".  Used by the
    local-updates trainer (optim/podlocal) every K steps; between flushes the
    pods run entirely on ICI, zero DCN traffic (the CNA analogue of keeping
    the lock on-socket between threshold events)."""
    if "pod" not in mesh.axis_names:
        raise ValueError("pod axis required for pod averaging")

    def avg_leaf(x):
        def f(x_shard):
            return jax.lax.pmean(x_shard, "pod")

        return f(x)

    def pod_average(params):
        flat, treedef = jax.tree.flatten(params)
        flat_specs, _ = jax.tree.flatten(specs)
        out = []
        for x, spec in zip(flat, flat_specs):
            fn = jax.shard_map(
                avg_leaf,
                mesh=mesh,
                in_specs=(spec,),
                out_specs=spec,
                check_vma=False,
            )
            out.append(fn(x))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(pod_average)


def wire_bytes_allreduce(nbytes: int, axis_size: int) -> float:
    """Ring all-reduce per-chip wire traffic: 2 * s * (n-1)/n."""
    return 2.0 * nbytes * (axis_size - 1) / axis_size


def wire_bytes_allgather(shard_bytes: int, axis_size: int) -> float:
    return float(shard_bytes) * (axis_size - 1)


def wire_bytes_reducescatter(nbytes: int, axis_size: int) -> float:
    return float(nbytes) * (axis_size - 1) / axis_size
