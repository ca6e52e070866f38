"""Logical-axis sharding: MaxText-style rules mapping logical axes to mesh axes.

Params and activations are annotated with *logical* axis names; a rules table
maps those to mesh axes (with automatic divisibility fallback to replication).
On a single-device CPU (smoke tests) the context is unset and every constraint
is a no-op, so model code is identical between tests and the 512-device
dry-run.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (str, tuple of axes, or None)
DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),     # global batch across pod+data
    "seq": "model",               # residual-stream sequence sharding (Megatron-SP)
    "embed": None,                # residual d_model stays unsharded
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "kv_seq": "model",            # decode KV-cache sequence sharding
    "mlp": "model",
    "fsdp": "data",               # weight-matrix dim sharded ZeRO-style
    "expert": "data",             # expert parallelism (when divisible)
    "layers": None,
    "conv": None,
    "state": None,
    "stack": None,
}


@dataclass
class MeshContext:
    mesh: Mesh
    rules: dict[str, Any] = field(default_factory=lambda: dict(DEFAULT_RULES))

    def axis_size(self, name) -> int:
        if name is None:
            return 1
        if isinstance(name, (tuple, list)):
            out = 1
            for n in name:
                out *= self.axis_size(n)
            return out
        return self.mesh.shape[name] if name in self.mesh.axis_names else 0


_ctx = threading.local()
_NO_MESH = object()          # sentinel: traces ran with no mesh context
# last mesh traced under (for cache invalidation); starts at the no-mesh
# sentinel so the first use_mesh entry also invalidates anything traced at
# top level before it (costs one clear of a cold cache at process start)
_last_mesh: list = [_NO_MESH]


def current_ctx() -> MeshContext | None:
    return getattr(_ctx, "value", None)


def _note_mesh(mesh) -> None:
    """Invalidate jax's trace caches when the effective mesh changes.

    jax's internal trace caches key on function identity + avals, NOT on our
    mesh context, so a re-trace under a *different* mesh (or under none, via
    the ``_NO_MESH`` sentinel) can reuse a jaxpr whose sharding constraints
    reference the old device set (the elastic-restart bug).  Clearing only on
    an actual mesh change keeps the common single-mesh path at full cache
    speed.  The sentinel (and jax's caches) are process-global, so a workload
    that alternates meshes — across iterations or threads — recompiles on
    every switch; give such a workload one mesh per *process*.  Known hole:
    tracing at top level (outside any ``use_mesh``) after mesh use is not a
    hookable transition — enter ``use_mesh(None)`` to trace mesh-free."""
    if mesh != _last_mesh[0]:
        jax.clear_caches()
        _last_mesh[0] = mesh


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None, rules: dict[str, Any] | None = None):
    old = getattr(_ctx, "value", None)
    if mesh is None:
        _note_mesh(_NO_MESH)
        _ctx.value = None
    else:
        _note_mesh(mesh)
        r = dict(DEFAULT_RULES)
        if rules:
            r.update(rules)
        _ctx.value = MeshContext(mesh, r)
    try:
        yield _ctx.value
    finally:
        _ctx.value = old
        if old is not None:
            # re-entering an outer context is also a mesh transition: code
            # after a nested `use_mesh(B)` block traces under A again
            _note_mesh(old.mesh)


def _resolve(logical, dim: int, ctx: MeshContext):
    """Map one logical axis to a mesh axis, replicating when not divisible."""
    if logical is None:
        return None
    mesh_axis = ctx.rules.get(logical, None)
    if mesh_axis is None:
        return None
    size = ctx.axis_size(mesh_axis)
    if size == 0:  # mesh axis absent (e.g. no 'pod' on single-pod mesh)
        if isinstance(mesh_axis, (tuple, list)):
            present = tuple(a for a in mesh_axis if a in ctx.mesh.axis_names)
            if not present:
                return None
            sz = 1
            for a in present:
                sz *= ctx.mesh.shape[a]
            if sz and dim % sz == 0:
                return present if len(present) > 1 else present[0]
        return None
    if dim % size != 0:
        return None
    return tuple(mesh_axis) if isinstance(mesh_axis, list) else mesh_axis


def axis_shards(logical: str, dim: int) -> int:
    """Shards the active mesh splits a ``dim``-long ``logical`` axis into: 1
    with no mesh, or where the rule replicates it (absent or not dividing)."""
    ctx = current_ctx()
    if ctx is None:
        return 1
    resolved = _resolve(logical, dim, ctx)
    return 1 if resolved is None else ctx.axis_size(resolved)


def spec_for(shape: tuple[int, ...], logical_axes: tuple[Any, ...]) -> P:
    ctx = current_ctx()
    if ctx is None:
        return P()
    assert len(shape) == len(logical_axes), (shape, logical_axes)
    used: set = set()
    parts = []
    for dim, ax in zip(shape, logical_axes):
        resolved = _resolve(ax, dim, ctx)
        # one mesh axis may appear only once in a spec
        flat = resolved if isinstance(resolved, tuple) else (resolved,)
        if resolved is not None and any(f in used for f in flat):
            resolved = None
        if resolved is not None:
            used.update(flat)
        parts.append(resolved)
    return P(*parts)


def shard(x: jax.Array, *logical_axes) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without a mesh ctx."""
    ctx = current_ctx()
    if ctx is None:
        return x
    spec = spec_for(x.shape, tuple(logical_axes))
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def sharding_for(shape: tuple[int, ...], logical_axes: tuple[Any, ...]):
    ctx = current_ctx()
    if ctx is None:
        return None
    return NamedSharding(ctx.mesh, spec_for(shape, logical_axes))


def tree_shardings(abstract_tree, logical_tree):
    """Build a NamedSharding pytree for (abstract shapes, logical axes)."""
    ctx = current_ctx()
    if ctx is None:
        return None
    return jax.tree.map(
        lambda a, l: NamedSharding(ctx.mesh, spec_for(a.shape, tuple(l))),
        abstract_tree,
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(i, (str, type(None))) for i in x),
    )
