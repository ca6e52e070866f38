"""CNA-inspired collective schedules: wire-byte accounting + numerics.

The multi-pod analogue of the paper's locality argument: per-step traffic on
the slow (DCN/"remote-socket") axis should carry 1/N-sized shards, compressed
payloads, or nothing at all (deferred sync) — measured here with the same
wire models the roofline uses, plus numeric validation on a subprocess mesh.
"""

from __future__ import annotations

import subprocess
import sys
import textwrap

import numpy as np

from repro.core.collectives import (
    dequantize_int8,
    quantize_int8,
    wire_bytes_allgather,
    wire_bytes_allreduce,
    wire_bytes_reducescatter,
)

from .common import REPO_ROOT, claim, subproc_env, table


def wire_accounting(grad_bytes=2 * 8_000_000_000, intra=16, pods=2):
    """Per-chip DCN traffic per step for an 8B-param bf16 gradient."""
    flat = wire_bytes_allreduce(grad_bytes, intra * pods)       # flat ring over all chips
    flat_dcn = flat  # worst-case: the ring crosses pods every hop / no locality
    hier_dcn = wire_bytes_allreduce(grad_bytes / intra, pods)   # after intra-pod RS
    comp_dcn = hier_dcn / 2                                      # int8 vs bf16
    defer_dcn = hier_dcn / 64                                    # sync every K=64 steps
    rows = [
        ["flat all-reduce (pod-oblivious)", flat_dcn / 2**30],
        ["hierarchical (CNA: RS-intra -> AR-pod -> AG-intra)", hier_dcn / 2**30],
        ["hierarchical + int8 compression", comp_dcn / 2**30],
        ["hierarchical + deferred K=64 (amortised)", defer_dcn / 2**30],
    ]
    table("gradient-sync DCN bytes per chip per step (8B params, GiB)",
          ["schedule", "dcn_GiB"], rows)
    claim("collectives: hierarchical cuts slow-axis traffic by ~intra x",
          flat_dcn / hier_dcn > intra * 0.9, f"ratio={flat_dcn / hier_dcn:.1f}")


def quantization_error():
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (512, 512)).astype(np.float32)
    q, s = quantize_int8(x)
    err = np.abs(dequantize_int8(np.asarray(q), np.asarray(s)) - x).max()
    bound = float(np.asarray(s)) / 2 + 1e-7
    table("int8 compression error", ["max_err", "bound(scale/2)"], [[float(err), bound]])
    claim("collectives: int8 error <= scale/2", err <= bound, f"{err:.5f} <= {bound:.5f}")


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.collectives import cna_grad_sync, hierarchical_grad_sync

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8), jnp.float32)

    def flat(g):
        return jax.lax.psum(g, ("pod", "data"))

    spec = P(None, None)
    args = dict(mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False)
    flat_fn = jax.jit(jax.shard_map(flat, **args))
    hier_fn = jax.jit(jax.shard_map(lambda g: hierarchical_grad_sync(g), **args))
    comp_fn = jax.jit(jax.shard_map(lambda g: cna_grad_sync(g, compress=True), **args))

    want = np.asarray(flat_fn(x))
    got_h = np.asarray(hier_fn(x))
    got_c = np.asarray(comp_fn(x))
    np.testing.assert_allclose(got_h, want, rtol=1e-5)
    err = np.abs(got_c - want).max() / np.abs(want).max()
    assert err < 0.02, err
    print("MESH_OK hierarchical exact, compressed rel-err", float(err))
""")


def mesh_numerics():
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT], capture_output=True, text=True, timeout=300,
        env=subproc_env(), cwd=REPO_ROOT,
    )
    ok = proc.returncode == 0 and "MESH_OK" in proc.stdout
    claim("collectives: hierarchical == flat psum; compressed within 2% (8-dev mesh)",
          ok, proc.stdout.strip().splitlines()[-1] if ok else proc.stderr[-300:])


def run_all():
    wire_accounting()
    quantization_error()
    mesh_numerics()
