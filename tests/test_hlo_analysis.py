"""The trip-count-aware HLO analyzer: validated against cost_analysis() on
scan-free programs, and against known loop structure on scanned ones."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import REPO_ROOT, run_env
from repro.launch.hlo_analysis import (
    _parse_groups,
    _wire_bytes,
    analyze_hlo,
    parse_module,
)


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_dot_flops_match_cost_analysis_scan_free():
    a = jnp.zeros((256, 512), jnp.float32)
    b = jnp.zeros((512, 128), jnp.float32)
    comp = _compile(lambda a, b: a @ b, a, b)
    got = analyze_hlo(comp.as_text()).flops
    want = comp.cost_analysis()["flops"]
    assert got == pytest.approx(want, rel=1e-6)
    assert got == pytest.approx(2 * 256 * 512 * 128, rel=1e-6)


def test_scan_flops_scale_with_trip_count():
    w = jnp.zeros((64, 64), jnp.float32)
    x = jnp.zeros((8, 64), jnp.float32)

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    comp = _compile(f, x, w)
    got = analyze_hlo(comp.as_text()).flops
    per_iter = 2 * 8 * 64 * 64
    # cost_analysis counts the body once; the analyzer must count 10x
    assert got == pytest.approx(10 * per_iter, rel=0.05)
    assert comp.cost_analysis()["flops"] < got


def test_nested_scan_multiplicity():
    w = jnp.zeros((32, 32), jnp.float32)

    def f(w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=4)
            return ci, None
        out, _ = jax.lax.scan(outer, jnp.eye(32), None, length=3)
        return out

    comp = _compile(f, w)
    got = analyze_hlo(comp.as_text()).flops
    assert got == pytest.approx(12 * 2 * 32**3, rel=0.05)


def test_parse_iota_replica_groups():
    n, g = _parse_groups("replica_groups=[4,2]<=[8]")
    assert n == 2 and g.shape == (4, 2)
    np.testing.assert_array_equal(g, np.arange(8).reshape(4, 2))
    n, g = _parse_groups("replica_groups=[2,4]<=[4,2]T(1,0)")
    assert n == 4 and g.shape == (2, 4)
    np.testing.assert_array_equal(g, np.arange(8).reshape(4, 2).T.reshape(2, 4))


def test_parse_explicit_replica_groups():
    n, g = _parse_groups("replica_groups={{0,1,2},{3,4,5}}")
    assert n == 3
    np.testing.assert_array_equal(g, [[0, 1, 2], [3, 4, 5]])


def test_cross_pod_classification():
    # groups spanning id 255->256 are cross-pod at chips_per_pod=256
    hlo = """
HloModule m
ENTRY %main (p: f32[512]) -> f32[512] {
  %p = f32[512]{0} parameter(0)
  ROOT %ar = f32[512]{0} all-reduce(%p), replica_groups=[256,2]<=[2,256]T(1,0), to_apply=%add
}
"""
    cost = analyze_hlo(hlo, chips_per_pod=256)
    assert cost.dcn_wire > 0 and cost.ici_wire == 0
    hlo_local = hlo.replace("[256,2]<=[2,256]T(1,0)", "[2,256]<=[512]")
    cost2 = analyze_hlo(hlo_local, chips_per_pod=256)
    assert cost2.ici_wire > 0 and cost2.dcn_wire == 0


def test_wire_byte_models():
    assert _wire_bytes("all-reduce", 100, 4) == pytest.approx(150.0)
    assert _wire_bytes("all-gather", 100, 4) == pytest.approx(75.0)
    assert _wire_bytes("reduce-scatter", 25, 4) == pytest.approx(75.0)
    assert _wire_bytes("collective-permute", 100, 2) == 100.0
    assert _wire_bytes("all-reduce", 100, 1) == 0.0


def test_collectives_inside_scan_multiply():
    """A psum inside a scanned body must be charged trip_count times."""
    import subprocess, sys, textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import analyze_hlo
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        def f(x, w):
            def body(c, _):
                y = c @ w
                return y - y.mean(), None   # mean over sharded rows -> all-reduce
            out, _ = jax.lax.scan(body, x, None, length=6)
            return out
        comp = jax.jit(f, in_shardings=(NamedSharding(mesh, P("data", None)), None)).lower(x, w).compile()
        cost = analyze_hlo(comp.as_text(), chips_per_pod=256)
        ar = {k: v for k, v in cost.collectives.items() if "all-reduce" in k}
        counts = sum(v["count"] for v in ar.values())
        print("COUNTS", counts)
        assert counts >= 6, (counts, cost.collectives)
    """)
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=300,
        env=run_env(), cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
