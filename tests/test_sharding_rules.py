"""Logical-axis sharding rules: divisibility fallback, axis dedup, pod axis."""

import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.models.sharding import DEFAULT_RULES, spec_for, use_mesh

from _subproc import REPO_ROOT, run_env


def test_no_mesh_is_noop():
    assert spec_for((4, 8), ("batch", "embed")) == P()


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.models.sharding import spec_for, use_mesh

    mesh = make_mesh((2, 2, 4), ("pod", "data", "model"))
    with use_mesh(mesh):
        # batch maps to (pod, data); divisible
        assert spec_for((8, 128), ("batch", None)) == P(("pod", "data"), None), spec_for((8,128),("batch",None))
        # batch not divisible by 4 -> replicated
        assert spec_for((3, 128), ("batch", None)) == P(None, None)
        # heads / mlp to model
        assert spec_for((16, 8, 64), ("fsdp", "heads", None)) == P("data", "model", None)
        # dedup: expert wants data, fsdp also wants data -> second gets None
        assert spec_for((8, 64, 32), ("expert", "fsdp", "mlp")) == P("data", None, "model")
        # vocab to model
        assert spec_for((1024, 64), ("vocab", "fsdp")) == P("model", "data")

    mesh1 = make_mesh((4, 4), ("data", "model"))
    with use_mesh(mesh1):
        # no pod axis: batch falls back to data alone
        assert spec_for((8, 128), ("batch", None)) == P("data", None)
        # kv heads=2 not divisible by model=4 -> replicated
        assert spec_for((16, 2, 64), ("fsdp", "kv_heads", None)) == P("data", None, None)
    print("SHARDING_OK")
""")


def test_rules_on_multi_axis_mesh():
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT], capture_output=True, text=True, timeout=300,
        env=run_env(), cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARDING_OK" in proc.stdout


def test_default_rules_cover_model_axes():
    for ax in ("batch", "heads", "kv_heads", "mlp", "vocab", "fsdp", "expert", "seq"):
        assert ax in DEFAULT_RULES
