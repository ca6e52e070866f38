"""CNA expert-parallel MoE: equivalence with the TP layer + locality wins."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.common import ParamBuilder
from repro.models.moe import declare_moe
from repro.models.moe_ep import ep_routing_stats

from _subproc import REPO_ROOT, run_env


def _cfg(**kw):
    base = dict(
        name="t", family="moe", n_layers=1, d_model=32, n_heads=4, n_kv=4,
        d_ff=64, vocab=128, n_experts=8, top_k=2, moe_d_ff=48,
        capacity_factor=4.0, ep_remote_capacity_factor=1.0,
    )
    base.update(kw)
    return ModelConfig(**base)


_EP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig
    from repro.models.common import ParamBuilder
    from repro.models.moe import declare_moe, moe_apply
    from repro.models.moe_ep import moe_apply_ep
    from repro.launch.mesh import make_mesh
    from repro.models.sharding import use_mesh

    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32, n_heads=4,
                      n_kv=4, d_ff=64, vocab=128, n_experts=8, top_k=2, moe_d_ff=48,
                      capacity_factor=4.0, ep_remote_capacity_factor=2.0)
    pb = ParamBuilder(dtype=jnp.float32)
    declare_moe(pb, "moe", cfg)
    params = pb.init(jax.random.PRNGKey(0))["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32), jnp.float32)

    # reference: the TP (local-dispatch) layer, generous capacity, no mesh
    ref, _ = moe_apply(params, x, cfg)

    mesh = make_mesh((4,), ("data",))
    with use_mesh(mesh):
        out, aux = jax.jit(lambda p, x: moe_apply_ep(p, x, cfg))(params, x)
    err = float(jnp.max(jnp.abs(out - ref)))
    # generous capacities => no drops on either path => near-exact agreement
    assert err < 1e-4, err
    print("EP_OK", err)
""")


def test_ep_matches_tp_reference():
    proc = subprocess.run(
        [sys.executable, "-c", _EP_SCRIPT], capture_output=True, text=True, timeout=600,
        env=run_env(), cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "EP_OK" in proc.stdout


def test_cna_bias_raises_locality_and_cuts_drops():
    """The paper's main-queue preference: biased routing keeps most tokens on
    their own shard, so the remote exchange can be provisioned smaller at the
    same drop rate."""
    key = jax.random.PRNGKey(0)
    pb = ParamBuilder(dtype=jnp.float32)
    cfg0 = _cfg(cna_routing=False, ep_remote_capacity_factor=0.5)
    declare_moe(pb, "moe", cfg0)
    params = pb.init(key)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32), jnp.float32)

    s_off = ep_routing_stats(params, x, cfg0, n_ep=4)
    cfg1 = _cfg(cna_routing=True, cna_routing_bias=2.0, ep_remote_capacity_factor=0.5)
    s_on = ep_routing_stats(params, x, cfg1, n_ep=4)

    assert s_on["locality"] > s_off["locality"] + 0.2, (s_on["locality"], s_off["locality"])
    assert s_on["drop_rate"] <= s_off["drop_rate"] + 1e-9
