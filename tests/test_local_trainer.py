"""Pod-local deferred-sync training (the keep_lock_local optimizer analogue)."""

import subprocess
import sys
import textwrap

from _subproc import REPO_ROOT, run_env

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import get_reduced_config
    from repro.data.pipeline import BigramLMDataset
    from repro.models.registry import build_model
    from repro.launch.mesh import make_mesh
    from repro.models.sharding import use_mesh
    from repro.training.local import (make_local_train_step, pod_average,
                                      pod_drift, replicate_for_pods)
    from repro.training.step import init_state

    N_PODS, K = 2, 4
    cfg = get_reduced_config("granite_3_8b").replace(accum=1, vocab=64)
    model = build_model(cfg)
    ds = BigramLMDataset(cfg.vocab, seq_len=32, global_batch=8 * N_PODS, seed=0, branching=4)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

    with use_mesh(mesh):
        state = replicate_for_pods(init_state(model, jax.random.PRNGKey(0), cfg), N_PODS)
        step = jax.jit(make_local_train_step(model, cfg, sync_every=K,
                                             lr_fn=lambda s: 5e-3, weight_decay=0.0))
        losses, drifts, syncs = [], [], []
        for i in range(16):
            b = ds.batch(i)
            b = jax.tree.map(lambda x: x.reshape((N_PODS, -1) + x.shape[1:]), b)
            state, m = step(state, b)
            losses.append(float(m["loss"]))
            drifts.append(float(pod_drift(state)))
            syncs.append(bool(m["synced"]))

    assert losses[-1] < losses[0] - 0.2, losses
    # pods drift between syncs and re-converge exactly at sync steps
    assert any(d > 1e-6 for d in drifts), drifts
    for d, s in zip(drifts, syncs):
        if s:
            assert d < 1e-5, (d, "params must agree after a pod average")
    assert sum(syncs) == 4, syncs  # steps 4, 8, 12, 16
    print("LOCAL_TRAINER_OK", losses[0], losses[-1], max(drifts))
""")


def test_pod_local_deferred_sync():
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True, timeout=900,
        env=run_env(), cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "LOCAL_TRAINER_OK" in proc.stdout
