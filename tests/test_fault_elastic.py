"""Fault tolerance: heartbeats, stragglers, checkpoint-restart, elastic re-mesh."""

import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.runtime.fault import HeartbeatMonitor, StragglerDetector, WorkerFailure

from _subproc import REPO_ROOT, run_env


def test_heartbeat_detects_dead_worker():
    t = [0.0]
    mon = HeartbeatMonitor(n_workers=4, timeout=10.0, clock=lambda: t[0])
    t[0] = 5.0
    for w in (0, 1, 3):
        mon.beat(w)
    t[0] = 12.0
    assert mon.dead_workers() == [2]
    with pytest.raises(WorkerFailure):
        mon.check()


def test_straggler_detection_and_recovery():
    det = StragglerDetector(n_workers=4, factor=2.0, min_samples=3)
    for step in range(5):
        for w in range(4):
            det.record(w, 1.0 if w != 2 else 5.0)
    assert det.stragglers() == [2]
    # worker 2 recovers -> EWMA decays below threshold -> readmitted
    for _ in range(20):
        det.record(2, 1.0)
    assert det.stragglers() == []


def test_straggler_reassignment_prefers_pod_peers():
    det = StragglerDetector(n_workers=8, factor=2.0, min_samples=3)
    for _ in range(3):
        for w in range(8):
            det.record(w, 4.0 if w == 1 else 1.0)
    plan = det.reassignment(n_hosts=8)
    assert sum(len(v) for v in plan.values()) == 1
    donor = next(iter(plan))
    # worker 1 is in pod 0 (hosts 0-3); the donor must be a pod-0 peer
    assert donor in (0, 2, 3)


_ELASTIC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import get_reduced_config
    from repro.models.registry import build_model, synthetic_batch
    from repro.checkpoint.manager import CheckpointManager
    from repro.launch.mesh import make_mesh
    from repro.runtime.elastic import ElasticTrainer, plan_mesh, make_mesh_from_plan
    from repro.models.sharding import use_mesh
    from repro.training.step import init_state, make_train_step, state_abstract, state_logical, tree_shardings

    cfg = get_reduced_config("granite_3_8b").replace(accum=1)
    model = build_model(cfg)
    ckpt = CheckpointManager("{root}")

    # phase 1: train on an 8-device mesh (2 pods x 2 data x 2 model)
    mesh8 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    step_fn = make_train_step(model, cfg, lr_fn=lambda s: 1e-3)
    with use_mesh(mesh8):
        state = init_state(model, jax.random.PRNGKey(0), cfg)
        sh = tree_shardings(state_abstract(model, cfg), state_logical(model))
        state = jax.device_put(state, sh)
        batch = synthetic_batch(cfg, "train", 8, 16)
        state, m = jax.jit(step_fn, in_shardings=(sh, None), out_shardings=(sh, None))(state, batch)
    loss8 = float(m["loss"])
    ckpt.save(int(state["step"]), state, extra={"loss": loss8})

    # phase 2: "pod failure" -> only 4 devices -> restore elastically
    trainer = ElasticTrainer(model, cfg, ckpt, model_parallel=2)
    mesh4, state4, extra = trainer.restore_on(jax.devices()[:4], want_pods=1)
    assert tuple(mesh4.shape.values()) == (2, 2), mesh4.shape
    with use_mesh(mesh4):
        sh4 = tree_shardings(state_abstract(model, cfg), state_logical(model))
        batch = synthetic_batch(cfg, "train", 8, 16)
        state4b, m4 = jax.jit(step_fn, in_shardings=(sh4, None), out_shardings=(sh4, None))(state4, batch)

    # the restored step must continue from the checkpoint
    assert int(state4b["step"]) == int(state["step"]) + 1

    # determinism: same batch, same params => same loss on both meshes
    with use_mesh(mesh8):
        state8r, m8 = jax.jit(step_fn, in_shardings=(sh, None), out_shardings=(sh, None))(
            jax.device_put(ckpt.restore(ckpt.latest_step(), state_abstract(model, cfg)), sh), batch)
    np.testing.assert_allclose(float(m4["loss"]), float(m8["loss"]), rtol=1e-4)
    print("ELASTIC_OK", loss8, float(m4["loss"]))
""")


def test_elastic_restart_across_meshes(tmp_path):
    """Full scenario: train on 8 devices (2 pods), checkpoint, lose a pod,
    restore on 4 devices with re-sharding, continue training with identical
    numerics.  Runs in a subprocess so XLA_FLAGS can fake 8 CPU devices."""
    script = _ELASTIC_SCRIPT.replace("{root}", str(tmp_path / "ckpt"))
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=600,
        env=run_env(), cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ELASTIC_OK" in proc.stdout


def test_plan_mesh_shapes():
    from repro.runtime.elastic import plan_mesh

    assert plan_mesh(512, model_parallel=16, want_pods=2) == ((2, 16, 16), ("pod", "data", "model"))
    assert plan_mesh(256, model_parallel=16) == ((16, 16), ("data", "model"))
    assert plan_mesh(4, model_parallel=2) == ((2, 2), ("data", "model"))
    with pytest.raises(ValueError):
        plan_mesh(10, model_parallel=4)
