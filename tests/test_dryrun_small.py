"""End-to-end dry-run machinery on a small faked mesh (fast CI-scale proof;
the full 512-device 80-cell run is the results/dryrun_opt/ artifact)."""

import subprocess
import sys
import textwrap

from _subproc import REPO_ROOT, run_env

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from repro.configs.base import SHAPES, get_reduced_config, ShapeConfig
    from repro.launch import roofline as rl
    from repro.models.registry import build_model, input_specs
    from repro.launch.mesh import make_mesh
    from repro.models.sharding import use_mesh
    from repro.training.step import (make_train_step, state_abstract,
                                     state_logical, tree_shardings)

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    shapes = {
        "train": ShapeConfig("t", 64, 8, "train"),
        "prefill": ShapeConfig("p", 64, 8, "prefill"),
        "decode": ShapeConfig("d", 64, 8, "decode"),
    }
    for arch in ("granite_3_8b", "mixtral_8x22b", "mamba2_130m"):
        cfg = get_reduced_config(arch)
        model = build_model(cfg)
        for kind, shape in shapes.items():
            with use_mesh(mesh):
                specs, logical = input_specs(cfg, shape, model)
                in_sh = tree_shardings(specs, logical)
                p_abs = model.abstract_params()
                p_sh = tree_shardings(p_abs, model.logical_tree())
                if kind == "train":
                    step = make_train_step(model, cfg)
                    st = state_abstract(model, cfg)
                    st_sh = tree_shardings(st, state_logical(model))
                    lowered = jax.jit(step, in_shardings=(st_sh, in_sh)).lower(st, specs)
                elif kind == "prefill":
                    lowered = jax.jit(model.prefill, in_shardings=(p_sh, in_sh)).lower(p_abs, specs)
                else:
                    lowered = jax.jit(
                        model.decode_step,
                        in_shardings=(p_sh, in_sh["cache"], in_sh["tokens"]),
                    ).lower(p_abs, specs["cache"], specs["tokens"])
                compiled = lowered.compile()
            r, hc = rl.analyze(compiled, arch=arch, shape=shape, cfg=cfg,
                               mesh_name="2x2x2", chips=8)
            assert r.flops_per_chip > 0, (arch, kind)
            assert r.bytes_per_chip > 0, (arch, kind)
            assert r.dominant in ("compute", "memory", "collective")
            print("CELL_OK", arch, kind, r.dominant)
    print("DRYRUN_SMALL_OK")
""")


def test_dryrun_small_mesh_all_kinds():
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True, timeout=900,
        env=run_env(), cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "DRYRUN_SMALL_OK" in proc.stdout
    assert proc.stdout.count("CELL_OK") == 9
