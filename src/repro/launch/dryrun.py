import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks the device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract memory/cost/collective evidence.

    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out results/dryrun

Each cell produces a JSON record: memory_analysis (proves it fits),
cost_analysis (FLOPs/bytes for §Roofline), the collective schedule (op kind /
bytes / group size / ICI-vs-DCN), and the three roofline terms.
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs.base import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models.registry import build_model, input_specs
from repro.models.sharding import use_mesh
from repro.training.step import (
    make_train_step,
    state_abstract,
    state_logical,
    tree_shardings,
)


def lower_cell(arch: str, shape_name: str, multi_pod: bool, *, hlo_dir: str | None = None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name, "status": "skipped", "why": why}

    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()
    with use_mesh(mesh):
        specs, logical = input_specs(cfg, shape, model)
        in_sh = tree_shardings(specs, logical)
        p_abs = model.abstract_params()
        p_sh = tree_shardings(p_abs, model.logical_tree())

        if shape.kind == "train":
            step = make_train_step(model, cfg)
            st_abs = state_abstract(model, cfg)
            st_sh = tree_shardings(st_abs, state_logical(model))
            lowered = jax.jit(
                step, in_shardings=(st_sh, in_sh), donate_argnums=0
            ).lower(st_abs, specs)
        elif shape.kind == "prefill":
            lowered = jax.jit(
                model.prefill, in_shardings=(p_sh, in_sh)
            ).lower(p_abs, specs)
        else:  # decode
            lowered = jax.jit(
                model.decode_step,
                in_shardings=(p_sh, in_sh["cache"], in_sh["tokens"]),
                donate_argnums=1,
            ).lower(p_abs, specs["cache"], specs["tokens"])
        t_lower = time.time() - t0

        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    print(mem)                       # proves it fits (per-device bytes)
    ca = compiled.cost_analysis()
    print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})

    r, hc = rl.analyze(compiled, arch=arch, shape=shape, cfg=cfg, mesh_name=mesh_name, chips=chips)
    by_kind = hc.collectives

    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        with open(os.path.join(hlo_dir, f"{arch}.{shape_name}.{mesh_name}.hlo.txt"), "w") as f:
            f.write(compiled.as_text())

    out = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "status": "ok",
        "chips": chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_gb": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2**30, 3),
        },
        "cost": {k: float(v) for k, v in ca.items() if isinstance(v, (int, float))},
        "collectives": by_kind,
        "roofline": r.to_dict(),
    }
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--hlo-dump", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" or args.all else args.arch.replace("-", "_").replace(".", "").split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                mesh_name = "2x16x16" if multi else "16x16"
                path = os.path.join(args.out, f"{arch}.{shape_name}.{mesh_name}.json")
                if args.skip_existing and os.path.exists(path):
                    continue
                tag = f"[{arch} {shape_name} {mesh_name}]"
                print(f"{tag} lowering...", flush=True)
                try:
                    rec = lower_cell(arch, shape_name, multi, hlo_dir=args.hlo_dump)
                except Exception as e:  # noqa: BLE001 — a failed cell is a bug to report
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                           "status": "error", "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if rec["status"] == "ok":
                    rr = rec["roofline"]
                    print(
                        f"{tag} OK compile={rec['compile_s']}s "
                        f"mem/dev={rec['memory']['peak_per_device_gb']}GB "
                        f"c={rr['compute_s']:.4f} m={rr['memory_s']:.4f} x={rr['collective_s']:.4f} "
                        f"dom={rr['dominant']} mfu={rr['mfu']:.3f}",
                        flush=True,
                    )
                else:
                    print(f"{tag} {rec['status'].upper()}: {rec.get('why') or rec.get('error')}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
