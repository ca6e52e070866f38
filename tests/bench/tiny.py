"""A benchmark root at a size a test can run on the CPU: the repository's
own programs, references and metric readers beside tiny configurations of
both architectures' variants, derived from the real configuration files."""

import json
from pathlib import Path

from conftest import ROOT

LIMIT = 0.05   # tiny-size max_logit_gap limit; see test_check.py

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "vocab_size": 512}
TRAFFIC = {
    "chat": {"generator": "stratified", "loop": "open", "rate_per_s": 20,
             "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.8, "min": 4, "max": 40},
             "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.6, "min": 2, "max": 20}},
    "decode": {"generator": "stratified", "loop": "closed", "clients": 8, "think_s": 0.0,
               "prompt_len": {"dist": "uniform", "min": 4, "max": 16},
               "output_len": {"dist": "uniform", "min": 16, "max": 40}, "requests": 32},
}


def make_root(base: Path) -> Path:
    (base / "bench").mkdir(parents=True)
    for d in ("programs", "references", "metrics", "generators", "loops"):
        (base / "bench" / d).symlink_to(ROOT / "bench" / d)
    for d in ("configs", "traffic", "cells"):
        (base / "bench" / d).mkdir()
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    man["configs"], man["workloads"] = [], []
    for src, name, kv in (("granite-3-8b", "tiny", 2), ("stablelm-3b", "tinyln", 4)):
        cfg = json.loads((ROOT / "bench" / "configs" / f"{src}.json").read_text())
        cfg.update(TINY, name=name, num_key_value_heads=kv,
                   serving={"n_slots": 4, "cache_len": 64, "pack_width": 4})
        (base / "bench" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        man["configs"].append({"name": name, "source": cfg["source"],
                               "file": f"bench/configs/{name}.json", "reduced": [], "why": "test"})
        for traffic in ("chat", "decode"):
            cell = f"{name}.{traffic}"
            man["workloads"].append({"name": cell, "config": name, "traffic": traffic,
                                     "chips": 1, "why": "test"})
            (base / "bench" / "cells" / f"{cell}.json").write_text(json.dumps(
                {"max_logit_gap": LIMIT, "check_requests": 8, "block_rows": 4}))
    for traffic, t in TRAFFIC.items():
        (base / "bench" / "traffic" / f"{traffic}.json").write_text(json.dumps(t))
    for m in man["end_to_end"] + man["per_layer"]:
        m.pop("workloads", None)
    (base / "BENCHMARK.json").write_text(json.dumps(man))
    return base


def run(root: Path, cell: str, seed: int, *, control: bool = False, seconds: float = 2.0):
    from bench import run as bench_run

    args = bench_run.parse(["--workload", cell, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", "0"])
    return bench_run.run_cell(args, on_chip=False, root=root, control=control)
