"""Finds what a cell is made of, by the names ``BENCHMARK.json`` gives.

A cell ``<config>.<traffic>`` resolves to ``bench/configs/<config>.json``
(the configuration as run), ``bench/traffic/<traffic>.json`` (the traffic
mix), ``bench/cells/<cell>.json`` (its correctness limits and sample size),
the architecture's files ``bench/programs/<architecture>.py`` (how the
program under test is built) and ``bench/references/<architecture>.py``
(its plain float32 reference), the mix's ``bench/generators/<generator>.py``
and its schedule's ``bench/loops/<loop>.py`` (``bench/schedule.py``), and
one reader ``bench/metrics/<metric>.py`` per metric the cell reports.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list        # manifest entries this cell reports with --trace 0
    per_layer: list         # manifest entries this cell reports with --trace 1
    root: Path

    @property
    def architecture(self) -> str:
        return self.config["architecture"]

    def module(self, kind: str):
        """``programs`` or ``references`` module of the cell's architecture."""
        return load_module(self.root / "bench" / kind / f"{self.architecture}.py")

    def reader(self, metric: str):
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py").read

    def schedule(self, seed: int, seconds: float):
        """The traffic of one run, from the mix's generator."""
        gen = load_module(self.root / "bench" / "generators" / f"{self.traffic['generator']}.py")
        return gen.generate(self.traffic, seed, self.config["vocab_size"],
                            self.config["serving"]["cache_len"], seconds)

    def loop(self, name: str):
        """The module of ``bench/loops/`` that offers a schedule."""
        return load_module(self.root / "bench" / "loops" / f"{name}.py")


def load_module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(path)
    name = "bench_" + "_".join(path.relative_to(path.parents[1]).with_suffix("").parts)
    spec = importlib.util.spec_from_file_location(name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    man = manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in man["configs"]}
    cfg = _json(root / configs[w["config"]]["file"])
    e2e = [m for m in man["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in man["per_layer"]
                 if _reports(m, name) and m["moves"] in e2e_names]
    return Cell(
        name=name, chips=int(w["chips"]), config=cfg,
        traffic=_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        limits=_json(root / "bench" / "cells" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer, root=root,
    )
