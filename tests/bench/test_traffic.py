"""The traffic generator, the manifest's resolution to files, and the
entry point's refusal to run without a chip."""

import json
import math
import shutil
import subprocess
import sys
from statistics import NormalDist

import numpy as np
import pytest

from bench import spec
from bench.generators import stratified as generator

from conftest import ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))


def mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = generator.generate(mix(name), 2**33 + 7, 49155, 1024, 40)
    b = generator.generate(mix(name), 2**33 + 7, 49155, 1024, 40)
    c = generator.generate(mix(name), 2**33 + 8, 49155, 1024, 40)
    key = lambda s: [(r.due_s, r.prompt.tolist(), r.max_new) for r in s.requests]
    assert key(a) == key(b)
    assert key(a) != key(c)
    # another seed offers the same work in another order
    assert sorted(len(r.prompt) for r in a.requests) == sorted(len(r.prompt) for r in c.requests)
    assert sorted(r.max_new for r in a.requests) == sorted(r.max_new for r in c.requests)
    if a.loop == "open":
        gaps = lambda s: sorted(np.round(np.diff([0.0] + [r.due_s for r in s.requests]), 9))
        assert gaps(a) == gaps(c)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_their_distribution(name):
    t = mix(name)
    s = generator.generate(t, 5, 49155, 1024, 40)
    for key, got in (("prompt_len", [len(r.prompt) for r in s.requests]),
                     ("output_len", [r.max_new for r in s.requests])):
        d = t[key]
        assert min(got) >= d["min"] and max(got) <= d["max"]
        if d["dist"] == "lognormal":
            # the median of the stratified draws is the distribution's, and
            # the clip holds the tail
            assert abs(np.median(got) - d["median"]) <= 1
            q = np.quantile(got, 0.9)
            want = min(d["max"], d["median"] * math.exp(d["sigma"] * NormalDist().inv_cdf(0.9)))
            assert abs(q - want) / want < 0.05
        else:
            assert abs(np.mean(got) - (d["min"] + d["max"]) / 2) <= 1
    for r in s.requests:
        assert len(r.prompt) + r.max_new <= 1023
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 49155


@pytest.mark.parametrize("seconds", [10, 40])
def test_open_loop_offers_the_window_a_fixed_load(seconds):
    t = mix("chat")
    for seed in (9, 10):
        s = generator.generate(t, seed, 49155, 1024, seconds)
        dues = [r.due_s for r in s.requests]
        assert dues == sorted(dues)
        assert len(dues) == round(t["rate_per_s"] * seconds)
        # every request is due inside the window, at the stated mean rate
        assert 0.9 * seconds < dues[-1] < seconds


def test_a_request_that_cannot_fit_is_refused():
    t = dict(mix("chat"), output_len={"dist": "uniform", "min": 8, "max": 200})
    with pytest.raises(ValueError, match="does not fit"):
        generator.generate(t, 1, 49155, 1024, 40)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_workload_resolves(cell):
    c = spec.cell(cell)
    assert c.config["name"] == cell.split(".")[0]
    assert {"max_logit_gap", "check_requests", "block_rows"} <= set(c.limits)
    for kind in ("programs", "references"):
        assert (ROOT / "bench" / kind / f"{c.architecture}.py").is_file()
    s = c.schedule(2**33 + 1, MANIFEST["run_seconds"])
    assert s.requests and callable(c.loop(s.loop).run)
    lo, hi = s.prompt_range
    assert all(lo <= len(r.prompt) <= hi and r.max_new <= s.out_max for r in s.requests)
    names = [m["name"] for m in c.end_to_end + c.per_layer]
    assert "setup_s" in names and c.per_layer
    for m in names:
        assert callable(c.reader(m))


def test_every_metric_has_a_reader():
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def _cpu_env(tmp_path):
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    env["HOME"] = str(tmp_path)
    return env


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_run_fails_without_a_tpu(where, tmp_path):
    """Under JAX_PLATFORMS=cpu, and in a directory holding only the
    manifest and the benchmark's own files, it exits non-zero and prints no
    result."""
    root = ROOT
    if where == "alone":
        root = tmp_path / "alone"
        root.mkdir()
        shutil.copy(ROOT / "BENCHMARK.json", root)
        for p in MANIFEST["paths"]:
            shutil.copytree(ROOT / p, root / p, ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable] + MANIFEST["command"][1:] + [
            "--workload", MANIFEST["workloads"][0]["name"], "--seed", "1",
            "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=_cpu_env(tmp_path), cwd=root)
    assert proc.returncode != 0, proc.stdout
    assert '"metrics"' not in proc.stdout
