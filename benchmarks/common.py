"""Shared helpers for the benchmark suite: CSV tables, claim checks, and the
shared ``BENCH_<section>.json`` emitter every ``benchmarks/run.py`` section
writes through (one schema: claims, headline metrics, pass/fail)."""

from __future__ import annotations

import json
import sys
import time
from contextlib import contextmanager


def table(title: str, header: list[str], rows: list[list]):
    print(f"\n## {title}")
    print(",".join(str(h) for h in header))
    for r in rows:
        print(",".join(f"{x:.4g}" if isinstance(x, float) else str(x) for x in r))
    sys.stdout.flush()


# --smoke: tiny iteration counts so CI can exercise every benchmark's code
# path in seconds.  Claims still print but are not load-bearing at smoke
# scale (the curves need full durations); run.py only gates on them in a
# full run.
SMOKE = False

FAILED_CLAIMS: list[str] = []


def smoke(full, tiny):
    """Pick the full-scale or smoke-scale value for an iteration knob."""
    return tiny if SMOKE else full


def zipf_draws(n: int, n_items: int, skew: float, rng) -> list[int]:
    """n inverse-CDF draws over items weighted 1/(k+1)^skew (skew 0 =
    uniform).  The one Zipf sampler for every bench workload — domain mixes
    and shared-prefix pools must skew identically to be comparable."""
    weights = [1.0 / (k + 1) ** skew for k in range(n_items)]
    tot = sum(weights)
    out = []
    for _ in range(n):
        r = rng.random() * tot
        acc = 0.0
        for k, w in enumerate(weights):
            acc += w
            if r <= acc:
                out.append(k)
                break
        else:
            out.append(n_items - 1)
    return out


def claim(name: str, ok: bool, detail: str = ""):
    status = "PASS" if ok else "FAIL"
    if not ok:
        FAILED_CLAIMS.append(name)
    if _SECTION is not None:
        _SECTION["claims"].append({"name": name, "ok": bool(ok), "detail": detail})
    print(f"CLAIM [{status}] {name}  {detail}")
    return ok


# -- shared BENCH_<section>.json schema ---------------------------------------
# One record per run.py section: {"bench", "schema", "smoke", "claims":
# [{name, ok, detail}], "metrics": {...}, "passed"}.  Claims land via claim()
# while the section is active; headline numbers via headline() /
# headline_registry() (the latter snapshots a repro.obs.MetricsRegistry, which
# is how sections source their numbers from the unified registry).
BENCH_SCHEMA = 1

_SECTION: dict | None = None


@contextmanager
def bench_section(name: str, json_dir: str = "."):
    """Collect claims + headline metrics for one bench section and write
    ``BENCH_<name>.json`` on exit (even when the section raises — a partial
    record with its failed claims beats no record)."""
    global _SECTION
    prev = _SECTION
    _SECTION = {
        "bench": name,
        "schema": BENCH_SCHEMA,
        "smoke": SMOKE,
        "claims": [],
        "metrics": {},
    }
    try:
        yield _SECTION
    finally:
        rec, _SECTION = _SECTION, prev
        rec["passed"] = all(c["ok"] for c in rec["claims"])
        path = _os.path.join(json_dir, f"BENCH_{name}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2, default=str)
        print(
            f"[wrote {path}: {len(rec['claims'])} claims, "
            f"{'pass' if rec['passed'] else 'FAIL'}]"
        )


def headline(**metrics) -> None:
    """Merge headline numbers into the active section's record (no-op when
    no section is active, so benches stay runnable standalone)."""
    if _SECTION is not None:
        _SECTION["metrics"].update(metrics)


def headline_registry(registry, prefix: str = "") -> None:
    """Snapshot a ``repro.obs.MetricsRegistry`` into the active section's
    metrics — the registry-sourced path for BENCH records."""
    if _SECTION is not None:
        snap = registry.collect()
        if prefix:
            snap = {f"{prefix}{k}": v for k, v in snap.items()}
        _SECTION["metrics"].update(snap)


def ascii_plot(title: str, xs, series: dict, *, width: int = 64, height: int = 16,
               logy: bool = False):
    """Paper-style ASCII line chart: one mark per series, shared y scale.

    ``series`` maps name -> list of y values (same length as ``xs``).  Keeps
    benchmark output self-contained (no matplotlib in the container)."""
    import math

    marks = "ox+*#@%&"
    ys_all = [y for ys in series.values() for y in ys if y is not None]
    if not ys_all:
        return
    f = (lambda v: math.log10(max(v, 1e-12))) if logy else (lambda v: v)
    lo, hi = min(f(y) for y in ys_all), max(f(y) for y in ys_all)
    span = (hi - lo) or 1.0
    grid = [[" "] * width for _ in range(height)]
    for si, (name, ys) in enumerate(series.items()):
        for i, y in enumerate(ys):
            if y is None:
                continue
            col = round(i * (width - 1) / max(1, len(xs) - 1))
            row = height - 1 - round((f(y) - lo) / span * (height - 1))
            grid[row][col] = marks[si % len(marks)]
    print(f"\n## {title}")
    ylab = "log10 " if logy else ""
    print(f"  y: {ylab}[{lo:.3g} .. {hi:.3g}]   x: {xs[0]} .. {xs[-1]}")
    for row in grid:
        print("  |" + "".join(row))
    print("  +" + "-" * width)
    legend = "   ".join(f"{marks[i % len(marks)]}={name}" for i, name in enumerate(series))
    print(f"   {legend}")
    sys.stdout.flush()


@contextmanager
def timed(name: str):
    t0 = time.time()
    yield
    print(f"({name}: {time.time() - t0:.1f}s)")


THREADS_2S = [1, 2, 4, 8, 16, 24, 36, 48, 70]
THREADS_4S = [1, 2, 4, 8, 16, 36, 72, 108, 142]
LOCK_SET = ["mcs", "cna", "cna_opt", "c-bo-mcs", "hmcs", "tas", "ticket", "hbo"]
MAIN_LOCKS = ["mcs", "cna", "cna_opt", "c-bo-mcs", "hmcs"]


# -- subprocess harness (mirrors tests/_subproc.py — keep the two in sync) ----
# Subprocesses must not inherit hardcoded machine paths, and must pin
# JAX_PLATFORMS=cpu: with libtpu installed but no TPU attached, an unpinned
# jax spends minutes probing TPU metadata endpoints.
import os as _os

REPO_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))


def subproc_env() -> dict:
    env = dict(_os.environ)
    env["PYTHONPATH"] = _os.path.join(REPO_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    return env
