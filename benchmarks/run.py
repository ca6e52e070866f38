"""Benchmark orchestrator: one section per paper table/figure + the framework
benches (serving scheduler, slot placement, collective schedules, roofline).

    PYTHONPATH=src python -m benchmarks.run [--smoke] [section ...]

Sections: paper, locks, restriction, placement, serving, serving_prefix,
serving_continuous, serving_paging, router, fastpath, region, obs,
collectives, moe_ep, roofline.  Default: all.
``region`` (fleets-of-fleets under the diurnal multi-tenant trace,
``benchmarks/region_bench.py``) is jax-free and smoke-lane-safe.
``serving_prefix`` is the jax-free shared-prefix slice of the serving section
(prefix-index build/lookup/re-home) so the dependency-light smoke lane can
cover it; ``serving`` already includes it.  ``router`` (fleet routing on the
jax-free discrete-event simulator) and ``obs`` (tracing overhead + the
attribution conservation law, ``benchmarks/obs_bench.py``) are
smoke-lane-safe as well.
``serving_continuous`` is the continuous-batching slice (needs jax): it — and
the full ``serving`` section — emits machine-readable ``BENCH_serving.json``
(tokens/sec, TTFT p50/p99, prefill trace count) so the perf trajectory is
tracked across PRs; the CI bench lane runs it at smoke scale.

Every section runs inside ``benchmarks.common.bench_section`` and emits a
``BENCH_<section>.json`` record in one shared schema — claims, headline
metrics (sourced from the unified ``repro.obs.MetricsRegistry`` where the
section keeps one), pass/fail — so the bench trajectory file set covers the
whole suite, not just serving.  ``fastpath`` (the fissile contention-adaptive
fast path on the fleet router, ``benchmarks/fastpath_bench.py``) is jax-free
and smoke-lane-safe.

``--smoke`` shrinks every iteration knob (see benchmarks.common.smoke) so CI
can exercise each benchmark's code path in seconds; claims still print but do
not gate the exit code at smoke scale (the curves need full durations).  In a
full run, any failed CLAIM makes the process exit 1 so regressions cannot
scroll by silently.

docs/benchmarks.md documents every section — the claim each bench asserts
and how to read the ASCII figures.
"""

from __future__ import annotations

import sys
import time


def locks_hostlevel():
    """The faithful host-threads CNA implementation under stress (GIL-bound:
    correctness + admission-order behaviour, not wall-clock)."""
    from repro.core.cna import CNALock, MCSLock, run_lock_stress

    from . import common
    from .common import claim, table

    iters = common.smoke(300, 40)
    rows = []
    for name, factory in [
        ("cna", lambda sock: CNALock(numa_node_of=sock, threshold=0xF)),
        ("cna_opt", lambda sock: CNALock(numa_node_of=sock, threshold=0xF, shuffle_reduction=True)),
        ("mcs", lambda sock: MCSLock()),
    ]:
        t0 = time.time()
        shared = run_lock_stress(factory, n_threads=8, n_sockets=2, iters=iters)
        dt = time.time() - t0
        ok = shared.counter == 8 * iters
        rows.append([name, shared.counter, f"{dt:.2f}s", "OK" if ok else "RACE!"])
        claim(f"locks: mutual exclusion holds under stress ({name})", ok,
              f"counter={shared.counter}")
    table(f"host-threads lock stress (8 threads x {iters} iters, 2 virtual sockets)",
          ["lock", "counter", "time", "status"], rows)


def main() -> int:
    from repro.launch.compile_cache import enable_compile_cache

    from . import common

    enable_compile_cache()
    args = sys.argv[1:]
    if "--smoke" in args:
        args.remove("--smoke")
        common.SMOKE = True
    sections = args or [
        "paper", "locks", "restriction", "placement", "serving", "router",
        "fastpath", "region", "obs", "collectives", "moe_ep", "roofline",
    ]  # "serving" subsumes serving_prefix and serving_continuous
    t0 = time.time()
    # every section runs inside bench_section so it emits BENCH_<name>.json
    # in the shared schema (claims, headline metrics, pass/fail)
    if "paper" in sections:
        from . import paper_figures

        with common.bench_section("paper"):
            paper_figures.run_all()
    if "locks" in sections:
        with common.bench_section("locks"):
            locks_hostlevel()
    if "restriction" in sections:
        from . import restriction_bench

        with common.bench_section("restriction"):
            restriction_bench.run_all()
    if "placement" in sections:
        from . import placement_bench

        with common.bench_section("placement"):
            placement_bench.run_all()
    if "serving" in sections:
        from . import serving_bench

        with common.bench_section("serving"):
            serving_bench.run_all(json_path="BENCH_serving.json")
    else:
        if "serving_prefix" in sections:
            from . import serving_bench

            with common.bench_section("serving_prefix"):
                serving_bench.shared_prefix()
        if "serving_continuous" in sections:
            from . import serving_bench

            with common.bench_section("serving"):
                serving_bench.continuous(json_path="BENCH_serving.json")
    if "serving" in sections or "serving_paging" in sections:
        # always its own record (jax-free): the paged-KV headline must stay
        # comparable across PRs even when only the smoke lane runs
        from . import serving_bench

        with common.bench_section("serving_paging"):
            serving_bench.paging()
    if "router" in sections:
        from . import router_bench

        with common.bench_section("router"):
            router_bench.run_all()
    if "fastpath" in sections:
        from . import fastpath_bench

        with common.bench_section("fastpath"):
            fastpath_bench.run_all()
    if "region" in sections:
        from . import region_bench

        with common.bench_section("region"):
            region_bench.run_all()
    if "obs" in sections:
        from . import obs_bench

        with common.bench_section("obs"):
            obs_bench.run_all()
    if "collectives" in sections:
        from . import collectives_bench

        with common.bench_section("collectives"):
            collectives_bench.run_all()
    if "moe_ep" in sections:
        from . import moe_ep_bench

        with common.bench_section("moe_ep"):
            moe_ep_bench.run_all()
    if "roofline" in sections:
        from . import roofline_report

        with common.bench_section("roofline"):
            roofline_report.run_all()
    print(f"\n(total: {time.time() - t0:.1f}s)")
    if common.FAILED_CLAIMS:
        print(f"{len(common.FAILED_CLAIMS)} claim(s) FAILED:")
        for name in common.FAILED_CLAIMS:
            print(f"  - {name}")
        if not common.SMOKE:
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
