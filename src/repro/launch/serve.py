"""Serving driver: continuous batching with the CNA admission scheduler.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b \
        --requests 32 --domains 2 --scheduler cna

Prints per-policy throughput/locality/fairness so the CNA-vs-FIFO trade-off
is visible on a real (reduced-config) model.  ``--derived-homes`` drops the
caller-supplied domain oracle: requests submit with ``domain=None`` and the
engine derives homes from the prefix index over a NUMA-placed slot cache
(pod topology over ``--domains``), with shared prompt prefixes so the index
has something to match.  ``--replicas N`` runs the router tier instead: N
engine replicas behind ``repro.router.ReplicaRouter`` — federated prefix
summaries steer each session to the replica already holding its prefix, and
per-engine ``PrefixKVStore`` reuse turns the steering into skipped prefill
positions (printed per replica).  KV shipping is on by default in the fleet
demo (``--no-kv-ship`` reverts to shed-and-re-prefill): every priced
ship-vs-reprefill decision prints one ``[ship?]`` line — the runnable
companion to docs/architecture.md's router walkthrough.  ``--fissile`` turns
on the contention-adaptive fast path (uncontended sessions dispatch home in
one step; the ``[router]`` line reports ``fast_dispatches``).

``--arrivals RATE`` switches the driver to a continuous Poisson arrival
process (RATE requests per engine tick, mixed prompt lengths) against the
bucketed/packed/AOT-warmed engine and prints wall-clock tokens/sec +
TTFT p50/p99 — the live demo of ``repro.serving.batching``.

``--preset`` picks the model config: ``reduced`` (the default, CPU-sized) or
a cut the architecture's config module defines, such as granite-3-8b's
``one_chip`` (published widths, 20 of 40 layers, for one TPU v5e).  With
several devices, ``--replicas N`` places replica ``r`` and its own copy of
the params on device ``r mod n_devices``.

``--regions N`` runs the region tier instead (jax-free): a deterministic
diurnal multi-tenant trace (``repro.workload``) replayed through
``repro.region`` — N regions of simulated fleets behind the federated
``RegionRouter``, against a region-oblivious least-loaded control — and
prints locality, admission-stall percentiles, and per-tenant stall
summaries.  ``--tenant-caps K`` adds the (tenant x fleet) fairness governor.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.base import get_preset_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build_model
from repro.serving.engine import DecodeEngine, Request
from repro.serving.scheduler import CNAScheduler, FIFOScheduler


def _mk_obs(args):
    """--trace/--metrics: one Tracer + MetricsRegistry per driver run (both
    None-off, so the default path stays zero-cost)."""
    from repro.obs import MetricsRegistry, Tracer

    tracer = Tracer() if args.trace else None
    registry = MetricsRegistry() if args.metrics else None
    return tracer, registry


def _emit_obs(args, tracer, registry, trace_path=None):
    from repro.obs import flame, render_prometheus, to_jsonl

    if tracer is not None:
        path = trace_path or args.trace
        n = to_jsonl(tracer, path)
        print(f"[trace] wrote {n} spans to {path}")
        traces = tracer.traces()
        if traces:
            deepest = max(traces, key=lambda t: len(tracer.for_trace(t)))
            print(flame(tracer, deepest))
    if registry is not None:
        print("[metrics]")
        print(render_prometheus(registry))


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--preset", default="reduced", choices=["reduced", "one_chip"],
                    help="model config: the CPU-sized reduced cut, or the "
                         "architecture's one-chip depth cut at published "
                         "widths (granite-3-8b)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--domains", type=int, default=2)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--scheduler", default="both", choices=["cna", "fifo", "both"])
    ap.add_argument("--fairness-threshold", type=lambda x: int(x, 0), default=0xF)
    ap.add_argument("--switch-cost", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--derived-homes", action="store_true",
                    help="submit domain=None and derive homes from the prefix "
                         "index over a placement-aware slot cache")
    ap.add_argument("--replicas", type=int, default=1,
                    help="front N engine replicas with the federated router "
                         "tier (repro.router) instead of a single engine")
    ap.add_argument("--sync-every", type=int, default=4,
                    help="router ticks between federation summary syncs")
    ap.add_argument("--no-kv-ship", action="store_true",
                    help="disable priced prefix-KV shipping in the fleet "
                         "demo (PR 4's shed-and-re-prefill behaviour)")
    ap.add_argument("--fissile", action="store_true",
                    help="enable the contention-adaptive fast path in the "
                         "fleet demo: uncontended arrivals dispatch to their "
                         "home replica in one step, contention inflates back "
                         "to full CNA admission")
    ap.add_argument("--regions", type=int, default=0, metavar="N",
                    help="run the region tier demo: a diurnal multi-tenant "
                         "trace over N regions of fleets (jax-free)")
    ap.add_argument("--tenants", type=int, default=4,
                    help="with --regions: tenant profiles in the trace mix")
    ap.add_argument("--horizon", type=int, default=4096,
                    help="with --regions: trace horizon in ticks")
    ap.add_argument("--tenant-caps", type=int, default=None, metavar="K",
                    help="with --regions: cap each (tenant x fleet) pair at "
                         "K in-flight sessions (the fairness governor)")
    ap.add_argument("--arrivals", type=float, default=None, metavar="RATE",
                    help="drive a continuous Poisson arrival process at RATE "
                         "requests/tick (mixed prompt lengths) and print "
                         "tokens/sec + TTFT p50/p99")
    ap.add_argument("--paged", action="store_true",
                    help="back the engine's KV with the refcounted page "
                         "table (copy-on-write prefix sharing); dense-"
                         "attention stacks only")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page with --paged (must divide "
                         "--cache-len)")
    ap.add_argument("--trace", metavar="OUT.jsonl", default=None,
                    help="record causal request spans (repro.obs.Tracer), "
                         "dump them as JSONL to OUT.jsonl and print one "
                         "ASCII flame summary for the deepest trace")
    ap.add_argument("--metrics", action="store_true",
                    help="register every stat surface into the unified "
                         "repro.obs.MetricsRegistry and print its "
                         "Prometheus-style rendering at exit")
    return ap.parse_args(argv)


def load_model(args):
    """-> (cfg, model, params): the ``--arch``/``--preset`` config with
    random params from ``--seed``, drawn by one compiled program (eager,
    every leaf's draw compiles on its own)."""
    cfg = get_preset_config(args.arch, args.preset)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    return cfg, model, params


def main(argv=None) -> int:
    args = parse_args(argv)

    if args.regions > 0:
        return serve_region(args)
    enable_compile_cache()
    if args.arrivals is not None:
        return serve_arrivals(args)
    if args.replicas > 1:
        return serve_fleet(args)

    cfg, model, params = load_model(args)
    rng = np.random.default_rng(args.seed)
    if args.derived_homes:
        # a small pool of shared prefixes (Zipf-free uniform draw keeps the
        # driver simple) + unique tails: the index has prefixes to re-match
        n_shared = max(2, args.prompt_len // 2)
        shared = [rng.integers(0, cfg.vocab, n_shared).astype(np.int32)
                  for _ in range(max(2, args.domains))]
        base = [
            Request(rid=i,
                    prompt=np.concatenate([
                        shared[int(rng.integers(0, len(shared)))],
                        rng.integers(0, cfg.vocab, args.prompt_len - n_shared).astype(np.int32),
                    ]),
                    max_new=args.max_new, domain=None)
            for i in range(args.requests)
        ]
    else:
        base = [
            Request(rid=i, prompt=rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32),
                    max_new=args.max_new, domain=int(rng.integers(0, args.domains)))
            for i in range(args.requests)
        ]

    from repro.core.topology import pod

    def engine_kwargs(mk_sched):
        kw = dict(paging=args.paged, page_size=args.page_size) if args.paged else {}
        if not args.derived_homes:
            return dict(scheduler=mk_sched(), **kw)
        return dict(scheduler=mk_sched(topology=pod(1, args.domains)),
                    placement="nearest_spill", prefix_index=True, **kw)

    policies = {"cna": lambda **kw: CNAScheduler(fairness_threshold=args.fairness_threshold, **kw),
                "fifo": lambda **kw: FIFOScheduler(**kw)}
    run = [args.scheduler] if args.scheduler != "both" else ["cna", "fifo"]
    registry = None
    if args.metrics:
        from repro.obs import MetricsRegistry

        registry = MetricsRegistry()
    for name in run:
        # a fresh tracer per policy: the two arms reuse request ids, and one
        # JSONL per arm keeps the traces causally clean
        tracer = None
        if args.trace:
            from repro.obs import Tracer

            tracer = Tracer()
        reqs = [Request(r.rid, r.prompt, r.max_new, r.domain) for r in base]
        eng = DecodeEngine(model, params, n_slots=args.slots, cache_len=args.cache_len,
                           domain_switch_cost=args.switch_cost, tracer=tracer,
                           **engine_kwargs(policies[name]))
        t0 = time.time()
        if args.derived_homes:
            mid = len(reqs) // 2
            eng.run(reqs[:mid])  # first wave warms the index from placements
            eng.run(reqs[mid:])  # second wave homes by matched prefixes
        else:
            eng.run(reqs)
        wall = time.time() - t0
        m = eng.scheduler.metrics
        tokens = sum(len(r.out) for r in reqs)
        extra = ""
        if eng.prefix_index is not None:
            tel = eng.slots.telemetry
            extra = (f" derived={tel.derived_homes} "
                     f"prefix_hit_rate={tel.prefix_hit_rate:.2f} "
                     f"placement_locality={tel.locality:.2f}")
        print(f"[{name}] requests={len(reqs)} tokens={tokens} sim_time={eng.sim_time} "
              f"locality={m.locality:.2f} switches={m.domain_switches} "
              f"fairness={m.fairness_factor():.3f} wall={wall:.1f}s "
              f"tok_per_simtick={tokens / max(1, eng.sim_time):.2f}{extra}")
        if args.paged:
            # the page-table gauges, one line — the same numbers --metrics
            # exports as {name}_engine_pages_* through the registry
            pt = eng.slots.table
            print(f"  [pages] total={pt.pages_total} shared={pt.pages_shared} "
                  f"free={pt.pages_free} kv_bytes_held={pt.kv_bytes_held} "
                  f"cow_copies={pt.cow_copies}")
        if registry is not None:
            eng.register_metrics(registry, prefix=f"{name}_engine")
        if tracer is not None:
            path = args.trace if len(run) == 1 else f"{name}.{args.trace}"
            _emit_obs(args, tracer, None, trace_path=path)
    _emit_obs(args, None, registry)
    return 0


def serve_region(args) -> int:
    """The --regions demo: the diurnal multi-tenant trace through the region
    tier (fleets-of-fleets, jax-free), paired against a region-oblivious
    least-loaded control on the identical schedule."""
    from repro.region import simulate_region
    from repro.workload import DiurnalWave, TraceGenerator, uniform_tenants

    tracer, registry = _mk_obs(args)
    gen = TraceGenerator(
        n_regions=args.regions,
        tenants=uniform_tenants(args.tenants, args.regions,
                                followup_p=0.4, suffix_len=24),
        seed=args.seed,
        wave=DiurnalWave(period=max(256, args.horizon // 3), amplitude=0.8),
        base_rate=0.03,
    )
    trace = gen.generate(horizon=args.horizon)
    print(f"[trace] {len(trace)} requests, {args.regions} regions, "
          f"{args.tenants} tenants, "
          f"{sum(1 for r in trace.requests if r.turn > 0)} follow-up turns")
    t0 = time.time()
    results = {}
    for arm in ("region", "least_loaded"):
        results[arm] = simulate_region(
            arm, trace, seed=args.seed,
            tenant_caps=args.tenant_caps if arm == "region" else None,
            tracer=tracer if arm == "region" else None,
            registry=registry if arm == "region" else None,
        )
    wall = time.time() - t0
    for arm, r in results.items():
        print(f"[{arm}] served={r.served} rejected={r.rejected} "
              f"locality={r.reuse_fraction:.2f} "
              f"reprefill_tokens={r.reprefill_tokens}/{r.routed_tokens} "
              f"stall_p50={r.admission_stall_p50:.0f} "
              f"stall_p99={r.admission_stall_p99:.0f} sheds={r.sheds} "
              f"deposits={r.deposits} per_fleet={r.per_fleet_served}")
    reg = results["region"]
    print("  [tenants]")
    for tenant, summary in sorted(reg.tenant_stalls.summary().items()):
        print(f"    tenant {tenant}: stall p50={summary['p50']:.0f} "
              f"p99={summary['p99']:.0f} n={summary['count']}")
    if reg.tenant_parked or reg.tenant_rejected:
        print(f"  [governor] parked={reg.tenant_parked} "
              f"unparked={reg.tenant_unparked} rejected={reg.tenant_rejected}")
    print(f"  (wall={wall:.1f}s)")
    _emit_obs(args, tracer, registry)
    return 0


def arrival_workload(args, vocab: int):
    """-> (requests, arrival ticks): ``--requests`` prompts of mixed lengths
    from ``--seed``, each short enough to decode all ``--max-new`` tokens
    within ``--cache-len``, arriving as a Poisson process at ``--arrivals``
    requests per engine tick."""
    rng = np.random.default_rng(args.seed)
    lens = rng.integers(2, args.cache_len - args.max_new, args.requests)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, vocab, int(l)).astype(np.int32),
                max_new=args.max_new, domain=int(rng.integers(0, args.domains)))
        for i, l in enumerate(lens)
    ]
    arrivals = np.floor(
        np.cumsum(rng.exponential(1.0 / args.arrivals, args.requests))
    ).astype(int).tolist()
    return reqs, arrivals


def arrivals_engine(args, model, params, tracer=None) -> DecodeEngine:
    """The engine ``--arrivals`` drives: CNA admission, bucketed/packed
    prefill.  Construction compiles every bucket ahead of time."""
    return DecodeEngine(model, params, n_slots=args.slots, cache_len=args.cache_len,
                        scheduler=CNAScheduler(fairness_threshold=args.fairness_threshold),
                        domain_switch_cost=args.switch_cost, tracer=tracer)


def drive_arrivals(eng: DecodeEngine, reqs, arrivals) -> tuple[dict, float]:
    """Submit each request at its arrival tick and step until every one
    retires.  -> (rid -> seconds from submit to first token, wall seconds).
    TTFT includes queueing — what a serving SLO sees."""
    submit_at, ttft = {}, {}
    i = tick = 0
    t0 = time.time()
    while i < len(reqs) or len(eng.scheduler) or eng.active_req:
        while i < len(reqs) and arrivals[i] <= tick:
            submit_at[reqs[i].rid] = time.time()
            eng.submit(reqs[i])
            i += 1
        eng.step()
        for r in reqs:
            if r.rid not in ttft and r.out:
                ttft[r.rid] = time.time() - submit_at[r.rid]
        tick += 1
    return ttft, time.time() - t0


def serve_arrivals(args) -> int:
    """The --arrivals demo: a continuous Poisson arrival process against the
    engine (bucketed/packed/AOT-warmed), wall-clock measured."""
    cfg, model, params = load_model(args)
    reqs, arrivals = arrival_workload(args, cfg.vocab)
    tracer, registry = _mk_obs(args)
    t_build = time.time()
    eng = arrivals_engine(args, model, params, tracer)
    warm = time.time() - t_build  # AOT bucket traces compile in here, not below
    ttft, wall = drive_arrivals(eng, reqs, arrivals)

    tokens = sum(len(r.out) for r in reqs)
    waits = np.array([ttft[r.rid] for r in reqs])
    cc = eng.compile_counts
    traces = sum(n for entry, n in cc.items() if entry != "decode")
    print(f"[arrivals] rate={args.arrivals}/tick requests={len(reqs)} "
          f"tokens={tokens} tokens_per_sec={tokens / wall:.1f} "
          f"ttft_p50={np.percentile(waits, 50) * 1e3:.0f}ms "
          f"ttft_p99={np.percentile(waits, 99) * 1e3:.0f}ms "
          f"prefill_traces={traces} decode_traces={cc['decode']} "
          f"warmup={warm:.1f}s wall={wall:.1f}s")
    if registry is not None:
        eng.register_metrics(registry)
    _emit_obs(args, tracer, registry)
    return 0


def fleet_sessions(args, vocab: int) -> list:
    """``--requests`` sessions from ``--seed``: one of a few shared prefixes
    plus a unique tail, ``--prompt-len`` tokens in all."""
    from repro.router import Session

    rng = np.random.default_rng(args.seed)
    n_shared = max(2, args.prompt_len // 2)
    shared = [rng.integers(0, vocab, n_shared).astype(np.int32)
              for _ in range(max(2, args.replicas))]
    return [
        Session(sid=i,
                prompt=tuple(int(t) for t in np.concatenate([
                    shared[int(rng.integers(0, len(shared)))],
                    rng.integers(0, vocab, args.prompt_len - n_shared).astype(np.int32),
                ])),
                decode_len=args.max_new)
        for i in range(args.requests)
    ]


def build_fleet(args, model, params, devices, tracer=None):
    """-> (router, replicas): ``--replicas`` engines behind the router, replica
    ``r`` on ``devices[r % len(devices)]`` with its own copy of ``params``
    and its caches there (one copy per device; replicas sharing a device
    share it)."""
    from repro.core.topology import pod
    from repro.router import EngineReplica, ReplicaRouter

    placed = {}
    replicas = []
    for r in range(args.replicas):
        dev = devices[r % len(devices)]
        if dev not in placed:
            placed[dev] = jax.device_put(params, dev)
        with jax.default_device(dev):
            eng = DecodeEngine(
                model, placed[dev], n_slots=args.slots, cache_len=args.cache_len,
                scheduler=CNAScheduler(fairness_threshold=args.fairness_threshold,
                                       topology=pod(1, args.domains)),
                placement="nearest_spill", prefix_index=True, prefix_kv=True,
                paging=args.paged, page_size=args.page_size,
                domain_switch_cost=args.switch_cost, tracer=tracer,
            )
        replicas.append(EngineReplica(r, eng, device=dev))
    # the shared tracer nests each engine's "request" span under the router's
    # "session" span (same trace key), giving the one-trace-every-level view
    router = ReplicaRouter(replicas, sync_every=args.sync_every,
                           kv_ship=not args.no_kv_ship,
                           fissile=args.fissile, tracer=tracer)
    return router, replicas


def drive_fleet(router, replicas, sessions) -> float:
    """Submit one session per router tick and step every replica until all
    sessions retire; each session's ``out`` then holds its tokens.  Prints
    one line per priced ship-vs-reprefill decision.  -> wall seconds."""
    t0 = time.time()
    i = done = 0
    while done < len(sessions):
        router.tick()
        if i < len(sessions):
            router.submit(sessions[i])
            i += 1
        for session, target, _dist in router.dispatch():
            d = session.ship
            if d is not None:
                # one line per priced decision, the docs walkthrough's
                # runnable companion: what the argmin saw and what happened
                outcome = d.choice
                if d.choice == "ship" and not d.executed:
                    outcome = "ship (refused -> reprefill)"
                print(f"  [ship?] sid={session.sid} home={session.home} -> "
                      f"replica {target}: src={d.src} holds {d.src_matched} "
                      f"tok (target {d.local_matched}); "
                      f"ship={d.ship_total}cy vs reprefill="
                      f"{d.reprefill_cycles}cy -> {outcome}")
        for rep in replicas:
            for session, ttft in rep.step():
                router.complete(session, ttft=ttft)
                done += 1
    return time.time() - t0


def serve_fleet(args) -> int:
    """The --replicas demo: N engines behind the router, spread over the
    visible devices."""
    cfg, model, params = load_model(args)
    sessions = fleet_sessions(args, cfg.vocab)
    tracer, registry = _mk_obs(args)
    router, replicas = build_fleet(args, model, params, jax.devices(), tracer)
    wall = drive_fleet(router, replicas, sessions)

    s = router.stats
    print(f"[router] replicas={args.replicas} sessions={len(sessions)} "
          f"reuse_frac={s.reuse_fraction:.2f} hit_rate={s.hit_rate:.2f} "
          f"reprefill_tokens={s.reprefill_tokens}/{s.routed_tokens} "
          f"sheds={s.sheds} ships={s.ships} shipped_tok={s.shipped_tokens} "
          f"reprefill_avoided={s.reprefill_avoided} syncs={s.syncs} "
          f"fast_dispatches={s.fast_dispatches} "
          f"dispatch_locality={router.metrics.locality:.2f} wall={wall:.1f}s")
    for rep in replicas:
        eng = rep.engine
        print(f"  [replica {rep.rid}] served={eng.scheduler.metrics.admitted} "
              f"prefill_positions={eng.prefill_positions} "
              f"reused_positions={eng.reused_positions} "
              f"prefix_hit_rate={eng.slots.telemetry.prefix_hit_rate:.2f} "
              f"cap={router.fleet.cap(rep.rid)}")
    if registry is not None:
        router.stats.register_into(registry)
        router.scheduler.metrics.register_into(registry, prefix="router_sched")
        if router.fabric is not None:
            router.fabric.stats.register_into(registry)
        for rep in replicas:
            rep.engine.register_metrics(registry, prefix=f"replica{rep.rid}")
    _emit_obs(args, tracer, registry)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
