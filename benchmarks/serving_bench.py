"""CNA admission vs FIFO in the serving scheduler (the paper's policy carried
to the decode engine).  Three levels:

  * policy-level (fast): thousands of requests through the scheduler with a
    simulated switch cost — throughput/locality/fairness curves vs the
    fairness threshold (the paper's Fig. 6/8 trade-off, serving edition);
  * shared-prefix (fast, jax-free): a Zipf workload over a pool of common
    system-prompt prefixes through the scheduler + placement stack, comparing
    request homes *derived* from the prefix index (what production traffic
    has) against the caller-oracle (what the PR-2 benchmarks assumed) and a
    static domain-0 baseline;
  * engine-level (slower): a real reduced-config model decode on CPU.
"""

from __future__ import annotations

import random

import numpy as np

from repro.serving.scheduler import CNAScheduler, FIFOScheduler

from . import common
from .common import claim, smoke, table, zipf_draws


def policy_level(n_requests=4000, domains=4, switch_cost=8, service=1, seed=7):
    rows = []
    results = {}
    for name, mk in [
        ("fifo", lambda: FIFOScheduler()),
        ("cna_thr3", lambda: CNAScheduler(fairness_threshold=0x3, seed=seed)),
        ("cna_thrF", lambda: CNAScheduler(fairness_threshold=0xF, seed=seed)),
        ("cna_thrFF", lambda: CNAScheduler(fairness_threshold=0xFF, seed=seed)),
        ("cna_thrFFFF", lambda: CNAScheduler(fairness_threshold=0xFFFF, seed=seed)),
        # GCR-style admission control: only 16 requests circulate in the CNA
        # queues at once, the rest wait passivated.
        ("cna_rcr16", lambda: CNAScheduler(fairness_threshold=0xFF, seed=seed, max_active=16)),
    ]:
        rng = np.random.default_rng(seed)
        s = mk()
        t = 0
        # Poisson-ish arrivals, random domains; serve one request per grant
        arrivals = list(rng.integers(0, domains, n_requests))
        ai = 0
        served = 0
        while served < n_requests:
            # arrivals trickle in (2 per tick) so the queue has depth
            for _ in range(2):
                if ai < n_requests:
                    s.submit(f"r{ai}", int(arrivals[ai]))
                    ai += 1
            if len(s):
                before = s.current_domain
                s.next_request()
                served += 1
                t += service + (switch_cost if s.current_domain != before else 0)
            s.tick()
        m = s.metrics
        waits = np.array(m.waits)
        rows.append([name, n_requests / t, m.locality, m.domain_switches,
                     m.fairness_factor(), float(waits.mean()), float(np.percentile(waits, 99))])
        results[name] = (n_requests / t, m.locality, m.fairness_factor())
    table(
        f"serving scheduler policy level ({n_requests} reqs, {domains} domains, switch={switch_cost})",
        ["policy", "throughput", "locality", "switches", "fairness", "wait_mean", "wait_p99"],
        rows,
    )
    claim("serving: CNA throughput > FIFO (switch-cost amortised)",
          results["cna_thrFF"][0] > 1.5 * results["fifo"][0],
          f"{results['cna_thrFF'][0]:.3f} vs {results['fifo'][0]:.3f}")
    claim("serving: CNA locality >> FIFO",
          results["cna_thrFF"][1] > 0.8 > results["fifo"][1], "")
    claim("serving: fairness knob works (thr3 fairer than thrFFFF)",
          results["cna_thr3"][2] <= results["cna_thrFFFF"][2] + 1e-9,
          f"{results['cna_thr3'][2]:.3f} vs {results['cna_thrFFFF'][2]:.3f}")


# -- shared-prefix workload: derived homes vs oracle vs static ----------------


def _shared_prefix_reqs(n, n_prefixes, prefix_len, suffix_len, skew, rng):
    """Zipf draw over a pool of common system-prompt prefixes; every request
    is one shared prefix plus a unique per-request suffix."""
    prefixes = [
        [1_000 * p + j for j in range(prefix_len)] for p in range(n_prefixes)
    ]
    return [
        (pid, prefixes[pid] + [900_000 + i * suffix_len + j for j in range(suffix_len)])
        for i, pid in enumerate(zipf_draws(n, n_prefixes, skew, rng))
    ]


def _prefix_sim(arm, reqs, *, topo, n_slots, seed):
    """CNA admission + NUMA placement over one shared-prefix trace.  ``arm``
    picks where request homes come from: ``derived`` (PrefixIndex, fed from
    actual placements/retirements — the engine's wiring), ``oracle`` (a
    caller that tracks each prefix's true last-held pool — the label
    production traffic doesn't have), or ``static0``.  Returns warm-phase
    (second-half) locality and migration cycles plus the telemetry."""
    from repro.placement import DomainFreeLists, PlacementTelemetry, get_policy
    from repro.core.numasim import TWO_SOCKET
    from repro.serving.prefixindex import PrefixIndex

    pools = DomainFreeLists(n_slots, topo)
    policy = get_policy("nearest_spill")
    tel = PlacementTelemetry(n_domains=topo.n_domains)
    sched = CNAScheduler(fairness_threshold=0xFF, seed=seed, topology=topo)
    index = PrefixIndex(n_domains=topo.n_domains,
                        occupancy=lambda: tel.per_domain_occupancy)
    oracle_home = {}

    def cold_home():
        # the oracle arm's cold-start rule; the derived arm's comes from
        # PrefixIndex._fallback (same least-occupied convention) so the two
        # arms start from the same place
        occ = tel.per_domain_occupancy
        return min(range(topo.n_domains), key=lambda d: (occ.get(d, 0), d))

    rng = random.Random(seed)
    active = []  # (retire_t, slot, tokens)
    t = i = placed = 0
    half = len(reqs) // 2
    snap = None
    while placed < len(reqs):
        t += 1
        sched.tick()
        for entry in [a for a in active if a[0] <= t]:
            _, slot, tokens = entry
            if arm == "derived":
                # the engine's retirement hook: the pool held the full
                # sequence until this release
                index.record(tokens, pools.slot_domain[slot])
            tel.record_release(pools.release(slot))
            active.remove(entry)
        if i < len(reqs):  # arrivals pace just under service capacity: homes
            pid, tokens = reqs[i]  # only matter when pools have headroom
            if arm == "derived":
                home, matched = index.home(tokens)  # int: n_domains is set
                tel.record_derived_home(matched, len(tokens))
            elif arm == "oracle":
                home = oracle_home.get(pid)
                if home is None:
                    home = cold_home()
            else:
                home = 0
            sched.submit((pid, tokens, home), home)
            i += 1
        while len(pools) and len(sched):
            out = sched.next_request()
            if out is None:
                break
            pid, tokens, home = out
            p = policy.place(pools, home, TWO_SOCKET)
            tel.record_placement(p)
            if arm == "derived":
                index.record(tokens, p.slot_domain)  # re-home to reality
            elif arm == "oracle":
                oracle_home[pid] = p.slot_domain
            active.append((t + rng.randrange(6, 18), p.slot, tokens))
            placed += 1
            if placed == half:
                snap = (tel.placements, tel.local_placements, tel.migration_cycles)
    n0, l0, m0 = snap
    warm_loc = (tel.local_placements - l0) / max(1, tel.placements - n0)
    warm_mig = tel.migration_cycles - m0
    return warm_loc, warm_mig, tel


def shared_prefix(n_requests=4000, n_prefixes=12, prefix_len=24, suffix_len=8,
                  skew=1.1, seed=11):
    from repro.core.topology import pod

    topo = pod(2, 2)
    n_requests = smoke(n_requests, 300)
    rng = random.Random(seed)
    reqs = _shared_prefix_reqs(n_requests, n_prefixes, prefix_len, suffix_len, skew, rng)
    rows, results = [], {}
    for arm in ("derived", "oracle", "static0"):
        loc, mig, tel = _prefix_sim(arm, reqs, topo=topo, n_slots=16, seed=seed)
        results[arm] = (loc, mig)
        rows.append([arm, loc, mig, tel.locality, tel.migration_cycles,
                     tel.cross_spills,
                     tel.prefix_hit_rate if arm == "derived" else ""])
    table(
        f"shared-prefix serving workload on pod(2,2) ({n_requests} reqs, "
        f"{n_prefixes} prefixes, zipf {skew}; warm = second half)",
        ["homes", "warm_locality", "warm_migr_cycles", "locality", "migr_cycles",
         "cross_spills", "prefix_hit_rate"],
        rows,
    )
    # claims print at smoke scale too (they only gate full runs, per the
    # common.SMOKE contract) so the CI lane still shows the comparison
    d, o, s = results["derived"], results["oracle"], results["static0"]
    claim(
        "serving prefix: derived homes match the caller-oracle locality within 5% (warm)",
        d[0] >= 0.95 * o[0],
        f"derived={d[0]:.3f} oracle={o[0]:.3f}",
    )
    claim(
        "serving prefix: derived homes beat static domain-0 on locality",
        d[0] > s[0],
        f"derived={d[0]:.3f} static0={s[0]:.3f}",
    )
    claim(
        "serving prefix: derived homes beat static domain-0 on migration cycles",
        d[1] < s[1],
        f"derived={d[1]} static0={s[1]}",
    )
    return results


def engine_level():
    import jax

    from repro.configs.base import get_reduced_config
    from repro.models.registry import build_model
    from repro.serving.engine import DecodeEngine, Request

    cfg = get_reduced_config("granite_3_8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    base = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                max_new=4, domain=i % 2)
        for i in range(16)
    ]
    rows = []
    stats = {}
    for name, sched in [("cna", CNAScheduler(fairness_threshold=0xF)), ("fifo", FIFOScheduler())]:
        reqs = [Request(r.rid, r.prompt, r.max_new, r.domain) for r in base]
        eng = DecodeEngine(model, params, n_slots=4, cache_len=32,
                           scheduler=sched, domain_switch_cost=8)
        eng.run(reqs)
        m = eng.scheduler.metrics
        rows.append([name, eng.sim_time, m.locality, m.domain_switches, m.fairness_factor()])
        stats[name] = eng.sim_time
    table("serving engine level (reduced granite, real decode)",
          ["policy", "sim_time", "locality", "switches", "fairness"], rows)
    claim("serving engine: CNA completes sooner than FIFO",
          stats["cna"] < stats["fifo"], f"{stats['cna']} vs {stats['fifo']}")


def tracing(n_requests=12, max_new=4, cache_len=32, n_slots=4, seed=5):
    """The zero-cost-off / bounded-overhead contract at engine level: the
    same workload through an untraced and a traced engine must emit bitwise
    identical tokens (tracing never perturbs admission or decode), and the
    traced run's wall-clock overhead must stay within a generous bound (the
    spans are python dataclass appends next to real jax decode steps)."""
    import time as _time

    import jax

    from repro.configs.base import get_reduced_config
    from repro.models.registry import build_model
    from repro.obs import MetricsRegistry, Tracer
    from repro.serving.engine import DecodeEngine, Request

    n_requests = smoke(n_requests, 8)
    cfg = get_reduced_config("granite_3_8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    base = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                max_new=max_new, domain=i % 2)
        for i in range(n_requests)
    ]

    def run(tracer):
        reqs = [Request(r.rid, r.prompt, r.max_new, r.domain) for r in base]
        eng = DecodeEngine(model, params, n_slots=n_slots, cache_len=cache_len,
                           scheduler=CNAScheduler(fairness_threshold=0xF),
                           domain_switch_cost=8, tracer=tracer)
        t0 = _time.perf_counter()
        eng.run(reqs)
        return _time.perf_counter() - t0, reqs, eng

    run(None)  # warm the jit caches so neither timed arm pays compilation
    off_wall, off_reqs, _ = run(None)
    tr = Tracer()
    on_wall, on_reqs, eng = run(tr)
    overhead = on_wall / max(off_wall, 1e-9)
    table("engine tracing overhead (reduced granite, real decode)",
          ["arm", "wall_s", "spans"],
          [["tracer_off", f"{off_wall:.3f}", 0],
           ["tracer_on", f"{on_wall:.3f}", len(tr.spans)]])
    claim("obs: engine outputs bitwise-identical with tracer on",
          all(x.out == y.out for x, y in zip(off_reqs, on_reqs)), "")
    claim("obs: engine tracing overhead bounded (<= 1.5x wall)",
          overhead <= 1.5, f"{overhead:.2f}x, {len(tr.spans)} spans")
    claim("obs: every engine span closed at drain",
          not tr.check(), f"{len(tr.check())} open")
    reg = MetricsRegistry()
    eng.register_metrics(reg)
    common.headline_registry(reg, prefix="tracing_")
    common.headline(tracing_overhead_x=overhead, tracing_spans=len(tr.spans))


# -- paged KV with copy-on-write prefix sharing (jax-free accounting) ---------


def paging(n_requests=600, n_prefixes=8, prefix_len=112, suffix_len=32,
           skew=1.1, page_size=16, seed=13):
    """The paged-KV headline, entirely jax-free.

    Memory half: drive the page-table-backed prefix store
    (``PagedPrefixKVStore`` in accounting mode, no jax pool) and the
    contiguous ``PrefixKVStore`` through the same Zipf shared-prefix deposit
    stream — boundary (shared prefix) plus full prompt per request, the
    engine's planting + retirement pattern — and compare tokens of KV held.
    The contiguous number is the *unpadded* sum of entry lengths, which
    undercounts the slot engine (``fit_single`` pads every entry to
    cache_len), so the claim is conservative.

    Fabric half: the fleet sim over two-level prefixes (one fleet-wide base,
    per-group extensions, unique suffixes) with KV shipping priced whole-
    bundle (``page_size=0``) vs page-granular — a target that already holds
    the base prefix receives only the pages it lacks, so shipped tokens must
    strictly drop at the same bandwidth."""
    from repro.obs import MetricsRegistry
    from repro.router.kvship import ShipCostModel
    from repro.router.router import Session
    from repro.router.sim import simulate
    from repro.serving.paging import PagedPrefixKVStore, PageTable
    from repro.serving.prefixkv import PrefixKVStore

    n_requests = smoke(n_requests, 150)
    rng = random.Random(seed)
    reqs = _shared_prefix_reqs(n_requests, n_prefixes, prefix_len, suffix_len,
                               skew, rng)

    table_ = PageTable(256, page_size)
    paged_store = PagedPrefixKVStore(16, table=table_)
    flat_store = PrefixKVStore(16)
    for _pid, prompt in reqs:
        for store in (paged_store, flat_store):
            store.put(prompt[:prefix_len], None, None)  # boundary planting
            store.put(prompt, None, None)               # retirement deposit
    table_.check()
    paged_tokens = table_.pages_held * page_size
    flat_tokens = sum(len(k) for k in flat_store._lru)
    share = prefix_len / (prefix_len + suffix_len)
    reg = MetricsRegistry()
    table_.register_into(reg, prefix="paging")
    table(
        f"paged vs contiguous prefix store ({n_requests} reqs, "
        f"{n_prefixes} prefixes, zipf {skew}, share {share:.2f}, "
        f"page_size {page_size})",
        ["store", "entries", "kv_tokens_held", "pages_shared", "cow_copies",
         "zero_page_deposits"],
        [
            ["paged", len(paged_store), paged_tokens, table_.pages_shared,
             table_.cow_copies, paged_store.zero_page_deposits],
            ["contiguous", len(flat_store), flat_tokens, 0, 0, 0],
        ],
    )
    claim(
        "paging: pages held < 0.5x the contiguous store's KV footprint "
        f"at >=0.6 prefix share (share={share:.2f})",
        share >= 0.6 and paged_tokens < 0.5 * flat_tokens,
        f"paged={paged_tokens} tokens, contiguous={flat_tokens} tokens "
        f"({paged_tokens / max(1, flat_tokens):.2f}x, unpadded baseline)",
    )
    claim(
        "paging: page-table invariants hold after Zipf churn",
        True,  # table_.check() above raises on violation
        f"{table_.pages_total} pages, {table_.pages_free} free, "
        f"{table_.pages_shared} shared",
    )

    # fabric half: two-level prefixes so ship targets hold partial prefixes
    def nested_sessions(n):
        base = tuple(range(64))
        out = []
        for i, pid in enumerate(zipf_draws(n, 6, skew, random.Random(seed))):
            p = base \
                + tuple(10_000 * (pid + 1) + j for j in range(32)) \
                + tuple(900_000 + i * 16 + j for j in range(16))
            out.append(Session(sid=i, prompt=p, decode_len=8))
        return out

    n_sessions = smoke(200, 80)
    sim_kw = dict(seed=5, n_replicas=4, n_slots=2, cache_budget=400,
                  inter_arrival=8)
    whole = simulate("federated", nested_sessions(n_sessions),
                     kv_ship=ShipCostModel(), **sim_kw)
    paged = simulate("federated", nested_sessions(n_sessions),
                     kv_ship=ShipCostModel(page_size=page_size), **sim_kw)
    spec = simulate("federated", nested_sessions(n_sessions),
                    kv_ship=ShipCostModel(page_size=page_size),
                    router_kwargs=dict(prefetch=True, victim_cache=True),
                    **sim_kw)
    table(
        f"kv shipping: whole-bundle vs page-granular ({n_sessions} sessions, "
        "two-level prefixes, default bandwidth)",
        ["pricing", "ships", "segments", "shipped_tokens", "ship_cycles",
         "reuse_fraction", "prefetch_ships", "victim_ships"],
        [
            ["whole-bundle", whole.ships, whole.ship_segments,
             whole.shipped_tokens, whole.ship_cycles,
             whole.reuse_fraction, 0, 0],
            ["paged", paged.ships, paged.ship_segments, paged.shipped_tokens,
             paged.ship_cycles, paged.reuse_fraction, 0, 0],
            ["paged+spec", spec.ships, spec.ship_segments, spec.shipped_tokens,
             spec.ship_cycles, spec.reuse_fraction, spec.prefetch_ships,
             spec.victim_ships],
        ],
    )
    claim(
        "paging: page-granular shipping moves strictly fewer tokens than "
        "whole-bundle at default bandwidth",
        paged.ships > 0 and paged.shipped_tokens < whole.shipped_tokens,
        f"paged={paged.shipped_tokens} whole={whole.shipped_tokens} "
        f"({paged.ships} ships)",
    )
    common.headline_registry(reg)
    common.headline(
        paging_kv_tokens_paged=paged_tokens,
        paging_kv_tokens_contiguous=flat_tokens,
        paging_footprint_x=round(paged_tokens / max(1, flat_tokens), 4),
        paging_cow_copies=table_.cow_copies,
        paging_zero_page_deposits=paged_store.zero_page_deposits,
        paging_shipped_tokens_whole=whole.shipped_tokens,
        paging_shipped_tokens_paged=paged.shipped_tokens,
        paging_ship_segments=paged.ship_segments,
        paging_prefetch_ships=spec.prefetch_ships,
        paging_prefetch_tokens=spec.prefetch_tokens,
        paging_victim_ships=spec.victim_ships,
        paging_victim_tokens=spec.victim_tokens,
    )


def run_all():
    # NB: paging() is not called here — run.py gives it its own
    # bench_section so BENCH_serving_paging.json is always a separate record
    policy_level()
    shared_prefix()
    engine_level()
    tracing()
