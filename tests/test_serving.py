"""Serving engine + CNA scheduler: correctness is admission-order-invariant,
locality/throughput favor CNA, fairness is preserved."""

import functools

import jax
import numpy as np
import pytest

from repro.configs.base import get_reduced_config
from repro.models.registry import build_model
from repro.serving.engine import DecodeEngine, Request
from repro.serving.scheduler import CNAScheduler, FIFOScheduler


@pytest.fixture(scope="module")
def small_model():
    cfg = get_reduced_config("granite_3_8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n=8, domains=2, seed=0, plen=8, max_new=5):
    rng = np.random.default_rng(seed)
    return [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
                max_new=max_new, domain=i % domains)
        for i in range(n)
    ]


@functools.lru_cache(maxsize=None)
def _jitted(model):
    return jax.jit(model.prefill), jax.jit(model.decode_step)


def _greedy_reference(model, params, prompt, n_new):
    """Free-running single-request decode (no batching)."""
    import jax.numpy as jnp

    prefill, step = _jitted(model)
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt)[None]})
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(n_new - 1):
        logits, cache = step(params, cache, jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_outputs_match_unbatched_reference(small_model):
    cfg, model, params = small_model
    reqs = _requests(cfg, n=5, seed=1)
    eng = DecodeEngine(model, params, n_slots=3, cache_len=32)
    eng.run(reqs)
    for r in reqs:
        ref = _greedy_reference(model, params, r.prompt, r.max_new)
        assert r.out[: r.max_new] == ref, f"rid={r.rid}: {r.out} vs {ref}"


def test_outputs_invariant_to_scheduler(small_model):
    """Per-request generations are identical under CNA and FIFO admission —
    the policy reorders work, never changes results."""
    cfg, model, params = small_model
    base = _requests(cfg, n=8, seed=2)
    outs = {}
    for name, sched in [("cna", CNAScheduler(fairness_threshold=0xF)), ("fifo", FIFOScheduler())]:
        reqs = [Request(r.rid, r.prompt, r.max_new, r.domain) for r in base]
        DecodeEngine(model, params, n_slots=3, cache_len=32, scheduler=sched).run(reqs)
        outs[name] = {r.rid: tuple(r.out) for r in reqs}
    assert outs["cna"] == outs["fifo"]


def test_cna_beats_fifo_on_locality_and_switch_cost(small_model):
    cfg, model, params = small_model
    base = _requests(cfg, n=12, domains=2, seed=3)
    stats = {}
    for name, sched in [("cna", CNAScheduler(fairness_threshold=0xF)), ("fifo", FIFOScheduler())]:
        reqs = [Request(r.rid, r.prompt, r.max_new, r.domain) for r in base]
        eng = DecodeEngine(model, params, n_slots=3, cache_len=32,
                           scheduler=sched, domain_switch_cost=8)
        eng.run(reqs)
        stats[name] = (eng.scheduler.metrics.locality, eng.scheduler.metrics.domain_switches, eng.sim_time)
    assert stats["cna"][0] > stats["fifo"][0]       # higher locality
    assert stats["cna"][1] < stats["fifo"][1]       # fewer domain switches
    assert stats["cna"][2] < stats["fifo"][2]       # lower simulated time


def test_fairness_no_domain_starves(small_model):
    """With a small fairness threshold, every domain gets served even when
    domain 0 floods the queue (the paper's long-term fairness property)."""
    cfg, model, params = small_model
    reqs = [
        Request(rid=i, prompt=np.arange(4, dtype=np.int32), max_new=2,
                domain=0 if i < 20 else 1)
        for i in range(24)
    ]
    eng = DecodeEngine(model, params, n_slots=2, cache_len=32,
                       scheduler=CNAScheduler(fairness_threshold=0x3, seed=5))
    eng.run(reqs)
    per_dom = eng.scheduler.metrics.per_domain
    assert per_dom.get(0, 0) == 20 and per_dom.get(1, 0) == 4
    assert all(r.done for r in reqs)


def test_slot_reuse_and_release(small_model):
    cfg, model, params = small_model
    reqs = _requests(cfg, n=9, seed=4, max_new=3)
    eng = DecodeEngine(model, params, n_slots=2, cache_len=32)
    eng.run(reqs)
    assert all(r.done for r in reqs)
    assert len(eng.slots.free) == 2 and not eng.active_req


def test_released_slot_does_not_leak_stale_kv(small_model):
    """Regression: SlotCache.release must zero the slot's position so a
    re-claimed slot reads as empty (no stale KV visible) until insert, and a
    request served from a reused slot decodes identically to a fresh one."""
    cfg, model, params = small_model
    # two requests forced through the same single slot, back to back
    reqs = _requests(cfg, n=2, seed=6, plen=6, max_new=4)
    eng = DecodeEngine(model, params, n_slots=1, cache_len=32)
    eng.run(reqs)
    assert all(r.done for r in reqs)
    assert int(eng.slots.cache["pos"][0]) == 0  # released slot reads empty
    for r in reqs:
        ref = _greedy_reference(model, params, r.prompt, r.max_new)
        assert r.out[: r.max_new] == ref


def test_scheduler_rejects_out_of_range_domain():
    from repro.core.topology import pod
    from repro.serving.scheduler import FIFOScheduler as FS

    s = FS(topology=pod(2, 2))
    with pytest.raises(ValueError, match="domain 7 out of range"):
        s.submit("r", 7)
    s.submit("r", 3)  # in range: 4 domains


def test_engine_rejects_conflicting_scheduler_and_topology():
    from repro.core.topology import pod
    from repro.serving.scheduler import FIFOScheduler as FS

    with pytest.raises(ValueError, match="topology via the scheduler"):
        DecodeEngine(None, None, scheduler=FS(), topology=pod(2, 2))


def test_placement_engine_outputs_invariant_and_telemetry(small_model):
    """A placement-aware SlotCache changes WHERE caches live, never what gets
    decoded: outputs match the baseline engine, and per-domain telemetry is
    surfaced through the scheduler metrics."""
    from repro.core.topology import pod

    cfg, model, params = small_model
    base = _requests(cfg, n=10, domains=4, seed=7)
    outs = {}
    for name, kw in [
        ("baseline", {}),
        ("placed", dict(scheduler=CNAScheduler(fairness_threshold=0xF, topology=pod(2, 2)),
                        placement="nearest_spill")),
    ]:
        reqs = [Request(r.rid, r.prompt, r.max_new, r.domain) for r in base]
        eng = DecodeEngine(model, params, n_slots=4, cache_len=32, **kw)
        eng.run(reqs)
        outs[name] = {r.rid: tuple(r.out) for r in reqs}
        if name == "placed":
            tel = eng.scheduler.metrics.placement
            assert tel is eng.slots.telemetry
            assert tel.placements == 10 and tel.releases == 10
            assert tel.placements == tel.local_placements + tel.spills
            assert tel.handover_samples == 10  # one sample per admission
            assert sum(tel.per_domain_occupancy.values()) == 0  # all released
    assert outs["placed"] == outs["baseline"]


def test_placement_requires_topology():
    with pytest.raises(ValueError, match="placement needs a topology"):
        DecodeEngine(None, None, placement="nearest_spill")


def test_engine_rejects_overlength_prompt(small_model):
    """Regression: a prompt with len(prompt) >= cache_len used to be admitted
    unguarded — prefill returned pos > cache_len, ``_fit`` silently trimmed
    the KV, and the decode write clamped onto the last cache entry.  It must
    be rejected at submit; the longest fitting prompt still decodes."""
    cfg, model, params = small_model
    eng = DecodeEngine(model, params, n_slots=1, cache_len=16)
    bad = Request(rid=0, prompt=np.arange(16, dtype=np.int32) % cfg.vocab, max_new=2)
    with pytest.raises(ValueError, match="cannot fit cache_len"):
        eng.submit(bad)
    assert len(eng.scheduler) == 0  # nothing half-queued
    ok = Request(rid=1, prompt=np.arange(15, dtype=np.int32) % cfg.vocab, max_new=2)
    eng.run([ok])
    assert ok.done


def test_slotcache_claim_validates_domain_and_exhaustion():
    """Regression: under placement, claim() used to coerce domain=None to 0
    (skewing domain-0 telemetry) and let out-of-range domains surface as an
    opaque IndexError inside the pools; the baseline path's exhausted-cache
    error was heapq's bare 'index out of range'."""
    import jax.numpy as jnp

    from repro.core.topology import pod
    from repro.serving.kvcache import SlotCache

    def mk(**kw):
        return SlotCache({"pos": jnp.zeros((2,), jnp.int32)}, {"pos": None}, 2, **kw)

    sc = mk(topology=pod(2, 1))
    with pytest.raises(ValueError, match="domain=None"):
        sc.claim("r0")
    with pytest.raises(ValueError, match="domain 5 out of range"):
        sc.claim("r0", 5)
    with pytest.raises(ValueError, match="domain -1 out of range"):
        sc.claim("r0", -1)
    assert sc.telemetry.placements == 0 and not sc.owner  # rejects left no trace
    assert sc.claim("r0", 1) is not None and sc.slot_domain(0) == 0
    sc.claim("r1", 1)
    with pytest.raises(IndexError, match="claim from an exhausted SlotCache"):
        sc.claim("r2", 1)

    base = mk()
    base.claim("a"), base.claim("b")
    assert base.slot_domain(0) is None  # baseline: no domains
    with pytest.raises(IndexError, match="claim from an exhausted SlotCache"):
        base.claim("c")


def test_adaptive_scheduler_in_engine_feeds_controller(small_model):
    """CNAScheduler(max_active=AdaptiveController) in a real engine run: the
    engine feeds one handover sample per admission and decode output is
    unchanged by the adaptive cap."""
    from repro.core.topology import pod
    from repro.placement import AdaptiveController

    cfg, model, params = small_model
    base = _requests(cfg, n=8, domains=4, seed=8)
    ctrl = AdaptiveController(initial=2, max_cap=8, window=4)
    sched = CNAScheduler(fairness_threshold=0xF, topology=pod(2, 2), max_active=ctrl)
    reqs = [Request(r.rid, r.prompt, r.max_new, r.domain) for r in base]
    eng = DecodeEngine(model, params, n_slots=2, cache_len=32, scheduler=sched)
    eng.run(reqs)
    assert all(r.done for r in reqs)
    assert sched.controller is ctrl and ctrl.samples == 8
    for r in reqs:
        ref = _greedy_reference(model, params, r.prompt, r.max_new)
        assert r.out[: r.max_new] == ref


def test_topology_scheduler_scales_switch_cost(small_model):
    """Cross-pod admissions stall the engine twice as long as same-pod ones
    under a hierarchical topology."""
    from repro.core.topology import pod
    from repro.serving.scheduler import FIFOScheduler as FS

    cfg, model, params = small_model
    topo = pod(2, 2)
    # domains 0,2 are in different pods; 0,1 share a pod
    far = [Request(rid=i, prompt=np.arange(4, dtype=np.int32), max_new=2,
                   domain=[0, 2][i % 2]) for i in range(4)]
    near = [Request(rid=i, prompt=np.arange(4, dtype=np.int32), max_new=2,
                    domain=[0, 1][i % 2]) for i in range(4)]
    times = {}
    for name, reqs in [("far", far), ("near", near)]:
        eng = DecodeEngine(model, params, n_slots=1, cache_len=32,
                           scheduler=FS(topology=topo), domain_switch_cost=10)
        eng.run(reqs)
        times[name] = eng.sim_time
        assert eng.scheduler.metrics.domain_switches > 0
    assert times["far"] > times["near"]


# -- prefix-KV reuse (matched_len-aware prefill) -------------------------------


def test_prefix_kv_store_exact_prefix_lookup_and_lru():
    from repro.serving.prefixkv import PrefixKVStore

    s = PrefixKVStore(capacity=2)
    s.put([1, 2, 3], "c123", "l123")
    s.put([1, 2], "c12", "l12")
    # longest *exact* prefix wins; a shared run that diverges is not a hit
    assert s.longest([1, 2, 3, 4]) == (3, "c123", "l123")
    assert s.longest([1, 2, 9]) == (2, "c12", "l12")
    assert s.longest([9, 9]) is None
    assert s.common_run([1, 2, 9]) == 2
    s.put([7, 7, 7], "c777", "l777")  # capacity 2: LRU ([1,2,3]? no — it was
    # touched last by the [1,2,3,4] lookup before [1,2] was) evicts oldest
    assert len(s) == 2
    with pytest.raises(ValueError):
        PrefixKVStore(capacity=0)


def test_matched_len_aware_prefill_skips_cached_positions(small_model):
    """The ROADMAP unlock, pinned by counting prefill positions: with a
    PrefixKVStore the engine computes each shared prefix once; later prompts
    sharing it prefill only their suffix — and decode exactly what a
    from-scratch prefill decodes."""
    cfg, model, params = small_model
    rng = np.random.default_rng(12)
    P = rng.integers(0, cfg.vocab, 12).astype(np.int32)
    prompts = [np.concatenate([P, rng.integers(0, cfg.vocab, 4).astype(np.int32)])
               for _ in range(3)]

    from repro.core.topology import pod

    eng = DecodeEngine(model, params, n_slots=1, cache_len=64,
                       topology=pod(1, 2), placement="nearest_spill",
                       prefix_index=True, prefix_kv=True)
    reqs = [Request(rid=i, prompt=p, max_new=3, domain=None)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    # req0: full (16).  req1: no exact-prefix entry yet, but the common run
    # with the stored full prompt plants the boundary — 12 + 4 computed.
    # req2: resumes from the boundary — only its 4-token suffix.
    assert eng.prefill_positions == 16 + 16 + 4
    assert eng.reused_positions == 12
    assert eng.prefix_kv.hits == 1
    for r in reqs:
        ref = _greedy_reference(model, params, r.prompt, r.max_new)
        assert r.out[: r.max_new] == ref, f"rid={r.rid}"


def test_prefill_reuse_on_conversation_extension(small_model):
    """A prompt that extends a previously served prompt resumes from its
    stored cache directly (no boundary planting needed), and decodes what a
    from-scratch prefill decodes."""
    cfg, model, params = small_model
    rng = np.random.default_rng(13)
    first = rng.integers(0, cfg.vocab, 10).astype(np.int32)
    eng = DecodeEngine(model, params, n_slots=1, cache_len=64, prefix_kv=True)
    r1 = Request(rid=0, prompt=first, max_new=3)
    eng.run([r1])
    ext = np.concatenate([first, rng.integers(0, cfg.vocab, 5).astype(np.int32)])
    r2 = Request(rid=1, prompt=ext, max_new=3)
    before = eng.prefill_positions
    eng.run([r2])
    assert eng.prefill_positions - before == 5        # only the extension
    ref = _greedy_reference(model, params, ext, r2.max_new)
    assert r2.out[: r2.max_new] == ref


# -- FIFO scheduler kwargs (regression) ----------------------------------------


def test_fifo_scheduler_rejects_unknown_kwargs():
    """Regression: FIFOScheduler(**_) used to swallow anything — a misspelled
    fairness_threshold= or a controller= silently ran a different experiment."""
    with pytest.raises(TypeError):
        FIFOScheduler(fairness_threshold=0xF)
    with pytest.raises(TypeError):
        FIFOScheduler(controller=object())
    with pytest.raises(TypeError):
        FIFOScheduler(fairness_treshold=3)  # the misspelling, explicitly


def test_fifo_scheduler_honours_restriction_kwargs():
    """The shared GCR knobs are accepted AND honoured: a capped FIFO parks
    excess arrivals (visible in the queue stats) while preserving FIFO grant
    order."""
    s = FIFOScheduler(max_active=2)
    for i in range(5):
        s.submit(f"r{i}", i % 2)
    assert s.max_active == 2
    assert s._q.stats.parked == 3
    granted = [s.next_request() for _ in range(5)]
    assert granted == [f"r{i}" for i in range(5)]  # order unchanged
    from repro.placement import AdaptiveController

    ctl = AdaptiveController(initial=3)
    s2 = FIFOScheduler(max_active=ctl)
    assert s2.controller is ctl and s2.max_active == 3
    s2.observe_handover(7)
    assert ctl.samples == 1


# -- engine replicas behind the router -----------------------------------------


def test_engine_replicas_behind_router(small_model):
    """End-to-end: two DecodeEngine replicas behind ReplicaRouter — summaries
    flow to the federation, sessions route and complete, fleet inflight
    drains to zero, and prefix-KV reuse shows up as skipped prefill."""
    from repro.core.topology import pod
    from repro.router import EngineReplica, ReplicaRouter, Session

    cfg, model, params = small_model
    rng = np.random.default_rng(21)
    shared = [rng.integers(0, cfg.vocab, 8).astype(np.int32) for _ in range(2)]
    sessions = [
        Session(sid=i,
                prompt=tuple(int(t) for t in np.concatenate(
                    [shared[i % 2], rng.integers(0, cfg.vocab, 3).astype(np.int32)])),
                decode_len=2)
        for i in range(8)
    ]
    replicas = [
        EngineReplica(r, DecodeEngine(
            model, params, n_slots=2, cache_len=32,
            scheduler=CNAScheduler(topology=pod(1, 2)),
            placement="nearest_spill", prefix_index=True, prefix_kv=True))
        for r in range(2)
    ]
    router = ReplicaRouter(replicas, sync_every=2)
    i = done = 0
    for _ in range(500):
        router.tick()
        if i < len(sessions):
            router.submit(sessions[i])
            i += 1
        router.dispatch()
        for rep in replicas:
            for session, ttft in rep.step():
                assert ttft >= 1
                router.complete(session, ttft=ttft)
                done += 1
        if done == len(sessions):
            break
    assert done == len(sessions)
    assert router.fleet.inflight == [0, 0]
    assert router.stats.dispatched == len(sessions)
    assert router.federation.stats.applied >= 2      # summaries flowed
    served = [r.engine.scheduler.metrics.admitted for r in replicas]
    assert sum(served) == len(sessions)
    total_prompt = sum(len(s.prompt) for s in sessions)
    computed = sum(r.engine.prefill_positions for r in replicas)
    assert computed < total_prompt                   # real prefill skipped
    assert all(s.finish_t >= 0 for s in sessions)


def test_engine_replica_requires_prefix_index(small_model):
    from repro.router import EngineReplica

    cfg, model, params = small_model
    eng = DecodeEngine(model, params, n_slots=1, cache_len=32)
    with pytest.raises(ValueError, match="prefix index"):
        EngineReplica(0, eng)


# -- controller-coupled shedding through the engine ----------------------------


def test_engine_auto_wires_controller_shedding(small_model):
    """Regression for the shed-before-spill ordering at the engine level:
    with placement + an adaptive controller, the engine wires the
    controller's occupancy view and a saturated home re-homes new
    submissions to its same-pod sibling (no migration) before nearest_spill
    is forced cross-pod."""
    from repro.core.topology import pod
    from repro.placement import AdaptiveController

    cfg, model, params = small_model
    ctl = AdaptiveController(initial=8)
    eng = DecodeEngine(
        model, params, n_slots=8, cache_len=32,
        scheduler=CNAScheduler(topology=pod(2, 2), max_active=ctl),
        placement="nearest_spill",
    )
    assert ctl.occupancy is not None          # auto-wired
    assert ctl.domain_capacity == (2, 2, 2, 2)
    assert ctl.shed_topology is eng.scheduler.topology
    tel = eng.slots.telemetry

    def feed(rid):  # submit homed at 0, admit immediately, never retires
        r = Request(rid=rid, prompt=np.arange(4, dtype=np.int32), max_new=30, domain=0)
        eng.submit(r)
        eng.step()
        return r

    homes = [feed(i).domain for i in range(5)]
    assert homes == [0, 0, 1, 1, 0]           # home, home, shed, shed, pod full
    assert tel.sheds == 2
    assert tel.cross_spills == 1 and tel.sibling_spills == 0
    assert tel.migration_cycles > 0           # only the final cross-pod spill


# -- wall-clock phases of the tick (repro.obs.PhaseClock) ---------------------

PHASES_NS = ("admit", "admit_insert", "admit_wait", "dispatch", "wait", "retire")


@pytest.mark.parametrize("prefix_kv", [False, True])
def test_engine_phase_counters(small_model, prefix_kv):
    """Every tick is counted and timed; its phases fit inside it; decode
    ticks and live lanes match what the decode call actually saw."""
    from repro.obs import MetricsRegistry

    cfg, model, params = small_model
    eng = DecodeEngine(model, params, n_slots=3, cache_len=32,
                       prefix_kv=True if prefix_kv else None)
    reg = MetricsRegistry()
    eng.register_metrics(reg)
    lanes_seen = []
    decode = eng._step

    def spy(*args):
        lanes_seen.append(len(eng.active_req))
        return decode(*args)

    eng._step = spy
    for r in _requests(cfg, n=7, plen=6, max_new=4):
        eng.submit(r)
    n = 0
    while len(eng.scheduler) or eng.active_req:
        eng.step()
        n += 1
    for _ in range(2):          # idle ticks: counted, no decode call
        eng.step()
        n += 1
    snap = reg.collect()
    assert snap["engine_ticks"] == n
    assert snap["engine_decode_ticks"] == len(lanes_seen) <= n - 2
    assert snap["engine_decode_lanes"] == sum(lanes_seen) > 0
    ns = {k: snap[f"engine_{k}_ns"] for k in PHASES_NS}
    assert all(isinstance(v, int) and v >= 0 for v in ns.values())
    assert ns["admit"] > 0 and ns["dispatch"] > 0 and ns["wait"] > 0
    assert ns["admit_wait"] <= ns["admit"]
    assert 0 < ns["admit_insert"] <= ns["admit"] - ns["admit_wait"]
    assert ns["admit"] + ns["dispatch"] + ns["wait"] + ns["retire"] <= snap["engine_tick_ns"]
    assert "engine_retire_ns" in reg.render_prometheus()


def test_engine_phases_are_profiler_host_spans(small_model, tmp_path):
    """With a profiler running, each tick is an ``engine.step`` host span on
    ``/host:CPU`` and every phase span lies inside its tick."""
    import glob

    from jax.profiler import ProfileData

    cfg, model, params = small_model
    eng = DecodeEngine(model, params, n_slots=2, cache_len=32)
    for r in _requests(cfg, n=2, plen=6, max_new=6):
        eng.submit(r)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events if e.name.startswith("engine.")]
    assert {n for n, _, _ in spans} == {"engine.step", "engine.admit", "engine.admit.insert",
                                        "engine.admit.wait", "engine.dispatch", "engine.wait",
                                        "engine.retire"}
    steps = [(a, b) for n, a, b in spans if n == "engine.step"]
    assert len(steps) == 3
    for n, a, b in spans:
        assert any(x <= a and b <= y for x, y in steps), n
    (a, b), = [(a, b) for n, a, b in spans if n == "engine.admit.wait"]
    assert any(n == "engine.admit" and x <= a and b <= y for n, x, y in spans)
    for a, b in [(a, b) for n, a, b in spans if n == "engine.admit.insert"]:
        assert any(n == "engine.admit" and x <= a and b <= y for n, x, y in spans)
