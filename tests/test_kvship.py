"""repro.router.kvship: the priced ship/re-prefill boundary.

Three layers of pinning, matching the ISSUE's acceptance criteria:

  * property — ``decide()``'s choice equals the argmin of the two priced
    costs at ANY bandwidth/distance/backlog (hypothesis, or the seeded
    fallback sweep in containers without it);
  * sim — every decision a live fleet run records is the argmin of its own
    recorded costs, the fabric serializes in-flight ships, and shipping
    never loses to the shed-before-stall baseline;
  * contract (jax) — a shipped session's decode output bitwise-matches the
    re-prefilled one, and retirement-time deposits let conversation
    follow-ups resume from prompt *plus* generated output.
"""

import random
import sys

import pytest

sys.path.insert(0, __file__.rsplit("/", 1)[0])
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, st

from repro.core.topology import flat, pod
from repro.router import (
    Fabric,
    ReplicaRouter,
    Session,
    ShipCostModel,
    SimReplica,
    decide,
    shared_prefix_sessions,
    simulate,
)

# -- decide(): the priced argmin, as a property --------------------------------


@settings(max_examples=200, deadline=None)
@given(
    prompt_len=st.integers(min_value=0, max_value=512),
    local=st.integers(min_value=0, max_value=512),
    src_m=st.integers(min_value=0, max_value=512),
    distance=st.integers(min_value=1, max_value=2),
    backlog=st.integers(min_value=0, max_value=10_000),
    bw=st.integers(min_value=1, max_value=1024),
    bpt=st.integers(min_value=1, max_value=256),
    c_prefill=st.integers(min_value=1, max_value=64),
)
def test_decide_choice_is_the_priced_argmin(
    prompt_len, local, src_m, distance, backlog, bw, bpt, c_prefill
):
    local = min(local, prompt_len)
    src_m = min(src_m, prompt_len)
    cm = ShipCostModel(
        kv_bytes_per_token=bpt, fabric_bytes_per_cycle=bw, c_prefill=c_prefill
    )
    d = decide(
        prompt_len=prompt_len, local_matched=local, src_matched=src_m,
        src=0, dst=1, distance=distance, backlog=backlog, cm=cm,
    )
    # the two priced costs, recomputed from the model's published formula
    xfer = cm.xfer_cycles(src_m, distance)
    ship_total = backlog + xfer + c_prefill * (prompt_len - src_m)
    reprefill = c_prefill * (prompt_len - local)
    assert d.ship_cycles == xfer
    assert d.ship_total == ship_total
    assert d.reprefill_cycles == reprefill
    eligible = src_m > local and src_m >= cm.min_ship_tokens
    assert d.choice == ("ship" if eligible and ship_total < reprefill else "reprefill")


def test_decide_validates_matched_ranges():
    with pytest.raises(ValueError):
        decide(prompt_len=4, local_matched=5, src_matched=2, src=0, dst=1, distance=1)
    with pytest.raises(ValueError):
        decide(prompt_len=4, local_matched=0, src_matched=9, src=0, dst=1, distance=1)


def test_decide_ties_and_tiny_prefixes_reprefill():
    # a zero-gain ship (equal cost) must not buy fabric traffic
    cm = ShipCostModel(kv_bytes_per_token=4, fabric_bytes_per_cycle=1,
                       c_ship_setup=0, c_prefill=4, min_ship_tokens=1)
    d = decide(prompt_len=8, local_matched=0, src_matched=8, src=0, dst=1,
               distance=1, cm=cm)  # ship 8*4/1 = 32 == reprefill 8*4
    assert d.choice == "reprefill"
    # below min_ship_tokens never ships, however cheap
    d = decide(prompt_len=8, local_matched=0, src_matched=2, src=0, dst=1,
               distance=1, cm=ShipCostModel(min_ship_tokens=4))
    assert d.choice == "reprefill"


# -- Fabric: serialized in-flight ships ----------------------------------------


def test_fabric_serializes_ships_and_prices_backlog():
    fab = Fabric(flat(2), ShipCostModel(fabric_bytes_per_cycle=64))
    d1 = fab.price(prompt_len=96, local_matched=0, src_matched=96,
                   src=0, dst=1, now=100)
    assert d1.choice == "ship" and d1.wait_cycles == 0
    end1 = fab.reserve(100, d1)
    assert end1 == 100 + d1.ship_cycles == d1.fabric_end
    # second ship at the same tick queues behind the first — and its PRICE
    # already includes that wait
    d2 = fab.price(prompt_len=96, local_matched=0, src_matched=96,
                   src=1, dst=0, now=100)
    assert d2.wait_cycles == d1.ship_cycles
    if d2.choice == "ship":
        assert fab.reserve(100, d2) == end1 + d2.ship_cycles
    assert fab.stats.ships >= 1
    with pytest.raises(ValueError):
        fab.reserve(0, decide(prompt_len=4, local_matched=0, src_matched=0,
                              src=0, dst=1, distance=1))


def test_fabric_distance_scales_ship_cost():
    cm = ShipCostModel()
    near = cm.xfer_cycles(64, 1)
    far = cm.xfer_cycles(64, 2)
    assert far > near
    assert cm.xfer_cycles(0, 2) == 0


# -- router: ship moves the prefix before admit --------------------------------


def _warm_router(**kw):
    reps = [SimReplica(r, 1, cache_budget=600) for r in range(4)]
    router = ReplicaRouter(reps, topology=pod(2, 2), sync_every=0,
                           kv_ship=True, **kw)
    reps[0].cache.insert(tuple(range(50)))   # only replica 0 is warm
    router.sync()
    return router, reps


def test_router_ships_warm_prefix_on_shed():
    router, reps = _warm_router()
    reps[0].inflight = 1                     # home full -> shed
    s = Session(sid=0, prompt=tuple(range(50)) + (99,), decode_len=1)
    assert router.submit(s) == 0
    sess, target, _ = router.dispatch_one()
    assert target != 0 and router.stats.sheds == 1
    assert s.ship is not None and s.ship.choice == "ship" and s.ship.executed
    assert s.ship.src == 0 and s.ship.dst == target
    # the shipped prefix landed before admit: the target reused all 50 tokens
    assert s.local_matched == 50
    assert router.stats.ships == 1
    assert router.stats.shipped_tokens == 50
    assert router.stats.reprefill_avoided == 50
    assert router.stats.reprefill_tokens == 1     # only the suffix token


def test_router_records_declined_decision_on_slow_fabric():
    # fabric priced at 16 ticks/token vs c_prefill 4: re-prefill must win,
    # but the priced decision is still recorded on the session for audit
    router, reps = _warm_router()
    router.fabric.cm = ShipCostModel(fabric_bytes_per_cycle=4)
    reps[0].inflight = 1
    s = Session(sid=0, prompt=tuple(range(50)) + (99,), decode_len=1)
    router.submit(s)
    sess, target, _ = router.dispatch_one()
    assert s.ship is not None and s.ship.choice == "reprefill"
    assert s.ship.ship_total >= s.ship.reprefill_cycles
    assert router.stats.ships == 0 and router.stats.ship_declined == 1
    assert s.local_matched == 0                   # nothing moved


def test_router_does_not_price_when_target_already_holds_best():
    router, reps = _warm_router()
    s = Session(sid=0, prompt=tuple(range(50)) + (99,), decode_len=1)
    router.submit(s)
    sess, target, _ = router.dispatch_one()
    assert target == 0                            # home had capacity
    assert s.ship is None                         # nothing beyond its own holding


# -- sim: recorded decisions are argmins; ship never loses ---------------------


def _workload(n=240, n_prefixes=6, seed=3):
    rng = random.Random(seed)
    draws = [rng.randrange(n_prefixes) for _ in range(n)]
    return lambda: shared_prefix_sessions(draws, prefix_len=64, suffix_len=8,
                                          decode_len=16)


@pytest.mark.parametrize("bw", [512, 64, 8])
def test_sim_recorded_choices_match_priced_argmin(bw):
    mk = _workload()
    sessions = mk()
    simulate("federated", sessions, n_replicas=3, n_slots=2, cache_budget=400,
             inter_arrival=10, seed=5,
             kv_ship=ShipCostModel(fabric_bytes_per_cycle=bw))
    priced = [s.ship for s in sessions if s.ship is not None]
    assert priced, "workload produced no priced decisions"
    for d in priced:
        should_ship = (
            d.src_matched > d.local_matched
            and d.src_matched >= ShipCostModel().min_ship_tokens
            and d.ship_total < d.reprefill_cycles
        )
        assert d.choice == ("ship" if should_ship else "reprefill"), vars(d)


def test_sim_ship_never_loses_and_degrades_to_baseline():
    mk = _workload(n=200, seed=9)
    kw = dict(n_replicas=3, n_slots=3, cache_budget=400, inter_arrival=12, seed=7)
    base = simulate("federated", mk(), **kw)
    results = {
        bw: simulate("federated", mk(),
                     kv_ship=ShipCostModel(fabric_bytes_per_cycle=bw), **kw)
        for bw in (512, 64, 8)
    }
    for bw, r in results.items():
        assert r.admission_stall_total <= base.admission_stall_total, bw
    assert results[512].ships > 0
    assert results[512].admission_stall_total < base.admission_stall_total
    # a fabric slower than prefill ships nothing and coincides with baseline
    assert results[8].ships == 0
    assert results[8].admission_stall_total == base.admission_stall_total
    assert results[8].reprefill_tokens == base.reprefill_tokens


def test_sim_deterministic_with_shipping():
    mk = _workload(n=100, seed=13)
    kw = dict(n_replicas=3, n_slots=2, cache_budget=300, inter_arrival=10,
              seed=5, kv_ship=True)
    a = simulate("federated", mk(), **kw)
    b = simulate("federated", mk(), **kw)
    assert (a.ships, a.shipped_tokens, a.admission_stall_total, a.ticks) == (
        b.ships, b.shipped_tokens, b.admission_stall_total, b.ticks
    )


def test_replica_cache_peek_has_no_side_effects():
    from repro.router import ReplicaCache

    c = ReplicaCache(16)
    c.insert((1, 1, 1, 1))
    c.insert((2, 2, 2, 2))
    assert c.peek((1, 1, 1, 9)) == 3
    # peek must NOT have refreshed (1,1,1,1): inserting a large entry now
    # evicts it first (oldest), unlike after a match()
    c.insert((3, 3, 3, 3, 3, 3, 3, 3, 3, 3))
    assert c.peek((1, 1)) == 0


def test_sim_replica_embargoes_inflight_ships():
    """A shipped prefix is invisible until the fabric delivers it: a second
    session racing the transfer cannot reuse bytes that have not arrived,
    while the shipping session itself (whose prefill waits for fabric_end)
    does see its own bundle."""
    rep = SimReplica(0, 4, cache_budget=600)
    assert rep.import_kv((1, 2, 3, 4, 5), None, ready_t=100)
    assert rep.peek_match((1, 2, 3, 4, 5), now=50) == 0    # in flight
    racer = Session(sid=1, prompt=(1, 2, 3, 4, 5), decode_len=1)
    assert rep.admit(racer, now=50) == 0                   # no time travel
    assert rep.peek_match((1, 2, 3, 4, 5), now=100) == 5   # delivered


def test_router_books_nothing_when_import_refused():
    """A target that refuses the bundle (here: no store behind import_kv)
    must leave no fabric reservation and no ship counters; the recorded
    decision keeps its argmin (`choice` stays "ship") with `executed`
    False, and the refusal counts as ship_failed, not ship_declined."""
    router, reps = _warm_router()
    reps[0].inflight = 1
    target_rep = reps[1]
    target_rep.import_kv = lambda tokens, payload, ready_t=0: False
    s = Session(sid=0, prompt=tuple(range(50)) + (99,), decode_len=1)
    router.submit(s)
    _, target, _ = router.dispatch_one()
    assert s.ship is not None and s.ship.choice == "ship"
    assert not s.ship.executed
    assert router.stats.ships == 0
    assert router.stats.ship_failed == 1 and router.stats.ship_declined == 0
    assert router.fabric.busy_until == 0                   # nothing reserved
    assert router.fabric.stats.ships == 0
    assert s.local_matched == 0                            # it re-prefilled


# -- federation: shippable holders ---------------------------------------------


def test_router_picks_nearest_source_among_equal_holders():
    """Equal advertised lengths tie toward the holder nearest the target:
    distance multiplies the priced bytes, so the far source could flip the
    argmin and lose a profitable ship."""
    reps = [SimReplica(r, 1, cache_budget=600) for r in range(4)]
    router = ReplicaRouter(reps, topology=pod(2, 2), sync_every=0, kv_ship=True)
    seq = tuple(range(40))
    reps[0].cache.insert(seq)     # cross-pod holder relative to the target
    reps[3].cache.insert(seq)     # same-pod holder (recorded later -> fresher
    router.sync()                 # stamp -> the federation homes here)
    s = Session(sid=0, prompt=seq + (99,), decode_len=1)
    assert router.submit(s) == 3  # equal-occupancy tie -> fresher stamp
    reps[3].inflight = 1          # home full -> shed to 2, its pod sibling
    _, target, _ = router.dispatch_one()
    assert target == 2
    assert s.ship is not None and s.ship.executed
    assert s.ship.src == 3 and s.ship.distance == 1   # not the distance-2 holder


def test_federation_shippable_reports_longest_remote_holder():
    reps = [SimReplica(r, 2, cache_budget=400) for r in range(3)]
    router = ReplicaRouter(reps, sync_every=0)
    reps[0].cache.insert((1, 2, 3, 4, 5, 6))
    reps[1].cache.insert((1, 2, 3))
    router.sync()
    probe = (1, 2, 3, 4, 5, 6, 7)
    assert router.federation.shippable(probe, now=0) == (0, 6)
    # excluding the best holder falls to the next-longest
    assert router.federation.shippable(probe, now=0, exclude=0) == (1, 3)
    assert router.federation.shippable((9, 9), now=0) == (None, 0)


def test_prefix_index_holders_is_read_only():
    from repro.serving.prefixindex import PrefixIndex

    idx = PrefixIndex(n_domains=3)
    idx.record((1, 2, 3, 4), 0)
    idx.record((1, 2), 1)
    lookups_before = idx.lookups
    h = idx.holders((1, 2, 3, 4, 5))
    assert h == {0: 4, 1: 2}
    assert idx.lookups == lookups_before     # pricing probes are not traffic
    assert idx.holders((7,)) == {}


# -- engine contract (jax): shipped == re-prefilled, bit for bit ---------------


@pytest.fixture(scope="module")
def small_model():
    jax = pytest.importorskip("jax")
    import numpy as np  # noqa: F401  (fixture consumers use it)

    from repro.configs.base import get_reduced_config
    from repro.models.registry import build_model

    cfg = get_reduced_config("granite_3_8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(model, params, **kw):
    from repro.serving.engine import DecodeEngine

    return DecodeEngine(model, params, n_slots=1, cache_len=32, prefix_kv=True, **kw)


def test_shipped_decode_bitwise_matches_reprefilled(small_model):
    """The acceptance contract: run the same prompt (a) from scratch and
    (b) resuming from a KV bundle shipped out of another engine — the decode
    outputs must be identical token for token."""
    import numpy as np

    from repro.serving.engine import Request

    cfg, model, params = small_model
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab, 12).astype(np.int32)
    prompt = np.concatenate([shared, rng.integers(0, cfg.vocab, 5).astype(np.int32)])

    src = _engine(model, params)
    src.run([Request(rid=0, prompt=shared, max_new=1)])  # warms src's store
    exported = src.export_kv(prompt)
    assert exported is not None and len(exported[0]) >= len(shared)

    dst = _engine(model, params)
    assert dst.import_kv(*exported)
    shipped = Request(rid=1, prompt=prompt, max_new=5)
    dst.run([shipped])
    assert dst.reused_positions >= len(shared)   # the ship actually resumed

    fresh = _engine(model, params)
    reprefilled = Request(rid=2, prompt=prompt, max_new=5)
    fresh.run([reprefilled])
    assert fresh.reused_positions == 0

    assert shipped.out == reprefilled.out        # bitwise contract


def test_retirement_deposit_resumes_follow_ups(small_model):
    """ROADMAP "retirement-time prefix-KV deposits": after a request
    retires, its prompt *plus generated output* is resumable — a follow-up
    extending the whole conversation computes only its new tokens (plus the
    final emitted token the cache never encoded)."""
    import numpy as np

    from repro.serving.engine import Request

    cfg, model, params = small_model
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab, 10).astype(np.int32)
    eng = _engine(model, params)
    r1 = Request(rid=0, prompt=prompt, max_new=4)
    eng.run([r1])
    assert eng.kv_deposits == 1
    convo = np.concatenate([prompt, np.asarray(r1.out, np.int32)])
    # the store holds prompt + out[:-1]: everything the model ever encoded
    assert eng.peek_match(convo) == len(prompt) + len(r1.out) - 1

    follow = np.concatenate([convo, rng.integers(0, cfg.vocab, 3).astype(np.int32)])
    before = eng.prefill_positions
    r2 = Request(rid=1, prompt=follow, max_new=3)
    eng.run([r2])
    # computed: 3 new tokens + the one emitted-but-never-fed token
    assert eng.prefill_positions - before == 4

    ref = _engine(model, params)
    r3 = Request(rid=2, prompt=follow, max_new=3)
    ref.run([r3])
    assert r2.out == r3.out                      # deposits change cost, not output


def test_engine_replica_admit_counts_shipped_bundles(small_model):
    """RouterStats consistency over live engines: admit() must report the
    replica's *actual* resumable holding — including a just-imported
    (shipped) bundle the prefix index knows nothing about — so the router
    does not book the same tokens as both re-prefilled and avoided."""
    import numpy as np

    from repro.core.topology import pod
    from repro.router import EngineReplica, Session as RSession
    from repro.serving.engine import DecodeEngine

    cfg, model, params = small_model
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab, 10).astype(np.int32)
    prompt = tuple(int(t) for t in np.concatenate(
        [shared, rng.integers(0, cfg.vocab, 3).astype(np.int32)]))

    src = _engine(model, params)
    src.run([__import__("repro.serving.engine", fromlist=["Request"])
             .Request(rid=0, prompt=shared, max_new=1)])
    exported = src.export_kv(prompt)
    assert exported is not None

    dst = EngineReplica(1, DecodeEngine(
        model, params, n_slots=1, cache_len=32,
        scheduler=None, topology=pod(1, 2),
        placement="nearest_spill", prefix_index=True, prefix_kv=True))
    assert dst.import_kv(*exported)              # the ship lands
    got = dst.admit(RSession(sid=7, prompt=prompt, decode_len=1), now=0)
    assert got >= len(shared)                    # shipped tokens count as held


def test_import_kv_refuses_overlength_bundle(small_model):
    import numpy as np

    from repro.serving.engine import Request

    cfg, model, params = small_model
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, 12).astype(np.int32)
    src = _engine(model, params)
    src.run([Request(rid=0, prompt=prompt, max_new=1)])
    exported = src.export_kv(prompt)
    assert exported is not None

    from repro.serving.engine import DecodeEngine

    tiny = DecodeEngine(model, params, n_slots=1, cache_len=8, prefix_kv=True)
    assert not tiny.import_kv(*exported)         # cannot fit cache_len=8
    assert len(tiny.prefix_kv) == 0
