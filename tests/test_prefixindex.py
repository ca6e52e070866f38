"""repro.serving.prefixindex: radix index over token prompts deriving
request homes from actual placements — plus the cross-layer contract that a
warm index drives the identical admission/placement trajectory an oracle
caller would produce."""

import numpy as np
import pytest

from repro.serving.prefixindex import PrefixIndex


# -- index structure -----------------------------------------------------------


def test_cold_index_misses_and_falls_back():
    ix = PrefixIndex(n_domains=4)
    assert ix.home([1, 2, 3]) == (0, 0)  # least-occupied fallback, no match
    ix.occupancy = lambda: {0: 3, 1: 1, 2: 2, 3: 5}
    assert ix.home([1, 2, 3]) == (1, 0)  # occupancy steers the cold start
    assert PrefixIndex().home([1, 2, 3]) == (None, 0)  # no n_domains: no opinion
    assert ix.lookups == 2 and ix.hits == 0


def test_longest_prefix_match_and_matched_len():
    ix = PrefixIndex(n_domains=4)
    ix.record([1, 2, 3, 4], 2)
    assert ix.home([1, 2, 3, 4]) == (2, 4)        # exact
    assert ix.home([1, 2, 9, 9]) == (2, 2)        # diverges mid-edge
    assert ix.home([1, 2, 3, 4, 5, 6]) == (2, 4)  # extends past the cache
    assert ix.home([7, 8]) == (0, 0)              # total miss -> fallback
    ix.record([1, 2, 3, 4, 5, 6], 3)              # deeper record wins the LPM
    assert ix.home([1, 2, 3, 4, 5, 6, 7]) == (3, 6)
    assert ix.home([1, 2, 3, 4])[1] == 4          # matched_len <= query length


def test_record_tags_every_prefix_and_splits_edges():
    ix = PrefixIndex(n_domains=4)
    ix.record([1, 2, 3, 4], 1)
    assert ix.n_nodes == 1                 # one compressed edge
    ix.record([1, 2, 8, 9], 2)             # split at [1,2]
    assert ix.n_nodes == 3
    dom, matched = ix.home([1, 2])
    assert matched == 2 and dom in (1, 2)  # both pools hold the shared run
    # domain 1 still owns the deep [1,2,3,4] branch it wrote
    assert ix.home([1, 2, 3, 4]) == (1, 4)
    assert ix.home([1, 2, 8, 9]) == (2, 4)


def test_ties_break_toward_least_occupied_domain():
    occ = {}
    ix = PrefixIndex(n_domains=4, occupancy=lambda: occ)
    ix.record([5, 6, 7], 1)
    ix.record([5, 6, 7], 2)  # same prefix now held by two pools
    occ.update({1: 4, 2: 0})
    assert ix.home([5, 6, 7]) == (2, 3)
    occ.update({1: 0, 2: 4})
    assert ix.home([5, 6, 7]) == (1, 3)
    occ.update({1: 2, 2: 2})
    assert ix.home([5, 6, 7]) == (2, 3)  # occupancy tie -> most recent holder


def test_rehoming_follows_the_latest_record():
    ix = PrefixIndex(n_domains=4)  # no occupancy signal: recency decides
    ix.record([5, 6, 7], 1)
    assert ix.home([5, 6, 7]) == (1, 3)
    ix.record([5, 6, 7], 3)  # placement spilled the prefix to domain 3
    assert ix.home([5, 6, 7]) == (3, 3)


def test_capacity_evicts_lru_leaves():
    ix = PrefixIndex(n_domains=2, capacity=16)
    ix.record([1, 2, 3], 0)
    for i in range(200):
        ix.record([1, 2, 3, 100 + i], 1)   # unique suffixes churn the leaves
        ix.home([1, 2, 3])                  # keep the shared prefix hot
    assert ix.n_nodes <= 16
    assert ix.home([1, 2, 3])[1] == 3       # the hot prefix survived eviction
    assert ix.records == 201


def test_record_validates_domain_and_ignores_empty():
    ix = PrefixIndex(n_domains=4)
    with pytest.raises(ValueError, match="out of range"):
        ix.record([1], 4)
    with pytest.raises(ValueError, match="out of range"):
        ix.record([1], -1)
    with pytest.raises(ValueError, match="out of range"):
        ix.record([1], None)
    ix.record([], 0)
    assert ix.n_nodes == 0 and ix.records == 0
    with pytest.raises(ValueError):
        PrefixIndex(capacity=0)


def test_numpy_prompts_and_python_lists_are_the_same_key():
    ix = PrefixIndex(n_domains=2)
    ix.record(np.array([4, 5, 6], dtype=np.int32), 1)
    assert ix.home([4, 5, 6]) == (1, 3)
    assert ix.home(np.array([4, 5, 6, 7], dtype=np.int64)) == (1, 3)


# -- engine wiring -------------------------------------------------------------


@pytest.fixture(scope="module")
def small_model():
    import jax

    from repro.configs.base import get_reduced_config
    from repro.models.registry import build_model

    cfg = get_reduced_config("granite_3_8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(model, params, *, index):
    from repro.core.topology import pod
    from repro.serving.engine import DecodeEngine
    from repro.serving.scheduler import CNAScheduler

    return DecodeEngine(
        model, params, n_slots=4, cache_len=32,
        scheduler=CNAScheduler(fairness_threshold=0xF, topology=pod(2, 2)),
        placement="nearest_spill", prefix_index=index,
    )


def _shared_prefix_requests(phase, n=6):
    """n requests over 3 shared 6-token prefixes with unique 2-token tails."""
    from repro.serving.engine import Request

    prefixes = [[10 + p, 11 + p, 12 + p, 13 + p, 14 + p, 15 + p] for p in (0, 20, 40)]
    return [
        Request(rid=100 * phase + i,
                prompt=np.array(prefixes[i % 3] + [70 + 10 * phase + i, 80 + i],
                                dtype=np.int32),
                max_new=3, domain=None)
        for i in range(n)
    ]


def _trace_claims(eng):
    trace = []
    orig = eng.slots.claim

    def claim(owner, domain=None):
        slot = orig(owner, domain)
        trace.append((owner, domain, slot))
        return slot

    eng.slots.claim = claim
    return trace


def test_prefix_index_requires_placement(small_model):
    cfg, model, params = small_model
    from repro.serving.engine import DecodeEngine

    with pytest.raises(ValueError, match="prefix index needs placement"):
        DecodeEngine(model, params, prefix_index=PrefixIndex())


def test_engine_auto_wires_index_to_topology_and_telemetry(small_model):
    cfg, model, params = small_model
    eng = _engine(model, params, index=True)
    assert eng.prefix_index.n_domains == 4
    assert eng.prefix_index.occupancy() == eng.slots.telemetry.per_domain_occupancy
    # a warm index handed to a NEW engine rebinds to the new engine's live
    # telemetry (it must not keep reading the retired engine's counters) and
    # rejects a topology of a different width
    eng2 = _engine(model, params, index=eng.prefix_index)
    assert eng2.prefix_index is eng.prefix_index
    assert eng2.prefix_index.occupancy() is eng2.slots.telemetry.per_domain_occupancy
    from repro.core.topology import pod
    from repro.serving.engine import DecodeEngine
    from repro.serving.scheduler import CNAScheduler

    with pytest.raises(ValueError, match="spans 4 domains"):
        DecodeEngine(model, params, n_slots=4, cache_len=64,
                     scheduler=CNAScheduler(topology=pod(1, 2)),
                     placement="nearest_spill", prefix_index=eng.prefix_index)


def test_engine_derives_homes_and_learns_from_placements(small_model):
    """domain=None requests get index-derived homes; after a warm phase the
    index answers with the full shared prefix matched, telemetry counts the
    derivations, and retirement records extend the cached sequences."""
    cfg, model, params = small_model
    eng = _engine(model, params, index=True)
    warm = _shared_prefix_requests(phase=0)
    eng.run(warm)
    assert all(r.done for r in warm)
    assert all(r.domain is not None for r in warm)  # resolved in place
    tel = eng.slots.telemetry
    assert tel.derived_homes == 6
    # retirement recorded prompt+output sequences, so the index holds more
    # tokens than the prompts alone
    probe = warm[0]
    dom, matched = eng.prefix_index.home(
        np.concatenate([probe.prompt, np.asarray(probe.out)]))
    assert matched == len(probe.prompt) + len(probe.out)
    test = _shared_prefix_requests(phase=1)
    eng.run(test)
    assert tel.derived_homes == 12
    for r in test:
        assert r.matched_len >= 6  # the shared prefix was cached and matched
    # warm-phase lookups all missed (6*8 tokens), test phase matched the
    # 6-token prefix of each 8-token prompt: 36/96
    assert tel.prefix_hit_rate == pytest.approx(0.375)


def test_contract_warm_index_matches_oracle_trajectory(small_model):
    """Cross-layer contract: the warm index's derived homes drive the
    IDENTICAL admission/placement trajectory that an oracle caller supplying
    those homes explicitly would produce — derivation changes labels, never
    the discipline — and the matched_len discount can only reduce the charged
    migration stall."""
    cfg, model, params = small_model
    from repro.serving.engine import Request

    # derived run: homes come from the index (warm after phase 0)
    eng_d = _engine(model, params, index=True)
    trace_d = _trace_claims(eng_d)
    warm_d = _shared_prefix_requests(phase=0)
    test_d = _shared_prefix_requests(phase=1)
    eng_d.run(warm_d)
    eng_d.run(test_d)
    resolved = {r.rid: r.domain for r in warm_d + test_d}

    # oracle run: a caller that already knows those homes submits them
    # explicitly (domain=..., matched_len untouched) over the same prompts
    eng_o = _engine(model, params, index=None)
    trace_o = _trace_claims(eng_o)
    warm_o = [Request(r.rid, r.prompt.copy(), r.max_new, domain=resolved[r.rid])
              for r in warm_d]
    test_o = [Request(r.rid, r.prompt.copy(), r.max_new, domain=resolved[r.rid])
              for r in test_d]
    eng_o.run(warm_o)
    eng_o.run(test_o)

    assert trace_d == trace_o  # identical (rid, home, slot) claim sequence
    md, mo = eng_d.scheduler.metrics, eng_o.scheduler.metrics
    assert (md.admitted, md.local_admits, md.domain_switches) == \
           (mo.admitted, mo.local_admits, mo.domain_switches)
    td, to = eng_d.slots.telemetry, eng_o.slots.telemetry
    assert td.per_domain_placements == to.per_domain_placements
    assert (td.locality, td.migration_cycles) == (to.locality, to.migration_cycles)
    # same decode output, and the uncached-suffix discount never charges MORE
    assert {r.rid: r.out for r in test_d} == {r.rid: r.out for r in test_o}
    assert eng_d.sim_time <= eng_o.sim_time


def test_summary_exports_distinct_maximal_runs():
    """summary(top_k) emits hottest-first distinct maximal runs: a path that
    is a prefix of another emitted path never spends a second slot — the
    shallower-but-hotter case deepens the chosen entry in place (recording
    the extension covers every prefix of it)."""
    ix = PrefixIndex(n_domains=2)
    ix.record([1, 2, 3, 4], 0)
    ix.record([1, 2], 0)        # hotter, but subsumed by the deeper run
    ix.record([9, 9, 9], 1)
    out = ix.summary(top_k=3)
    paths = [p for p, _ in out]
    assert (9, 9, 9) in paths and (1, 2, 3, 4) in paths
    assert len(paths) == 2      # no slot wasted on (1, 2)
    assert paths[0] == (9, 9, 9)  # hottest first
    # top_k bounds the emission; deepening still applies under the bound
    assert [p for p, _ in ix.summary(top_k=1)] == [(9, 9, 9)]
    assert ix.summary(top_k=0) == []
    assert PrefixIndex().summary() == []
