"""Bucketed/packed/AOT-warmed prefill (repro.serving.batching).

Three contracts pinned here:

  * **planning** — buckets are powers of two, ``log2(cache_len)`` of them
    for a power-of-two cache, and ``plan_packs`` preserves admission order.
  * **bitwise** — a packed prefill row, and a continuation-prefill resume,
    are bit-for-bit what the per-request ``prefill`` returns for that
    prompt alone (logits, KV over the *whole* slot cache, and pos) —
    including bucket-boundary lengths, ``cache_len - 1``, and packs mixing
    buckets.  This is what lets the engine pack every model the gate admits
    without changing a single emitted token.
  * **compile count** — a 40-prompt mixed-length workload leaves the trace
    counters exactly where AOT warm-up put them: packed-prefill traces
    <= log2(cache_len), one decode trace, no per-request prefill entry.

Models the gate refuses are served one prefill per request, with no prefix
store and no paging.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import example, given, settings, strategies as st
except ImportError:  # container lacks hypothesis: seeded fallback
    from _hypothesis_compat import example, given, settings, st

from repro.configs.base import get_reduced_config
from repro.models.registry import build_model
from repro.serving.batching import (
    PrefillBatcher,
    bucket_for,
    plan_packs,
    prompt_buckets,
)
from repro.serving.engine import DecodeEngine, Request
from repro.serving.kvcache import SlotCache

CACHE_LEN = 32
PACK = 4


@functools.lru_cache(maxsize=1)
def _setup():
    """Module-level (not a fixture: the hypothesis shim's runner takes no
    pytest arguments) — one reduced model + batcher + reference slot cache
    shared by every property test so jit caches amortise."""
    cfg = get_reduced_config("granite_3_8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batcher = PrefillBatcher(model, cache_len=CACHE_LEN, pack_width=PACK)
    slots = SlotCache.zeros(model, PACK, CACHE_LEN)
    ref_prefill = jax.jit(model.prefill)
    return cfg, model, params, batcher, slots, ref_prefill


def _prompts(lengths, seed):
    cfg = _setup()[0]
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, int(l)).astype(np.int32) for l in lengths]


def _single(prompt):
    """Per-request reference: prefill one prompt, refit to the slot shape."""
    _, model, params, _, slots, ref_prefill = _setup()
    logits, cache = ref_prefill(params, {"tokens": jnp.asarray(prompt)[None]})
    return logits[0], slots.fit_single(cache)


def _row(logits, cache, i):
    """Row ``i`` of a packed result in the same refitted slot shape."""
    _, _, _, batcher, slots, _ = _setup()
    return logits[i], slots.fit_single(batcher.extract_row(cache, i))


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        jnp.shape(x) == jnp.shape(y) and bool((jnp.asarray(x) == jnp.asarray(y)).all())
        for x, y in zip(la, lb)
    )


# ---------------------------------------------------------------------------
# planning core (pure python)
# ---------------------------------------------------------------------------

def test_buckets_power_of_two_budget():
    assert prompt_buckets(32) == [2, 4, 8, 16, 32]
    assert len(prompt_buckets(32)) == int(math.log2(32))
    assert prompt_buckets(2) == [2]
    with pytest.raises(ValueError):
        prompt_buckets(1)


@settings(max_examples=25, deadline=None)
@given(cache_len=st.integers(min_value=2, max_value=4096))
def test_buckets_cover_and_stay_logarithmic(cache_len):
    buckets = prompt_buckets(cache_len)
    assert buckets == sorted(set(buckets))
    assert all(b & (b - 1) == 0 for b in buckets)          # powers of two
    assert buckets[-1] >= cache_len - 1                     # longest admissible prompt fits
    assert len(buckets) <= math.log2(cache_len) + 1
    for l in (1, 2, cache_len - 1):
        b = bucket_for(l, buckets)
        assert l <= b and (b == buckets[0] or b // 2 < l)   # smallest covering bucket


@settings(max_examples=25, deadline=None)
@given(
    lengths=st.lists(st.integers(min_value=1, max_value=31), min_size=1, max_size=17),
    pack_width=st.integers(min_value=1, max_value=6),
)
def test_plan_packs_preserves_admission_order(lengths, pack_width):
    buckets = prompt_buckets(32)
    packs = plan_packs(lengths, pack_width=pack_width, buckets=buckets)
    flat = [i for _, rows in packs for i in rows]
    assert flat == list(range(len(lengths)))                # order is the fairness contract
    for bucket, rows in packs:
        assert len(rows) <= pack_width
        assert bucket == bucket_for(max(lengths[i] for i in rows), buckets)


# ---------------------------------------------------------------------------
# bitwise: packed prefill vs per-request reference
# ---------------------------------------------------------------------------

def test_packed_rows_bitwise_at_boundaries():
    """Bucket-boundary lengths, the longest admissible prompt, and a
    mixed-bucket pack — the explicit worst cases, always run."""
    _, _, params, batcher, _, _ = _setup()
    for lengths in ([2, 4, 8, 16], [CACHE_LEN - 1], [3, 16, 2, 31], [1, 5]):
        prompts = _prompts(lengths, seed=sum(lengths))
        logits, cache = batcher.prefill(params, prompts)
        for i, p in enumerate(prompts):
            ref_logits, ref_cache = _single(p)
            got_logits, got_cache = _row(logits, cache, i)
            assert bool((got_logits == ref_logits).all()), (lengths, i)
            assert _tree_equal(got_cache, ref_cache), (lengths, i)


@settings(max_examples=6, deadline=None)
@given(
    lengths=st.lists(st.integers(min_value=1, max_value=CACHE_LEN - 1), min_size=1, max_size=PACK),
    seed=st.integers(min_value=0, max_value=2**16),
)
# single-row packs whose caches once differed from the reference: pinned
# here so the result does not depend on a local example database
@example(lengths=[9], seed=2348)
@example(lengths=[6], seed=0)
def test_packed_rows_bitwise_property(lengths, seed):
    _, _, params, batcher, _, _ = _setup()
    prompts = _prompts(lengths, seed)
    logits, cache = batcher.prefill(params, prompts)
    for i, p in enumerate(prompts):
        ref_logits, ref_cache = _single(p)
        got_logits, got_cache = _row(logits, cache, i)
        assert bool((got_logits == ref_logits).all())
        assert _tree_equal(got_cache, ref_cache)


def test_dummy_rows_stay_empty():
    """Pack remainder rows (length 0) must read as vacant slots: pos 0 and
    all-zero KV, so inserting one over a free lane is indistinguishable
    from never touching it."""
    _, _, params, batcher, slots, _ = _setup()
    logits, cache = batcher.prefill(params, _prompts([5], seed=9))
    for i in range(1, PACK):
        row = slots.fit_single(batcher.extract_row(cache, i))
        assert int(row["pos"]) == 0
        assert all(
            bool((jnp.asarray(l) == 0).all())
            for k in row if k != "pos"
            for l in jax.tree.leaves(row[k])
        )


# ---------------------------------------------------------------------------
# bitwise: continuation prefill vs from-scratch reference
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(
    spec=st.lists(
        st.tuples(
            st.integers(min_value=2, max_value=CACHE_LEN - 1),  # full length
            st.integers(min_value=1, max_value=CACHE_LEN - 2),  # seeded prefix
        ),
        min_size=1,
        max_size=PACK,
    ),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_continuation_bitwise_property(spec, seed):
    """Seed each row with a per-request prefill of a proper prefix, extend
    by the suffix via ``continue_rows`` — bitwise the from-scratch prefill
    of the full prompt.  This is the contract that lets prefix-KV resumes
    ride the packed path without perturbing a single token."""
    _, _, params, batcher, _, _ = _setup()
    spec = [(l, min(m, l - 1)) for l, m in spec]             # 1 <= matched < len
    prompts = _prompts([l for l, _ in spec], seed)
    rows = [_single(p[:m])[1] for p, (_, m) in zip(prompts, spec)]
    suffixes = [p[m:] for p, (_, m) in zip(prompts, spec)]
    logits, cache = batcher.continue_rows(params, rows, suffixes)
    for i, p in enumerate(prompts):
        ref_logits, ref_cache = _single(p)
        got_logits, got_cache = _row(logits, cache, i)
        assert bool((got_logits == ref_logits).all()), spec[i]
        assert _tree_equal(got_cache, ref_cache), spec[i]


# ---------------------------------------------------------------------------
# compile-count regression (the trace-budget acceptance criterion)
# ---------------------------------------------------------------------------

def test_compile_count_bounded_on_mixed_workload():
    """40 prompts spanning every length the cache admits: packed-prefill
    traces stay <= log2(cache_len) (all paid at AOT warm-up, none in the
    serving loop), decode traces exactly 1, per-request prefill never runs."""
    _, model, params, _, _, _ = _setup()
    eng = DecodeEngine(model, params, n_slots=4, cache_len=CACHE_LEN)
    warm = dict(eng.compile_counts)
    assert warm["packed_prefill"] == len(eng.batcher.buckets)   # warmed at construction
    assert warm["packed_prefill"] <= math.log2(CACHE_LEN)
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, 64, 2 + i % (CACHE_LEN - 2)).astype(np.int32),
                max_new=2, domain=i % 2)
        for i in range(40)
    ]
    eng.run(reqs)
    assert all(r.done for r in reqs)
    cc = eng.compile_counts
    assert cc["packed_prefill"] == warm["packed_prefill"]    # zero serving-loop traces
    assert cc["packed_prefill"] <= math.log2(CACHE_LEN)
    assert cc["decode"] == 1
    assert "prefill" not in cc


# ---------------------------------------------------------------------------
# engine equivalence: packing changes schedule shape, never tokens
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _jitted(model):
    return jax.jit(model.prefill), jax.jit(model.decode_step)


def _reference(model, params, prompt, n_new):
    """Jitted per-request reference: prefill the prompt alone, then greedy
    ``decode_step`` one token at a time."""
    prefill, step = _jitted(model)
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt)[None]})
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(n_new - 1):
        logits, cache = step(params, cache, jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(logits[0])))
    return out


def _mixed_requests(cfg, seed, n=8, max_new=3):
    rng = np.random.default_rng(seed)
    lens = rng.integers(2, CACHE_LEN - 1, n)
    rng2 = np.random.default_rng(seed + 1)
    return [
        Request(rid=i, prompt=rng2.integers(0, cfg.vocab, int(l)).astype(np.int32),
                max_new=max_new, domain=i % 2)
        for i, l in enumerate(lens)
    ]


def test_batched_engine_matches_legacy():
    cfg, model, params, _, _, _ = _setup()
    reqs = _mixed_requests(cfg, seed=3)
    eng = DecodeEngine(model, params, n_slots=4, cache_len=CACHE_LEN)
    eng.run(reqs)
    for r in reqs:
        assert r.out == _reference(model, params, r.prompt, len(r.out)), r.rid
    assert "prefill" not in eng.compile_counts


def test_batched_prefix_kv_matches_from_scratch():
    """Shared-prefix traffic over a live PrefixKVStore: full hits, partial
    hits (continuation pack) and boundary plants all active — outputs stay
    bitwise what a *from-scratch* engine (no store) emits: ``prefill_cont``
    replays the exact prefill op order, where a ``decode_step`` suffix
    replay would agree only to cache-dtype resolution."""
    cfg, model, params, _, _, _ = _setup()

    def mk(seed):
        rng = np.random.default_rng(seed)
        sys_p = np.random.default_rng(42).integers(0, cfg.vocab, 10).astype(np.int32)
        reqs = []
        for i in range(5):  # divergent suffixes off a shared system prompt
            sfx = rng.integers(0, cfg.vocab, 3 + i).astype(np.int32)
            reqs.append(Request(rid=i, prompt=np.concatenate([sys_p, sfx]),
                                max_new=3, domain=i % 2))
        for i in range(3):  # exact repeats -> full store hits
            reqs.append(Request(rid=5 + i, prompt=reqs[i].prompt.copy(),
                                max_new=3, domain=i % 2))
        for i in range(2):  # follow-ups extending prompt+output -> partial hits
            ext = np.concatenate([reqs[i].prompt,
                                  rng.integers(0, cfg.vocab, 3).astype(np.int32)])
            reqs.append(Request(rid=8 + i, prompt=ext, max_new=3, domain=i % 2))
        return reqs

    a, c = mk(5), mk(5)
    scratch = DecodeEngine(model, params, n_slots=4, cache_len=2 * CACHE_LEN)
    scratch.run(a)
    bat = DecodeEngine(model, params, n_slots=4, cache_len=2 * CACHE_LEN, prefix_kv=True)
    bat.run(c)
    assert [r.out for r in a] == [r.out for r in c]
    assert bat.reused_positions > 0                          # the store actually fired
    assert bat.compile_counts["cont_prefill"] <= math.log2(2 * CACHE_LEN)
    # reuse accounting is conserved: every prompt position is either
    # computed or resumed from the store, once
    assert bat.prefill_positions + bat.reused_positions == scratch.prefill_positions


# ---------------------------------------------------------------------------
# the gate: archs where right-padding is not bitwise-invisible refuse
# ---------------------------------------------------------------------------

def test_gate_refuses_non_dense_arch():
    # SSD stacks take the packed path since their pad positions leave the
    # state as it stands (tests/test_hybrid.py); RG-LRU state does not
    cfg = get_reduced_config("recurrentgemma_2b")
    model = build_model(cfg)
    assert not model.supports_packed_prefill(CACHE_LEN)
    with pytest.raises(ValueError, match="one request at a time"):
        PrefillBatcher(model, cache_len=CACHE_LEN, pack_width=2)


@pytest.mark.parametrize("arch", ["recurrentgemma_2b", "mixtral_8x22b", "pixtral_12b"])
def test_gate_refused_arch_admits_per_request(arch):
    """RG-LRU, MoE with sliding windows and patch-prefixed models, which
    the gate refuses, are served one prefill per request and decode what
    the per-request reference decodes."""
    cfg = get_reduced_config(arch)
    model = build_model(cfg)
    assert not model.supports_packed_prefill(CACHE_LEN)
    params = model.init(jax.random.PRNGKey(0))
    eng = DecodeEngine(model, params, n_slots=2, cache_len=CACHE_LEN)
    assert eng.batcher is None
    rng = np.random.default_rng(11)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n).astype(np.int32), max_new=4)
            for i, n in enumerate([5, 9, 3])]
    eng.run(reqs)
    for r in reqs:
        assert r.out == _reference(model, params, r.prompt, r.max_new), (arch, r.rid)
    assert eng.compile_counts["prefill"] >= 1 and "packed_prefill" not in eng.compile_counts


@pytest.mark.parametrize("kw", ["prefix_kv", "paging"])
def test_gate_refused_arch_refuses_store(kw):
    model = build_model(get_reduced_config("mixtral_8x22b"))
    with pytest.raises(ValueError, match="served per request only"):
        DecodeEngine(model, None, n_slots=2, cache_len=CACHE_LEN, **{kw: True})


def test_engine_rejects_batching_false():
    _, model, params, _, _, _ = _setup()
    with pytest.raises(ValueError, match="packed-prefill gate"):
        DecodeEngine(model, params, n_slots=2, cache_len=CACHE_LEN, batching=False)


def test_gate_checks_attn_dispatch_per_bucket():
    """Chunked attention streams above ``attn_chunk`` — a bucket past it
    would diverge from the per-request reference's dispatch, so the gate
    must refuse exactly then."""
    cfg = dataclasses.replace(get_reduced_config("granite_3_8b"), attn_chunk=8)
    model = build_model(cfg)
    assert model.supports_packed_prefill(8)
    assert not model.supports_packed_prefill(32)
    cfg_xla = dataclasses.replace(cfg, attn_impl="xla")
    assert build_model(cfg_xla).supports_packed_prefill(32)
