"""The Mamba-2 + attention hybrid (granite-4.0-h) on the serving path, at a
small size on the CPU: cache axes declared per leaf, packed right-padded
prefill of state layers, and the engine's refusals and gauges.  The
comparison with the float32 reference is in ``tests/bench``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_reduced_config
from repro.models.registry import build_model
from repro.serving.engine import DecodeEngine, Request
from repro.serving.kvcache import SlotCache


def _is_axes(x):
    return isinstance(x, tuple) and all(isinstance(i, (str, type(None))) for i in x)


# (arch, overrides, kind, stacked): each block kind as a scanned (stacked)
# leaf and as an unrolled one (a pattern remainder, or deepseek-style leading
# dense layers)
CACHE_CASES = {
    "attn-stacked": ("granite_3_8b", {}, "attn", True),
    "attn-unrolled": ("granite_3_8b", {"first_k_dense": 1}, "attn", False),
    "rec-stacked": ("recurrentgemma_2b", {"n_layers": 3}, "rec", True),
    "rec-unrolled": ("recurrentgemma_2b", {"n_layers": 4}, "rec", False),
    "ssd-stacked": ("mamba2_130m", {}, "ssd", True),
    "ssd-unrolled": ("granite_4_0_h_micro", {"n_layers": 4}, "ssd", False),
}


@pytest.mark.parametrize("case", sorted(CACHE_CASES))
def test_slot_cache_round_trips_one_lane(case):
    """``insert_row`` puts lane ``row`` of a packed cache into one slot and
    ``extract`` hands back exactly that lane; every other slot is left as
    it was.  A leaf whose batch axis were mislabelled (a stacked conv tail
    or RG-LRU state read by rank) would land on the wrong axis."""
    arch, over, kind, stacked = CACHE_CASES[case]
    model = build_model(get_reduced_config(arch).replace(**over))
    seg = [s for s in model.segments if kind in s.kinds and (s.mode == "scan") == stacked]
    assert seg, f"{case}: no {'scanned' if stacked else 'unrolled'} {kind} segment"

    abstract = model.cache_abstract(3, 16)
    logical = model.cache_logical(abstract)
    for sds, axes in zip(jax.tree.leaves(abstract),
                         jax.tree.leaves(logical, is_leaf=_is_axes)):
        assert len(axes) == len(sds.shape), (sds.shape, axes)
        if sds.shape:
            assert axes.index("batch") == (1 if axes[0] == "layers" else 0)

    slots = SlotCache.zeros(model, 3, 16)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))

    def fill(tree):
        return jax.tree.map(
            lambda s: jax.random.normal(next(keys), s.shape, jnp.float32).astype(s.dtype), tree)

    before = fill({k: v for k, v in slots.cache.items() if k != "pos"})
    slots.cache.update(before)
    donated = jax.tree.leaves(before)
    before = jax.tree.map(np.array, before)     # copies: the insert takes the cache donated
    packed = fill({k: v for k, v in model.cache_abstract(2, 16).items() if k != "pos"})
    packed["pos"] = jnp.asarray([5, 9], jnp.int32)

    slots.claim("req")                      # slot 0
    slots.claim("req2")                     # slot 1
    slots.insert_row(1, packed, 1)
    assert all(leaf.is_deleted() for leaf in donated)   # written in place
    lane = slots.extract(1)
    assert int(lane["pos"]) == 9
    for key in before:
        got = jax.tree.leaves(lane[key])
        want = jax.tree.leaves(packed[key])
        old = jax.tree.leaves(before[key])
        now = jax.tree.leaves(slots.cache[key])
        axes = jax.tree.leaves(logical[key], is_leaf=_is_axes)
        for g, w, o, n, ax in zip(got, want, old, now, axes):
            b = ax.index("batch")
            np.testing.assert_array_equal(np.asarray(g), np.take(np.asarray(w), [1], axis=b))
            for other in (0, 2):
                np.testing.assert_array_equal(np.take(np.asarray(n), other, axis=b),
                                              np.take(np.asarray(o), other, axis=b))


@pytest.fixture(scope="module")
def hybrid32():
    """The reduced hybrid computing in float32, so packed and per-request
    prefill differ only by float32 reassociation."""
    cfg = get_reduced_config("granite_4_0_h_micro").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    return cfg, model, params


def _state_leaves(model, cache, lane):
    """Every SSD leaf (state and conv tail) of one lane of a cache."""
    out = []
    for seg in model.segments:
        per = cache[seg.name] if seg.mode == "scan" else (cache[seg.name],)
        for kind, c in zip(seg.kinds, per):
            if kind == "ssd":
                ax = 1 if seg.mode == "scan" else 0
                out += [np.take(np.asarray(t), lane, axis=ax) for t in c]
    return out


def test_packed_rows_match_each_prompt_alone(hybrid32):
    """Right-padded rows of mixed lengths in one packed call give each
    prompt's last-token logits, its SSD state and conv tail at its true
    length, and then the same decode step, as that prompt prefilled alone.
    Tolerance 1e-5 of the logits' scale: float32 throughout, the only
    difference is where the chunked scan splits a row (its chunk is the
    bucket, 16, in the pack; the prompt's length alone), which reorders
    float32 sums.  A pad position that moved the state (dt left as it is at
    pads) or a conv tail taken at the padded end misses by orders more."""
    cfg, model, params = hybrid32
    lengths = [3, 16, 9, 1, 0]
    rng = np.random.default_rng(0)
    toks = np.zeros((len(lengths), 16), np.int32)
    for i, n in enumerate(lengths):
        toks[i, :n] = rng.integers(0, cfg.vocab, n)
    logits, cache = jax.jit(model.prefill_packed)(params, toks, np.asarray(lengths, np.int32))
    nxt = jnp.asarray(rng.integers(0, cfg.vocab, (len(lengths), 1)), jnp.int32)
    step_logits, _ = jax.jit(model.decode_step)(params, cache, nxt)
    scale = float(jnp.std(logits[:, : cfg.vocab]))
    for i, n in enumerate(lengths):
        if n == 0:                             # a dummy row keeps zero state
            assert all(not np.any(t) for t in _state_leaves(model, cache, i))
            continue
        alone_logits, alone = model.prefill(params, {"tokens": jnp.asarray(toks[i:i + 1, :n])})
        np.testing.assert_allclose(logits[i], alone_logits[0], atol=1e-5 * scale, rtol=0)
        for got, want in zip(_state_leaves(model, cache, i), _state_leaves(model, alone, 0)):
            np.testing.assert_allclose(got, want, atol=1e-5 * max(1.0, np.abs(want).max()), rtol=0)
        alone_step, _ = model.decode_step(params, alone, nxt[i:i + 1])
        np.testing.assert_allclose(step_logits[i], alone_step[0], atol=1e-5 * scale, rtol=0)


def test_batching_serves_the_hybrid_and_mamba2():
    """The gate admits SSD stacks, alone or between attention layers, and
    the batched engine serves them; RG-LRU stacks stay refused."""
    for arch in ("granite_4_0_h_micro", "mamba2_130m"):
        model = build_model(get_reduced_config(arch))
        assert model.supports_packed_prefill(64) and model.has_recurrent_state
        params = model.init(jax.random.PRNGKey(0))
        eng = DecodeEngine(model, params, n_slots=2, cache_len=64)
        assert eng.batcher is not None, arch
        reqs = [Request(rid=i, prompt=np.arange(3 + 4 * i, dtype=np.int32) % 500, max_new=5)
                for i in range(3)]
        eng.run(reqs)
        assert all(len(r.out) == 5 for r in reqs), arch
    assert not build_model(get_reduced_config("recurrentgemma_2b")).supports_packed_prefill(64)


@pytest.mark.parametrize("kw", [{"paging": True}, {"prefix_kv": True}])
def test_state_layers_refuse_paging_and_prefix_kv(kw):
    model = build_model(get_reduced_config("granite_4_0_h_micro"))
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="state snapshots"):
        DecodeEngine(model, params, n_slots=2, cache_len=64, **kw)


def test_engine_gauges_split_state_and_kv_bytes():
    from repro.obs import MetricsRegistry

    cfg = get_reduced_config("granite_4_0_h_micro")
    model = build_model(cfg)
    eng = DecodeEngine(model, model.init(jax.random.PRNGKey(0)), n_slots=3, cache_len=64)
    reg = MetricsRegistry()
    eng.register_metrics(reg)
    snap = reg.collect()
    n_ssd, n_attn = cfg.blocks.count("ssd"), cfg.blocks.count("attn")
    conv_ch = cfg.d_inner + 2 * cfg.ssm_state
    state = n_ssd * 3 * (cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
                         + (cfg.conv_width - 1) * conv_ch * 2)
    assert snap["engine_state_bytes"] == state
    assert snap["engine_kv_bytes"] == n_attn * 2 * 3 * 64 * cfg.n_kv * cfg.hd * 2


def test_multipliers_default_to_no_ops():
    """Granite's four multipliers at their defaults leave a config's logits
    bitwise as they were; set, each one moves them."""
    cfg = get_reduced_config("granite_3_8b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    toks = {"tokens": jnp.asarray([[5, 7, 11, 13, 17]], jnp.int32)}
    base, _ = build_model(cfg).prefill(params, toks)
    same = cfg.replace(embedding_multiplier=1.0, attention_multiplier=0.0,
                       residual_multiplier=1.0, logits_scaling=1.0)
    np.testing.assert_array_equal(build_model(same).prefill(params, toks)[0], base)
    halved, _ = build_model(cfg.replace(logits_scaling=2.0)).prefill(params, toks)
    np.testing.assert_array_equal(halved, base / 2)   # a power of two divides exactly
    for field, value in (("embedding_multiplier", 3.0), ("attention_multiplier", 0.5),
                         ("residual_multiplier", 0.5)):
        moved, _ = build_model(cfg.replace(**{field: value})).prefill(params, toks)
        assert not np.array_equal(moved, base), field
