"""The decode step's state layers: carried through the layer scan and
updated in place.

``DecoderLM.decode_step`` carries a scanned segment's SSD and RG-LRU state
leaves in the scan's carry and writes each layer's new state at its layer
index, while attention KV stays scan xs merged after the scan.  These tests
hold it bitwise to the former formulation, state as scan xs and ys, kept
here as the reference, and check that the engine's tick donates the
hybrid's cache."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_reduced_config
from repro.models.registry import build_model
from repro.models.transformer import block_step
from repro.serving.engine import DecodeEngine, Request
from test_decode_kv_write import _random_cache


def _decode_state_as_xs(model, params, cache, tokens):
    """The former decode step: every cache leaf is read-only scan xs, each
    state layer's new state comes back as the scan's ys."""
    cfg = model.cfg
    pos = cache["pos"]
    x = model._embed(params, tokens, pos_offset=pos)
    new_cache = dict(cache)
    for seg in model.segments:
        p = model._seg_params(params, seg)
        assert seg.mode == "scan", seg

        def body(xx, xs, _kinds=seg.kinds):
            ps, cs = xs
            outs = []
            for j, kind in enumerate(_kinds):
                xx, out = block_step(ps[j], xx, cfg, kind, pos, cs[j])
                if kind not in ("attn", "moe"):
                    out = jax.tree.map(lambda n, c: n.astype(c.dtype), out, cs[j])
                outs.append(out)
            return xx, tuple(outs)

        x, ys = jax.lax.scan(body, x, (p, cache[seg.name]))
        new_cache[seg.name] = tuple(
            tuple(model._merge_kv(c, n, pos) for c, n in zip(cache[seg.name][j], ys[j]))
            if kind in ("attn", "moe") else ys[j]
            for j, kind in enumerate(seg.kinds)
        )
    logits = model._logits(params, x)
    new_cache["pos"] = pos + 1
    return logits[:, 0], new_cache


# (arch, overrides): the reduced configs whose scanned segments hold state
# kinds, and recurrentgemma at six layers so its rec/rec/attn cycle repeats
CONFIGS = {
    "granite-4.0-h-micro": ("granite_4_0_h_micro", {}),
    "mamba2-130m": ("mamba2_130m", {}),
    "recurrentgemma-2b": ("recurrentgemma_2b", {}),
    "recurrentgemma-2b-6l": ("recurrentgemma_2b", {"n_layers": 6}),
}


@pytest.mark.parametrize("case", sorted(CONFIGS))
def test_carried_state_is_bitwise_the_scan_outputs(case):
    """Logits and every cache leaf after one decode step, with per-lane
    positions that differ, are bitwise those of state as scan xs and ys."""
    arch, over = CONFIGS[case]
    model = build_model(get_reduced_config(arch).replace(**over))
    assert any(k in ("ssd", "rec") for s in model.segments for k in s.kinds)
    params = model.init(jax.random.PRNGKey(1))
    cache = _random_cache(model, 3, 16, [0, 6, 13], seed=len(case))
    tokens = jnp.asarray([[5], [17], [2]], jnp.int32)

    logits, out = jax.jit(model.decode_step)(params, cache, tokens)
    ref_logits, ref = jax.jit(lambda p, c, t: _decode_state_as_xs(model, p, c, t))(
        params, cache, tokens)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    assert jax.tree.structure(out) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # every state leaf was written: the step moved each layer's state
    for seg in model.segments:
        for kind, new, old in zip(seg.kinds, out[seg.name], cache[seg.name]):
            if kind in ("ssd", "rec"):
                for a, b in zip(new, old):
                    for layer in range(a.shape[0]):
                        assert not np.array_equal(np.asarray(a[layer]), np.asarray(b[layer]))


def test_all_attention_segments_trace_the_former_program():
    """A segment with no state kind carries only x: its step traces to the
    same program as the former formulation, so dense models compile as
    they did."""
    model = build_model(get_reduced_config("granite_3_8b"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = model.cache_abstract(3, 16)
    cache["pos"] = jax.ShapeDtypeStruct((3,), jnp.int32)
    tokens = jax.ShapeDtypeStruct((3, 1), jnp.int32)
    new = jax.make_jaxpr(model.decode_step)(params, cache, tokens)
    old = jax.make_jaxpr(lambda p, c, t: _decode_state_as_xs(model, p, c, t))(
        params, cache, tokens)
    assert str(new) == str(old)


def test_tick_decode_donates_the_hybrid_cache():
    """After a tick the hybrid's previous slot cache, state leaves
    included, is deleted, and the tick's program aliases every cache leaf
    to its output."""
    cfg = get_reduced_config("granite_4_0_h_micro")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = DecodeEngine(model, params, n_slots=3, cache_len=32)
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=rng.integers(0, cfg.vocab, 6).astype(np.int32),
                           max_new=8))
    eng.step()                      # admits all three, decodes once
    before = eng.slots.cache
    eng.step()                      # decodes only: nothing admitted, none retired
    assert len(eng.active_req) == 3
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(eng.slots.cache))

    hlo = eng._step._fn.lower(params, eng.slots.cache, eng.tokens).compile().as_text()
    header = hlo.splitlines()[0]          # HloModule ..., input_output_alias={...}
    aliases = re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(set(aliases)) == len(jax.tree.leaves(eng.slots.cache)), header[:300]
