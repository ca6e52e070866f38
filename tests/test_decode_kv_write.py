"""The decode step's KV write: one position per lane, in place.

``DecoderLM._merge_kv`` writes each step's new position with
``dynamic_update_slice``, or, where the TPU lays the cache out
position-minor (head size not a multiple of 128, cache a multiple of 128
positions), rewrites the whole aligned 128-position block around it with a
Pallas kernel; it keeps the whole-cache masked select only under a mesh
that shards the cache's sequence axis.  The engine's tick donates the slot
cache so that write lands in the buffer it came from, while the prefix
store's resume loop steps its shared entries without donating them."""

import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import REPO_ROOT, run_env
from repro.configs.base import get_reduced_config
from repro.models import transformer
from repro.models.registry import build_model
from repro.serving.engine import DecodeEngine, Request


@pytest.fixture(scope="module")
def small_model():
    cfg = get_reduced_config("granite_3_8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _random_cache(model, batch, cache_len, pos, seed):
    """A cache whose every position holds distinct values, so a write that
    lands anywhere but ``pos`` shows."""
    abstract = model.cache_abstract(batch, cache_len)
    leaves, tree = jax.tree.flatten(abstract)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    cache = jax.tree.unflatten(
        tree,
        [jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype) for k, s in zip(keys, leaves)],
    )
    cache["pos"] = jnp.asarray(pos, jnp.int32)
    return cache


# (window, cache_len, pos): lanes at 0 and the last position; a scalar pos;
# a ring of 8 positions that lanes have wrapped (12 -> slot 4, 15 -> 7)
CASES = {
    "lanes": (0, 16, [0, 5, 15]),
    "scalar": (0, 16, 7),
    "ring": (8, 16, [3, 12, 15]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_write_is_bitwise_the_masked_select(small_model, monkeypatch, case):
    """Cache and logits after one decode step are bitwise those of the
    whole-cache masked select the write replaces."""
    cfg, _, params = small_model
    window, cache_len, pos = CASES[case]
    model = build_model(cfg.replace(window=window))
    batch = len(pos) if isinstance(pos, list) else 2
    cache = _random_cache(model, batch, cache_len, pos, seed=len(case))
    tokens = jnp.arange(1, batch + 1, dtype=jnp.int32)[:, None]

    def step():
        return jax.jit(lambda p, c, t: model.decode_step(p, c, t))(params, cache, tokens)

    logits, out = step()
    # the select branch, as under a mesh that shards kv_seq
    monkeypatch.setattr(transformer, "axis_shards", lambda logical, dim: 2)
    ref_logits, ref = step()
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the write really landed: the step changed the cache
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(cache))
    )


# (window, head_dim, pos) over a 2-layer, 4-lane, 2-head cache of 1024
# positions (a 256-position ring for the window config): the block edges;
# positions past the end, which clamp onto the last entry; a ring's wrapped
# positions (300 -> slot 44, 1000 -> 232); lanes in different blocks; one
# scalar pos for every lane; an unstacked layer's (B, S, kv, hd) cache; and
# head 128, which keeps the one-position write
BLOCK_CASES = {
    "edges": (0, 80, [0, 127, 128, 1023]),
    "past_end": (0, 80, [1024, 1151, 4000, 5]),
    "ring": (256, 80, [255, 256, 300, 1000]),
    "mixed": (0, 64, [3, 700, 129, 1022]),
    "scalar": (0, 80, 700),
    "one_layer": (0, 80, [511, 0, 900, 1023]),
    "head128": (0, 128, [0, 127, 128, 1023]),
}


@pytest.mark.parametrize("case", sorted(BLOCK_CASES))
def test_block_write_is_bitwise_the_one_position_write(small_model, monkeypatch, case):
    """Where the head size is not a multiple of 128 the write rewrites whole
    128-position blocks, and leaves the cache bitwise what the
    one-position write leaves; head 128 takes the one-position write."""
    cfg, _, _ = small_model
    window, head_dim, pos = BLOCK_CASES[case]
    model = build_model(cfg.replace(window=window))
    layers = () if case == "one_layer" else (2,)
    s_max = window or 1024
    k_old, k_new = jax.random.split(jax.random.PRNGKey(len(case)))
    old = jax.random.normal(k_old, layers + (4, s_max, 2, head_dim)).astype(jnp.bfloat16)
    new = jax.random.normal(k_new, layers + (4, 1, 2, head_dim)).astype(jnp.bfloat16)
    pos = jnp.asarray(pos, jnp.int32)

    prims = str(jax.make_jaxpr(model._merge_kv)(old, new, pos))
    assert ("pallas_call" in prims) == (head_dim % 128 != 0), case
    got = np.asarray(model._merge_kv(old, new, pos))
    monkeypatch.setattr(transformer, "kv_write_block", lambda seq_len, head_dim: 1)
    assert "pallas_call" not in str(jax.make_jaxpr(model._merge_kv)(old, new, pos))
    want = np.asarray(model._merge_kv(old, new, pos))
    np.testing.assert_array_equal(got, want)
    assert not np.array_equal(got, np.asarray(old))


@pytest.mark.parametrize("head_dim,block", [(80, 128), (128, 1)])
def test_engine_gauges_its_kv_write_block(small_model, head_dim, block):
    """``engine_kv_write_block`` reads the positions a lane's decode KV
    write spans: the 128-position block for head 80, one for head 128."""
    from repro.obs.registry import MetricsRegistry

    cfg, _, _ = small_model
    model = build_model(cfg.replace(head_dim=head_dim))
    eng = DecodeEngine(model, model.init(jax.random.PRNGKey(0)), n_slots=2, cache_len=128)
    reg = MetricsRegistry()
    eng.register_metrics(reg)
    assert reg.collect()["engine_kv_write_block"] == block


def _requests(cfg, n, plen, max_new, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32), max_new=max_new)
        for i in range(n)
    ]


def test_tick_decode_donates_the_slot_cache(small_model):
    """After a tick the previous slot cache's leaves are deleted, the tick's
    program aliases every cache leaf to its output, and it copies no
    cache-shaped buffer."""
    cfg, model, params = small_model
    eng = DecodeEngine(model, params, n_slots=3, cache_len=32)
    for r in _requests(cfg, n=3, plen=6, max_new=8):
        eng.submit(r)
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
    for leaf in jax.tree.leaves(eng.slots.cache):
        if leaf.ndim < 4:
            continue
        dims = ",".join(map(str, leaf.shape))
        assert not re.search(rf"= \w+\[{dims}\]\{{[^}}]*\}} copy\(", hlo), dims


def test_prefix_store_entry_survives_resumed_decode(small_model):
    """The resume loop steps a cache the store holds by reference: that
    entry, and the one the resume deposits, read back bitwise unchanged
    after the resumed request has decoded for several ticks."""
    cfg, model, params = small_model
    eng = DecodeEngine(model, params, n_slots=2, cache_len=32, prefix_kv=True)
    (first,) = _requests(cfg, n=1, plen=8, max_new=3, seed=1)
    eng.run([first])
    stored = eng.prefix_kv.get(first.prompt)
    assert stored is not None
    snap = jax.tree.map(np.array, stored)

    rng = np.random.default_rng(2)
    extra = rng.integers(0, cfg.vocab, 3).astype(np.int32)
    follow = Request(rid=1, prompt=np.concatenate([first.prompt, extra]), max_new=6)
    eng.submit(follow)
    eng.step()
    assert eng.reused_positions == len(first.prompt)     # resumed, not re-prefilled
    resumed = eng.prefix_kv.get(follow.prompt)
    assert resumed is not None
    resumed_snap = jax.tree.map(np.array, resumed)
    for _ in range(4):
        eng.step()
    assert len(follow.out) >= 5

    for entry, ref in ((stored, snap), (resumed, resumed_snap)):
        leaves = jax.tree.leaves(entry)
        assert not any(leaf.is_deleted() for leaf in leaves)
        for a, b in zip(leaves, jax.tree.leaves(ref)):
            np.testing.assert_array_equal(np.asarray(a), b)


_CHOICE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.configs.base import get_reduced_config
    from repro.launch.mesh import make_mesh
    from repro.models.registry import build_model
    from repro.models.sharding import use_mesh

    model = build_model(get_reduced_config("granite_3_8b"))
    old = jax.ShapeDtypeStruct((2, 4, 16, 2, 16), jnp.bfloat16)
    new = jax.ShapeDtypeStruct((2, 4, 1, 2, 16), jnp.bfloat16)

    def writes(jaxpr):
        # primitives that produce a cache-shaped value, through nested jits
        for e in jaxpr.eqns:
            inner = e.params.get("jaxpr")
            if inner is not None:
                yield from writes(getattr(inner, "jaxpr", inner))
            elif any(v.aval.shape == old.shape for v in e.outvars):
                yield e.primitive.name
    for mesh_name, shape in (("none", None), ("model8", (1, 8)), ("model1", (8, 1))):
        mesh = None if shape is None else make_mesh(shape, ("data", "model"))
        with use_mesh(mesh):
            for kind, pos in (("lanes", (4,)), ("scalar", ())):
                jaxpr = jax.make_jaxpr(model._merge_kv)(
                    old, new, jax.ShapeDtypeStruct(pos, jnp.int32))
                prims = sorted(set(writes(jaxpr.jaxpr)))
                print("WRITE", mesh_name, kind, ",".join(prims))
""")


def test_write_follows_the_meshs_kv_seq_axis():
    """The masked select only where the active mesh splits the cache's
    sequence axis; with no mesh, or a ``model`` axis of size 1, the
    dynamic-update-slice write."""
    proc = subprocess.run(
        [sys.executable, "-c", _CHOICE], capture_output=True, text=True, timeout=300,
        env=run_env(), cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    seen = {
        tuple(line.split()[1:3]): line.split()[3] if len(line.split()) > 3 else ""
        for line in proc.stdout.splitlines() if line.startswith("WRITE")
    }
    assert len(seen) == 6, proc.stdout
    for (mesh_name, kind), prims in seen.items():
        prims = set(prims.split(","))
        want, not_want = "select_n", "dynamic_update_slice"
        if mesh_name != "model8":
            want, not_want = not_want, want
        assert want in prims and not_want not in prims, (mesh_name, kind, prims)
