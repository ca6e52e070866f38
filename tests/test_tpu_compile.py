"""Compile-only guards for a TPU v5e chip that is described, not attached.

The TPU compiler refuses what interpret mode accepts (block shapes that do
not tile, too much VMEM, programs that do not fit), so the kernels compile
here at real widths and must lower to a Mosaic ``tpu_custom_call``.  Nothing
runs: these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.rglru_scan.kernel import linear_scan_bsw
from repro.kernels.ssd_scan.kernel import ssd_intra_bchlp


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip.  The programs compiled for it lower their
    kernels for Mosaic, as on the chip, not in the CPU's interpret mode."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    from repro.kernels.kv_block_write import ops as kvw_ops

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kvw_ops, "interpret_mode", lambda: False)
        yield SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_flash_attention_compiles_at_granite_widths(one_chip):
    # granite-3-8b: 32 query heads over 8 KV heads (group 4), head_dim 128
    q = _sds(one_chip, (32, 2048, 128), jnp.bfloat16)
    kv = _sds(one_chip, (8, 2048, 128), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v: flash_attention_bhsd(
            q, k, v, group=4, causal=True, window=0, interpret=False
        ),
        q, kv, kv,
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_intra_compiles_at_mamba2_widths(one_chip):
    # mamba2-130m: 24 SSD heads of head_dim 64, state 128, chunk 128; 2048
    # tokens are 16 chunks
    b, nc, h, l, p, n = 1, 16, 24, 128, 64, 128
    compiled = _compile(
        lambda x, da, bm, cm: ssd_intra_bchlp(x, da, bm, cm, interpret=False),
        _sds(one_chip, (b, nc, h, l, p)),
        _sds(one_chip, (b, h, nc, 1, l)),
        _sds(one_chip, (b, nc, l, n)),
        _sds(one_chip, (b, nc, l, n)),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_rglru_scan_compiles_at_recurrentgemma_widths(one_chip):
    # recurrentgemma-2b: lru_width 2560 over a 2048-token prompt
    b, s, w = 1, 2048, 2560
    compiled = _compile(
        lambda a, x, h0: linear_scan_bsw(a, x, h0, interpret=False),
        _sds(one_chip, (b, s, w)),
        _sds(one_chip, (b, s, w)),
        _sds(one_chip, (b, 1, w)),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_one_chip_decode_step_compiles(one_chip):
    """The serving decode step of granite-3-8b's one-chip config at its
    published widths, cut to 2 layers, 8 slots over a 1024-position cache."""
    from repro.configs.base import get_preset_config
    from repro.models.registry import build_model

    cfg = get_preset_config("granite_3_8b", "one_chip").replace(n_layers=2)
    model = build_model(cfg)
    place = lambda s: _sds(one_chip, s.shape, s.dtype)  # noqa: E731
    params = jax.tree.map(place, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = jax.tree.map(place, model.cache_abstract(8, 1024))
    cache["pos"] = _sds(one_chip, (8,), jnp.int32)
    tokens = _sds(one_chip, (8, 1), jnp.int32)
    compiled = _compile(model.decode_step, params, cache, tokens)
    mem = compiled.memory_analysis()
    # 2 layers of bf16 weights, embedding and head: about 1.2 GB of arguments
    assert mem.argument_size_in_bytes > 1e9


@pytest.mark.parametrize("head_dim", [128, 80])
def test_donated_decode_step_writes_the_cache_in_place(one_chip, head_dim):
    """With the slot cache donated, as the engine's tick donates it, the
    decode step aliases every cache byte to its output and its temporaries
    stay far under one cache: no second cache is made.  Head size 80 is
    stablelm-3b's, whose cache the compiler lays out sequence-minor."""
    from repro.configs.base import get_preset_config
    from repro.models.registry import build_model

    cfg = get_preset_config("granite_3_8b", "one_chip").replace(n_layers=2, head_dim=head_dim)
    model = build_model(cfg)
    place = lambda s: _sds(one_chip, s.shape, s.dtype)  # noqa: E731
    params = jax.tree.map(place, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = jax.tree.map(place, model.cache_abstract(8, 1024))
    cache["pos"] = _sds(one_chip, (8,), jnp.int32)
    tokens = _sds(one_chip, (8, 1), jnp.int32)
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(params, cache, tokens).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes / 10


def test_hybrid_decode_step_compiles_donated_at_32_slots(one_chip):
    """granite-4.0-h-micro's serving decode step, whole (40 layers at the
    published widths), over 32 slots of a 1024-position cache, donated as
    the engine's tick donates it: every cache byte (2.71 GB, 89% of it
    float32 SSD state) aliases its output.  The SSD state rides in the
    layer scan's carry and is updated in place, so no state-shaped leaf is
    copied, and the readout reads the new state back from the carry, so no
    layer's new state (33.5 MB) is held as a temporary either: the
    temporaries stay under one layer's state (as scan outputs copied back
    into the cache they were 2.50 GB)."""
    from repro.configs.base import get_config
    from repro.models.registry import build_model

    model = build_model(get_config("granite_4_0_h_micro"))
    place = lambda s: _sds(one_chip, s.shape, s.dtype)  # noqa: E731
    params = jax.tree.map(place, model.abstract_params())
    cache = jax.tree.map(place, model.cache_abstract(32, 1024))
    cache["pos"] = _sds(one_chip, (32,), jnp.int32)
    tokens = _sds(one_chip, (32, 1), jnp.int32)
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(params, cache, tokens).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(cache))
    state_bytes = 36 * 32 * 64 * 64 * 128 * 4
    assert 2.7e9 < cache_bytes < 2.72e9 and state_bytes / cache_bytes > 0.89
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < state_bytes / 36
    hlo = compiled.as_text()
    assert not re.search(r"= (f32\[4,32,64,64,128\]|bf16\[4,32,3,4352\])\{[^}]*\} copy\(", hlo)
    # weights (6.38 GB) and cache, with the step's temporaries, fit one chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 0.75 * 16 * 2**30


def test_hybrid_admission_insert_writes_in_place(one_chip):
    """Admission's insert of one packed-prefill row (8 rows at the 128
    bucket) into granite-4.0-h-micro's 32-slot cache is one program that
    aliases every cache byte to its output, so an admission holds no second
    slot cache (2.71 GB) beside the first."""
    from repro.configs.base import get_config
    from repro.models.registry import build_model
    from repro.serving.kvcache import _insert_lane

    model = build_model(get_config("granite_4_0_h_micro"))
    place = lambda s: _sds(one_chip, s.shape, s.dtype)  # noqa: E731
    cache = jax.tree.map(place, model.cache_abstract(32, 1024))
    cache["pos"] = _sds(one_chip, (32,), jnp.int32)
    rows = jax.tree.map(place, model.cache_abstract(8, 128))
    pos = rows.pop("pos")
    logical = model.cache_logical()
    axes = tuple(ax.index("batch") for ax in jax.tree.leaves(
        {k: v for k, v in logical.items() if k != "pos"},
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(i, (str, type(None))) for i in x)))
    lane = _sds(one_chip, (), jnp.int32)
    mem = _insert_lane.lower(axes, cache, rows, pos, lane, lane).compile().memory_analysis()
    cache_bytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes / 100


# (config, preset, slots): stablelm-3b whole (32 heads of 80), the hybrid
# whole (4 attention layers, 8 heads of 64) and granite-3-8b's one-chip
# config (8 heads of 128), each over a 1024-position slot cache
KV_WRITE_CASES = {
    "stablelm-3b": ("stablelm_3b", None, 8),
    "granite-4.0-h-micro": ("granite_4_0_h_micro", None, 32),
    "granite-3-8b": ("granite_3_8b", "one_chip", 8),
}


@pytest.mark.parametrize("case", sorted(KV_WRITE_CASES))
def test_decode_kv_write_follows_the_cache_layout(one_chip, case):
    """The donated decode step's KV write on the chip's compiler.  Head 80
    and head 64 caches are laid out position-minor, the premise of
    ``kv_write_block``: there the whole-tile kernel writes them, on the
    cache's own bytes (no copy of a KV leaf).  Head 128 keeps its natural
    layout and the one-position ``dynamic-update-slice``.  Either way the
    temporaries stay under 0.7 GB: no second cache."""
    from repro.configs.base import get_config, get_preset_config
    from repro.models.registry import build_model
    from repro.models.transformer import kv_write_block

    arch, preset, slots = KV_WRITE_CASES[case]
    cfg = get_preset_config(arch, preset) if preset else get_config(arch)
    model = build_model(cfg)
    place = lambda s: _sds(one_chip, s.shape, s.dtype)  # noqa: E731
    params = jax.tree.map(place, model.abstract_params())
    cache = jax.tree.map(place, model.cache_abstract(slots, 1024))
    cache["pos"] = _sds(one_chip, (slots,), jnp.int32)
    tokens = _sds(one_chip, (slots, 1), jnp.int32)
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(params, cache, tokens).compile()
    hlo = compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9

    kv_dims = {s.shape for s in jax.tree.leaves(cache) if s.ndim == 5 and s.shape[2] == 1024}
    assert len(kv_dims) == 1, kv_dims
    (dims,) = kv_dims
    shape = ",".join(map(str, dims))
    minor = "2,4,3,1,0" if kv_write_block(1024, cfg.hd) > 1 else "4,3,2,1,0"
    params_at = re.findall(rf"= bf16\[{shape}\]\{{([\d,]+):T[^}}]*\}} parameter\(", hlo)
    assert params_at and set(params_at) == {minor}, (case, params_at)
    for d in (dims, (dims[0], dims[1], dims[3], dims[4], dims[2])):
        assert not re.search(rf"= bf16\[{','.join(map(str, d))}\]\{{[^}}]*\}} copy\(", hlo), d
    if minor == "2,4,3,1,0":
        assert hlo.count('custom_call_target="tpu_custom_call"') >= 2
        assert "kv_block_write" in hlo
    else:
        assert "kv_block_write" not in hlo
        assert re.search(rf"= bf16\[{shape}\]\{{[^}}]*\}} dynamic-update-slice\(", hlo)
