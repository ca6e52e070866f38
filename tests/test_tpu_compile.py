"""Compile-only guards for a TPU v5e chip that is described, not attached.

The TPU compiler refuses what interpret mode accepts (block shapes that do
not tile, too much VMEM, programs that do not fit), so the kernels compile
here at real widths and must lower to a Mosaic ``tpu_custom_call``.  Nothing
runs: these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.rglru_scan.kernel import linear_scan_bsw
from repro.kernels.ssd_scan.kernel import ssd_intra_bchlp


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


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
