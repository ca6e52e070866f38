"""The roofline's FLOP and byte counts against numbers worked out by hand
for both configurations: real prompt tokens only, live lanes' KV only."""

import json

import pytest

from bench.work import Dims, decode_work, prefill_work

from conftest import ROOT


def dims(name):
    return Dims.from_config(json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text()))


def test_granite_sizes():
    g = dims("granite-3-8b")
    # attention 4096*128*(32+32+8+8), SwiGLU 3*4096*12800
    assert g.layer_matmul_params == 41_943_040 + 157_286_400
    # 20 layers + 41 RMSNorm vectors + the 4096 x 49155 head, in bf16
    assert g.weight_bytes == 8_372_191_232
    assert g.kv_bytes_per_position == 81_920          # 20 layers x K,V x 8 x 128 x 2 B
    assert g.flops_per_token == 8_371_855_360


def test_stablelm_sizes():
    s = dims("stablelm-3b")
    assert s.layer_matmul_params == 26_214_400 + 53_084_160
    assert s.weight_bytes == 5_333_329_920         # LayerNorm: weight and bias
    assert s.kv_bytes_per_position == 327_680       # 32 layers x K,V x 32 x 80 x 2 B


def test_granite_prefill_counts_real_tokens():
    g = dims("granite-3-8b")
    # one 100-token row: matmuls, causal attention over 5050 pairs, one logits row
    want = (798_975_221_760, 8_381_300_742)
    assert prefill_work(g, [100]) == want
    # pad rows (length 0) add nothing
    assert prefill_work(g, [100, 0, 0, 0, 0, 0, 0, 0]) == want


def test_stablelm_prefill():
    assert prefill_work(dims("stablelm-3b"), [7]) == (35_792_486_400, 5_335_760_128)


def test_granite_decode_counts_live_positions():
    g = dims("granite-3-8b")
    # two live lanes holding 10 and 20 positions: one weight read, their KV
    assert decode_work(g, [10, 20]) == (16_754_196_480, 8_375_025_676)


def test_stablelm_decode_kv_next_to_weights():
    s = dims("stablelm-3b")
    flops, bytes_ = decode_work(s, [999])
    assert (flops, bytes_) == (5_660_344_320, 5_661_115_648)
    # each further live position costs one position of KV, in every lane
    f8, b8 = decode_work(s, [999] * 8)
    assert b8 - bytes_ == 7 * (bytes_ - s.weight_bytes)
    # eight full lanes need 2.6 GB of KV beside 5.3 GB of weights; granite's
    # eight lanes need a quarter as much KV beside 8.4 GB
    g = dims("granite-3-8b")
    assert b8 - s.weight_bytes == 8 * (1000 * 327_680 + 2560 * 2 + 50304 * 2)
    assert decode_work(g, [999] * 8)[1] - g.weight_bytes < (b8 - s.weight_bytes) / 3


@pytest.mark.parametrize("name", ["granite-3-8b", "stablelm-3b"])
def test_empty_calls_need_nothing(name):
    d = dims(name)
    assert prefill_work(d, []) == (0, 0)
    assert decode_work(d, []) == (0, 0)
