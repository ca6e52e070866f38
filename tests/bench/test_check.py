"""The comparison that decides ``correct``, at a size a test can hold: the
program's served tokens pass it, and the control, the float32 reference
with its weights in float8 put in the program's place, fails it.

Tiny-size readings (CPU, 2 layers, width 64, 8 requests compared): the
program's widest gap 0-0.022, the float8 control's 0.106-0.220 over both
configurations and both mixes, so the limit here is 0.05.  With only 3
requests (about 100 tokens) the control at this size can miss every near
tie and read under 1e-4, so the tiny cells compare 8.
The cells' own limits come from readings at their own sizes on the chip
(PERF.md)."""

import pytest

from bench import check

from tiny import LIMIT, make_root, run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell,seed", [("tiny.chat", 11), ("tinyln.decode", 2**33 + 5),
                                       ("tiny.decode", 3)])
def test_program_passes_and_control_fails(root, cell, seed):
    r = run(root, cell, seed, control=True)
    c = r["check"]
    assert r["correct"], c
    assert c["served_tokens"]["value"] >= 20
    assert c["max_logit_gap"]["value"] <= LIMIT
    # the control comes out not correct by the comparison the run uses
    limits = {"max_logit_gap": c["control_gap"]["limit"]}
    assert c["control_gap"]["value"] > LIMIT
    assert not check.passes(c["control_gap"]["value"], limits)
    assert check.passes(c["max_logit_gap"]["value"], limits)
    assert r["attempted"] > 0 and r["failed"] == 0
    assert {"itl_p90_ms", "output_tok_s", "setup_s"} <= set(r["metrics"])


def test_passes():
    lim = {"max_logit_gap": 0.2}
    assert check.passes(0.0, lim) and check.passes(0.2, lim)
    assert not check.passes(0.2001, lim)
    assert not check.passes(float("nan"), lim) and not check.passes(float("inf"), lim)


def test_sample_holds_the_longest():
    fin = [([1] * n, [2] * m) for n, m in ((5, 3), (40, 10), (7, 2), (9, 9), (3, 1))]
    for seed in range(5):
        s = check.sample(fin, 3, seed)
        assert len(s) == 3 and s[0] == fin[1]
        assert len({id(x) for x in s}) == 3
    assert check.sample([], 3, 0) == []


def test_pack_positions():
    toks, pos, served, valid = check.pack([([5, 6, 7], [8, 9])], width=8, out_max=4, block=2)
    assert toks[0].tolist() == [5, 6, 7, 8, 0, 0, 0, 0]
    assert pos[0, :2].tolist() == [2, 3] and served[0, :2].tolist() == [8, 9]
    assert valid.tolist() == [[True, True, False, False], [False] * 4]
