"""The trace reduction: busy time as a union of device intervals, programs
attributed to the harness spans that launched them, and idle gaps by the
host span open during them.  Synthetic traces check the arithmetic; a small
trace recorded on a TPU v5e checks it on the real format."""

import json
from pathlib import Path

import pytest

from bench import trace

HERE = Path(__file__).resolve().parent
MS = 1e6   # ns


def test_union_and_overlap():
    merged = trace.union([(0, 2), (1, 3), (5, 6), (6, 7), (10, 11), (10.5, 10.6)])
    assert merged == [(0, 3), (5, 7), (10, 11)]
    assert trace.overlap(merged, 2, 10.5) == 1 + 2 + 0.5
    assert trace.overlap(merged, 3, 5) == 0


def _synthetic(shift=-1.5 * MS):
    """Host: a window of 100 ms holding two ticks.  Device (clock ``shift``
    off the host's): a prefill program, a decode program and one eager op."""
    spans = [("bench.window", 0, 100 * MS),
             ("bench.step", 10 * MS, 40 * MS), ("bench.admit", 10 * MS, 20 * MS),
             ("bench.prefill", 11 * MS, 12 * MS), ("bench.decode", 21 * MS, 22 * MS),
             ("bench.wait", 45 * MS, 60 * MS),
             ("bench.step", 60 * MS, 90 * MS), ("bench.decode", 62.5 * MS, 63 * MS)]
    d = lambda a, b: (a * MS + shift, b * MS + shift)
    modules = [("jit_counted(111)", *d(12, 18)), ("jit_dynamic_update_slice(5)", *d(19, 20)),
               ("jit_counted(222)", *d(22, 30)), ("jit_counted(222)", *d(62.5, 70))]
    ops = [("%while.4 = (s32[]) while(x)", *d(12, 18)),
           ("%fusion.1 = bf16[8] fusion(a)", *d(12, 15)), ("%fusion.2 = bf16[8] fusion(b)", *d(15, 18)),
           ("%copy = bf16[8] copy(c)", *d(19, 20)),
           ("%fusion.3 = bf16[8] fusion(d)", *d(22, 30)), ("%fusion.3 = bf16[8] fusion(d)", *d(62.5, 70))]
    return trace.Trace([ops], [modules], spans)


def test_pairs_and_alignment():
    r = trace.reduce(_synthetic())
    assert [k for k, _, _ in r.pairs] == ["prefill", "decode", "decode"]
    # device time shifted so no program starts before its launching span:
    # the last decode launched with no latency, so the 1.5 ms offset comes back
    assert r.shift == [pytest.approx(1.5 * MS)]
    assert r.pairs[0][2][1] == pytest.approx(12 * MS)


def test_busy_and_idle():
    r = trace.reduce(_synthetic())
    assert r.busy_ns == pytest.approx((6 + 1 + 8 + 7.5) * MS)
    gaps = dict(r.idle_gaps)
    # idle: 0-12, 18-19, 20-22, 30-62.5, 70-100 ms, split where spans open
    # and close, each piece labelled by the innermost span open over it
    assert gaps["bench.window"] == pytest.approx((10 + 5 + 10) * 1e-3)   # 0-10, 40-45, 90-100
    assert gaps["bench.admit"] == pytest.approx(2e-3)                   # 10-11, 18-19
    assert gaps["bench.prefill"] == pytest.approx(1e-3)                 # 11-12
    assert gaps["bench.decode"] == pytest.approx(1e-3)                  # 21-22
    assert gaps["bench.wait"] == pytest.approx(15e-3)                   # 45-60
    assert gaps["bench.step"] == pytest.approx((1 + 10 + 2.5 + 20) * 1e-3)
    assert sum(gaps.values()) == pytest.approx(0.1 - r.busy_ns * 1e-9)


def test_ops_attributed_to_their_program():
    ops = dict(trace.reduce(_synthetic()).device_ops)
    assert ops["decode:fusion"] == pytest.approx(15.5e-3)
    assert ops["prefill:fusion"] == pytest.approx(6e-3)
    assert ops["jit_dynamic_update_slice:copy"] == pytest.approx(1e-3)
    assert "prefill:while" not in ops      # a container: its body is counted


def test_recorded_trace():
    """About 150 ms of a granite-3-8b.chat window traced on one TPU v5e, cut
    at engine-tick boundaries (op names shortened to their HLO names)."""
    tr = trace.Trace.from_json(json.loads((HERE / "granite_chat_trace.json").read_text()))
    r = trace.reduce(tr)
    w = r.window[1] - r.window[0]
    assert 0 < r.busy_ns <= w
    assert r.busy_ns == pytest.approx(trace.overlap(trace.union(
        [(a + r.shift[0], b + r.shift[0]) for _, a, b in tr.ops[0]]), *r.window))
    kinds = {k for k, _, _ in r.pairs}
    assert {"prefill", "decode"} <= kinds
    for kind, span, (name, a, b) in r.pairs:
        assert name.startswith("jit_counted(") and a >= span[1]
    # one fingerprint per kind of program
    fp = {}
    for kind, _, (name, _, _) in r.pairs:
        fp.setdefault(name, set()).add(kind)
    assert all(len(v) == 1 for v in fp.values())
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(w * 1e-9 - r.busy_ns * 1e-9, rel=1e-6)
