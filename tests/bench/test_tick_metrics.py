"""The engine-tick readers (``tick_*_ms``, ``decode_lane_share``) over the
counters of a tiny engine run on the CPU: each reads a number, the host
phases fit inside the tick's host time, and a program that publishes no
phase counters reads None rather than failing."""

import numpy as np
import pytest

from bench import spec
from bench.run import RunContext

from tiny import make_root

PARTS = ("tick_admit_ms", "tick_dispatch_ms", "tick_retire_ms")
READERS = ("tick_host_ms", *PARTS, "decode_lane_share")


@pytest.fixture(scope="module")
def window(tmp_path_factory):
    """The cell and the program's counters around a stretch of ticks that
    admits, decodes and retires requests."""
    from repro.serving.engine import Request

    cell = spec.cell("tiny.decode", make_root(tmp_path_factory.mktemp("tiny")))
    prog, cfg = cell.module("programs"), cell.config
    model = prog.build(cfg)
    eng = prog.engine(model, prog.init_params(model, 2**33 + 1), cfg)
    rng = np.random.default_rng(0)
    for rid in range(6):
        eng.submit(Request(rid=rid, prompt=rng.integers(0, cfg["vocab_size"], 5 + rid)
                           .astype(np.int32), max_new=6))
    eng.step()
    start = prog.counters(eng)
    while len(eng.scheduler) or eng.active_req:
        eng.step()
    return cell, start, prog.counters(eng)


def _ctx(cell, start, end):
    return RunContext(cell=cell, counters_start=start, counters_end=end)


def test_tick_readers_read_the_phase_counters(window):
    cell, start, end = window
    ctx = _ctx(cell, start, end)
    got = {name: cell.reader(name)(ctx) for name in READERS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert sum(got[p] for p in PARTS) <= got["tick_host_ms"]
    assert 0 < got["decode_lane_share"] <= 100
    ticks = ctx.counter("engine_ticks")
    assert ticks > 0 and ctx.counter("engine_decode_ticks") <= ticks


@pytest.mark.parametrize("name", READERS)
def test_tick_reader_reads_none_without_phase_counters(window, name):
    cell, start, end = window
    bare = lambda snap: {k: v for k, v in snap.items()
                         if not (k.endswith("_ns") or k.startswith(("engine_ticks", "engine_decode")))}
    assert cell.reader(name)(_ctx(cell, bare(start), bare(end))) is None
