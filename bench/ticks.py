"""Per-tick reductions of the engine's own phase counters, for the readers
in ``bench/metrics/``.  ``DecodeEngine.register_metrics`` publishes them
(``repro.obs.PhaseClock``: ``engine_ticks``, ``engine_<phase>_ns``,
``engine_decode_ticks``, ``engine_decode_lanes``); a program without them
reads None."""

from __future__ import annotations


def moved(ctx, names) -> list | None:
    """How far each named counter moved in the window, or None where the
    program does not publish one of them."""
    if any(n not in ctx.counters_start or n not in ctx.counters_end for n in names):
        return None
    return [ctx.counter(n) for n in names]


def per_tick_ms(ctx, add, sub=()) -> float | None:
    """(sum of ``add`` less sum of ``sub``) nanosecond counters, per engine
    tick in the window, in ms."""
    got = moved(ctx, ["engine_ticks", *add, *sub])
    if got is None or got[0] <= 0:
        return None
    ticks, plus, minus = got[0], got[1:1 + len(add)], got[1 + len(add):]
    return 1e-6 * (sum(plus) - sum(minus)) / ticks
