"""Least time for the real prompt tokens' work of each packed prefill (no
pad rows, no bucket padding), over its measured device time."""

from bench.readings import programs
from bench.work import least_seconds, prefill_work


def read(ctx):
    progs = programs(ctx, ("prefill",))
    if ctx.peak is None or not progs:
        return None
    least = sum(least_seconds(*prefill_work(ctx.dims, c.work), ctx.peak) for c, _ in progs)
    return 100.0 * least / sum(s for _, s in progs)
