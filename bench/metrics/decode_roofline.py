"""Least time for what each decode step needs (one weight read, the live
lanes' KV and FLOPs), over its measured device time."""

from bench.readings import programs
from bench.work import decode_work, least_seconds


def read(ctx):
    progs = programs(ctx, ("decode",))
    if ctx.peak is None or not progs:
        return None
    least = sum(least_seconds(*decode_work(ctx.dims, c.work), ctx.peak) for c, _ in progs)
    return 100.0 * least / sum(s for _, s in progs)
