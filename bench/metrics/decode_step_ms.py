"""Mean device time of one decode-step program (device trace)."""

from bench.readings import programs


def read(ctx):
    progs = programs(ctx, ("decode",))
    return 1e3 * sum(s for _, s in progs) / len(progs) if progs else None
