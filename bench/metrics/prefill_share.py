"""Share of device-busy time spent in prefill programs (packed and
continuation), from the device trace."""

from bench.readings import programs


def read(ctx):
    progs = programs(ctx, ("prefill", "cont"))
    if ctx.trace is None or not progs or ctx.trace.busy_ns <= 0:
        return None
    return 100.0 * sum(s for _, s in progs) / (ctx.trace.busy_ns * 1e-9)
