"""The ``ttft_p90_ms`` reduction in a closed-loop cell, where a request is
due when its caller sends it: the wait for a slot behind the other callers."""

from bench.readings import p90, ttft_s


def read(ctx):
    if ctx.schedule.loop != "closed":
        return None
    v = p90(ttft_s(ctx))
    return None if v is None else 1e3 * v
