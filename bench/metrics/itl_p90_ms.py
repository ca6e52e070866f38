"""90th percentile of the gaps between consecutive output tokens (host clock)."""

from bench.readings import itl_s, p90


def read(ctx):
    v = p90(itl_s(ctx))
    return None if v is None else 1e3 * v
