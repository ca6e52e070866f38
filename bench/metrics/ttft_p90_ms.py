"""90th percentile time to first token of the requests due in the window,
from their due time (host clock)."""

from bench.readings import p90, ttft_s


def read(ctx):
    v = p90(ttft_s(ctx))
    return None if v is None else 1e3 * v
