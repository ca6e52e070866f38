"""90th percentile wait from due time to the admission that gave the
request a slot: queueing in ``CNAScheduler`` (host clock)."""

from bench.readings import p90, sched_wait_s


def read(ctx):
    v = p90(sched_wait_s(ctx))
    return None if v is None else 1e3 * v
