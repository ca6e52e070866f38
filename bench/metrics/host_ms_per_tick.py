"""Host time per engine tick: the wall time of each ``DecodeEngine.step``
span less the device-busy time inside it (device trace, host spans)."""

from bench.trace import overlap


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    steps = [(a, b) for name, a, b in tr.spans if name == "bench.step"]
    if not steps:
        return None
    host_ns = sum((b - a) - overlap(tr.merged, a, b) for a, b in steps)
    return 1e-6 * host_ns / len(steps)
