"""Host time per engine tick spent launching the decode step and its
next-token ``argmax``."""

from bench.ticks import per_tick_ms


def read(ctx):
    return per_tick_ms(ctx, ["engine_dispatch_ns"])
