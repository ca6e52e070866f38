"""Host time per engine tick in the per-slot loop after the decode step's
read: token appends, EOS and length checks, retirement."""

from bench.ticks import per_tick_ms


def read(ctx):
    return per_tick_ms(ctx, ["engine_retire_ns"])
