"""Host time per engine tick in the scheduler's tick and admission (grants,
slot claims, prefill dispatch, row inserts), less admission's wait for the
first tokens."""

from bench.ticks import per_tick_ms


def read(ctx):
    return per_tick_ms(ctx, ["engine_admit_ns"], ["engine_admit_wait_ns"])
