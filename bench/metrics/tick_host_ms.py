"""Host time per engine tick, from the program's phase clock: the tick's
wall time less the time the host blocked on the device (the decode step's
read and admission's first-token read)."""

from bench.ticks import per_tick_ms


def read(ctx):
    return per_tick_ms(ctx, ["engine_tick_ns"], ["engine_wait_ns", "engine_admit_wait_ns"])
