"""``mfu`` in the cells judged on time to first token: the whole step's
share of the chip's peak beside ``prefill_roofline``."""

from bench.readings import mfu


def read(ctx):
    return mfu(ctx)
