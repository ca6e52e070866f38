"""Useful FLOPs per second over the chip's peak: real prompt tokens and
decoded lanes in the window times the configuration's matmul FLOPs per
token, over 197e12 (the whole step's share beside ``decode_roofline``)."""

from bench.readings import mfu


def read(ctx):
    return mfu(ctx)
