"""Output tokens emitted in the window over the window's seconds (host clock)."""

from bench.readings import output_tokens


def read(ctx):
    return output_tokens(ctx) / ctx.seconds
