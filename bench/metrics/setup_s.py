"""Process start to window start: imports, params, compiles or cache reads,
warm-up (host clock)."""


def read(ctx):
    return ctx.setup_s
