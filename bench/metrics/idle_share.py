"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    return 100.0 * (1.0 - tr.busy_ns / (tr.window[1] - tr.window[0]))
