"""Share of the lanes the decode step computed that held a live request:
live lanes summed over decode calls, over decode calls times slots."""

from bench.ticks import moved


def read(ctx):
    got = moved(ctx, ["engine_decode_lanes", "engine_decode_ticks"])
    if got is None or got[1] <= 0:
        return None
    lanes, calls = got
    return 100.0 * lanes / (calls * ctx.cell.config["serving"]["n_slots"])
