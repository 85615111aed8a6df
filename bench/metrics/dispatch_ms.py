"""Engine: median wall time of one dispatch in the window, as the engine
times it around block_until_ready (EngineStats.lat_p50_ms)."""


def read(ctx):
    return ctx.stats.lat_p50_ms if ctx.stats.n_requests else None
