"""Search core: distances computed per true query in the window
(EngineStats.dists_per_query, from SearchStats.n_dist)."""


def read(ctx):
    return ctx.stats.dists_per_query if ctx.stats.n_queries else None
