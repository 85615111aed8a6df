"""Engine: share of the dispatched lanes that were padding, summed over
the window's dispatches (EngineStats rows_padded / (n_queries +
rows_padded); n_queries counts the live lanes)."""


def read(ctx):
    s = ctx.stats
    padded = getattr(s, "rows_padded", None)
    if padded is None or s.n_queries + padded == 0:
        return None
    return padded / (s.n_queries + padded)
