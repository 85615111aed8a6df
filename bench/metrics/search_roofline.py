"""Kernels: percent of the memory roofline reached by the search. The
least time is every distance counted in the traced part of the window
(SearchStats.n_dist, as the engine's stats stood at its end) times the
bytes of the narrowest row the index stores, at the chip's peak HBM
bandwidth; the time taken is the device's busy time in the same part."""
from bench import peaks

# bytes per stored row, by quantizer kind, for a vector of `dim` floats;
# a configuration with another kind adds its entry here
ROW_BYTES = {
    "none": lambda q, dim: 4 * dim,
    "pq": lambda q, dim: q["pq_m"],
}


def read(ctx):
    t, st = ctx.trace, ctx.window.traced_stats
    if t is None or t.busy_s <= 0 or st.n_queries == 0:
        return None
    index = ctx.cell.config["index"]
    q = index.get("quant", {})
    row = ROW_BYTES[q.get("kind", "none")](q, index["dim"])
    n_dist = st.dists_per_query * st.n_queries
    return peaks.roofline_share(ctx.device["kind"], 0.0, n_dist * row,
                                t.busy_s)
