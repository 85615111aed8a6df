"""95th percentile latency in ms over every request due in the window,
each timed from its due time to the return of the serve_loop call that
answered it."""
import numpy as np


def read(ctx):
    lat = [s.done_s - s.due_s for s in ctx.window.served]
    return float(np.percentile(lat, 95) * 1e3) if lat else None
