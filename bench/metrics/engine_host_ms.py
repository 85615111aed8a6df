"""Engine: median host time of one bucket dispatch in the window, from the
start of its `kbest.pad` span to the end of its `kbest.fetch` span, less
its `kbest.device_wait` span: padding, the jit call, the fetch of results
and counters (EngineStats.host_p50_ms)."""


def read(ctx):
    s = ctx.stats
    v = getattr(s, "host_p50_ms", None)
    return v if v is not None and s.n_requests else None
