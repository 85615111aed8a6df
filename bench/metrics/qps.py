"""Queries per second: queries of the requests completed in the window
over the time from the window's start to the last completion."""


def read(ctx):
    w = ctx.window
    done = [s for s in w.served if s.status == "ok"]
    if not done:
        return None
    return sum(s.queries.size for s in done) / max(s.done_s for s in done)
