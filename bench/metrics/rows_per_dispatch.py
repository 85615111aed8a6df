"""Scheduler: true queries per engine dispatch, summed over the window's
serve_loop reports (ServeReport.n_served / n_dispatches)."""


def read(ctx):
    w = ctx.window
    return w.n_rows / w.n_dispatches if w.n_dispatches else None
