"""Search core: share of the lockstep traversal's expansion slots that a
live lane used, summed over the window's dispatches: SearchStats hops over
iters x live lanes x beam width (EngineStats hops / lane_slots)."""


def read(ctx):
    s = ctx.stats
    slots = getattr(s, "lane_slots", None)
    return s.hops / slots if slots else None
