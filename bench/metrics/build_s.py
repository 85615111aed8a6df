"""Build: wall seconds of the index build, summed over KBest.build_s
stages."""


def read(ctx):
    return sum(ctx.build_s.values())
