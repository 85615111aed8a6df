"""Set-up seconds: process start to the window's start (data, index
build, warm-up, and in a checkout's first run compilation)."""


def read(ctx):
    return ctx.setup_s
