"""Recall@10 of every query sent in the window against the exact
reference (bench/correct.py computes it)."""


def read(ctx):
    return ctx.checks["recall_at_10"]
