"""Segment kernels executed per launch over the window: the pallas
backend's tiled plus scalar segment executions (``block_stats()``) over
the launches completed."""


def read(ctx):
    n = sum(1 for r in ctx.records if r["kind"] == "launch")
    return ctx.extra["segments"] / n if n else None
