"""Mean number of launches in flight in the serving front end
(``ServingFrontEnd.total_inflight``), sampled as each request falls due."""


def read(ctx):
    xs = ctx.extra["inflight"]
    return sum(xs) / len(xs) if xs else None
