"""Mean time the target spent warming its translation cache from the
store before the restore (``last_migration['warmup_ms']``)."""


def read(ctx):
    xs = [r["warmup_ms"] for r in ctx.records if r["kind"] == "migration"]
    return sum(xs) / len(xs) if xs else None
