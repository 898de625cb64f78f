"""Mean time to capture a paused launch's state on the source
(``last_migration['checkpoint_ms']``: snapshot and serialization)."""


def read(ctx):
    xs = [r["checkpoint_ms"] for r in ctx.records if r["kind"] == "migration"]
    return sum(xs) / len(xs) if xs else None
