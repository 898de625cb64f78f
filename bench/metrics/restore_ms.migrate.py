"""Mean time to re-instantiate a checkpoint on the target
(``last_migration['restore_ms']``: deserialization, engine resume and
buffer re-binding)."""


def read(ctx):
    xs = [r["restore_ms"] for r in ctx.records if r["kind"] == "migration"]
    return sum(xs) / len(xs) if xs else None
