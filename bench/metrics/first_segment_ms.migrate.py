"""Mean time from the restored launch's start to its first segment done
on the target: the downtime left after checkpoint, warm-up and restore."""


def read(ctx):
    xs = [r["downtime_ms"] - r["checkpoint_ms"] - r["warmup_ms"]
          - r["restore_ms"] for r in ctx.records if r["kind"] == "migration"]
    return sum(xs) / len(xs) if xs else None
