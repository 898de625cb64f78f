"""Serialization of one migration's payload: the snapshot's encoding on
the source (``hetgpu.checkpoint.encode``) plus its decoding on the
target (``hetgpu.restore.decode``), per migration of the traced window,
in ms."""
import program_spans as ps


def read(ctx):
    r = ps.read(ctx)
    if r is None:
        return None
    n = len(ps.named(r, "checkpoint"))
    if not n:
        return None
    return sum(ps.ms(ev) for step in ("checkpoint.encode", "restore.decode")
               for ev in ps.named(r, step)) / n
