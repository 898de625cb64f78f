"""The front end's own time per pump: the median over the traced
window's ``hetgpu.serve.pump`` spans of the pump's duration less the
``hetgpu.segment`` time under it (scheduling decisions, binds,
write-backs and reaping), in ms."""
import program_spans as ps


def read(ctx):
    r = ps.read(ctx)
    if r is None:
        return None
    pumps = ps.named(r, "serve.pump")
    segs = ps.inside(pumps, ps.named(r, "segment"))
    return ps.median([ps.ms(ev) - s for ev, s in zip(pumps, segs)])
