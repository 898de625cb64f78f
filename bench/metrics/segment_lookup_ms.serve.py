"""Translation lookup of one segment: the median over the traced
window's ``hetgpu.segment.lookup`` spans (the state's shape signature,
the cache key and the translation cache's lookup), in ms."""
import program_spans as ps


def read(ctx):
    r = ps.read(ctx)
    if r is None:
        return None
    return ps.median([ps.ms(ev) for ev in ps.named(r, "segment.lookup")])
