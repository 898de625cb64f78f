"""Host time of one segment: the median over the traced window's
``hetgpu.segment`` spans (``Engine.run`` around the backend's
``run_segment``: signature and translation lookup, argument assembly,
dispatch) less the upload of host-resident buffers under it, in ms.
The one reader of every ``segment_host_ms.<cell>`` metric."""
import program_spans as ps


def read(ctx):
    r = ps.read(ctx)
    if r is None:
        return None
    segs = ps.named(r, "segment")
    up = ps.inside(segs, ps.named(r, "segment.upload"))
    return ps.median([ps.ms(ev) - u for ev, u in zip(segs, up)])
