"""Queue wait of a launch: p90 (nearest rank) over the traced window's
launches of the ``queued_ms`` counter of ``hetgpu.launch.bind``, the
time from ``launch_async`` to the launch's bind at its stream's front,
in ms."""
import loadgen
import program_spans as ps


def read(ctx):
    r = ps.read(ctx)
    if r is None:
        return None
    return loadgen.percentile([float(ev[3]["queued_ms"])
                               for ev in ps.named(r, "launch.bind")], 90)
