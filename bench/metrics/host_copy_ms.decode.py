"""Time the chip waits on a launch's host copies, per launch of the
traced window, in ms: the device-idle time under the bind's snapshot of
the launch's buffers (``hetgpu.launch.bind``), under the call that
uploads host-resident buffers (``hetgpu.segment.upload``) and under the
pull of the results back into the launch's buffers
(``hetgpu.launch.writeback``).  Only idle time counts: the write-back's
wait on kernels still running is not a copy.  The upload's transfer runs
after its call returns, so the chip's wait on it lies under the
following ``hetgpu.segment`` spans and is not in this metric."""
import program_spans as ps

COPIES = ("launch.bind", "segment.upload", "launch.writeback")


def read(ctx):
    r = ps.read(ctx)
    if r is None:
        return None
    n = len(ps.named(r, "launch.bind"))
    if not n:
        return None
    idle = r["idle_by_span"]
    return sum(idle.get(ps.PREFIX + step, 0.0) for step in COPIES) * 1e3 / n
