"""Share of the traced window in which the device ran nothing while a
``hetgpu.*`` span of the program was the innermost span on the host,
in %.  The one reader of every ``program_idle_share.<cell>`` metric."""
import program_spans as ps


def read(ctx):
    r = ps.read(ctx)
    if r is None:
        return None
    idle = sum(s for name, s in r["idle_by_span"].items()
               if name.startswith(ps.PREFIX))
    return 100.0 * idle / r["window_s"]
