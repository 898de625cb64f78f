"""Share of the traced window in which no operation ran on the device:
1 - busy / window, busy being the union of the device's op intervals,
in %.  The one reader of every ``idle_share.<cell>`` metric."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
