"""Share of the roofline reached by the attention decode's segment
kernels: the least time the chip could take for the launches of the
window (``work.attn_decode`` at the peaks) over the summed device time of
the Mosaic kernels (``tpu_custom_call``) in the traced window, in %."""


def read(ctx):
    if ctx.trace is None or ctx.trace["kernel_s"] <= 0:
        return None
    n = sum(1 for r in ctx.records if r["kind"] == "launch")
    if not n:
        return None
    return 100.0 * ctx.bound_s * n / ctx.trace["kernel_s"]
