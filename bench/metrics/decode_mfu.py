"""The whole launch's share of the chip's peak: the least time the chip
could take for one launch (``work.attn_decode`` at the peaks) over
``decode_ms``, in %.  It bounds a gain from any change to the launch,
including one that removes or fuses its kernels."""


def read(ctx):
    ms = ctx.e2e.get("decode_ms")
    return 100.0 * ctx.bound_s / (ms / 1e3) if ms else None
