"""Seconds the translation cache spent over set-up: translating segments
(Python trace, export, XLA/Mosaic compile) plus reviving them from the
persistent store (``HetSession.cache_stats()``: ``translate_ms`` +
``restore_ms``)."""


def read(ctx):
    c = ctx.setup_counters
    return (float(c["translate_ms"]) + float(c["restore_ms"])) / 1e3
