"""Kernels: the quantile compaction kernel's share of its roofline
(``bench/costs/quantile_compact.py`` over the kernel's trace time)."""


def read(ctx):
    return ctx.roofline("quantile_compact", "quantile_compact")
