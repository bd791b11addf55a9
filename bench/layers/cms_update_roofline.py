"""Kernels: the count-min update kernel's share of its roofline
(``bench/costs/cms_update.py`` over the kernel's trace time)."""


def read(ctx):
    return ctx.roofline("cms_update", "cms_update")
