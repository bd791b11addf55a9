"""Epoch program: device milliseconds of one execution of the jitted
epoch (the scan over the tree, the samplers and the query plane),
averaged over the executions in the traced window."""


def read(ctx):
    return ctx.module_ms(r"epoch")
