"""Sketch merge: milliseconds per epoch in which the chips ran
collective operations (the ``psum``-ed moments, the all-gathered
quantile buffers and count-min tables), per chip, averaged over the
chips and over the epochs of the traced window."""


def read(ctx):
    ms = ctx.collective_ms()
    if ms is None or not ctx.run.epochs:
        return None
    return ms / ctx.run.epochs
