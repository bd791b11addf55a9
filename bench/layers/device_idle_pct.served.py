"""Device: share of the traced window in which no operation ran on the
chip, in percent."""


def read(ctx):
    return ctx.idle_pct()
