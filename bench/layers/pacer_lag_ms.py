"""Load generator: how late each pump of the paced window started
against its schedule, in milliseconds, averaged over the window.
A starved generator shows here, not as a slow server."""


def read(ctx):
    lags = ctx.run.lag_s
    return 1e3 * sum(lags) / len(lags) if lags else None
