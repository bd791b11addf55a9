"""Executor host path: milliseconds of host time per ``pump()`` (one
tick of ingest: sources, queues, staging, and at epoch edges the
dispatch and the collection), by the benchmark clock around each call,
averaged over the pumps of the window."""


def read(ctx):
    pumps = ctx.run.pump_s
    return 1e3 * sum(pumps) / len(pumps) if pumps else None
