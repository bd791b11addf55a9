"""Executor host path: milliseconds from the end of an epoch's device
execution (the trace's ``epoch`` program) to the start of the
program's ``executor.collect`` span that publishes its windows,
averaged over the epochs of the window: how long finished answers wait
on the device for the host to come for them."""

from harness import program_spans


def read(ctx):
    spans = program_spans.spans_in(ctx.run.t0, ctx.run.t_end)
    if spans is None:
        return None
    pumps = [h for h in ctx.trace.host if h.name == "pump"]
    modules = ctx.trace.modules[0] if ctx.trace.modules else []
    return program_spans.publish_lag_ms(spans, pumps, modules)
