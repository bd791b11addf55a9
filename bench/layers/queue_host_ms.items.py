"""Executor host path: milliseconds per pump spent moving items through
the shard queues, the program's own ``executor.enqueue`` (sources into
``BoundedShardQueue.put``) and ``executor.dequeue`` (``get_many``)
spans, self time, averaged over the pumps of the window."""

from harness import program_spans


def read(ctx):
    spans = program_spans.spans_in(ctx.run.t0, ctx.run.t_end)
    return None if spans is None else program_spans.queue_ms_per_pump(spans)
