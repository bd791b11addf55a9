"""Mesh front door: mean milliseconds of the program's ``spmd.place``
span, the host work of one epoch in the mesh pipeline's ``run_epoch``
(its node rows typed and put onto the mesh, split on the node axis)."""

from harness import program_spans


def read(ctx):
    spans = program_spans.spans_in(ctx.run.t0, ctx.run.t_end)
    if spans is None:
        return None
    ms = [1e3 * (s.t1 - s.t0) for s in spans if s.name == "spmd.place"]
    return sum(ms) / len(ms) if ms else None
