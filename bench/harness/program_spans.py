"""The program's own spans (``repro.obs.trace``), as the per-layer
readers of the served cells take them.

The streaming executor opens one ``executor.*`` span per stage of a
pump on the process's default tracer. Each span opens a profiler
annotation as well, but the trace reduction (``harness.tracing.load``)
keeps only the benchmark's own host spans. So the readers take the
program's spans from the tracer's ring, in the same process and on the
benchmark's own clock (``time.perf_counter``), and move them onto the
trace's clock where they meet the device: each pump's ``executor.tick``
is paired with the benchmark's ``pump`` annotation around it.

A program that opens no such spans leaves the readers nothing to read:
they return None. So does a window with more spans than the ring holds
(4,096; a paced window fills about 2,900).

This is a stopgap: once ``tracing.HOST_SPANS`` keeps the ``executor.*``
names, the readers should take the spans from ``ctx.trace.host``, on
the trace's own clock, and ``spans_in`` and ``trace_offset`` go.
"""
from __future__ import annotations

import re
import sys

import numpy as np


def spans_in(t0: float, t1: float) -> list | None:
    """The default tracer's spans that opened and closed in ``[t0, t1]``
    (``window``), or None, with a line on stderr, where its bounded ring
    has already dropped spans that closed after ``t0``: a mean over the
    rest would cover only part of the window."""
    from repro.obs.trace import get_tracer

    events = get_tracer().events
    spans = list(events)
    if spans and len(spans) == events.maxlen and spans[0].t1 >= t0:
        print(f"program_spans: the tracer's ring ({events.maxlen} spans) "
              f"overflowed inside the window; no span metric",
              file=sys.stderr)
        return None
    return window(spans, t0, t1)


def window(spans, t0: float, t1: float) -> list:
    """The spans that opened and closed in ``[t0, t1]``, in the order
    they opened on each thread."""
    return sorted((s for s in spans if s.t0 >= t0 and s.t1 <= t1),
                  key=lambda s: (s.tid, s.t0, -s.t1))


def parents(spans) -> list:
    """Per span (in ``window``'s order), the index of the span it is
    nested directly inside on its thread, or None."""
    out: list = []
    stack: list[int] = []
    for s in spans:
        while stack and not (spans[stack[-1]].tid == s.tid
                             and s.t1 <= spans[stack[-1]].t1):
            stack.pop()
        out.append(stack[-1] if stack else None)
        stack.append(len(out) - 1)
    return out


def self_seconds(spans, up=None) -> list:
    """Per span, its seconds less those of the spans nested directly
    inside it."""
    up = parents(spans) if up is None else up
    own = [s.t1 - s.t0 for s in spans]
    for s, p in zip(spans, up):
        if p is not None:
            own[p] -= s.t1 - s.t0
    return own


def pump_ticks(spans) -> list:
    """Indices of the ``executor.tick`` spans of pumps (not of the
    drain in ``stop()``)."""
    return [i for i, s in enumerate(spans) if s.name == "executor.tick"
            and not s.meta.get("drain", False)]


def queue_ms_per_pump(spans) -> float | None:
    """Mean milliseconds per pump of ``executor.enqueue`` and
    ``executor.dequeue`` inside it, self time, from ``window``'s
    spans."""
    ticks = set(pump_ticks(spans))
    if not ticks:
        return None
    up = parents(spans)
    own = self_seconds(spans, up)
    total = sum(own[j] for j, s in enumerate(spans)
                if s.name in ("executor.enqueue", "executor.dequeue")
                and up[j] in ticks)
    return 1e3 * total / len(ticks)


def trace_offset(spans, pumps) -> float | None:
    """Seconds to add to a span's time to put it on the trace's clock:
    the median gap between each pump's ``executor.tick`` and the
    benchmark's ``pump`` annotation (``pumps``, trace events) around
    it, the last of each paired with the last of the other."""
    ticks = [spans[i].t0 for i in pump_ticks(spans)]
    starts = sorted(p.start for p in pumps)
    n = min(len(ticks), len(starts))
    if n == 0:
        return None
    return float(np.median(np.asarray(starts[-n:]) - np.asarray(ticks[-n:])))


def publish_lag_ms(spans, pumps, modules, pattern: str = r"epoch"
                   ) -> float | None:
    """Mean milliseconds, per collected epoch, from the end of its device
    execution to the start of its ``executor.collect``: the first
    execution of a program named ``pattern`` (``modules``, trace events
    of one device) that begins after the epoch's ``executor.dispatch``
    opened. A collect that opened before its epoch ended counts 0."""
    offset = trace_offset(spans, pumps)
    if offset is None:
        return None
    rx = re.compile(pattern)
    runs = sorted((m for m in modules if rx.search(m.name)),
                  key=lambda m: m.start)
    starts = [m.start for m in runs]
    dispatched = {s.meta.get("epoch"): s.t0 + offset for s in spans
                  if s.name == "executor.dispatch"}
    lags = []
    for s in spans:
        if s.name != "executor.collect":
            continue
        t = dispatched.get(s.meta.get("epoch"))
        if t is None:
            continue
        k = int(np.searchsorted(starts, t))
        if k == len(runs):
            continue
        lags.append(max(0.0, s.t0 + offset - runs[k].end))
    return 1e3 * float(np.mean(lags)) if lags else None
