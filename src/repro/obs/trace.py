"""Layer 2: host span tracer with Chrome/Perfetto export.

Context-manager spans record monotonic wall-time plus metadata into a
bounded ring buffer. The canonical span names the drivers emit:

* ``ingest``            — host-side epoch batch staging (CLIs)
* ``epoch_dispatch``    — the jitted epoch call (async dispatch, CLIs)
* ``block_until_ready`` — device→host sync on the epoch outputs (CLIs)
* ``admit`` / ``retire``— tenant churn state edits
* ``checkpoint``        — ``save_state`` / ``restore_state``
* ``executor.*``        — the stages of ``serve.StreamingExecutor``,
  one span per stage per pump: ``tick`` (one pump or drain tick),
  ``enqueue``, ``dequeue``, ``stage``, ``account``; at an epoch edge
  ``flush``, ``wait_device``, ``swap``, ``dispatch``, ``collect``; and
  ``drain`` around ``stop()``'s source-less ticks
* ``spmd.place``        — the mesh pipeline's host work of one epoch:
  its node rows typed and put onto the mesh, split on the node axis
  (``epoch`` ticks, ``bytes`` handed over, ``devices``)

Counters (:meth:`SpanTracer.count`) are snake_case names, exposed as
``repro_<name>_total``; the executor bumps ``executor_h2d_bytes``,
``executor_collect_waited`` and ``executor_queue_split_chunks``, the
mesh pipeline ``spmd_merge_bytes`` (per dispatched epoch, its windows
times ``summary_bytes_per_window``).

Export with :meth:`SpanTracer.chrome_trace` / :meth:`SpanTracer.save`:
the JSON loads directly in ``chrome://tracing`` and
https://ui.perfetto.dev. Each span also opens a ``jax.profiler``
``TraceAnnotation`` carrying its scalar metadata, so a profiler trace
(``jax.profiler.trace``) holds every span on the device's clock, nested
under whatever annotation encloses the call.

A module-global default tracer (:func:`get_tracer`) keeps the call
sites one-liners — ``with obs.span("epoch_dispatch"): ...`` — and a
disabled tracer's span is a no-op (one truthiness check), so
instrumented hot paths cost nothing when tracing is off. A span yields
its metadata dict: keys set inside the block (a count known only at
the end) are recorded too.
"""
from __future__ import annotations

import collections
import json
import re
import threading
import time
from contextlib import contextmanager
from typing import NamedTuple

try:  # optional: line spans up with device profiles when jax is importable
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # pragma: no cover - jax is a hard dep elsewhere
    _TraceAnnotation = None


class Span(NamedTuple):
    name: str
    t0: float          # perf_counter seconds
    t1: float
    depth: int         # nesting depth at open time (0 = top level)
    tid: int
    meta: dict
    parent: str | None = None   # enclosing span on the same thread


_COUNTER_NAME = re.compile(r"[a-z_][a-z0-9_]*\Z")


def _scalars(items) -> dict:
    """The metadata a ``TraceAnnotation`` can carry: scalar values."""
    return {k: v for k, v in items if isinstance(v, (bool, int, float, str))}


class SpanTracer:
    """Bounded ring buffer of :class:`Span` records + per-name totals.
    Nesting is tracked per thread; each span records its parent. The
    default capacity holds a minute of a served stream pumped flat out
    (about 4,000 ``executor.*`` spans when a pump takes 65 ms, as on one
    TPU v5e chip) several times over."""

    def __init__(self, capacity: int = 16384, enabled: bool = True):
        self.capacity = int(capacity)
        self.enabled = bool(enabled)
        self.events: collections.deque = collections.deque(maxlen=capacity)
        self.durations: dict[str, float] = collections.defaultdict(float)
        self.calls: collections.Counter = collections.Counter()
        self.counters: collections.Counter = collections.Counter()
        self._local = threading.local()

    def _stack(self) -> list[str]:
        """This thread's open spans, outermost first."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextmanager
    def span(self, name: str, **meta):
        """``with tracer.span("executor.stage", items=n) as meta: ...``;
        ``meta`` is the span's metadata, open to keys set in the block."""
        if not self.enabled:
            yield {}
            return
        stack = self._stack()
        parent = stack[-1] if stack else None
        n_open = len(meta)
        ann = (_TraceAnnotation(name, **_scalars(meta.items()))
               if _TraceAnnotation is not None else None)
        stack.append(name)
        if ann is not None:
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield meta
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                if len(meta) > n_open:      # keys set inside the block
                    ann.set_metadata(
                        **_scalars(list(meta.items())[n_open:]))
                ann.__exit__(None, None, None)
            stack.pop()
            self.events.append(Span(name, t0, t1, len(stack),
                                    threading.get_ident(), meta, parent))
            self.durations[name] += t1 - t0
            self.calls[name] += 1

    def count(self, name: str, n: int = 1) -> None:
        """Bump a named counter (exposed by the metrics layer as
        ``repro_<name>_total``, so ``name`` is snake_case)."""
        if self.enabled:
            if not _COUNTER_NAME.match(name):
                raise ValueError(f"counter name {name!r} is not snake_case")
            self.counters[name] += n

    def clear(self) -> None:
        self.events.clear()
        self.durations.clear()
        self.calls.clear()
        self.counters.clear()
        self._local = threading.local()

    # ------------------------------------------------------------ export --
    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON (complete 'X' events, µs timebase) —
        loads in chrome://tracing and ui.perfetto.dev unchanged."""
        events = [{
            "name": ev.name, "ph": "X", "cat": "repro",
            "ts": ev.t0 * 1e6, "dur": (ev.t1 - ev.t0) * 1e6,
            "pid": 0, "tid": ev.tid,
            "args": {**ev.meta, "depth": ev.depth, "parent": ev.parent},
        } for ev in self.events]
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def save(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)

    def well_formed(self) -> bool:
        """Spans form a proper tree per thread: every event either
        nests fully inside its enclosing (deeper events open later and
        close earlier) or is disjoint from its siblings."""
        per_tid: dict[int, list[Span]] = collections.defaultdict(list)
        for ev in sorted(self.events, key=lambda e: e.t0):
            per_tid[ev.tid].append(ev)
        for evs in per_tid.values():
            stack: list[Span] = []
            # events are recorded at CLOSE time; replay by open time and
            # check interval containment against the enclosing span
            for ev in evs:
                while stack and stack[-1].t1 <= ev.t0:
                    stack.pop()
                if stack and not (stack[-1].t0 <= ev.t0
                                  and ev.t1 <= stack[-1].t1 + 1e-9):
                    return False
                stack.append(ev)
        return True


_GLOBAL: SpanTracer | None = None
_LOCK = threading.Lock()


def get_tracer() -> SpanTracer:
    """The process-wide default tracer (created on first use)."""
    global _GLOBAL
    if _GLOBAL is None:
        with _LOCK:
            if _GLOBAL is None:
                _GLOBAL = SpanTracer()
    return _GLOBAL


def span(name: str, **meta):
    """``with obs.span("epoch_dispatch"): ...`` on the default tracer."""
    return get_tracer().span(name, **meta)
