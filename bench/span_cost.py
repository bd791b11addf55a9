#!/usr/bin/env python3
"""The cost of one program span on this host.

    python3 bench/span_cost.py [--spans 200000]

Times ``SpanTracer.span`` around an empty block, as the streaming
executor opens it (scalar metadata, one key set inside the block): with
the tracer enabled and the profiler off (how the benchmark's untraced
runs meet it), enabled under a running ``jax.profiler`` trace (the
traced runs: host tracer on, Python tracer off, as ``bench/run.py``
starts it), and disabled. Prints one JSON line of microseconds per span.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"


def per_span_us(tracer, n: int) -> float:
    span = tracer.span
    t0 = time.perf_counter()
    for i in range(n):
        with span("executor.stage", items=i) as meta:
            meta["windows"] = 1
    return 1e6 * (time.perf_counter() - t0) / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--spans", type=int, default=200_000)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.obs.trace import SpanTracer

    n = args.spans
    per_span_us(SpanTracer(), 1000)                  # warm the code path
    out = {"spans": n, "device": jax.devices()[0].device_kind,
           "enabled_profiler_off_us": per_span_us(SpanTracer(), n),
           "disabled_us": per_span_us(SpanTracer(enabled=False), n)}
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        out["enabled_profiler_on_us"] = per_span_us(SpanTracer(), n)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
