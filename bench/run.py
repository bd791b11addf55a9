#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs``) and a traffic mix (``bench/traffic``). The run makes
the cell's items from the seed, warms every shape the cell uses, drives
the system for ``--seconds`` by the traffic's driver, then checks every
published window against the plain reference. The last line of standard
output is one JSON object: ``correct``, ``attempted`` and ``failed``
(in windows), ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device``, and ``checks``, each number
compared beside its limit. The same numbers end standard error.

A configuration states the chips its deployment spans under ``mesh``
(``{"devices": N, "axis": "data"}``); without it the deployment is on
one chip. The pipeline is ``api.compile(spec, mesh=cells.mesh(config))``
and the drivers call it as they call a one-chip pipeline: that front
door (``init``, ``default_key``, ``query_layout``, ``run_epoch`` on
``values[T, n0, width]``, ``strata`` and ``counts``, and ``fanin`` for
the streaming executor) is the harness's contract for N chips. On N
chips ``memory_peak_bytes`` is the fullest chip's, beside
``memory_peak_bytes_per_chip``, and a traced run's ``busy_s`` is the
mean over the chips, beside ``busy_s_per_chip``.

Without a TPU, or with fewer chips than the cell or its configuration
asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# The persistent compilation cache lives at a fixed path inside the
# checkout, so that only a cell's first run there compiles.
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def log(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class CompileClock:
    """Seconds JAX spent in backend compiles (or loading them from the
    persistent cache), and how many ran."""

    def __init__(self):
        import jax

        self.seconds, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1


def main(argv=None, *, require_tpu: bool = True, config_override=None,
         traffic_override=None, wrap_pipeline=None, hook=None) -> int:
    """``require_tpu``, the overrides and ``wrap_pipeline`` are for the
    CPU tests of the harness: a small configuration, and a pipeline with
    a planted fault. ``hook(run, truth, layout, config, checks)`` is
    called once the checks are made: ``bench/readings.py`` reads the
    control there."""
    args = parse(argv)
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # The TPU runtime's own logs stay in the checkout too.
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".tpu_logs"))
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # The checkout's cache holds this benchmark's programs alone; no
    # eviction, so no entry is ever dropped or scanned for its age.
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    from harness import cells

    entry, config, traffic = cells.cell(args.workload)
    config = config_override or config
    traffic = traffic_override or traffic
    chips = max(entry["chips"], cells.chips(config))
    if (require_tpu and devices[0].platform != "tpu") or len(devices) < chips:
        print(f"bench: cell {args.workload} needs {chips} TPU "
              f"chip(s); JAX sees {len(devices)} {devices[0].platform} "
              f"device(s). Nothing was run.", file=sys.stderr)
        return 2
    from repro import api
    from repro.launch.compile_cache import enable_compile_cache

    from harness import drivers, reference, stream, tracing

    enable_compile_cache()
    cc = CompileClock()
    t_init = time.perf_counter()

    ring = stream.make_ring(config, traffic, args.seed,
                            int(traffic["ring_ticks"]))
    cap = cells.capacity(config, stream.tick_rates(config, traffic))
    pipe = api.compile(cells.pipeline_spec(config, cap, args.seed),
                       mesh=cells.mesh(config))
    if wrap_pipeline is not None:
        pipe = wrap_pipeline(pipe)
    layout = pipe.query_layout()
    mode = traffic["driver"]
    if mode == "bulk":
        driver = drivers.Bulk(pipe, traffic, ring, cap)
    else:
        driver = drivers.Served(pipe, config, traffic, ring, cap, args.seed)
    t_gen = time.perf_counter()
    c0 = cc.seconds
    driver.warm()
    t_warm = time.perf_counter()
    compile_s = cc.seconds - c0

    ann = drivers.Annotations(bool(args.trace))
    if args.trace:
        # The whole window is traced: device operations and the host's
        # annotations, without the Python function tracer, which would
        # slow the executor's per-item loops several times over.
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        t_trace = time.perf_counter()
    setup_s = time.perf_counter() - T_START
    log(setup_s=setup_s, init_s=t_init - T_START,
        generate_s=t_gen - t_init, compile_or_load_s=compile_s,
        warm_up_s=t_warm - t_gen - compile_s, capacity=cap,
        ring_ticks=len(ring))
    compiles0 = cc.compiles
    if mode == "bulk":
        run = driver.run(args.seconds, ann)
    else:
        run = driver.run(args.seconds, ann, paced=(mode == "paced"))
    compiles_in_window = cc.compiles - compiles0
    if args.trace:
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
    peak_mem = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in devices[:chips]]
    window_s = run.t_end - run.t0
    log(window_s=window_s, ticks=run.due, epochs=run.epochs,
        items=run.items, windows=int(run.windows.tick.size),
        compiles_in_window=compiles_in_window)
    if run.pumps:
        log(**drivers.pump_summary(run.pumps))

    # The reference, once the window has closed and the state is freed.
    del driver, pipe
    truth = reference.ring_truth(ring, len(config["substreams"]))
    checks = reference.check(layout, config["queries"], run.windows, truth,
                             run.due)
    correct = reference.verdict(checks)
    if hook is not None:
        hook(run=run, truth=truth, layout=layout, config=config,
             checks=checks)
    published = set(run.windows.tick.tolist())
    failed = len(set(range(run.due)) - published) + len(
        run.failed_ticks & published)

    if args.trace:
        try:
            trace = tracing.load(tracing.find_xplane(str(TRACE_DIR)))
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        traced_s = t_stop - t_trace
        metrics, breakdown, busy_s = _per_layer(
            args.workload, config, run, trace, traced_s, devices[0])
        device_extra = {"busy_s": busy_s,
                        "busy_s_per_chip": tracing.busy_per_chip(trace),
                        "window_s": traced_s}
    else:
        metrics = _end_to_end(args.workload, run, setup_s)
        breakdown, device_extra = None, {}
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result = {"correct": bool(correct), "attempted": int(run.due),
              "failed": int(failed), "metrics": metrics,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": max(peak_mem),
                         "memory_peak_bytes_per_chip": peak_mem,
                         **device_extra}}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


def _end_to_end(cell: str, run, setup_s: float) -> dict:
    import numpy as np

    from harness import cells

    values = {"setup_s": setup_s}
    if run.items:
        values["items_per_s"] = run.items / (run.t_end - run.t0)
    if run.latency_s is not None and run.latency_s.size:
        lat_ms = 1e3 * run.latency_s
        values["window_latency_p50_ms"] = float(np.percentile(lat_ms, 50))
        values["window_latency_p95_ms"] = float(np.percentile(lat_ms, 95))
    # A metric named ``<quantity>.<suffix>`` is that quantity, under a
    # bound of its own for the cells it lists.
    out = {}
    for m in cells.metrics_of(cell, "end_to_end"):
        quantity = m["name"].split(".")[0]
        if quantity in values:
            out[m["name"]] = {"value": values[quantity], "unit": m["unit"]}
    return out


def _per_layer(cell: str, config: dict, run, trace, traced_s, device):
    from harness import cells, tracing

    busy_s = tracing.busy_seconds(trace)
    peaks = cells.load_json(cells.BENCH / "peaks.json")
    if device.device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device.device_kind!r} "
                       f"in bench/peaks.json")
    peak = peaks[device.device_kind]

    def roofline(kernel, cost_name):
        cost = cells.load_module("costs", cost_name).cost
        return tracing.roofline_pct(trace, kernel, cost, peak)

    ctx = types.SimpleNamespace(
        run=run, trace=trace, config=config,
        roofline=roofline,
        module_ms=lambda pattern: tracing.module_ms(trace, pattern),
        collective_ms=lambda: tracing.collective_ms(trace),
        idle_pct=lambda: 100.0 * (1.0 - busy_s / traced_s))
    metrics = {}
    for m in cells.metrics_of(cell, "per_layer"):
        value = cells.load_module("layers", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    host = [h for h in trace.host]
    lo = min((h.start for h in host), default=0.0)
    hi = max((h.end for h in host), default=0.0)
    breakdown = {"device_ops": tracing.top_ops(trace),
                 "idle_gaps": tracing.idle_gaps(trace, lo, hi)}
    return metrics, breakdown, busy_s


if __name__ == "__main__":
    sys.exit(main())
