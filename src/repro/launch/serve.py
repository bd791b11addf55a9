"""Serving launcher: the ApproxIoT telemetry plane over an inference
fleet, in two modes.

**One-shot** (default): batched prefill + decode, then every serving
batch's per-request latency records become one tick of ingest into the
emulated edge hierarchy (edge aggregators → datacenter root) on a REAL
compiled pipeline, where the dashboard's standing queries (request
count → QPS, mean latency, p50/p99 via the quantile sketch) are a query
**tenant** answered at the root every window. One ``PipelineSpec``
declares the whole thing; one fused dispatch runs the epoch.

**Continuous** (``--serve-loop``): the same telemetry plane behind the
always-on ``repro.serve.StreamingExecutor`` — subscribed sources feed
bounded per-shard queues (``--backpressure`` policy), ingest
double-buffers against the in-flight device epoch, and every root
window publishes straggler-tolerantly: late shards yield *partial*
answers with Eq. 9-widened bounds and their data folds into the next
window. The loop registry adds the serve plane's recency queries
(sliding-window quantiles, decayed heavy hitters); ``stop()`` drains
the queues clean. ``--inject-straggler`` forces one edge shard late for
an epoch to demonstrate the partial-window path.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
        --requests 64 --decode-len 16
    PYTHONPATH=src python -m repro.launch.serve --serve-loop --duration 5 \
        --smoke --inject-straggler
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.configs import registry
from repro.data import stream as S
from repro.models import model as M
from repro.obs import telemetry as obs_telemetry
from repro.obs.metrics import metrics_text
from repro.obs.trace import get_tracer, span
from repro.optim import train_step
from repro.query.registry import QueryRegistry


NUM_CLASSES = 4          # request classes = telemetry strata
EDGE_NODES = 2           # telemetry aggregators in front of the root


def dashboard_registry() -> QueryRegistry:
    """The dashboard's standing queries, registered once."""
    return (QueryRegistry()
            .register_count("requests")
            .register_sum("latency_total_ms")
            .register_mean("latency_mean_ms")
            .register_quantile("latency_q_ms", qs=(0.5, 0.99), capacity=256))


def serve_registry(window: int = 4) -> QueryRegistry:
    """The continuous dashboard: everything the one-shot dashboard
    answers plus the serve plane's recency queries — "last ``window``
    windows" latency quantiles and exponentially decayed hot-class
    counts (a stream-so-far sketch never forgets old load)."""
    return (dashboard_registry()
            .register_windowed_quantile("latency_q_recent_ms",
                                        qs=(0.5, 0.99), capacity=128,
                                        window=window)
            .register_decayed_heavy_hitters("hot_latency_keys", k=4,
                                            width=256, decay=0.8))


def telemetry_spec(capacity: int, fraction: float, seed: int = 0,
                   telemetry: bool = False,
                   registry_fn=dashboard_registry) -> api.PipelineSpec:
    """The serving fleet's telemetry plane as one declarative spec:
    per-request records → 2 edge aggregators → 1 datacenter root, the
    dashboard (``registry_fn()``) as a query tenant on the shared tree."""
    return api.PipelineSpec(
        topology=api.TopologySpec(fanin=(EDGE_NODES, 1), capacity=capacity,
                                  num_strata=NUM_CLASSES),
        sampler=api.SamplerSpec(mode="whs", backend="topk",
                                fraction=fraction),
        tenants=(registry_fn().as_tenant("dashboard"),),
        telemetry=api.TelemetrySpec(enabled=telemetry),
        seed=seed,
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=registry.ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode-len", type=int, default=16)
    ap.add_argument("--telemetry-fraction", type=float, default=0.25)
    ap.add_argument("--hot-admit", action="store_true",
                    help="demo tenant churn on the live telemetry plane "
                         "(local path): serve half the epoch with the "
                         "dashboard tenant only, hot-admit an 'slo' "
                         "tenant mid-stream (a state edit, not a "
                         "recompile), answer its queries over the second "
                         "half, then retire + re-admit it and print the "
                         "zero-retrace evidence from the plan/program "
                         "caches")
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="run the telemetry plane on an N-device 'data' "
                         "mesh (repro.api.compile(spec, mesh=...)): each "
                         "device samples its shard of every batch's "
                         "records and the dashboard tenant answers from "
                         "merged sketch summaries — no raw records cross "
                         "devices. CPU: export XLA_FLAGS=--xla_force_"
                         "host_platform_device_count=N")
    ap.add_argument("--telemetry", action="store_true",
                    help="carry EpochTelemetry counters inside the "
                         "pipeline state (repro.obs) — sample state and "
                         "dashboard answers stay bit-identical")
    ap.add_argument("--metrics-dump", default=None, metavar="PATH",
                    help="write a Prometheus-text metrics snapshot of the "
                         "telemetry plane to PATH at exit (implies "
                         "--telemetry)")
    ap.add_argument("--metrics-every", type=int, default=None, metavar="N",
                    help="print a metrics snapshot to stdout every N "
                         "telemetry windows during the epoch (implies "
                         "--telemetry; local path only)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the host span tracer's Chrome/Perfetto "
                         "trace.json to PATH")
    ap.add_argument("--serve-loop", action="store_true",
                    help="continuous mode: run the telemetry plane behind "
                         "the always-on repro.serve.StreamingExecutor "
                         "(bounded queues, double-buffered ingest, "
                         "straggler-tolerant windows) instead of one "
                         "one-shot epoch; --requests/--batch set the "
                         "epoch length in ticks")
    ap.add_argument("--duration", type=float, default=5.0, metavar="SEC",
                    help="serve-loop: wall-clock seconds to pump before "
                         "draining")
    ap.add_argument("--tick-interval", type=float, default=0.02,
                    metavar="SEC",
                    help="serve-loop: target seconds between pumps")
    ap.add_argument("--backpressure", default="block",
                    choices=("block", "drop_oldest", "degrade"),
                    help="serve-loop: bounded-queue policy when ingest "
                         "outruns the device")
    ap.add_argument("--queue-capacity", type=int, default=4096,
                    help="serve-loop: per-shard bounded queue capacity")
    ap.add_argument("--inject-straggler", action="store_true",
                    help="serve-loop: hold one edge shard's deliveries "
                         "for a full epoch so partial windows with "
                         "widened bounds publish, then fold the late "
                         "data into the next window")
    args = ap.parse_args(argv)
    if args.metrics_dump or args.metrics_every:
        args.telemetry = True

    # Requests are served (and in loop mode, staged) in whole batches —
    # the same check guards both modes with the same actionable error.
    n_batches = args.requests // args.batch
    if n_batches == 0:
        ap.error(f"--requests {args.requests} < --batch {args.batch}: "
                 f"no serving batch would run (requests are served in "
                 f"whole batches)")

    if args.serve_loop:
        return _serve_loop(args)

    cfg = registry.get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    max_len = args.prompt_len + args.decode_len

    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    decode = jax.jit(train_step.make_decode_step(cfg), donate_argnums=(1,))

    rng = np.random.default_rng(0)
    tick_records: list[tuple[np.ndarray, np.ndarray]] = []
    t_all = time.time()
    for b in range(n_batches):
        toks = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len))
        cache = M.init_cache(cfg, args.batch, max_len)
        t0 = time.time()
        # prefill via repeated decode (teacher-forcing the prompt) — keeps
        # one compiled step; a production path would use a prefill kernel.
        tok = jnp.asarray(toks[:, :1], jnp.int32)
        for pos in range(args.prompt_len - 1):
            _, cache = decode(params, cache, jnp.asarray(toks[:, pos:pos+1], jnp.int32),
                              jnp.int32(pos))
        for pos in range(args.prompt_len - 1, max_len):
            logits, cache = decode(params, cache, tok, jnp.int32(pos))
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        jax.block_until_ready(tok)
        dt = (time.time() - t0) / args.batch
        # one tick of telemetry per serving batch: ms per request,
        # stratified by request class
        tick_records.append((
            np.full((args.batch,), dt * 1000, np.float32),
            rng.integers(0, NUM_CLASSES, args.batch).astype(np.int32)))
    wall = time.time() - t_all

    # ---- telemetry through the real pipeline -----------------------------
    # Each serving batch is one tick into the 2→1 hierarchy; the compiled
    # pipeline samples at every hop and the dashboard tenant's standing
    # queries are answered at the root each window — one fused dispatch
    # for the whole epoch. With --mesh the SAME spec lowers onto the
    # §III-E SPMD data plane instead: every device samples its shard of
    # each batch's records and the dashboard tenant answers from MERGED
    # sketch summaries — no raw record crosses a device boundary.
    capacity = max(64, args.batch)
    m = sum(len(v) for v, _ in tick_records)
    if args.mesh:
        from repro.launch.analytics import make_data_mesh

        pipe = api.compile(telemetry_spec(capacity, args.telemetry_fraction,
                                          telemetry=args.telemetry),
                           mesh=make_data_mesh(args.mesh))
        with span("ingest", ticks=len(tick_records)):
            flat = S.ticks_to_ingest(tick_records, n_nodes=1, width=capacity)
            width = -(-capacity // args.mesh) * args.mesh
            batches = S.rows_to_interval_batch(
                flat.values[:, 0], flat.strata[:, 0], flat.counts[:, 0],
                NUM_CLASSES, width=width)
        state = pipe.init()
        with span("epoch_dispatch", ticks=len(tick_records)):
            state, wa = pipe.run_epoch(state, pipe.default_key, batches)
        with span("block_until_ready"):
            jax.block_until_ready(wa)
    else:
        pipe = api.compile(telemetry_spec(capacity, args.telemetry_fraction,
                                          telemetry=args.telemetry))
        state = pipe.init()
        with span("ingest", ticks=len(tick_records)):
            batch = S.ticks_to_ingest(tick_records, n_nodes=EDGE_NODES,
                                      width=capacity)
        if args.hot_admit:
            from repro.api.pipeline import program_cache_stats

            h = max(1, len(tick_records) // 2)
            with span("epoch_dispatch", ticks=h):
                state, waA = pipe.run_epoch(state, pipe.default_key,
                                            batch.values[:h],
                                            batch.strata[:h],
                                            batch.counts[:h])
            rows_a = pipe.rows(waA)
            m0 = program_cache_stats()["misses"]
            slo = (QueryRegistry().register_count("n")
                   .register_mean("mean_ms")
                   .register_quantile("p999_ms", qs=(0.999,), capacity=128)
                   .as_tenant("slo"))
            # hot admit: slot edit on the carried state, answers resume
            # mid-stream — the dashboard tenant's sketches are untouched
            pipe2, state = pipe.admit(state, slo)
            with span("epoch_dispatch", ticks=len(batch.values) - h):
                state, waB = pipe2.run_epoch(state, pipe2.default_key,
                                             batch.values[h:],
                                             batch.strata[h:],
                                             batch.counts[h:])
            rows_b = pipe2.rows(waB)
            m1 = program_cache_stats()["misses"]
            pipe3, state = pipe2.retire(state, "slo")
            pipe4, state = pipe3.admit(state, slo)
            m2 = program_cache_stats()["misses"]
            last_b = rows_b[-1]
            slo_n = float(sum(pipe2.answer(r["answers"], "n", tenant="slo")[0]
                              for r in rows_b))
            p999 = float(pipe2.answer(last_b["answers"], "p999_ms",
                                      tenant="slo")[0])
            print(f"hot-admit 'slo' tenant after {h}/{len(tick_records)} "
                  f"ticks: {len(rows_b)} windows answered mid-stream "
                  f"({slo_n:.0f} requests seen, p99.9 ≈ {p999:.2f} ms)")
            print(f"  churn cost: admit into a new slot group traced "
                  f"{m1 - m0} program(s); retire + re-admit into the warm "
                  f"slot traced {m2 - m1} (plan cache: "
                  f"{program_cache_stats()['hits']} hits)")
            rows = rows_a + rows_b
            row_pipes = [pipe] * len(rows_a) + [pipe2] * len(rows_b)
            pipe = pipe4
        else:
            # --metrics-every N slices the epoch into N-tick chunks and
            # exposes the /metrics surface between dispatches; without it
            # the single chunk is the whole epoch (identical behaviour).
            n_ticks = len(batch.values)
            step = args.metrics_every or n_ticks
            chunk_rows = []
            for s0 in range(0, n_ticks, max(step, 1)):
                s1 = min(s0 + max(step, 1), n_ticks)
                with span("epoch_dispatch", ticks=s1 - s0):
                    state, wa = pipe.run_epoch(
                        state, pipe.default_key, batch.values[s0:s1],
                        batch.strata[s0:s1], batch.counts[s0:s1])
                with span("block_until_ready"):
                    jax.block_until_ready(wa)
                chunk_rows.extend(pipe.rows(wa))
                if args.metrics_every:
                    print(f"--- metrics after {s1}/{n_ticks} ticks ---")
                    print(metrics_text(pipeline=pipe, state=state,
                                       tracer=get_tracer()))
    if args.mesh:
        rows = pipe.rows(wa)
        row_pipes = [pipe] * len(rows)
    elif not args.hot_admit:
        rows = chunk_rows
        row_pipes = [pipe] * len(rows)
    # rows from before/after a hot admit carry different layouts — answer
    # each row through the pipeline that produced it
    pipe_of = {id(r): p for p, r in zip(row_pipes, rows)}
    a = lambda name, row: pipe_of[id(row)].answer(row["answers"], name,
                                                  tenant="dashboard")
    bnd = lambda name, row: pipe_of[id(row)].answer(row["bounds"], name,
                                                    tenant="dashboard")

    # CLT queries aggregate across windows; the quantile sketch is
    # continuous (its state spans the whole epoch), so the last window
    # answers over every request served.
    last = rows[-1]
    n_est = float(sum(a("requests", r)[0] for r in rows))
    total_est = float(sum(a("latency_total_ms", r)[0] for r in rows))
    mean_est = total_est / max(n_est, 1e-9)
    mean_bnd = float(max(bnd("latency_mean_ms", r)[0] for r in rows))
    p50, p99 = a("latency_q_ms", last)
    exact_all = np.concatenate([v for v, _ in tick_records])
    exact_mean = float(exact_all.mean())
    n_kept = int(sum(r["n_sampled"] for r in rows))
    plane = (f"{args.mesh}-device SPMD mesh (merged sketch summaries)"
             if args.mesh else f"{EDGE_NODES}→1 hierarchy")
    print(f"served {m} requests in {wall:.1f}s")
    print(f"telemetry plane: {len(rows)} windows through the "
          f"{plane}, {pipe.plan.k} standing queries, "
          f"1 fused dispatch, {n_kept}/{m} records at the root")
    print(f"  QPS              ≈ {n_est / max(wall, 1e-9):.2f}")
    print(f"  total latency-ms ≈ {total_est:.1f} "
          f"± {float(sum(bnd('latency_total_ms', r)[0] for r in rows)):.1f}"
          f" (2σ)")
    print(f"  mean latency-ms  ≈ {mean_est:.2f} ± {mean_bnd:.2f} "
          f"(exact {exact_mean:.2f})")
    print(f"  p50 / p99 ms     ≈ {float(p50):.2f} / {float(p99):.2f} "
          f"(sketch rank-ε {float(bnd('latency_q_ms', last)[0]):.3f})")
    snap = obs_telemetry.snapshot(state)
    if snap is not None:
        print(f"  telemetry        {snap['windows']} windows, realized "
              f"±2σ {snap['bound_2sigma']:.3e} "
              f"(rel {snap['rel_bound_2sigma']:.4f})"
              + (f", {snap['merge_bytes']:.0f} sketch bytes merged"
                 if args.mesh else ""))
    if args.metrics_dump:
        text = metrics_text(pipeline=pipe, state=state, tracer=get_tracer())
        with open(args.metrics_dump, "w") as f:
            f.write(text)
        print(f"  wrote {args.metrics_dump}")
    if args.trace:
        get_tracer().save(args.trace)
        print(f"  wrote {args.trace}")
    return mean_est, exact_mean


def _serve_loop(args):
    """Continuous mode: the telemetry plane behind the streaming
    executor (see module doc). Returns the executor's final stats."""
    from repro.serve import (LateShardSource, StreamingExecutor,
                             SyntheticSource)

    epoch_ticks = args.requests // args.batch
    capacity = max(64, args.batch)
    pipe = api.compile(telemetry_spec(capacity, args.telemetry_fraction,
                                      telemetry=args.telemetry,
                                      registry_fn=serve_registry))
    # Per-shard synthetic request-latency sources: NUM_CLASSES request
    # classes with distinct latency profiles (ms); class = stratum.
    per_class = max(2, args.batch // (EDGE_NODES * NUM_CLASSES))
    sources = [SyntheticSource(
        shard, specs=[S.SubstreamSpec("gaussian",
                                      (20.0 * 2 ** c, 2.0 * 2 ** c),
                                      per_class)
                      for c in range(NUM_CLASSES)], seed=shard)
        for shard in range(EDGE_NODES)]
    if args.inject_straggler:
        # Hold the last shard's deliveries for one full epoch starting
        # at the second: the affected windows publish partial (widened
        # bounds) and the backlog folds into the following window.
        sources[-1] = LateShardSource(sources[-1], epoch_ticks,
                                      2 * epoch_ticks)
    ex = StreamingExecutor(epoch_ticks=epoch_ticks, width=capacity,
                           queue_capacity=args.queue_capacity,
                           policy=args.backpressure)
    ex.start(pipe, sources)
    t0 = time.time()
    ticks = 0
    with span("serve_loop", duration=args.duration):
        while time.time() - t0 < args.duration:
            tick_t0 = time.time()
            ex.pump()
            ticks += 1
            sleep = args.tick_interval - (time.time() - tick_t0)
            if sleep > 0:
                time.sleep(sleep)
    summary = ex.stop()
    wall = time.time() - t0
    print(f"serve-loop: {ticks} ticks in {wall:.1f}s — "
          f"{summary['epochs']} epochs of {epoch_ticks} ticks, "
          f"backpressure={args.backpressure}"
          + (", straggler injected" if args.inject_straggler else ""))
    print(f"  windows published    {summary['windows_published']} "
          f"({summary['windows_partial']} partial, bounds widened 1/α)")
    print(f"  queue accounting     in {summary['queue_items_in']}, "
          f"dropped {summary['queue_items_dropped']}, deferred "
          f"{summary['queue_deferred']}, high-watermark "
          f"{summary['queue_high_watermark']}, drained to depth "
          f"{max(summary['queue_depth'], default=0)}")
    print(f"  ingest/dispatch overlap {summary['overlap_fraction']:.2f} "
          f"(measured while a device epoch was in flight)")
    print(f"  window latency       p50 {summary['latency_p50'] * 1e3:.1f} "
          f"ms / p99 {summary['latency_p99'] * 1e3:.1f} ms "
          f"(arrival → published answer)")
    if ex.published:
        last = ex.published[-1]
        p50, p99 = last.raw["answers"][
            slice(*_qslice(pipe, "latency_q_ms"))]
        r50, r99 = last.raw["answers"][
            slice(*_qslice(pipe, "latency_q_recent_ms"))]
        print(f"  latency p50/p99 ms   stream-so-far ≈ {float(p50):.1f} / "
              f"{float(p99):.1f}; recent windows ≈ {float(r50):.1f} / "
              f"{float(r99):.1f}")
    snap = obs_telemetry.snapshot(ex.state)
    if snap is not None:
        print(f"  telemetry            {snap['late_shards']} late shards, "
              f"{snap['widened_windows']} widened windows "
              f"(in-graph counters)")
    if args.metrics_dump:
        text = metrics_text(pipeline=pipe, state=ex.state,
                            tracer=get_tracer(), straggler=ex.monitor,
                            executor=ex)
        with open(args.metrics_dump, "w") as f:
            f.write(text)
        print(f"  wrote {args.metrics_dump}")
    if args.trace:
        get_tracer().save(args.trace)
        print(f"  wrote {args.trace}")
    return summary


def _qslice(pipe, name: str) -> tuple[int, int]:
    o, w, _ = pipe.query_layout()[name]
    return o, o + w


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
