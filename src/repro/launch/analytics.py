"""The paper's own workload: hierarchical stream analytics driver.

Builds the §V testbed topology (8 sources → 4 → 2 → 1 root) as a
``HostTree``, streams synthetic sub-streams through it, and reports
windowed SUM/MEAN with ±kσ error bounds, accuracy-vs-exact, throughput,
per-hop bandwidth, and a modeled end-to-end latency. This is what
benchmarks/fig*.py drive.

Latency model (Fig. 9/10): the testbed's WAN is emulated following §V-A —
RTTs of 20/40/80 ms between layers, 1 Gbps links, 16 B/item. End-to-end
latency of an item =

    window_wait (interval/2 on average, per level)
  + measured per-node processing time per interval
  + Σ_hops (RTT_h/2 + forwarded_bytes_h / link_bw)

Sampling cuts both the upper-level processing (smaller buffers) and the
transfer terms — the same mechanism as the paper's speedup.

    PYTHONPATH=src python -m repro.launch.analytics --dist gaussian \
        --fraction 0.1 --ticks 20
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro import api
from repro.api.spec import (BudgetSpec, PipelineSpec, SamplerSpec,
                            TenantSpec, TopologySpec)
from repro.core.tree import HostTree
from repro.data import stream as S

# §V-A WAN emulation constants.
HOP_RTT_S = (0.020, 0.040, 0.080)   # source→L0, L0→L1, L1→root
LINK_BW = 1e9 / 8                   # 1 Gbps in bytes/s
ITEM_BYTES = 16                     # value + stratum tag + framing


def default_capacity(specs, num_sources: int = 8, fanin=(4, 2, 1),
                     interval_ticks=None) -> int:
    """Level-0 buffer provisioning for the offered load (Σ rates ×
    sources per node × interval, 35% Poisson slack) — level-0 drops
    carry no metadata, so an under-provisioned ingest buffer silently
    biases the estimate downward."""
    per_node_rate = sum(s.rate for s in specs) * num_sources / fanin[0]
    iv0 = (interval_ticks or [1])[0]
    return max(int(1.35 * per_node_rate * iv0) + 256 & ~255, 1024)


def build_spec(specs=None, *, fraction: float, capacity: int | None = None,
               num_strata: int | None = None,
               num_sources: int = 8, fanin=(4, 2, 1), interval_ticks=None,
               allocation: str = "fair", seed: int = 0, mode: str = "whs",
               sampler_backend: str = "topk", queries=None,
               target_rel_error: float | None = None,
               max_fraction: float | None = None,
               telemetry: bool = False,
               strata=None) -> PipelineSpec:
    """The §V testbed job as ONE declarative ``PipelineSpec`` — what
    every driver (this CLI, benchmarks, examples) constructs and hands
    to ``repro.api.compile`` / ``HostTree.from_spec``.

    ``specs`` (the sub-stream mix) sizes the level-0 buffers for the
    offered load and sets ``num_strata``; pass explicit ``capacity``/
    ``num_strata`` to build a spec without a stream description.

    ``queries`` registers the standing-query plane: a ``QueryRegistry``
    becomes the single ``"default"`` tenant; a sequence of
    ``TenantSpec``s compiles N tenants into one shared batched root
    evaluation with per-tenant answer routing."""
    if capacity is None:
        capacity = default_capacity(specs, num_sources, fanin,
                                    interval_ticks)
    if num_strata is None:
        num_strata = len(specs)
    if queries is None:
        tenants = ()
    elif isinstance(queries, (list, tuple)):
        tenants = tuple(queries)
    else:
        tenants = (TenantSpec.from_registry("default", queries),)
    from repro.api.spec import StrataSpec, TelemetrySpec

    if strata is None:
        strata = StrataSpec()
    return PipelineSpec(
        topology=TopologySpec(fanin=tuple(fanin), capacity=capacity,
                              interval_ticks=(tuple(interval_ticks)
                                              if interval_ticks else None),
                              num_strata=num_strata),
        sampler=SamplerSpec(mode=mode, backend=sampler_backend,
                            allocation=allocation, fraction=fraction),
        tenants=tenants,
        budget=BudgetSpec(max_fraction=max_fraction,
                          target_rel_error=target_rel_error),
        seed=seed,
        telemetry=TelemetrySpec(enabled=telemetry),
        strata=strata,
    )


def _window_rel_error(w: dict, plan=None) -> float:
    """Measured relative ±2σ error of one root window — the signal the
    error-budget controller consumes (no ground truth needed online).

    With a registered query plan this is the WORST per-query relative
    bound across the CLT queries (sum/mean) in the window's answer
    vector; otherwise the built-in windowed SUM's. Sketch queries carry
    deterministic structural bounds, so they don't vote."""
    rels = []
    if plan is not None and "answers" in w:
        for _, (off, _, kind) in plan.layout().items():
            if kind in ("sum", "mean"):
                est = abs(float(w["answers"][off]))
                rels.append(float(w["bounds"][off]) / max(est, 1e-9))
    if not rels:
        est = abs(w["sum"])
        rels = [2.0 * float(np.sqrt(max(w["sum_var"], 0.0)))
                / max(est, 1e-9)]
    return max(rels)


def build_tree(num_strata: int, capacity: int, fraction: float,
               fanin=(4, 2, 1), interval_ticks=None, allocation="fair",
               seed: int = 0, mode: str = "whs", engine: str = "level",
               sampler_backend: str = "topk", queries=None,
               max_fraction: float | None = None) -> HostTree:
    """Back-compat wrapper: the keyword soup becomes one declarative
    ``PipelineSpec`` (see ``build_spec``) consumed through the
    ``HostTree.from_spec`` shim. Budget sizing (WHS fraction×capacity,
    the SRS HT-safe provisioning, controller ceilings) now lives in
    ``repro.api.spec.derive_sample_sizes`` — one source of truth."""
    spec = build_spec(fraction=fraction, capacity=capacity,
                      num_strata=num_strata, fanin=fanin,
                      interval_ticks=interval_ticks, allocation=allocation,
                      seed=seed, mode=mode, sampler_backend=sampler_backend,
                      queries=queries, max_fraction=max_fraction)
    return HostTree.from_spec(spec, engine=engine)


class _CompiledDriver:
    """``run_pipeline``'s scan-engine executor: drives a pure
    ``repro.api.CompiledPipeline`` (explicit donated state, budgets as
    traced inputs) while keeping ``HostTree``'s accounting surface
    (``results``/``items_*``/``level_time_s``/``dispatch_count``), so
    one driver body serves the per-tick shim engines and the compiled
    runtime alike. The scan engine cannot observe per-level time inside
    its fused dispatch, so epoch wall-time is attributed to levels
    proportionally to their buffer slots — same model as the old
    ``HostTree.run_epoch``."""

    def __init__(self, pipe: "api.CompiledPipeline"):
        self.pipe = pipe
        self.state = pipe.init()
        self.plan = pipe.plan
        self.fanin = list(pipe.fanin)
        self.capacities = list(pipe.capacities)
        self.sample_sizes = list(pipe.sample_sizes)
        self.max_sample_sizes = list(pipe.max_sample_sizes)
        self._key = pipe.default_key
        self.results: list[dict] = []
        self.items_ingested = 0
        self.items_forwarded = [0] * len(self.fanin)
        self.level_time_s = [0.0] * len(self.fanin)
        self.dispatch_count = 0

    def run_epoch(self, t0: int, values, strata, counts, offered=None):
        import time as _time

        from repro.core.tree import accumulate_epoch_accounting
        from repro.obs.trace import span

        t_start = _time.perf_counter()
        with span("epoch_dispatch", t0=t0, ticks=int(np.shape(counts)[0])):
            self.state, wa = self.pipe.run_epoch(
                self.state, self._key, values, strata, counts,
                budgets=self.sample_sizes)
        with span("block_until_ready"):
            rows = self.pipe.rows(wa)             # device→host sync
            n_fwd = np.asarray(wa.n_forwarded)
        wall = _time.perf_counter() - t_start
        accumulate_epoch_accounting(self, wall, counts, offered, n_fwd)
        self.results.extend(rows)

    def reset_query_state(self) -> None:
        self.state = self.pipe.reset_queries(self.state)

    def set_sample_sizes(self, sizes) -> None:
        self.sample_sizes = self.pipe.clamp_budgets(sizes)


def run_pipeline(specs, *, fraction: float = 0.1, ticks: int,
                 capacity: int | None = None,
                 num_sources: int = 8, fanin=(4, 2, 1), interval_ticks=None,
                 allocation: str = "fair", seed: int = 0, mode: str = "whs",
                 engine: str = "level", sampler_backend: str = "topk",
                 warmup_ticks: int = 0, epoch_ticks: int | None = None,
                 queries=None, target_rel_error: float | None = None,
                 max_fraction: float | None = None,
                 pipeline_spec: PipelineSpec | None = None,
                 return_stream: bool = False,
                 telemetry: bool = False,
                 strata=None):
    """Stream → tree → per-window results + ground truth. Returns a dict.

    ``capacity=None`` provisions level-0 buffers for the offered load
    (Σ rates × sources per node × interval, with 35% Poisson slack) —
    level-0 drops carry no metadata, so an under-provisioned ingest
    buffer silently biases the estimate downward.

    ``warmup_ticks`` extra ticks are run first (jit compilation, caches)
    and excluded from the throughput/latency wall-clock measurement —
    accuracy accounting starts after warmup too, so estimates match.

    ``engine="scan"`` batches ``epoch_ticks`` ticks (default:
    ``min(ticks, 64)`` — bounding the epoch keeps the host-side ingest
    batch and the stacked per-tick outputs flat in memory and the scan
    compile time constant for long runs) into one fused dispatch per
    epoch. Its warmup runs one full epoch (any ``warmup_ticks > 0``
    requests it) so the measured epochs hit a compiled program, and
    ``ticks`` is rounded up to whole epochs so every dispatch reuses
    the one compiled scan length.

    ``queries`` registers a ``repro.query`` standing-query registry at
    the root: every window's results then carry ``answers``/``bounds``
    vectors for all K queries (same dispatch count — the plan evaluates
    inside the tick). ``target_rel_error`` closes the §IV-B loop: a
    ``BudgetController`` reads each epoch's (window's) measured relative
    ±2σ error and moves the per-level sample budgets toward the target,
    within ``[8, capacity·max_fraction]`` (``max_fraction`` defaults to
    1.0 when a controller is active). ``return_stream`` additionally
    returns the raw ingested stream for ground-truth evaluation.

    ``pipeline_spec`` supplies the whole job as one declarative
    ``repro.api.PipelineSpec`` (what this function builds internally via
    ``build_spec`` otherwise); the keyword knobs it covers (fraction,
    mode, fanin, intervals, queries, budget policy, seed) are then read
    from the spec. ``engine="scan"`` executes through the compiled
    ``repro.api`` runtime (pure ``init``/``run_epoch`` with donated
    state); ``"level"``/``"loop"`` drive the per-tick ``HostTree`` shim
    on the same spec — bit-identical on identical ingest.
    """
    if pipeline_spec is None:
        if target_rel_error is not None:
            assert mode == "whs", "the error-budget loop drives WHS budgets"
            max_fraction = 1.0 if max_fraction is None else max_fraction
        pipeline_spec = build_spec(
            specs, fraction=fraction, capacity=capacity,
            num_sources=num_sources, fanin=fanin,
            interval_ticks=interval_ticks, allocation=allocation, seed=seed,
            mode=mode, sampler_backend=sampler_backend, queries=queries,
            target_rel_error=target_rel_error, max_fraction=max_fraction,
            telemetry=telemetry, strata=strata)
    # The spec is the job description: derive every reported/derived
    # quantity from it so an explicitly-passed spec and the legacy
    # keyword path behave identically.
    mode = pipeline_spec.sampler.mode
    fraction = pipeline_spec.sampler.fraction
    sampler_backend = pipeline_spec.sampler.backend
    fanin = tuple(pipeline_spec.topology.fanin)
    interval_ticks = (list(pipeline_spec.topology.interval_ticks)
                      if pipeline_spec.topology.interval_ticks else None)
    target_rel_error = pipeline_spec.budget.target_rel_error
    if engine == "scan":
        tree = _CompiledDriver(api.compile(pipeline_spec))
    else:
        assert not pipeline_spec.strata.adaptive, (
            "adaptive stratification rides the scan engine's route leaf")
        tree = HostTree.from_spec(pipeline_spec, engine=engine)
    manager = None
    if pipeline_spec.strata.adaptive:
        from repro import strata as strata_mod

        manager = strata_mod.StratumManager(
            np.asarray(tree.state.tree.route),
            pipeline_spec.topology.num_strata,
            split_occupancy=pipeline_spec.strata.split_occupancy,
            merge_occupancy=pipeline_spec.strata.merge_occupancy)
    sources = [S.StreamSource(specs, seed=pipeline_spec.seed * 977 + i)
               for i in range(num_sources)]
    controller = None
    trajectory: list[dict] = []
    if target_rel_error is not None:
        from repro.runtime.budget import (BudgetConfig, BudgetController,
                                          WorstTenantArbiter)

        cfg = BudgetConfig(min_size=pipeline_spec.budget.min_size,
                           max_size=int(tree.max_sample_sizes[0]),
                           target_rel_error=target_rel_error,
                           kp=pipeline_spec.budget.kp,
                           ki=pipeline_spec.budget.ki)
        if len(pipeline_spec.tenants) > 1:
            # N tenants share the tree: worst-tenant-first fairness on
            # the one budget knob (see runtime.budget).
            controller = WorstTenantArbiter(
                cfg, initial_size=int(tree.sample_sizes[0]))
        else:
            controller = BudgetController(
                cfg, initial_size=int(tree.sample_sizes[0]))
    # Only materialize the raw stream when the caller asked for it —
    # collection is O(items) host memory/time, which would silently void
    # the scan engine's flat-memory property on long --queries runs.
    collect = return_stream
    stream_v: list[np.ndarray] = []
    stream_s: list[np.ndarray] = []

    def _feedback(new_windows: list[dict], step: int) -> None:
        """Feed the controller the freshest measured relative ±2σ error
        and move every level's budget (§IV-B adaptive feedback). With
        N tenants the error is attributed per tenant and the worst-off
        tenant drives the shared budget (worst-tenant-first fairness)."""
        if controller is None or not new_windows:
            return
        if hasattr(controller, "last_tenant"):     # WorstTenantArbiter
            from repro.runtime.budget import (aggregate_tenant_rel_errors,
                                              level_error_shares)

            per = aggregate_tenant_rel_errors(tree.plan, new_windows)
            # Per-level attribution: split the worst tenant's error
            # across levels by measured (1-f)/f variance shares, so the
            # controller moves only the levels that dominate the error.
            ins = [tree.items_ingested] + list(tree.items_forwarded[:-1])
            shares = level_error_shares(ins, tree.items_forwarded)
            sizes = controller.update_levels(per, shares)
            entry = dict(step=step, rel_error=max(per.values() or [0.0]),
                         size=max(sizes), sizes=list(sizes),
                         level_shares=[round(float(s), 6) for s in shares],
                         tenant=controller.last_tenant,
                         tenant_rel_errors=per)
        else:
            rels = [_window_rel_error(w, tree.plan) for w in new_windows]
            rel = float(np.mean([r for r in rels if np.isfinite(r)]
                                or [0.0]))
            size = controller.update(rel_error=rel)
            sizes = [size] * len(tree.fanin)
            entry = dict(step=step, rel_error=rel, size=size)
        tree.set_sample_sizes(sizes)
        trajectory.append(entry)

    if engine == "scan":
        epoch_t = min(epoch_ticks or 64, ticks)
        n_epochs = -(-ticks // epoch_t)  # ceil: whole epochs only
        width = tree.capacities[0]
        t0_tick = 1
        if warmup_ticks > 0:  # one full epoch: compiles the scan program
            wb = S.batch_ingest(sources, epoch_t, tree.fanin[0], width)
            tree.run_epoch(t0_tick, wb.values, wb.strata, wb.counts,
                           offered=wb.offered)
            t0_tick += epoch_t
    else:
        for t in range(1, warmup_ticks + 1):
            for i, src in enumerate(sources):
                vals, strs = src.tick()
                tree.ingest(i % tree.fanin[0], vals, strs)
            tree.tick(t)
    # reset accounting after warmup (sketch state included: continuous
    # answers must cover exactly the measured stream)
    tree.reset_query_state()
    if engine == "scan":
        from repro.obs import telemetry as obs_telemetry

        tree.state = obs_telemetry.reset(tree.state)
    tree.results.clear()
    tree.items_ingested = 0
    tree.items_forwarded = [0] * len(tree.fanin)
    tree.level_time_s = [0.0] * len(tree.fanin)
    tree.dispatch_count = 0

    exact_sum = 0.0
    exact_cnt = 0
    ingest_truncation_warned = False
    t0 = time.time()
    if engine == "scan":
        from repro.obs.trace import span

        for e in range(n_epochs):
            with span("ingest", epoch=e):
                b = S.batch_ingest(sources, epoch_t, tree.fanin[0], width)
            exact_sum += b.exact_sum
            exact_cnt += b.exact_count
            dropped = int((b.offered - b.counts).sum())
            if dropped and not ingest_truncation_warned:
                # Level-0 drops carry no metadata, so truncation biases
                # every estimate downward with no error signal — this
                # happens when the stream offered to run_pipeline is
                # heavier than the load the spec's capacity was
                # provisioned for (e.g. a spec built for a different
                # num_sources/rates).
                import warnings

                warnings.warn(
                    f"level-0 ingest truncated {dropped} items in epoch "
                    f"{e} (capacity {width} per node/tick is below the "
                    f"offered load) — estimates will bias low; rebuild "
                    f"the PipelineSpec for the actual source count and "
                    f"rates", RuntimeWarning, stacklevel=2)
                ingest_truncation_warned = True
            if collect:
                for tt in range(epoch_t):
                    for node in range(tree.fanin[0]):
                        c = int(b.counts[tt, node])
                        stream_v.append(b.values[tt, node, :c])
                        stream_s.append(b.strata[tt, node, :c])
            n_before = len(tree.results)
            tree.run_epoch(t0_tick + e * epoch_t, b.values, b.strata,
                           b.counts, offered=b.offered)
            _feedback(tree.results[n_before:], step=e)
            if manager is not None and e + 1 < n_epochs:
                # Epoch boundary: fold this epoch's per-key arrival
                # counts into the manager and commit any split/merge as
                # a pure route+metadata edit — same shapes, so the next
                # epoch reuses the compiled program (zero retraces,
                # pinned in tests/test_strata.py).
                from repro import strata as strata_mod

                pos = np.arange(np.shape(b.strata)[-1])[None, None, :]
                live = pos < np.asarray(b.counts)[..., None]
                keys = np.asarray(b.strata)[live]
                kc = np.bincount(keys, minlength=manager.num_keys)
                km = np.bincount(keys, minlength=manager.num_keys,
                                 weights=np.abs(np.asarray(b.values)[live]))
                manager.observe(kc, km)
                ops = manager.maybe_adapt()
                if ops:
                    tree.state = tree.state._replace(
                        tree=strata_mod.remap_tree_state(
                            tree.state.tree, ops, manager.route))
    else:
        for t in range(warmup_ticks + 1, warmup_ticks + ticks + 1):
            for i, src in enumerate(sources):
                vals, strs = src.tick()
                exact_sum += float(vals.sum())
                exact_cnt += len(vals)
                if collect:
                    stream_v.append(vals)
                    stream_s.append(strs)
                tree.ingest(i % tree.fanin[0], vals, strs)
            n_before = len(tree.results)
            tree.tick(t)
            _feedback(tree.results[n_before:], step=t)
    wall = time.time() - t0

    approx_sum = float(sum(r["sum"] for r in tree.results))
    bound = 2 * float(np.sqrt(sum(r["sum_var"] for r in tree.results)))
    acc_loss = abs(approx_sum - exact_sum) / max(abs(exact_sum), 1e-9)

    # -------- latency + pipeline-throughput model (module docstring) -----
    # level_time_s[lvl] sums every node of the level; in the testbed the
    # nodes are separate machines, so per-item path cost and the sustained
    # rate are per-NODE quantities.
    n_windows = max(len(tree.results), 1)
    it = interval_ticks or [1] * len(tree.fanin)
    window_wait = sum(iv / 2.0 for iv in it)          # in ticks
    node_time = [lt / max(n, 1) for lt, n in zip(tree.level_time_s, tree.fanin)]
    proc = sum(nt / n_windows for nt in node_time)
    fwd = [tree.items_ingested] + tree.items_forwarded[:-1]
    transfer = sum(
        HOP_RTT_S[min(h, len(HOP_RTT_S) - 1)] / 2.0
        + (fwd[h] / n_windows / max(tree.fanin[min(h, len(tree.fanin) - 1)], 1))
        * ITEM_BYTES / LINK_BW
        for h in range(len(tree.fanin)))
    latency = proc + transfer
    # Sustained pipeline rate = the slowest stage (per node): the §V-A
    # methodology saturates the datacenter node, so at fraction 1.0 the
    # root is the bottleneck and sampling moves it toward the edge.
    bottleneck = max(nt / max(wall, 1e-9) for nt in node_time)  # utilization
    pipeline_tp = (exact_cnt / max(wall, 1e-9)) / max(bottleneck, 1e-9)
    extras = {}
    if tree.plan is not None:
        extras["query_layout"] = {
            n: dict(offset=o, width=wd, kind=k)
            for n, (o, wd, k) in tree.plan.layout().items()}
        extras["windows_answers"] = [r["answers"] for r in tree.results
                                     if "answers" in r]
        extras["windows_bounds"] = [r["bounds"] for r in tree.results
                                    if "bounds" in r]
    if controller is not None:
        extras["controller"] = trajectory
        extras["final_sample_sizes"] = list(tree.sample_sizes)
    if manager is not None:
        import dataclasses as _dc

        extras["strata_ops"] = [_dc.asdict(op) for op in manager.ops_log]
        extras["strata_route"] = np.asarray(tree.state.tree.route).tolist()
    if engine == "scan" and getattr(tree.pipe, "telemetry_enabled", False):
        from repro.obs.metrics import metrics_text
        from repro.obs.telemetry import snapshot, tenant_rel_bounds
        from repro.obs.trace import get_tracer

        snap = snapshot(tree.state)
        if snap is not None:
            snap["slot_rel_bound_mean"] = np.asarray(
                snap["slot_rel_bound_mean"]).tolist()
            snap["tenant_rel_bounds"] = tenant_rel_bounds(tree.pipe,
                                                          tree.state)
            extras["telemetry"] = snap
            extras["metrics"] = metrics_text(
                pipeline=tree.pipe, state=tree.state, tracer=get_tracer(),
                controller=controller)
    if return_stream:
        extras["stream_values"] = (np.concatenate(stream_v) if stream_v
                                   else np.zeros(0, np.float32))
        extras["stream_strata"] = (np.concatenate(stream_s) if stream_s
                                   else np.zeros(0, np.int32))
    return {
        **extras,
        "fraction": fraction,
        "mode": mode,
        "engine": engine,
        "sampler_backend": sampler_backend,
        "dispatches": tree.dispatch_count,
        "approx_sum": approx_sum,
        "exact_sum": exact_sum,
        "bound_2sigma": bound,
        "accuracy_loss": acc_loss,
        "within_2sigma": abs(approx_sum - exact_sum) <= bound,
        "items_ingested": tree.items_ingested,
        "items_forwarded": tree.items_forwarded,
        "bandwidth_fraction": (tree.items_forwarded[0] /
                               max(tree.items_ingested, 1)),
        "wall_s": wall,
        "throughput_items_s": exact_cnt / max(wall, 1e-9),
        "pipeline_items_s": pipeline_tp,
        "level_time_s": list(tree.level_time_s),
        "latency_s": latency,
        "latency_window_ticks": window_wait,
        "windows": len(tree.results),
    }


def make_data_mesh(n_devices: int):
    """A 1-axis ``("data",)`` mesh over ``n_devices`` local devices, with
    an actionable error when the host doesn't expose enough (CPU runs
    need ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    import jax

    have = len(jax.devices())
    if n_devices > have:
        raise RuntimeError(
            f"--mesh {n_devices} needs {n_devices} devices but jax sees "
            f"{have}; on CPU export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices} "
            f"before importing jax")
    return jax.make_mesh((n_devices,), ("data",),
                         devices=jax.devices()[:n_devices])


def run_spmd_pipeline(specs, *, fraction: float = 0.1, ticks: int,
                      n_devices: int = 1, mesh=None, queries=None,
                      seed: int = 0, mode: str = "whs",
                      sampler_backend: str = "topk",
                      allocation: str = "fair",
                      epoch_ticks: int | None = None,
                      target_rel_error: float | None = None,
                      max_fraction: float | None = None,
                      warmup: bool = True,
                      telemetry: bool = False):
    """The §III-E pod-scale data plane end to end: stream → mesh →
    merged-summary query plane → per-window answers. Returns a dict in
    the ``run_pipeline`` report style.

    Each device runs one level-0 node: every tick the pod's arrivals
    are dealt round robin over the nodes (``data.stream.ticks_to_ingest``)
    and enter ``run_epoch`` as node rows; ``epoch_ticks`` windows batch
    into one jitted dispatch. With ``queries`` tenants the root
    answers come from merged per-device sketch summaries (never raw
    reservoirs — see ``repro.api.spmd``); ``target_rel_error`` closes
    the §IV-B loop on the mesh: the per-epoch measured per-tenant error
    (attributed from the merged answers) drives the shared traced sample
    budget, worst-tenant-first when several tenants share the plane.
    """
    from repro import api

    mesh = mesh if mesh is not None else make_data_mesh(n_devices)
    n_dev = int(np.prod(list(mesh.shape.values())))
    src = S.StreamSource(specs, seed=seed * 977)
    per_tick = sum(sp.rate for sp in specs)
    # a node's buffer: its share of the offered load + Poisson slack
    cap = -(-(int(1.35 * per_tick) + 256) // n_dev)
    if target_rel_error is not None and max_fraction is None:
        max_fraction = 1.0
    spec = build_spec(specs, fraction=fraction, capacity=cap,
                      fanin=(n_dev, 1),
                      num_strata=len(specs), allocation=allocation,
                      seed=seed, mode=mode, sampler_backend=sampler_backend,
                      queries=queries, target_rel_error=target_rel_error,
                      max_fraction=max_fraction, telemetry=telemetry)
    pipe = api.compile(spec, mesh=mesh)
    epoch_t = min(epoch_ticks or 32, ticks)
    n_epochs = -(-ticks // epoch_t)

    def node_rows(source):
        b = S.ticks_to_ingest([source.tick() for _ in range(epoch_t)],
                              n_nodes=n_dev, width=cap)
        return b.values, b.strata, b.counts

    controller = None
    trajectory: list[dict] = []
    budget = float(pipe.local_budget)
    if target_rel_error is not None and pipe.plan is not None:
        from repro.runtime.budget import (BudgetConfig, BudgetController,
                                          WorstTenantArbiter)

        cfg = BudgetConfig(min_size=spec.budget.min_size,
                           max_size=pipe.max_local_budget,
                           target_rel_error=target_rel_error,
                           kp=spec.budget.kp, ki=spec.budget.ki)
        controller = (WorstTenantArbiter(cfg, initial_size=pipe.local_budget)
                      if len(spec.tenants) > 1 else
                      BudgetController(cfg, initial_size=pipe.local_budget))

    state = pipe.init()
    if warmup:  # compile the epoch program off the measured clock
        state, _ = pipe.run_epoch(
            state, pipe.default_key,
            *node_rows(S.StreamSource(specs, seed=seed * 977 + 1)),
            budgets=[budget] if pipe.plan else None)
        state = pipe.init()
        pipe.trace_counter["traces"] = 0

    from repro.obs import telemetry as obs_telemetry
    from repro.obs.trace import span

    state = obs_telemetry.reset(state)   # counters cover measured epochs
    results: list[dict] = []
    exact_sum, exact_cnt = 0.0, 0
    dispatches = 0
    t0 = time.time()
    for e in range(n_epochs):
        with span("ingest", epoch=e):
            ingest = node_rows(src)
            exact_sum += float(ingest[0].sum())   # padding slots hold 0
            exact_cnt += int(ingest[2].sum())
        if pipe.plan is not None:
            # the tenant path folds the carried GLOBAL tick into the key,
            # so one key gives fresh randomness every epoch
            with span("epoch_dispatch", epoch=e):
                state, wa = pipe.run_epoch(state, pipe.default_key, *ingest,
                                           budgets=[budget])
            with span("block_until_ready"):
                rows = pipe.rows(wa)
            if controller is not None and rows:
                if hasattr(controller, "last_tenant"):
                    size, per = controller.update_from_windows(pipe.plan,
                                                               rows)
                    entry = dict(step=e, size=size,
                                 rel_error=max(per.values() or [0.0]),
                                 tenant=controller.last_tenant,
                                 tenant_rel_errors=per)
                else:
                    rels = [_window_rel_error(w, pipe.plan) for w in rows]
                    rel = float(np.mean([r for r in rels
                                         if np.isfinite(r)] or [0.0]))
                    size = controller.update(rel_error=rel)
                    entry = dict(step=e, size=size, rel_error=rel)
                budget = float(size)
                trajectory.append(entry)
        else:
            # stateless path folds only the epoch-local tick index:
            # fold the epoch number here or every epoch would reuse the
            # exact same selection randomness
            import jax

            k_e = jax.random.fold_in(pipe.default_key, e)
            state, (sq, mq) = pipe.run_epoch(state, k_e, *ingest)
            rows = [dict(tick=e * epoch_t + i,
                         sum=float(np.asarray(sq.estimate)[i]),
                         sum_var=float(np.asarray(sq.variance)[i]),
                         mean=float(np.asarray(mq.estimate)[i]),
                         mean_var=float(np.asarray(mq.variance)[i]))
                    for i in range(epoch_t)]
        dispatches += 1
        results.extend(rows)
    wall = time.time() - t0

    approx_sum = float(sum(r["sum"] for r in results))
    bound = 2 * float(np.sqrt(sum(r["sum_var"] for r in results)))
    acc_loss = abs(approx_sum - exact_sum) / max(abs(exact_sum), 1e-9)
    out = {
        "fraction": fraction, "mode": mode, "engine": "spmd",
        "n_devices": n_dev, "sampler_backend": sampler_backend,
        "dispatches": dispatches, "retraces": pipe.trace_counter["traces"],
        "approx_sum": approx_sum, "exact_sum": exact_sum,
        "bound_2sigma": bound, "accuracy_loss": acc_loss,
        "within_2sigma": abs(approx_sum - exact_sum) <= bound,
        "items_ingested": exact_cnt,
        "wall_s": wall,
        "throughput_items_s": exact_cnt / max(wall, 1e-9),
        "windows": len(results),
    }
    if pipe.plan is not None:
        out["query_layout"] = {
            n: dict(offset=o, width=wd, kind=k)
            for n, (o, wd, k) in pipe.plan.layout().items()}
        out["windows_answers"] = [r["answers"] for r in results
                                  if "answers" in r]
        out["windows_bounds"] = [r["bounds"] for r in results
                                 if "bounds" in r]
        # the §III-E bandwidth story: what crosses the mesh per window
        out["summary_bytes_per_window"] = pipe.summary_bytes_per_window
        out["reservoir_bytes_per_window"] = pipe.reservoir_bytes_per_window
    if controller is not None:
        out["controller"] = trajectory
        out["final_sample_sizes"] = [budget]
    if telemetry and pipe.plan is not None:
        from repro.obs.metrics import metrics_text
        from repro.obs.telemetry import snapshot, tenant_rel_bounds
        from repro.obs.trace import get_tracer

        snap = snapshot(state)
        if snap is not None:
            snap["slot_rel_bound_mean"] = np.asarray(
                snap["slot_rel_bound_mean"]).tolist()
            snap["tenant_rel_bounds"] = tenant_rel_bounds(pipe, state)
            out["telemetry"] = snap
            out["metrics"] = metrics_text(
                pipeline=pipe, state=state, tracer=get_tracer(),
                controller=controller)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dist", default="gaussian",
                    choices=["gaussian", "poisson", "poisson-skewed", "taxi",
                             "pollution"])
    ap.add_argument("--fraction", type=float, default=0.1)
    ap.add_argument("--ticks", type=int, default=20)
    ap.add_argument("--allocation", default="fair",
                    choices=["fair", "proportional", "neyman"],
                    help="per-stratum reservoir split: fair = equal "
                         "water-filled shares, proportional = largest-"
                         "remainder by arrival count, neyman = count×std "
                         "optimal (the adaptive arm of Fig. 11c)")
    ap.add_argument("--adaptive-strata", action="store_true",
                    help="scan engine: split hot / merge starved strata "
                         "at epoch boundaries via the key→stratum route "
                         "table (repro.strata) — a pure state edit, no "
                         "recompiles")
    ap.add_argument("--mode", default="whs", choices=["whs", "srs"])
    ap.add_argument("--engine", default="level",
                    choices=["level", "loop", "scan"],
                    help="level = one jitted dispatch per level per tick; "
                         "loop = per-node reference engine; scan = whole "
                         "tree fused, one dispatch per epoch of ticks")
    ap.add_argument("--epoch-ticks", type=int, default=None,
                    help="scan engine: ticks fused per epoch dispatch "
                         "(default: min(ticks, 64))")
    ap.add_argument("--backend", default="topk",
                    choices=["argsort", "topk", "pallas", "pallas_fused"],
                    help="sampler selection backend: argsort = lexsort "
                         "reference, topk = dense partial-selection "
                         "thresholds, pallas = fused kernels (interpret "
                         "mode off-TPU)")
    ap.add_argument("--queries", default=None, metavar="TOKENS",
                    help="standing queries answered at the root every "
                         "window, e.g. "
                         "'sum,count,mean,hist:0:120000:32,q:0.5:0.9:0.99,hh'"
                         " (see repro.query.registry)")
    ap.add_argument("--target-rel-error", type=float, default=None,
                    help="close the §IV-B loop: adapt per-level sample "
                         "budgets online until the measured relative ±2σ "
                         "error meets this target")
    ap.add_argument("--max-fraction", type=float, default=None,
                    help="budget ceiling for the error-budget controller "
                         "(fraction of window capacity; default 1.0)")
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="run the §III-E SPMD data plane on an N-device "
                         "'data' mesh instead of the emulated tree "
                         "(CPU: export XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N); with --queries the "
                         "tenants lower onto the merged-summary query "
                         "plane — only sketch summaries cross devices")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the result report to PATH (BENCH artifact)")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable the in-graph observability plane "
                         "(repro.obs): the report/--json gains a "
                         "'telemetry' snapshot and a Prometheus-text "
                         "'metrics' block (scan engine and --mesh paths)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the host span tracer's Chrome/Perfetto "
                         "trace.json to PATH (load in ui.perfetto.dev)")
    args = ap.parse_args(argv)

    specs = {
        "gaussian": S.paper_gaussian(),
        "poisson": S.paper_poisson(),
        "poisson-skewed": S.paper_poisson(
            rates=tuple(8000 * s for s in S.SKEW_SHARES), skewed=True),
        "taxi": S.taxi_like(),
        "pollution": S.pollution_like(),
    }[args.dist]
    registry = None
    if args.queries:
        from repro.query.registry import QueryRegistry

        registry = QueryRegistry.from_tokens(args.queries)
    if args.telemetry and args.mesh is None and args.engine != "scan":
        # telemetry leaves live in the compiled runtimes' donated state
        args.engine = "scan"
    strata_spec = None
    if args.adaptive_strata:
        from repro.api.spec import StrataSpec

        assert args.mesh is None, "--adaptive-strata needs the scan engine"
        args.engine = "scan"   # the route leaf lives in the scan state
        strata_spec = StrataSpec(num_keys=len(specs), adaptive=True)
    if args.mesh is not None:
        r = run_spmd_pipeline(
            specs, fraction=args.fraction, ticks=args.ticks,
            n_devices=args.mesh, queries=registry, mode=args.mode,
            sampler_backend=args.backend, allocation=args.allocation,
            epoch_ticks=args.epoch_ticks,
            target_rel_error=args.target_rel_error,
            max_fraction=args.max_fraction, telemetry=args.telemetry)
    else:
        r = run_pipeline(specs, fraction=args.fraction, ticks=args.ticks,
                         allocation=args.allocation, mode=args.mode,
                         engine=args.engine, sampler_backend=args.backend,
                         warmup_ticks=2, epoch_ticks=args.epoch_ticks,
                         queries=registry,
                         target_rel_error=args.target_rel_error,
                         max_fraction=args.max_fraction,
                         telemetry=args.telemetry, strata=strata_spec)
    print(f"dist={args.dist} mode={args.mode} engine={r['engine']} "
          f"backend={args.backend} fraction={r['fraction']:.0%}"
          + (f" mesh={r['n_devices']}dev" if args.mesh else ""))
    print(f"  SUM ≈ {r['approx_sum']:.4e} ± {r['bound_2sigma']:.2e} "
          f"(exact {r['exact_sum']:.4e}; within 2σ: {r['within_2sigma']})")
    print(f"  accuracy loss  {r['accuracy_loss']:.5%}")
    if "strata_ops" in r:
        kinds = [op["kind"] for op in r["strata_ops"]]
        print(f"  strata         {kinds.count('split')} splits, "
              f"{kinds.count('merge')} merges; route {r['strata_route']}")
    if "bandwidth_fraction" in r:
        print(f"  bandwidth kept {r['bandwidth_fraction']:.1%} of ingested "
              f"items")
    elif "summary_bytes_per_window" in r:
        # both sides per device SHIPPED per window (gather traffic scales
        # with the mesh the same way on both paths)
        print(f"  cross-device   {r['summary_bytes_per_window']} B/window "
              f"of sketch summaries per device (reservoir all-gather "
              f"would ship {r['reservoir_bytes_per_window']} B and grow "
              f"with the sample budget)")
    print(f"  throughput     {r['throughput_items_s']:.0f} items/s "
          f"({r['items_ingested']} items, {r['windows']} windows, "
          f"{r['dispatches']} jitted dispatches)")
    if "latency_s" in r:
        print(f"  latency        {r['latency_s'] * 1e3:.1f} ms/window "
              f"(+{r['latency_window_ticks']:.1f} tick window wait)")
    if registry is not None and r.get("windows_answers"):
        last_a, last_b = r["windows_answers"][-1], r["windows_bounds"][-1]
        print("  standing queries (last window, ± bound):")
        for name, lay in r["query_layout"].items():
            o, wd = lay["offset"], lay["width"]
            a = ", ".join(f"{v:.4g}" for v in last_a[o:o + min(wd, 6)])
            b = ", ".join(f"{v:.3g}" for v in last_b[o:o + min(wd, 6)])
            more = " …" if wd > 6 else ""
            print(f"    {name:<12} [{a}{more}] ± [{b}{more}]")
    if r.get("controller"):
        tr = r["controller"]
        print(f"  error-budget controller: size {tr[0]['size']}→"
              f"{tr[-1]['size']} over {len(tr)} updates "
              f"(rel err {tr[0]['rel_error']:.4f}→{tr[-1]['rel_error']:.4f},"
              f" target {args.target_rel_error})")
    if r.get("telemetry"):
        tel = r["telemetry"]
        fr = ", ".join(f"L{i}:{lv['effective_fraction']:.3f}"
                       for i, lv in enumerate(tel["levels"]))
        print(f"  telemetry      {tel['windows']} windows, realized ±2σ "
              f"{tel['bound_2sigma']:.3e} "
              f"(rel {tel['rel_bound_2sigma']:.4f}); eff fraction {fr}")
    if args.trace:
        from repro.obs.trace import get_tracer

        get_tracer().save(args.trace)
        print(f"  wrote {args.trace}")
    if args.json:
        import json
        import pathlib

        payload = {k: v for k, v in r.items()
                   if k not in ("windows_answers", "windows_bounds")}
        pathlib.Path(args.json).write_text(
            json.dumps(payload, indent=1, default=str))
        print(f"  wrote {args.json}")
    return r


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
