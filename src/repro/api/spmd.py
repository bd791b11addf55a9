"""``compile(spec, mesh=...)`` — the same spec on the pod-scale data plane.

The SPMD lowering of a ``PipelineSpec`` is the paper's §III-E hierarchy
run in-graph across a mesh axis, one jitted dispatch per epoch of ``T``
ticks. Its front door is the local pipeline's: ``run_epoch(state, key,
values, strata, counts, budgets)`` on ``[T, fanin[0], width]`` node
rows, with ``fanin``, ``init`` (a fresh state's first window is tick 1)
and the routing surface. The node axis is split over the mesh axis in
consecutive blocks, so with as many level-0 nodes as devices edge node
``d`` runs on device ``d``; each device receives only its own rows and
builds its interval batch (validity mask, source-identity metadata)
inside the program. Three lowerings share the front door:

* **Query tenants registered** (the full multi-tenant query plane):
  every device WHS-samples its shard of each window with the spec's
  backend/allocation and its own DONATED sketch state, and the window is
  answered by one batched root ``MultiTenantPlan`` evaluation over
  MERGED per-device summaries — ``psum``-ed CLT moments, all-gathered
  quantile buffers and count-min tables (``query.sketches`` merge
  algebra). Only O(sketch) summaries ever cross the device boundary;
  raw reservoir items never do. Per-tenant ``WindowAnswers`` come back
  with the same routing surface as the local pipeline
  (``answer``/``tenant_answers``/``tenant_rel_errors``), so the
  worst-tenant-first error-budget loop closes on the mesh: the applied
  sample budget is a TRACED input — moving it between epochs never
  retraces. State (global tick + per-device sketches) is explicit and
  donated, so multi-epoch runs resume bit-identically to one long epoch.
* **``whs`` without tenants** (the original §III-E two-level path):
  every device samples its local interval batch, compacts to the spec's
  level-0 budget, all-gathers the *reservoirs*, and the root stage
  re-samples and answers SUM/MEAN with error bounds —
  ``core.tree.spmd_local_then_root_epoch``. Stateless between intervals.
* **``srs``** (the §IV-B baseline): per-device coin-flip keeps, HT
  SUM / sample MEAN merged from ``psum``-ed moments — no items cross.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api import spec as specmod
from repro.api.pipeline import QueryRouting, WindowAnswers
from repro.api.spec import PipelineSpec, SpecError
from repro.core import tree as T
from repro.core.types import IntervalBatch, StratumMeta
from repro.launch.sharding import spmd_epoch_specs, spmd_query_epoch_specs
from repro.obs.trace import get_tracer


def _check_vma(backend: str) -> bool:
    """Whether the epoch's ``shard_map`` type-checks varying axes. Every
    kernel output declares its varying axes (``kernels.out_struct``), so
    on TPU the check is on for every sampler backend. Off TPU the Pallas
    sampler backends run in the Pallas interpreter, which in JAX 0.9.0
    mis-types its block slices under the check, so there it is off for
    them; the check changes no value."""
    return (backend not in ("pallas", "pallas_fused")
            or jax.default_backend() == "tpu")


def _node_rows_batch(values, strata, counts, num_strata: int
                     ) -> IntervalBatch:
    """One device's node rows ``[T, k, width]`` (``counts[T, k]`` live
    items each, from the front) as the interval batch ``[T, k * width]``
    its epoch samples, with the source-identity metadata (weight 1,
    count 0). Traced inside the shard-mapped program, so no mask or
    metadata crosses from the host."""
    t, k, w = values.shape
    valid = jnp.arange(w, dtype=jnp.int32) < counts[..., None]
    flat = lambda a: a.reshape(t, k * w)
    return IntervalBatch(flat(values), flat(strata), flat(valid),
                         StratumMeta(jnp.ones((t, num_strata), jnp.float32),
                                     jnp.zeros((t, num_strata),
                                               jnp.float32)))


def _typed(a, dtype):
    """``a`` as ``dtype``, on the host for host input and on the device
    for device arrays (never a round trip through the host)."""
    if isinstance(a, jax.Array):
        return a if a.dtype == dtype else a.astype(dtype)
    return np.asarray(a, dtype)


class SpmdPipelineState(NamedTuple):
    """Explicit state of the tenant SPMD pipeline: the next global tick
    (i32 scalar, replicated) and the standing queries' sketch state with
    a leading per-device axis sharded over the mesh (device ``d`` owns
    slice ``[d]`` of every leaf). A plain pytree — donate it into
    ``run_epoch`` exactly like the local ``PipelineState``."""

    tick: Any
    qstate: Any
    # Optional replicated ``repro.obs.telemetry.EpochTelemetry`` leaves
    # (every counter derives from psums, so every device carries the
    # identical values); ``()`` when telemetry is disabled.
    telemetry: Any = ()


# Traced-program cache for the tenant lowering, keyed on everything the
# shard_map'd epoch closes over — with the plan component being the
# canonical name-free ``SlotPlanCore``, so tenant churn (admit/retire)
# and re-compiles of same-shaped specs reuse ONE jitted executable per
# (mesh, bucket configuration). Mirrors ``api.pipeline._PROGRAM_CACHE``.
_SPMD_PROGRAM_CACHE: dict = {}
_SPMD_PROGRAM_STATS = {"misses": 0, "hits": 0}


def spmd_program_cache_stats() -> dict:
    """{"misses": distinct shard_map'd programs traced, "hits": reuses}
    — the mesh-path counterpart of ``pipeline.program_cache_stats``,
    consumed by the ``repro.obs.metrics`` exposition layer."""
    return dict(_SPMD_PROGRAM_STATS)


def _spmd_program_entry(mesh, axis_name, core, max_budget, num_strata,
                        allocation, backend,
                        telemetry=False) -> tuple[tuple, dict]:
    sig = (mesh, axis_name, core, max_budget, num_strata, allocation,
           backend, telemetry)
    entry = _SPMD_PROGRAM_CACHE.get(sig)
    if entry is not None:
        _SPMD_PROGRAM_STATS["hits"] += 1
        return sig, entry
    _SPMD_PROGRAM_STATS["misses"] += 1
    counter = {"traces": 0}
    parts = spmd_query_epoch_specs(axis_name, core.init_state())
    # The telemetry leaves are replicated by construction (psum-derived);
    # a single P() prefix covers the whole subtree — and the empty ``()``
    # subtree when telemetry is off.
    state_spec = SpmdPipelineState(tick=parts["replicated"],
                                   qstate=parts["qstate"],
                                   telemetry=parts["replicated"])
    kw = dict(axis_name=axis_name, max_budget=max_budget,
              num_strata=num_strata, allocation=allocation,
              sampler_backend=backend)

    def spmd_epoch(state, key, budget, values, strata, counts):
        counter["traces"] += 1
        batches = _node_rows_batch(values, strata, counts, num_strata)
        n_ticks = values.shape[0]
        local_q = jax.tree.map(lambda v: v[0], state.qstate)
        qfinal, outs = T.spmd_query_plane_epoch(
            key, state.tick, budget, batches, local_q, core, **kw)
        ts = state.tick + jnp.arange(n_ticks, dtype=jnp.int32)
        tel = state.telemetry
        if telemetry:
            # All counters derive from psum/pmean outputs (replicated →
            # axis-invariant), so the update costs one extra psum of a
            # [T] vector and stays inside the same epoch dispatch.
            ok, se, sv, nsel = outs[0], outs[1], outs[2], outs[5]
            ans, bnd = outs[7], outs[8]
            off_t = jax.lax.psum(
                jnp.sum(batches.valid.astype(jnp.float32), axis=1),
                axis_name)
            kept_t = nsel.astype(jnp.float32)
            rel = bnd / jnp.maximum(jnp.abs(ans), 1e-9)
            tel = tel._replace(
                items_in=tel.items_in + jnp.sum(off_t),
                items_kept=tel.items_kept + jnp.sum(kept_t),
                flushes=tel.flushes + jnp.sum(ok.astype(jnp.int32)),
                saturation_hits=tel.saturation_hits + jnp.sum(
                    (ok & (kept_t >= off_t)).astype(jnp.int32)),
                windows=tel.windows + jnp.sum(ok.astype(jnp.int32)),
                root_sum=tel.root_sum + jnp.sum(jnp.where(ok, se, 0.0)),
                root_sum_var=tel.root_sum_var
                + jnp.sum(jnp.where(ok, sv, 0.0)),
                slot_rel_bound_sum=tel.slot_rel_bound_sum
                + jnp.sum(jnp.where(ok[:, None], rel, 0.0), axis=0))
        state2 = SpmdPipelineState(
            tick=state.tick + jnp.int32(n_ticks),
            qstate=jax.tree.map(lambda v: v[None], qfinal),
            telemetry=tel)
        return state2, (ts,) + outs

    rep, rows = parts["replicated"], parts["rows"]
    fn = jax.jit(jax.shard_map(
        spmd_epoch, mesh=mesh,
        in_specs=(state_spec, rep, rep, rows, rows, rows),
        out_specs=(state_spec, rep), check_vma=_check_vma(backend)),
        donate_argnums=(0,))
    entry = {"fn": fn, "trace_counter": counter}
    _SPMD_PROGRAM_CACHE[sig] = entry
    return sig, entry


class CompiledSpmdPipeline(QueryRouting):
    """Immutable SPMD compilation of one ``PipelineSpec`` (see module
    doc for the three lowerings).

    ``run_epoch(state, key, values, strata, counts[, budgets])`` takes
    the local pipeline's ingest: ``values``/``strata`` ``[T, fanin[0],
    width]`` node rows, ``counts[T, fanin[0]]`` live items per row.
    ``fanin[0]`` must be a multiple of the mesh axis: consecutive nodes
    share a device, and each device samples its nodes' items to the
    level-0 budget. With tenants it returns ``(state', WindowAnswers)``
    — per-window answers/bounds for every tenant, replicated across the
    axis (every device evaluates the root redundantly from the identical
    merged summaries; no single point of failure). Without tenants it
    returns the legacy ``(state, (sum, mean))`` per-tick
    ``QueryResult`` pair."""

    def __init__(self, spec: PipelineSpec, mesh, *, axis_name: str = "data"):
        if axis_name not in mesh.axis_names:
            raise SpecError(f"mesh has no axis {axis_name!r} "
                            f"(axes: {mesh.axis_names})")
        r = specmod.resolve(spec)
        self.spec = spec
        self.fanin = list(spec.topology.fanin)
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_devices = int(dict(mesh.shape)[axis_name])
        self._rows_sharding = NamedSharding(mesh, P(None, axis_name))
        self.plan = r.plan
        self.tenant_names = tuple(t.name for t in spec.tenants)
        self.local_budget = int(r.sample_sizes[0])
        self.max_local_budget = int(r.max_sample_sizes[0])
        self.root_budget = int(r.sample_sizes[-1])
        self.telemetry_enabled = spec.telemetry.enabled
        self.trace_counter = {"traces": 0}
        if self.plan is not None:
            # Tenant lowering: merged-summary query plane. Spec
            # validation already guarantees mode == "whs" here (tenants
            # need WHS stratum metadata). The traced epoch closes over
            # the name-free slot CORE and is fetched from the program
            # cache, so churned pipelines reuse the executable.
            self._program_sig, entry = _spmd_program_entry(
                mesh, axis_name, self.plan.core, self.max_local_budget,
                spec.topology.num_strata, spec.sampler.allocation,
                spec.sampler.backend, telemetry=self.telemetry_enabled)
            self._fn = entry["fn"]
            self.trace_counter = entry["trace_counter"]
            return
        num_strata = spec.topology.num_strata
        if spec.sampler.mode == "srs":
            frac = float(spec.sampler.fraction)

            def body(key, batches):
                self.trace_counter["traces"] += 1
                return T.spmd_srs_epoch(key, batches, axis_name=axis_name,
                                        fraction=frac)
        else:
            kw = dict(axis_name=axis_name, num_strata=num_strata,
                      local_budget=self.local_budget,
                      root_budget=self.root_budget,
                      allocation=spec.sampler.allocation,
                      sampler_backend=spec.sampler.backend)

            def body(key, batches):
                return T.spmd_local_then_root_epoch(key, batches, **kw)

        def spmd_epoch(key, values, strata, counts):
            return body(key, _node_rows_batch(values, strata, counts,
                                              num_strata))

        in_specs, out_specs = spmd_epoch_specs(axis_name)
        self._fn = jax.jit(jax.shard_map(
            spmd_epoch, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=_check_vma(spec.sampler.backend)))

    # ---------------------------------------------------- tenant churn --
    def _with_plan(self, plan, tenants) -> "CompiledSpmdPipeline":
        import dataclasses

        pipe = object.__new__(CompiledSpmdPipeline)
        pipe.__dict__.update(self.__dict__)
        pipe.plan = plan
        pipe.tenant_names = plan.tenant_names
        # reuse the caller's TenantSpec objects: admit stays O(live)
        pipe.spec = dataclasses.replace(self.spec, tenants=tuple(tenants))
        if plan.core is not self.plan.core:
            # bucket boundary crossed: fetch/build the next bucket's
            # cached executable (same mesh, same statics, CHURNED core)
            pipe._program_sig, entry = _spmd_program_entry(
                self.mesh, self.axis_name, plan.core,
                self.max_local_budget, self.spec.topology.num_strata,
                self.spec.sampler.allocation, self.spec.sampler.backend,
                telemetry=self.telemetry_enabled)
            pipe._fn = entry["fn"]
            pipe.trace_counter = entry["trace_counter"]
        return pipe

    def _sync_telemetry_slots(self, state, n_out: int):
        """Keep the telemetry ``slot_rel_bound_sum`` leaf in step with a
        churned plan's padded answer width (same rule as the local
        pipeline's ``_sync_telemetry_slots``)."""
        tel = getattr(state, "telemetry", ())
        if not hasattr(tel, "slot_rel_bound_sum"):
            return state
        cur = tel.slot_rel_bound_sum
        if cur.shape[0] == n_out:
            return state
        if cur.shape[0] < n_out:
            new = jnp.concatenate(
                [cur, jnp.zeros((n_out - cur.shape[0],), cur.dtype)])
        else:
            new = cur[:n_out]
        return state._replace(
            telemetry=tel._replace(slot_rel_bound_sum=new))

    def admit(self, state, tenant
              ) -> tuple["CompiledSpmdPipeline", "SpmdPipelineState"]:
        """Mesh-path hot admission: edits every device's slot row
        (``[n_devices, n_slots, ...]`` leaves at ``[:, slot]``) and the
        replicated-in-content active mask — a pure sharded-state edit;
        the shard_map'd epoch executable is reused from the program
        cache."""
        if self.plan is None:
            raise SpecError("admit() needs a tenanted pipeline — compile "
                            "with at least one TenantSpec")
        try:
            new_plan, transform = self.plan.admit(tenant.name,
                                                  tuple(tenant.queries))
        except (KeyError, ValueError) as e:
            raise SpecError(str(e)) from e
        qstate = transform(state.qstate, 1)    # axis 0 = device
        state = self._sync_telemetry_slots(
            state._replace(qstate=qstate), new_plan.core.n_out)
        return (self._with_plan(new_plan, self.spec.tenants + (tenant,)),
                state)

    def retire(self, state, tenant_id: str
               ) -> tuple["CompiledSpmdPipeline", "SpmdPipelineState"]:
        """Mesh-path retirement: flips the slot's mask bit on every
        device; state freezes, the slot recycles on a later admit."""
        if self.plan is None:
            raise SpecError("retire() needs a tenanted pipeline")
        try:
            new_plan, transform = self.plan.retire(tenant_id)
        except (KeyError, ValueError) as e:
            raise SpecError(str(e)) from e
        qstate = transform(state.qstate, 1)
        state = self._sync_telemetry_slots(
            state._replace(qstate=qstate), new_plan.core.n_out)
        return (self._with_plan(
            new_plan, tuple(t for t in self.spec.tenants
                            if t.name != tenant_id)),
            state)

    @property
    def default_key(self) -> jax.Array:
        return jax.random.PRNGKey(self.spec.seed)

    def init(self, key: jax.Array | None = None):
        """Fresh explicit state. With tenants: global tick 1 (the local
        pipeline's numbering: a fresh state's first window is tick 1)
        plus one empty sketch state per device (leaves
        ``[n_devices, ...]``). Without tenants the path is stateless
        between intervals (each interval batch is complete): empty
        pytree."""
        del key
        if self.plan is None:
            return ()
        q0 = self.plan.init_state()
        # commit with the exact shardings the epoch fn emits, so every
        # epoch (first included) hits one compiled executable
        stacked = jax.tree.map(
            lambda v: jax.device_put(
                jnp.stack([v] * self.n_devices),
                NamedSharding(self.mesh, P(self.axis_name))), q0)
        tick = jax.device_put(jnp.int32(1),
                              NamedSharding(self.mesh, P()))
        tel = ()
        if self.telemetry_enabled:
            from repro.obs.telemetry import EpochTelemetry

            # single merged "level", no per-stratum root telemetry on
            # the summary-merge path (strata merge via psums, not a
            # single root SampleResult), padded slot width from the core
            tel = jax.tree.map(
                lambda v: jax.device_put(v, NamedSharding(self.mesh, P())),
                EpochTelemetry.create(1, 0, self.plan.core.n_out))
        return SpmdPipelineState(tick=tick, qstate=stacked, telemetry=tel)

    def telemetry_snapshot(self, state) -> dict | None:
        """Host-readable snapshot of the in-graph telemetry counters
        (``None`` when disabled) — see ``repro.obs.snapshot``."""
        from repro.obs.telemetry import snapshot

        return snapshot(state)

    def clamp_budgets(self, budgets) -> float:
        """Applied level-0 sample budget clamped to [1, ceiling] — same
        rule as the local pipeline; accepts a scalar or the per-level
        list every driver passes (only level 0 exists on this path)."""
        if budgets is None:
            return float(self.local_budget)
        if np.ndim(budgets) > 0:
            budgets = np.asarray(budgets).reshape(-1)[0]
        return min(max(float(budgets), 1.0), float(self.max_local_budget))

    def _place(self, values, strata, counts):
        """The epoch's node rows on the mesh, split on the node axis:
        each device receives only its own rows."""
        n_ticks, n0 = np.shape(counts)
        if n0 != self.fanin[0]:
            raise SpecError(f"ingest rows must match level-0 nodes: got "
                            f"{n0} for fanin {tuple(self.fanin)}")
        if n0 % self.n_devices:
            raise SpecError(
                f"fanin[0] = {n0} level-0 nodes must be a multiple of mesh "
                f"axis {self.axis_name!r} ({self.n_devices} devices): "
                f"each device runs a block of consecutive nodes")
        with get_tracer().span("spmd.place", epoch=n_ticks,
                               devices=self.n_devices) as meta:
            rows = [_typed(values, np.float32), _typed(strata, np.int32),
                    _typed(counts, np.int32)]
            meta["bytes"] = sum(int(a.nbytes) for a in rows)
            return jax.device_put(rows, self._rows_sharding)

    def run_epoch(self, state, key: jax.Array, values, strata, counts,
                  budgets=None):
        """Advance ``T = counts.shape[0]`` ticks in ONE dispatch, on the
        local pipeline's ingest (see the class doc).

        Tenant path: window ``i`` folds the global tick ``state.tick+i``
        into ``key`` (multi-epoch runs resume bit-identically);
        ``state`` is donated — do not reuse the argument. ``budgets``
        (traced; a scalar or the per-level list, ``clamp_budgets``)
        moves the applied level-0 sample budget with zero retraces.
        Returns ``(state', WindowAnswers)``.

        Legacy/no-tenant paths: stateless — tick ``i`` folds ``i`` into
        ``key``, bit-matching ``T`` per-interval calls; returns
        ``(state, (sum, mean))``."""
        if self.plan is None and budgets is not None:
            raise SpecError("budgets are traced inputs of the tenant "
                            "query plane only — the no-tenant SPMD "
                            "path bakes the spec's budgets statically")
        rows = self._place(values, strata, counts)
        if self.plan is None:
            return state, self._fn(key, *rows)
        b = jnp.float32(self.clamp_budgets(budgets))
        state, outs = self._fn(state, key, b, *rows)
        ts, ok, se, sv, me, mv, nsel, hist, ans, bnd = outs
        # Host arithmetic on static shapes: it never waits for the epoch.
        get_tracer().count("spmd_merge_bytes",
                           ts.shape[0] * self.summary_bytes_per_window)
        tel = getattr(state, "telemetry", ())
        if hasattr(tel, "merge_bytes"):
            # The byte model depends on the LIVE tenant set (admit/retire
            # change what crosses the axis), so the fold happens here on
            # the host per epoch rather than being baked into the traced
            # program: windows × the current static per-window model.
            windows_delta = int(np.asarray(ok).sum())
            state = state._replace(telemetry=tel._replace(
                merge_bytes=tel.merge_bytes + jnp.float32(
                    windows_delta * self.summary_bytes_per_window)))
        # padded slot vector → public live-tenant vector (eager gather
        # outside the jit — follows churn with zero retraces)
        ans, bnd = self.plan.compact(ans), self.plan.compact(bnd)
        wa = WindowAnswers(
            tick=ts, ok=ok, sum=se, sum_var=sv, mean=me, mean_var=mv,
            n_sampled=nsel, histogram=hist, answers=ans, bounds=bnd,
            # no raw items ever cross a boundary on this path — the
            # would-be "forwarded items" channel is identically empty
            n_forwarded=np.zeros((ts.shape[0], 1), np.int32))
        return state, wa

    @property
    def summary_bytes_per_window(self) -> int:
        """Upper bound on the per-device bytes the tenant query plane
        ships per window: sketch summaries (quantile value/weight
        buffers, CM tables via psum, top-k candidate keys) plus the
        per-query CLT/histogram moment scalars and the built-in
        workload's per-stratum reductions. Compare against
        ``reservoir_bytes_per_window`` — the cost the reservoir
        all-gather of the no-tenant path would pay (the README
        bandwidth table; asserted against the traced collectives in
        ``tests/test_spmd_query_plane.py``)."""
        if self.plan is None:
            return 0
        plans = getattr(self.plan, "plans", (self.plan,))
        n = 0
        for p in plans:
            for sp in p.specs:
                if sp.kind == "quantile":
                    n += (2 * sp.capacity + 1) * 4      # value+weight+comps
                elif sp.kind == "heavy_hitters":
                    n += (sp.depth * sp.width + sp.k) * 4  # CM psum + keys
                elif sp.kind == "histogram":
                    n += 2 * sp.bins * 4                # est + var psums
                else:
                    n += 3 * 4                          # est/var/share
        x = self.spec.topology.num_strata
        return n + (64 + 4 * x + 8) * 4  # built-in hist + moments + scalars

    @property
    def reservoir_bytes_per_window(self) -> int:
        """What the same window costs when compacted reservoirs cross
        instead (value f32 + stratum i32 + valid per kept item, plus the
        W/C metadata sets) — the no-tenant path's all-gather."""
        x = self.spec.topology.num_strata
        return self.local_budget * (4 + 4 + 1) + 2 * x * 4
