"""Query-plan compiler: fuse K standing queries into one root evaluation.

``CompiledQueryPlan`` turns a tuple of ``QuerySpec``s into three pure
functions the tree engines call at the root, *inside* the jitted tick:

* ``init_state()``   — sketch state pytree (one entry per spec; ``()``
  for stateless CLT queries). Joins ``TreeState`` as donated
  device-resident leaves under the scan engine.
* ``evaluate(key, batch, res, state)`` — answers every registered query
  from ONE window sample: a single shared ``stratum_moments`` pass feeds
  all CLT queries (sum/count/mean), histograms do one bin-scatter each,
  and sketch queries fold the window into their state and answer from
  it. Returns ``(state', answers f32[n_out], bounds f32[n_out])`` — a
  flat, statically-laid-out answer vector, so the scan engine stacks T
  windows of answers into one ``[T, n_out]`` epoch output with zero
  host round-trips.
* ``exact_answers(values, strata)`` — host-side (NumPy) ground truth in
  the same layout, for accuracy benchmarks.

The evaluation draws NO randomness from the sampler's key stream — the
quantile compactor's offset comes from a ``fold_in`` side-branch — so
registering queries leaves every sample and every reservoir state
bit-identical to a run with no queries registered (asserted in
``tests/test_query_plane.py``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import error as err
from repro.core.types import IntervalBatch, SampleResult
from repro.query import sketches
from repro.query.registry import QuerySpec

# fold_in tag separating the query plane's PRNG stream from the sampler's
_QUERY_KEY_TAG = 0x51C7
# fold_in tag for the replicated cross-device merge randomness (SPMD path)
_MERGE_KEY_TAG = 0x4D52
# fold_in tag for the windowed-quantile ring's query-time merge randomness
# (a side branch of the per-query key, so the ring update stream is
# untouched by how often the ring is queried)
_WINDOW_MERGE_TAG = 0x574D


def stratum_stats(batch: IntervalBatch, num_strata: int):
    """Pre-sampling per-stratum ``(count, mean, std)`` of one window,
    from the same shared ``stratum_moments`` pass that feeds the CLT
    queries. This is the query-plane variance signal for the adaptive
    stratification plane (``repro.strata``): occupancy says where the
    arrivals go, std says which strata actually need rows. ``neyman``
    allocation recomputes the identical moments where the batch lives —
    ``core.sampling.stratum_stds`` in XLA, a one-hot ``dot_general``
    inside the fused Pallas tick — so the two views agree bitwise on
    the same window (pinned in ``tests/test_strata.py``)."""
    y, s1, s2 = err.stratum_moments(batch.value, batch.stratum,
                                    batch.valid, num_strata)
    safe = jnp.maximum(y, 1.0)
    mean = s1 / safe
    var = jnp.maximum(s2 / safe - mean * mean, 0.0)
    return y, mean, jnp.sqrt(var)


class CompiledQueryPlan:
    """Static, jit-closable fusion of K specs. All array work is pure."""

    def __init__(self, specs: tuple[QuerySpec, ...], num_strata: int):
        if not specs:
            raise ValueError("cannot compile an empty query registry")
        self.specs = tuple(specs)
        self.num_strata = int(num_strata)
        off = 0
        self._layout: dict[str, tuple[int, int, str]] = {}
        for sp in self.specs:
            self._layout[sp.name] = (off, sp.out_width, sp.kind)
            off += sp.out_width
        self.n_out = off

    @property
    def k(self) -> int:
        return len(self.specs)

    def layout(self) -> dict[str, tuple[int, int, str]]:
        """name → (offset, width, kind) into the flat answer vector."""
        return dict(self._layout)

    def answer(self, vec: np.ndarray, name: str) -> np.ndarray:
        """Slice one query's answers out of a flat (host) answer vector."""
        o, w, _ = self._layout[name]
        return np.asarray(vec)[..., o:o + w]

    def init_state(self) -> tuple:
        state = []
        for sp in self.specs:
            if sp.kind == "quantile":
                state.append(sketches.quantile_init(sp.capacity))
            elif sp.kind in ("heavy_hitters", "decayed_heavy_hitters"):
                state.append(sketches.hh_init(sp.k, sp.width, sp.depth))
            elif sp.kind == "windowed_quantile":
                state.append(sketches.windowed_quantile_init(sp.capacity,
                                                             sp.window))
            else:
                state.append(())
        return tuple(state)

    # ------------------------------------------------------------- eval --
    def evaluate(self, key: jax.Array, batch: IntervalBatch,
                 res: SampleResult, state: tuple) -> tuple:
        """(state', answers f32[n_out], bounds f32[n_out]) for one window."""
        x = self.num_strata
        sel = res.selected
        w_item = res.meta.weight[batch.stratum] * sel.astype(jnp.float32)
        # ONE moments pass shared by every CLT query (the fusion win: the
        # seed evaluated each query with its own segment-sum sweep).
        y, s1, s2 = err.stratum_moments(batch.value, batch.stratum, sel, x)

        outs, bnds, new_state = [], [], []
        for i, sp in enumerate(self.specs):
            kq = jax.random.fold_in(jax.random.fold_in(key, _QUERY_KEY_TAG), i)
            st = state[i]
            if sp.kind == "sum":
                q = err.approx_sum_from_moments(y, s1, s2, res.meta)
                a, b, st2 = q.estimate[None], q.bound(2.0)[None], ()
            elif sp.kind == "count":
                # HT count is exact per stratum given the metadata
                # (every item's indicator is 1): variance 0. It is the
                # integer number of items the sources sent, carried up
                # the tree by weights that divide; TPU f32 division is
                # not correctly rounded and leaves a few ulps, which
                # rounding to the integer removes.
                a = jnp.round(jnp.sum(y * res.meta.weight))[None]
                b, st2 = jnp.zeros((1,), jnp.float32), ()
            elif sp.kind == "mean":
                q = err.approx_mean_from_moments(y, s1, s2, res.meta)
                a, b, st2 = q.estimate[None], q.bound(2.0)[None], ()
            elif sp.kind == "histogram":
                from repro.core import queries as Q

                edges = jnp.linspace(sp.lo, sp.hi, sp.bins + 1)
                q = Q.weighted_histogram(batch, res, x, edges)
                a, b, st2 = q.estimate, q.bound(2.0), ()
            elif sp.kind == "quantile":
                st2 = sketches.quantile_update(kq, st, batch.value, w_item)
                a = sketches.quantile_query(st2, jnp.asarray(sp.qs))
                # live bound: 2·√(Σ quantum²)/W over the leveled
                # compaction history — honest for arbitrarily long
                # standing-query streams, and tighter than the collapsed
                # 2·√U/C because low-level quanta stay small.
                b = jnp.full((len(sp.qs),), 1.0) * st2.rank_error_bound
            elif sp.kind == "heavy_hitters":
                keys = sketches.hh_item_key(batch.value)
                st2 = sketches.hh_update(st, keys, w_item)
                eps_w = sketches.hh_error_bound(sp.width, st2.total_weight)
                a = jnp.concatenate([st2.key.astype(jnp.float32), st2.est])
                b = jnp.concatenate([jnp.zeros((sp.k,), jnp.float32),
                                     jnp.full((sp.k,), 1.0) * eps_w])
            elif sp.kind == "windowed_quantile":
                # one window → one ring slot; the query-time merge over
                # the last `window` slots answers "last N windows", which
                # a stream-so-far quantile sketch cannot.
                st2 = sketches.windowed_quantile_update(kq, st, batch.value,
                                                        w_item)
                km = jax.random.fold_in(kq, _WINDOW_MERGE_TAG)
                merged = sketches.windowed_quantile_merged(km, st2)
                a = sketches.quantile_query(merged, jnp.asarray(sp.qs))
                b = jnp.full((len(sp.qs),), 1.0) * merged.rank_error_bound
            elif sp.kind == "decayed_heavy_hitters":
                keys = sketches.hh_item_key(batch.value)
                st2 = sketches.hh_decayed_update(st, keys, w_item, sp.decay)
                eps_w = sketches.hh_error_bound(sp.width, st2.total_weight)
                a = jnp.concatenate([st2.key.astype(jnp.float32), st2.est])
                b = jnp.concatenate([jnp.zeros((sp.k,), jnp.float32),
                                     jnp.full((sp.k,), 1.0) * eps_w])
            else:  # pragma: no cover — registry validates kinds
                raise AssertionError(sp.kind)
            outs.append(a.astype(jnp.float32))
            bnds.append(b.astype(jnp.float32))
            new_state.append(st2)
        return tuple(new_state), jnp.concatenate(outs), jnp.concatenate(bnds)

    # ------------------------------------------------------------- spmd --
    def evaluate_spmd(self, key: jax.Array, batch: IntervalBatch,
                      res: SampleResult, state: tuple,
                      axis_name: str) -> tuple:
        """Distributed ``evaluate`` under ``shard_map``: every device
        holds one shard of the window (``batch``/``res`` are its local
        sample) plus its own sketch ``state``; the answers come from
        MERGED per-device summaries, and only those summaries —
        O(sketch) bytes — cross the device boundary:

        * CLT queries: per-device (estimate, variance) from the local
          moments pass, ``psum``-merged (independent local samples sum
          in estimate and variance; the mean re-weights by each shard's
          population share). ``count`` merges the *pre-sampling* stratum
          counts ``Σ C_i·W^in_i`` — the same quantity the HT count
          reconstructs, but summed as exact integers, so the merged
          answer is bitwise-identical across device counts.
        * histograms: per-bin HT estimate/variance, ``psum``-merged
          (linear queries merge exactly).
        * sketches: the local state updates from the local sample (own
          PRNG side-branch per device), then the per-device summaries
          all-gather and merge in-graph (``quantile_merge_stacked`` /
          ``hh_merge_stacked``) with REPLICATED merge randomness, so
          every device answers from the identical merged summary.

        ``key`` must be replicated across ``axis_name``. Returns
        ``(state', answers, bounds)`` with ``state'`` device-local and
        answers/bounds replicated in value (the caller re-types them
        with a ``pmean``, see ``core.tree.spmd_query_plane_tick``)."""
        x = self.num_strata
        sel = res.selected
        w_item = res.meta.weight[batch.stratum] * sel.astype(jnp.float32)
        y, s1, s2 = err.stratum_moments(batch.value, batch.stratum, sel, x)
        psum = lambda v: jax.lax.psum(v, axis_name)
        dev = jax.lax.axis_index(axis_name)
        # Each shard's estimated source population (Σ c_src) — the mean's
        # merge weight: MEAN over the union is the share-weighted mean.
        total_local = jnp.sum(y * res.meta.weight)
        total = jnp.maximum(psum(total_local), 1.0)
        share = total_local / total

        outs, bnds, new_state = [], [], []
        for i, sp in enumerate(self.specs):
            kq = jax.random.fold_in(jax.random.fold_in(key, _QUERY_KEY_TAG), i)
            kq_local = jax.random.fold_in(kq, dev)
            kq_merge = jax.random.fold_in(kq, _MERGE_KEY_TAG)
            st = state[i]
            if sp.kind == "sum":
                q = err.approx_sum_from_moments(y, s1, s2, res.meta)
                a = psum(q.estimate)[None]
                b, st2 = 2.0 * jnp.sqrt(psum(q.variance))[None], ()
            elif sp.kind == "count":
                # Exact by construction: C_i·W^in_i needs no sample, and
                # integer f32 sums are associative — N-device ≡ 1-device
                # to the bit (the harness' "exact queries" property).
                a = psum(jnp.sum(res.c * batch.meta.weight))[None]
                b, st2 = jnp.zeros((1,), jnp.float32), ()
            elif sp.kind == "mean":
                q = err.approx_mean_from_moments(y, s1, s2, res.meta)
                a = psum(q.estimate * share)[None]
                b = 2.0 * jnp.sqrt(psum(q.variance * share * share))[None]
                st2 = ()
            elif sp.kind == "histogram":
                from repro.core import queries as Q

                edges = jnp.linspace(sp.lo, sp.hi, sp.bins + 1)
                q = Q.weighted_histogram(batch, res, x, edges)
                a = psum(q.estimate)
                b, st2 = 2.0 * jnp.sqrt(psum(q.variance)), ()
            elif sp.kind == "quantile":
                st2 = sketches.quantile_update(kq_local, st, batch.value,
                                               w_item)
                g = jax.tree.map(lambda v: jax.lax.all_gather(v, axis_name),
                                 st2)
                merged = sketches.quantile_merge_stacked(kq_merge, g)
                a = sketches.quantile_query(merged, jnp.asarray(sp.qs))
                b = jnp.full((len(sp.qs),), 1.0) * merged.rank_error_bound
            elif sp.kind == "heavy_hitters":
                keys = sketches.hh_item_key(batch.value)
                st2 = sketches.hh_update(st, keys, w_item)
                # counts are linear: psum ≡ gather-then-sum, at 1/N the
                # gather bytes; only the k-slot candidate keys gather.
                g_counts = jax.lax.psum(st2.counts, axis_name)
                g_keys = jax.lax.all_gather(st2.key, axis_name, tiled=True)
                mk, me = sketches._refresh_topk(g_counts, g_keys, sp.k)
                eps_w = sketches.hh_error_bound(sp.width,
                                                jnp.sum(g_counts[0]))
                a = jnp.concatenate([mk.astype(jnp.float32), me])
                b = jnp.concatenate([jnp.zeros((sp.k,), jnp.float32),
                                     jnp.full((sp.k,), 1.0) * eps_w])
            elif sp.kind == "windowed_quantile":
                st2 = sketches.windowed_quantile_update(kq_local, st,
                                                        batch.value, w_item)
                # all-gather the per-device rings and flatten device×slot
                # into one stacked axis — one merge pass answers over the
                # union of every device's last `window` sub-sketches.
                gv = jax.lax.all_gather(st2.value, axis_name)
                gw = jax.lax.all_gather(st2.weight, axis_name)
                gc = jax.lax.all_gather(st2.compactions, axis_name)
                ge = jax.lax.all_gather(st2.err_q2, axis_name)
                stacked = sketches.QuantileSketch(
                    value=gv.reshape((-1,) + gv.shape[-2:]),
                    weight=gw.reshape((-1,) + gw.shape[-2:]),
                    compactions=gc.reshape(-1),
                    err_q2=ge.reshape(-1))
                km = jax.random.fold_in(kq_merge, _WINDOW_MERGE_TAG)
                merged = sketches.quantile_merge_stacked(km, stacked)
                a = sketches.quantile_query(merged, jnp.asarray(sp.qs))
                b = jnp.full((len(sp.qs),), 1.0) * merged.rank_error_bound
            elif sp.kind == "decayed_heavy_hitters":
                # decay is linear, so psum of per-device decayed tables
                # equals the decayed global table: γ(ΣA_i) + Σa_i.
                keys = sketches.hh_item_key(batch.value)
                st2 = sketches.hh_decayed_update(st, keys, w_item, sp.decay)
                g_counts = jax.lax.psum(st2.counts, axis_name)
                g_keys = jax.lax.all_gather(st2.key, axis_name, tiled=True)
                mk, me = sketches._refresh_topk(g_counts, g_keys, sp.k)
                eps_w = sketches.hh_error_bound(sp.width,
                                                jnp.sum(g_counts[0]))
                a = jnp.concatenate([mk.astype(jnp.float32), me])
                b = jnp.concatenate([jnp.zeros((sp.k,), jnp.float32),
                                     jnp.full((sp.k,), 1.0) * eps_w])
            else:  # pragma: no cover — registry validates kinds
                raise AssertionError(sp.kind)
            outs.append(a.astype(jnp.float32))
            bnds.append(b.astype(jnp.float32))
            new_state.append(st2)
        return tuple(new_state), jnp.concatenate(outs), jnp.concatenate(bnds)

    # ------------------------------------------------------ ground truth --
    def exact_answers(self, values: np.ndarray,
                      strata: np.ndarray | None = None) -> np.ndarray:
        """Host-side exact answers over the full stream, layout-aligned.

        Windowed CLT queries aggregate over the whole stream (their
        per-window estimates are summed/averaged the same way by the
        caller). Sketch slots need care:

        * ``quantile`` slots hold the exact ``inverted_cdf`` order
          statistics — the same "first value whose rank exceeds q·W"
          rule the sketch answers with. Compare in RANK space (measure
          the sketch value's rank on the stream, as fig8 does): value-
          space differences are density-sensitive and can be large in
          flat regions even at zero rank error.
        * ``heavy_hitters`` slots are NaN: the sketch reports *its own*
          candidate keys, so a slot-for-slot diff against the true
          top-k is meaningless — get per-key truth from the raw stream
          (``np.round(values)`` counts), keyed by the sketch's keys.
        """
        values = np.asarray(values, np.float64)
        out = np.zeros((self.n_out,), np.float64)
        for sp in self.specs:
            o, w, _ = self._layout[sp.name]
            if sp.kind == "sum":
                out[o] = values.sum()
            elif sp.kind == "count":
                out[o] = len(values)
            elif sp.kind == "mean":
                out[o] = values.mean() if len(values) else 0.0
            elif sp.kind == "histogram":
                edges = np.linspace(sp.lo, sp.hi, sp.bins + 1)
                ix = np.clip(np.searchsorted(edges, values, side="right") - 1,
                             0, sp.bins - 1)
                out[o:o + w] = np.bincount(ix, minlength=sp.bins)
            elif sp.kind == "quantile":
                out[o:o + w] = np.quantile(values, np.asarray(sp.qs),
                                           method="inverted_cdf")
            elif sp.kind in ("heavy_hitters", "decayed_heavy_hitters",
                             "windowed_quantile"):
                # sketch-relative answers: hh slots report the sketch's
                # own candidate keys, and the windowed/decayed variants
                # answer over the RECENT stream — a full-stream exact
                # value is the wrong ground truth for all three. Slice
                # the recent stream (or per-key counts) on the host when
                # truth is needed.
                out[o:o + w] = np.nan
        return out


class MultiTenantPlan:
    """K tenants' query registries fused into ONE batched root evaluation.

    Each tenant keeps its own ``CompiledQueryPlan`` (so its PRNG stream,
    sketch state, and answers are bit-identical to a single-tenant run of
    the same registry), but all plans evaluate inside the SAME traced root
    step from the SAME window sample — N tenants share one tree dispatch
    per epoch. The flat answer vector is the tenants' vectors concatenated
    in registration order; ``tenant_slice``/``answer`` route per-tenant
    views back out, and ``layout()`` exposes ``"tenant/query"``-prefixed
    names so shared consumers (error-budget feedback, dashboards) can
    attribute every slot to its tenant.

    Duck-types ``CompiledQueryPlan`` (``evaluate``/``init_state``/
    ``n_out``/``layout``/``answer``), so every engine — scan tick,
    level/loop root steps — accepts it unchanged.
    """

    def __init__(self, tenants, num_strata: int):
        """``tenants``: ordered ``(name, (QuerySpec, ...))`` pairs."""
        tenants = tuple((str(n), tuple(specs)) for n, specs in tenants)
        if not tenants:
            raise ValueError("cannot compile an empty tenant list")
        names = [n for n, _ in tenants]
        if len(set(names)) != len(names):
            dup = sorted({n for n in names if names.count(n) > 1})
            raise ValueError(f"duplicate tenant names: {dup}")
        self.tenant_names = tuple(names)
        self.num_strata = int(num_strata)
        self.plans = tuple(CompiledQueryPlan(specs, num_strata)
                           for _, specs in tenants)
        self._offsets = {}
        off = 0
        for name, plan in zip(self.tenant_names, self.plans):
            self._offsets[name] = off
            off += plan.n_out
        self.n_out = off

    @property
    def k(self) -> int:
        return sum(p.k for p in self.plans)

    def plan_for(self, tenant: str) -> CompiledQueryPlan:
        if tenant not in self._offsets:
            raise KeyError(f"unknown tenant {tenant!r}; "
                           f"registered: {list(self.tenant_names)}")
        return self.plans[self.tenant_names.index(tenant)]

    def tenant_slice(self, tenant: str) -> tuple[int, int]:
        """(offset, width) of one tenant's block in the flat vector."""
        return self._offsets[tenant], self.plan_for(tenant).n_out

    def layout(self) -> dict[str, tuple[int, int, str]]:
        """``"tenant/query"`` → (absolute offset, width, kind)."""
        out = {}
        for name, plan in zip(self.tenant_names, self.plans):
            base = self._offsets[name]
            for q, (o, w, kind) in plan.layout().items():
                out[f"{name}/{q}"] = (base + o, w, kind)
        return out

    def answer(self, vec: np.ndarray, name: str) -> np.ndarray:
        """Slice one ``"tenant/query"`` answer out of a flat vector."""
        o, w, _ = self.layout()[name]
        return np.asarray(vec)[..., o:o + w]

    def tenant_answers(self, vec: np.ndarray, tenant: str) -> np.ndarray:
        o, w = self.tenant_slice(tenant)
        return np.asarray(vec)[..., o:o + w]

    def init_state(self) -> tuple:
        return tuple(p.init_state() for p in self.plans)

    def evaluate(self, key: jax.Array, batch: IntervalBatch,
                 res: SampleResult, state: tuple) -> tuple:
        """One fused evaluation for all tenants. Every tenant plan gets
        the SAME key — exactly what a single-tenant run would pass — so
        each tenant's answers/bounds/sketch state bit-match an isolated
        run of its registry on the same sample."""
        states, outs, bnds = [], [], []
        for plan, st in zip(self.plans, state):
            st2, a, b = plan.evaluate(key, batch, res, st)
            states.append(st2)
            outs.append(a)
            bnds.append(b)
        return (tuple(states), jnp.concatenate(outs), jnp.concatenate(bnds))

    def evaluate_spmd(self, key: jax.Array, batch: IntervalBatch,
                      res: SampleResult, state: tuple,
                      axis_name: str) -> tuple:
        """Distributed fused evaluation for all tenants (one batched root
        over the merged summaries — see ``CompiledQueryPlan.
        evaluate_spmd``). Every tenant plan gets the SAME replicated key,
        mirroring the local ``evaluate``, so each tenant's merged answers
        match an isolated single-tenant SPMD run of its registry."""
        states, outs, bnds = [], [], []
        for plan, st in zip(self.plans, state):
            st2, a, b = plan.evaluate_spmd(key, batch, res, st, axis_name)
            states.append(st2)
            outs.append(a)
            bnds.append(b)
        return (tuple(states), jnp.concatenate(outs), jnp.concatenate(bnds))

    def exact_answers(self, values: np.ndarray,
                      strata: np.ndarray | None = None) -> np.ndarray:
        return np.concatenate([p.exact_answers(values, strata)
                               for p in self.plans])


def slot_bucket(n: int) -> int:
    """Smallest power-of-two slot count ≥ n. Buckets are what keep the
    compile count flat under churn: a group only retraces when its LIVE
    tenant count crosses a power of two, so an 8→10k admit sweep costs
    ⌈log2(10k/8)⌉+1 = 12 distinct traces, not 10k. No floor: small
    deployments pay zero padding (a 1-tenant group vmaps over 1 slot, so
    per-window compute and cross-device summary bytes match the unslotted
    plan exactly); padding waste is bounded at <2x live tenants at every
    scale."""
    n = max(int(n), 1)
    b = 1
    while b < n:
        b *= 2
    return b


def canonical_signature(specs) -> tuple[QuerySpec, ...]:
    """Name-free shape signature of a registry: the specs with names
    canonicalized to ``q0, q1, ...``. Two tenants share a signature iff
    their registries are identical up to query names — exactly the
    condition under which their root evaluations are the same traced
    program and can share one vmapped slot group."""
    return tuple(dataclasses.replace(sp, name=f"q{i}")
                 for i, sp in enumerate(specs))


class SlotPlanCore:
    """The TRACED half of the slotted tenant plan: per shape-signature
    group, one canonical template ``CompiledQueryPlan`` evaluated via
    ``jax.vmap`` over ``n_slots`` stacked sketch-state rows plus a
    per-slot active mask. Tenant NAMES never enter this object — routing
    lives on the cheap host-side ``SlottedTenantPlan`` wrapper — so one
    core (and one trace of everything closing over it) serves every
    pipeline whose live set maps onto the same (signature, bucket)s.

    Masking semantics (all verified bitwise): an active slot's answers
    and state updates are untouched by ``jnp.where(True, new, old)``;
    an inactive slot freezes at its current state and answers zeros.
    ``vmap`` row evaluation is bitwise row-position-independent, so a
    slot's answers don't depend on which slot it is, what the other
    slots hold, or the bucket size — the foundation of the
    churn ≡ fresh-compile equivalence law.

    The vmap is also the perf story: batch/res/key are unbatched, so
    the shared ``stratum_moments`` pass (and every other slot-
    independent intermediate) is computed ONCE per window; the per-slot
    marginal cost is just the answer assembly + sketch fold."""

    def __init__(self, groups, num_strata: int):
        """``groups``: ordered ``(canonical_specs, n_slots)`` pairs."""
        self.num_strata = int(num_strata)
        self.groups = tuple((CompiledQueryPlan(sig, num_strata), int(n))
                            for sig, n in groups)
        self._offsets = []
        off = 0
        for tmpl, n in self.groups:
            self._offsets.append(off)
            off += n * tmpl.n_out
        self.n_out = off

    def group_offset(self, gi: int) -> int:
        return self._offsets[gi]

    def init_state(self) -> tuple:
        """All slots inactive, all rows at the template's init state."""
        out = []
        for tmpl, n in self.groups:
            row = tmpl.init_state()
            stacked = jax.tree.map(
                lambda v: jnp.broadcast_to(v, (n,) + v.shape).copy(), row)
            out.append((jnp.zeros((n,), bool), stacked))
        return tuple(out)

    def _eval(self, key, batch, res, state, eval_one):
        states, outs, bnds = [], [], []
        for (tmpl, _n), (mask, st) in zip(self.groups, state):
            def row(m_, s_, tmpl=tmpl):
                s2, a, b = eval_one(tmpl, key, batch, res, s_)
                a = jnp.where(m_, a, 0.0)
                b = jnp.where(m_, b, 0.0)
                s2 = jax.tree.map(lambda nw, old: jnp.where(m_, nw, old),
                                  s2, s_)
                return s2, a, b
            s2, a, b = jax.vmap(row)(mask, st)
            states.append((mask, s2))
            outs.append(a.reshape(-1))
            bnds.append(b.reshape(-1))
        return tuple(states), jnp.concatenate(outs), jnp.concatenate(bnds)

    def evaluate(self, key: jax.Array, batch: IntervalBatch,
                 res: SampleResult, state: tuple) -> tuple:
        return self._eval(key, batch, res, state,
                          lambda p, k, b, r, s: p.evaluate(k, b, r, s))

    def evaluate_spmd(self, key: jax.Array, batch: IntervalBatch,
                      res: SampleResult, state: tuple,
                      axis_name: str) -> tuple:
        # collectives-under-vmap: psum/all_gather batch fine inside
        # shard_map, so the mesh path vmaps over slots identically.
        return self._eval(
            key, batch, res, state,
            lambda p, k, b, r, s: p.evaluate_spmd(k, b, r, s, axis_name))


# Canonical SlotPlanCore per (num_strata, ((signature, n_slots), ...)) —
# THE size-bucketed plan cache. Everything traced (tick fns, epoch fns,
# SPMD epoch fns) closes over the core object, so a cache hit here means
# jit cache hits everywhere downstream: admitting tenant #513 into an
# existing 1024-bucket reuses the 1024-bucket programs verbatim.
_CORE_CACHE: dict = {}
_CORE_STATS = {"builds": 0, "hits": 0}


def slot_plan_core(groups, num_strata: int) -> SlotPlanCore:
    key = (int(num_strata), tuple((tuple(sig), int(n)) for sig, n in groups))
    core = _CORE_CACHE.get(key)
    if core is None:
        core = SlotPlanCore(groups, num_strata)
        _CORE_CACHE[key] = core
        _CORE_STATS["builds"] += 1
    else:
        _CORE_STATS["hits"] += 1
    return core


def plan_cache_stats() -> dict:
    """{"builds": distinct traced plan shapes, "hits": cache reuses}."""
    return dict(_CORE_STATS)


class SlottedTenantPlan:
    """Host-side routing wrapper over a cached ``SlotPlanCore``: maps
    live tenant names to (group, slot) and answers layout/slicing
    queries. Construction is cheap (no tracing) and instances are
    IMMUTABLE — ``admit``/``retire`` return a new wrapper plus a pure
    qstate transform, never touching the shared core.

    Two answer-vector coordinate systems meet here. The TRACED programs
    produce the PADDED vector (``core.n_out`` — every slot, inactive
    ones zero); the PUBLIC vector is compacted to the live tenants'
    blocks in admission order (``n_out``, ``layout()``,
    ``tenant_slice`` — bit-for-bit the pre-slot ``MultiTenantPlan``
    layout, so every consumer reads it unchanged). ``compact(arr)``
    maps padded → public with one eager gather at the host boundary —
    OUTSIDE the jit, so churn moves the gather columns without
    retracing anything.

    Duck-types the plan protocol (``evaluate``/``evaluate_spmd``/
    ``init_state``/``layout``/``answer``/``tenant_slice``), so every
    engine and every ``MultiTenantPlan`` consumer accepts it unchanged.
    With a single live tenant, ``layout()`` uses plain query names
    (PR 4 behavior); with several, ``"tenant/query"``."""

    def __init__(self, core: SlotPlanCore, entries):
        """``entries``: ordered ``(name, specs, group_idx, slot_idx)``."""
        self.core = core
        self.entries = tuple(entries)
        self.num_strata = core.num_strata
        self.tenant_names = tuple(e[0] for e in self.entries)
        if len(set(self.tenant_names)) != len(self.tenant_names):
            ns = list(self.tenant_names)
            dup = sorted({n for n in ns if ns.count(n) > 1})
            raise ValueError(f"duplicate tenant names: {dup}")
        self._by_name = {e[0]: e for e in self.entries}
        self._plan_cache: dict = {}
        self._slices = {}
        off = 0
        for name, _, gi, _si in self.entries:
            w = core.groups[gi][0].n_out
            self._slices[name] = (off, w)
            off += w
        self.n_out = off            # PUBLIC (compacted) width
        self._cols = None           # lazy padded→public column map

    @property
    def k(self) -> int:
        return sum(len(e[1]) for e in self.entries)

    @property
    def plans(self) -> tuple:
        """Per-live-tenant template plans (host-side views)."""
        return tuple(self.plan_for(t) for t in self.tenant_names)

    def plan_for(self, tenant: str) -> CompiledQueryPlan:
        if tenant not in self._by_name:
            raise KeyError(f"unknown tenant {tenant!r}; "
                           f"registered: {list(self.tenant_names)}")
        if tenant not in self._plan_cache:
            self._plan_cache[tenant] = CompiledQueryPlan(
                self._by_name[tenant][1], self.num_strata)
        return self._plan_cache[tenant]

    def padded_slice(self, tenant: str) -> tuple[int, int]:
        """(offset, width) of one tenant's slot block in the PADDED
        (traced) answer vector."""
        _, _, gi, si = self._by_name[tenant]
        tmpl, _n = self.core.groups[gi]
        return self.core.group_offset(gi) + si * tmpl.n_out, tmpl.n_out

    def tenant_slice(self, tenant: str) -> tuple[int, int]:
        """(offset, width) of one tenant's block in the flat PUBLIC
        (compacted) answer vector — live blocks in admission order."""
        if tenant not in self._slices:
            raise KeyError(f"unknown tenant {tenant!r}; "
                           f"registered: {list(self.tenant_names)}")
        return self._slices[tenant]

    def live_columns(self) -> np.ndarray:
        """Padded-vector column index of every public-vector slot."""
        if self._cols is None:
            cols = []
            for name in self.tenant_names:
                o, w = self.padded_slice(name)
                cols.extend(range(o, o + w))
            self._cols = np.asarray(cols, np.int32)
        return self._cols

    def compact(self, arr):
        """Gather a padded answers/bounds array down to the public
        (live-tenant) vector along the last axis. Eager — never traced,
        so the column map follows churn with zero retraces."""
        if arr is None:
            return None
        return arr[..., self.live_columns()]

    def layout(self) -> dict[str, tuple[int, int, str]]:
        out = {}
        single = len(self.tenant_names) == 1
        for name in self.tenant_names:
            base, _ = self.tenant_slice(name)
            for q, (o, w, kind) in self.plan_for(name).layout().items():
                label = q if single else f"{name}/{q}"
                out[label] = (base + o, w, kind)
        return out

    def answer(self, vec: np.ndarray, name: str) -> np.ndarray:
        o, w, _ = self.layout()[name]
        return np.asarray(vec)[..., o:o + w]

    def tenant_answers(self, vec: np.ndarray, tenant: str) -> np.ndarray:
        o, w = self.tenant_slice(tenant)
        return np.asarray(vec)[..., o:o + w]

    def init_state(self) -> tuple:
        """Core init state with this wrapper's live slots activated."""
        state = list(self.core.init_state())
        for _, _, gi, si in self.entries:
            mask, st = state[gi]
            state[gi] = (mask.at[si].set(True), st)
        return tuple(state)

    def evaluate(self, key, batch, res, state):
        return self.core.evaluate(key, batch, res, state)

    def evaluate_spmd(self, key, batch, res, state, axis_name):
        return self.core.evaluate_spmd(key, batch, res, state, axis_name)

    def exact_answers(self, values, strata=None) -> np.ndarray:
        """Host-side exact answers in the PUBLIC (compacted) layout."""
        return np.concatenate([self.plan_for(t).exact_answers(values, strata)
                               for t in self.tenant_names])

    # ------------------------------------------------------- manifest --
    def slot_manifest(self) -> dict:
        """JSON-able description of the slot configuration — what the
        checkpoint manifest records so a restore into a differently-
        churned pipeline fails loudly instead of mis-routing answers."""
        groups = []
        for gi, (tmpl, n) in enumerate(self.core.groups):
            sig = [f"{sp.kind}:{sp.out_width}" for sp in tmpl.specs]
            slots = {name: si for name, _, g, si in self.entries if g == gi}
            groups.append({"signature": sig, "n_slots": int(n),
                           "slots": slots})
        return {"groups": groups}

    # ---------------------------------------------------- admit/retire --
    def admit(self, name: str, specs) -> tuple:
        """Returns ``(new_plan, transform)`` where ``transform(qstate,
        slot_axis)`` edits the state pytree: activates the new tenant's
        slot (resetting its row to init) and, when the signature's
        bucket is full, pads the group to the next bucket. Pure state
        edits — the only retrace is a bucket-cache MISS on growth."""
        name = str(name)
        specs = tuple(specs)
        if name in self._by_name:
            raise ValueError(f"tenant {name!r} already admitted")
        if not specs:
            raise ValueError(f"tenant {name!r} has an empty registry")
        sig = canonical_signature(specs)
        groups = [(tuple(t.specs), n) for t, n in self.core.groups]
        gi = next((i for i, (s, _) in enumerate(groups) if s == sig), None)
        if gi is None:
            # new signature: append a fresh minimum-bucket group
            gi, si = len(groups), 0
            groups.append((sig, slot_bucket(1)))
            core = slot_plan_core(groups, self.num_strata)
            tmpl, n = core.groups[gi]
            row = tmpl.init_state()

            def transform(qstate, slot_axis=0):
                lead = _lead_shape(qstate, slot_axis)
                mask = jnp.zeros(lead + (n,), bool).at[..., 0].set(True)
                st = jax.tree.map(
                    lambda v: jnp.broadcast_to(
                        v, lead + (n,) + v.shape).copy(), row)
                return tuple(qstate) + ((mask, st),)
        else:
            used = {e[3] for e in self.entries if e[2] == gi}
            n_now = groups[gi][1]
            free = [s for s in range(n_now) if s not in used]
            if free:
                si, core, grow = free[0], self.core, 0
            else:
                si, grow = n_now, n_now  # first slot of the padding
                groups[gi] = (sig, n_now * 2)
                core = slot_plan_core(groups, self.num_strata)
            tmpl, _n = core.groups[gi]
            row = tmpl.init_state()

            def transform(qstate, slot_axis=0, gi=gi, si=si, grow=grow):
                qstate = list(qstate)
                mask, st = qstate[gi]
                if grow:
                    pad = jax.tree.map(
                        lambda v: jnp.broadcast_to(
                            v, mask.shape[:slot_axis] + (grow,) + v.shape
                        ).copy(), row)
                    st = jax.tree.map(
                        lambda a, p: jnp.concatenate([a, p], axis=slot_axis),
                        st, pad)
                    mask = jnp.concatenate(
                        [mask, jnp.zeros(mask.shape[:slot_axis] + (grow,),
                                         bool)], axis=slot_axis)
                idx = (slice(None),) * slot_axis + (si,)
                mask = mask.at[idx].set(True)
                # reset the slot's row: it may hold a retired tenant's
                # frozen sketch, and admission must match fresh compile.
                st = jax.tree.map(lambda a, v: a.at[idx].set(v), st, row)
                qstate[gi] = (mask, st)
                return tuple(qstate)

        entries = self.entries + ((name, specs, gi, si),)
        return SlottedTenantPlan(core, entries), transform

    def retire(self, name: str) -> tuple:
        """Returns ``(new_plan, transform)``: flips the slot's mask bit
        off. The row's state freezes in place (never shrinks a bucket —
        shrinking would retrace; the slot is reused by a later admit)."""
        if name not in self._by_name:
            raise KeyError(f"unknown tenant {name!r}; "
                           f"registered: {list(self.tenant_names)}")
        if len(self.entries) == 1:
            raise ValueError(
                f"cannot retire {name!r}: it is the last live tenant")
        _, _, gi, si = self._by_name[name]
        entries = tuple(e for e in self.entries if e[0] != name)

        def transform(qstate, slot_axis=0):
            qstate = list(qstate)
            mask, st = qstate[gi]
            idx = (slice(None),) * slot_axis + (si,)
            qstate[gi] = (mask.at[idx].set(False), st)
            return tuple(qstate)

        return SlottedTenantPlan(self.core, entries), transform


def _lead_shape(qstate, slot_axis: int) -> tuple:
    """Leading (device) axes of the state layout, read off the first
    group's mask — ``()`` locally, ``(n_devices,)`` on the mesh."""
    if not qstate or slot_axis == 0:
        return ()
    return qstate[0][0].shape[:slot_axis]


def build_slotted_plan(tenants, num_strata: int) -> SlottedTenantPlan:
    """Group tenants by canonical shape signature, pad each group to its
    slot bucket, and wrap the cached core with name routing. Slots are
    assigned in admission order within each group, so a fresh compile of
    any live set is the canonical slot assignment churn must match."""
    tenants = tuple((str(n), tuple(specs)) for n, specs in tenants)
    if not tenants:
        raise ValueError("cannot compile an empty tenant list")
    sigs: list = []
    members: list = []
    for name, specs in tenants:
        sig = canonical_signature(specs)
        try:
            gi = sigs.index(sig)
        except ValueError:
            gi = len(sigs)
            sigs.append(sig)
            members.append([])
        members[gi].append(name)
    groups = tuple((sig, slot_bucket(len(m)))
                   for sig, m in zip(sigs, members))
    core = slot_plan_core(groups, num_strata)
    by_name = dict(tenants)
    entries = []
    slot_of = {name: (gi, si)
               for gi, m in enumerate(members) for si, name in enumerate(m)}
    for name, specs in tenants:
        gi, si = slot_of[name]
        entries.append((name, specs, gi, si))
    return SlottedTenantPlan(core, tuple(entries))


def tenant_rel_errors(plan, answers_row, bounds_row,
                      default_tenant: str = "default") -> dict[str, float]:
    """Per-tenant measured relative error of one window: the WORST
    relative ±2σ bound across each tenant's CLT queries (sum/mean) — the
    attribution signal the worst-tenant-first budget arbiter consumes.
    Sketch queries carry structural bounds and don't vote; a tenant with
    no CLT queries reports 0.0 (it never drives the shared budget). A
    plain single-registry ``CompiledQueryPlan`` attributes everything to
    ``default_tenant``. THE one implementation — the compiled-pipeline
    method and the analytics feedback loop both call this."""
    answers_row = np.asarray(answers_row)
    bounds_row = np.asarray(bounds_row)
    out = {t: 0.0 for t in
           (plan.tenant_names if hasattr(plan, "tenant_names")
            else (default_tenant,))}
    for tenant, off in tenant_clt_slots(plan, default_tenant):
        est = abs(float(answers_row[..., off]))
        rel = float(bounds_row[..., off]) / max(est, 1e-9)
        out[tenant] = max(out[tenant], rel)
    return out


def tenant_clt_slots(plan, default_tenant: str = "default"):
    """Yield ``(tenant, public_offset)`` for every CLT (sum/mean) query
    slot — THE tenant-attribution rule, shared by
    :func:`tenant_rel_errors` (one window's row) and
    ``repro.obs.telemetry.tenant_rel_bounds`` (the cumulative in-graph
    trajectory). Sketch slots carry structural bounds and are skipped."""
    multi = hasattr(plan, "tenant_names")
    names = plan.tenant_names if multi else (default_tenant,)
    for name, (off, _, kind) in plan.layout().items():
        if kind not in ("sum", "mean"):
            continue
        tenant = name.split("/", 1)[0] if (multi and "/" in name) \
            else names[0]
        yield tenant, off
