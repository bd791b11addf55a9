"""Hierarchy executors: the paper's logical tree, two ways.

``HostTree`` — a discrete-tick emulation of the edge topology (the Kafka
pipeline of §IV): per-node windows, asynchronous intervals, compacted
forwarding, query + error bounds at the root. Used by benchmarks/examples
to reproduce Figs. 6–12. Two execution engines share identical sampling
semantics (and identical randomness — per-node keys are derived by
folding (tick, level, node) into the tree's base key):

* ``engine="level"`` (default) — the level-vectorized engine. Each level's
  nodes live in one ``LevelState`` of stacked buffers; a tick issues
  exactly **one jitted dispatch per level**: WHS/SRS sampling vmapped (and
  selection flattened into a single composite-stratum sort / kernel pass,
  see ``whs.level_whsamp``), compaction row-wise, and child→parent routing
  done in-graph through static scatter indices, so the host only copies
  packed buffers. This is what keeps the host out of the hot loop at high
  fan-in, and — because a level is now a single array program — what makes
  sharding a level over a mesh axis a ``shard_map`` annotation rather than
  a rewrite.
* ``engine="loop"`` — the per-node reference engine (one jitted step per
  node per tick, the seed implementation). Kept as the bit-exact oracle
  for the vectorized engines and for dispatch-cost comparisons.
* ``engine="scan"`` — the fused whole-tree engine. The entire hierarchy
  (ingest → per-level sampling → in-graph child→parent routing →
  metadata fold → root query) is one traced tree-step, and ``T`` ticks
  are batched into a single ``lax.scan`` **epoch** dispatch with every
  reservoir/window buffer donated (``donate_argnums``), so state never
  leaves the device between ticks. Host cost per epoch: one ingest
  transfer down, one stacked result transfer up, one dispatch — the
  per-tick Python round-trip that bounds the ``level`` engine at high
  tick rates is gone. Level steps reuse the *same* core functions as the
  ``level`` engine (``_whs_level_core`` etc.) and the same
  ``(tick, level, node)`` key folding, so all three engines are
  bit-identical on identical ingest.

``sampler_backend`` selects the selection engine end-to-end — ``topk``
(``HostTree``'s default: dense partial-selection thresholds, bit-identical
to the reference and fastest on CPU), ``argsort`` (lexsort reference), or
``pallas`` (fused kernels); see ``core.sampling``. All three backends
trace inside the scan engine's ``lax.scan`` (the pallas kernels run in
interpret mode off-TPU).

``spmd_local_then_root`` — the in-graph two-level hierarchy used at pod
scale: every device samples its local sub-streams, compacts, all-gathers
the *reservoirs only* (this is the bandwidth saving), and the root stage
re-samples + answers the query. Pure ``shard_map``-compatible function; no
coordination beyond one all-gather of sampled data.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import error as err
from repro.core import sampling
from repro.core import whs
from repro.core.types import IntervalBatch, QueryResult, StratumMeta


# --------------------------------------------------------------------------
# Deterministic per-node keys: fold (tick, level, node) into the base key.
# Both engines use this chain, which is what makes them bit-comparable.
# --------------------------------------------------------------------------
def _node_key(key, t, lvl: int, ix):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.fold_in(key, t), lvl), ix
    )


def _level_keys(key, t, lvl: int, n_nodes: int):
    k = jax.random.fold_in(jax.random.fold_in(key, t), lvl)
    return jax.vmap(
        lambda i: jax.random.fold_in(k, i)
    )(jnp.arange(n_nodes, dtype=jnp.uint32))


def derive_capacities(fanin, capacity: int, max_sample_sizes,
                      interval_ticks) -> list[int]:
    """Per-level buffer capacities from the level-0 capacity and the
    per-level budget ceilings. Level ``l+1``'s buffer holds every child's
    budget times the exact arrival bound (ceil children-per-parent ×
    flushes-per-interval) — a parent buffer can never truncate. Shared by
    ``HostTree`` and the ``repro.api`` compiler, which is what keeps the
    two front doors bit-identical."""
    capacities: list[int] = []
    cap = int(capacity)
    for lvl, n_nodes in enumerate(fanin):
        capacities.append(cap)
        if lvl + 1 < len(fanin):
            children_per_parent = -(-n_nodes // fanin[lvl + 1])  # ceil
            flushes = -(-interval_ticks[lvl + 1] // interval_ticks[lvl])
            cap = max(int(max_sample_sizes[lvl]) * children_per_parent
                      * flushes, 64)
    return capacities


def _child_routing(n_nodes: int, n_parents: int) -> np.ndarray:
    """Static routing table: ``child_of[p, j]`` = index of parent ``p``'s
    ``j``-th child (ascending), padded with the sentinel ``n_nodes``.
    Children map to parents by ``ix % n_parents`` (the testbed wiring)."""
    cpp = -(-n_nodes // n_parents)  # ceil
    child_of = np.full((n_parents, cpp), n_nodes, np.int32)
    for j in range(n_nodes):
        child_of[j % n_parents, j // n_parents] = j
    return child_of


def _present_strata(strata_c, valid_c, num_strata: int):
    """bool[n, X]: strata each node actually forwards items for (drives the
    parent's metadata fold — a message with no items for a stratum must not
    contribute metadata, mirroring ``Window.deliver``)."""
    n = strata_c.shape[0]
    node_ix = jnp.arange(n, dtype=jnp.int32)[:, None]
    seg = jnp.where(valid_c, node_ix * num_strata + strata_c, n * num_strata)
    cnt = jnp.zeros((n * num_strata + 1,), jnp.int32).at[
        seg.reshape(-1)
    ].add(1)[: n * num_strata]
    return (cnt > 0).reshape(n, num_strata)


def _route_pack(values_c, strata_c, valid_c, child_of: np.ndarray):
    """In-graph child→parent routing + packing.

    Gathers each parent's children (static indices), then stably packs the
    valid items to the front of each parent row — children in child-index
    order, items in compacted order, i.e. exactly the order the per-node
    loop engine would deliver them in. Returns
    ``(packed_values[P, D], packed_strata[P, D], n_delivered[P])``.
    """
    n, oc = values_c.shape
    p = child_of.shape[0]
    d = child_of.shape[1] * oc
    vpad = jnp.concatenate([values_c, jnp.zeros((1, oc), values_c.dtype)])
    spad = jnp.concatenate([strata_c, jnp.zeros((1, oc), strata_c.dtype)])
    mpad = jnp.concatenate([valid_c, jnp.zeros((1, oc), bool)])
    gather = jnp.asarray(child_of)
    gv = vpad[gather].reshape(p, d)
    gs = spad[gather].reshape(p, d)
    gm = mpad[gather].reshape(p, d)
    packed_v, packed_s, n_deliv = whs.pack_rows(gv, gs, gm, d)
    return packed_v, packed_s, n_deliv


# --------------------------------------------------------------------------
# Pure core functions — the single source of truth for node/level/root math.
# The jitted `level`/`loop` step factories AND the scan engine's fused
# tree-step call these, which is what keeps every engine bit-identical.
# --------------------------------------------------------------------------
def _whs_root_core(key, t, lvl, values, strata, valid, w_in, c_in,
                   sample_size, *, num_strata, allocation, backend, budget,
                   hist_bins=64, plan=None, qstate=(), telemetry=False):
    """Root = sampling + the user query (§III-A lines 16-20). The query here
    is the paper's evaluation workload: windowed SUM and MEAN with error
    bounds, plus a value histogram (a representative GROUP-BY aggregate —
    the datacenter node runs the real analytics, not just the sampler).

    ``plan`` (a ``repro.query.compiler.CompiledQueryPlan``) extends the
    workload with the continuous query plane: every registered standing
    query is answered from the SAME window sample in the same traced
    program — the plan consumes no sampler randomness (its PRNG stream is
    a ``fold_in`` side-branch of the node key), so sample state is
    bit-identical with or without queries registered. Returns
    ``(outs, qstate')`` where ``outs`` gains ``(answers, bounds)``
    f32[plan.n_out] tails when a plan is present.
    """
    from repro.core import queries

    k = _node_key(key, t, lvl, 0)
    batch = IntervalBatch(values, strata, valid, StratumMeta(w_in, c_in))
    res = whs.whsamp(k, batch, sample_size, num_strata,
                     allocation=allocation, backend=backend,
                     max_reservoir=budget)
    s = err.approx_sum(batch.value, batch.stratum, res.selected, res.meta, num_strata)
    m = err.approx_mean(batch.value, batch.stratum, res.selected, res.meta, num_strata)
    lo = jnp.min(jnp.where(res.selected, batch.value, jnp.inf))
    hi = jnp.max(jnp.where(res.selected, batch.value, -jnp.inf))
    edges = jnp.linspace(lo, hi + 1e-6, hist_bins + 1)
    h = queries.weighted_histogram(batch, res, num_strata, edges)
    outs = (s.estimate, s.variance, m.estimate, m.variance,
            jnp.sum(res.selected.astype(jnp.int32)), h.estimate)
    if plan is None:
        if telemetry:
            outs = outs + (res.c.astype(jnp.float32),
                           res.y.astype(jnp.float32))
        return outs, ()
    qstate2, answers, bounds = plan.evaluate(k, batch, res, qstate)
    outs = outs + (answers, bounds)
    if telemetry:
        # per-stratum offered (c) and kept (y = min(c, reservoir)) counts —
        # the realized stratified sampling fraction comes straight from the
        # sampler's own bookkeeping, no recomputation.
        outs = outs + (res.c.astype(jnp.float32), res.y.astype(jnp.float32))
    return outs, qstate2


def _srs_root_core(key, t, lvl, values, strata, valid, w_in, c_in,
                   p_keep, f_total, *, num_strata, hist_bins=64):
    """Same query workload as the WHS root (fair throughput comparison):
    SUM/MEAN + histogram, with Horvitz–Thompson 1/f weights."""
    from repro.core import srs

    k = _node_key(key, t, lvl, 0)
    batch = IntervalBatch(values, strata, valid, StratumMeta(w_in, c_in))
    selected = srs.srs_select(k, batch, p_keep)
    s = srs.srs_sum(batch, selected, f_total)
    m = srs.srs_mean(batch, selected, f_total)
    lo = jnp.min(jnp.where(selected, batch.value, jnp.inf))
    hi = jnp.max(jnp.where(selected, batch.value, -jnp.inf))
    edges = jnp.linspace(lo, hi + 1e-6, hist_bins + 1)
    bin_ix = jnp.clip(jnp.searchsorted(edges, batch.value, side="right") - 1,
                      0, hist_bins - 1)
    hist = jnp.zeros((hist_bins,), jnp.float32).at[
        jnp.where(selected, bin_ix, hist_bins - 1)
    ].add(jnp.where(selected, 1.0 / f_total, 0.0))
    return (s.estimate, s.variance, m.estimate, m.variance,
            jnp.sum(selected.astype(jnp.int32)), hist)


def _whs_level_core(key, t, lvl, values, strata, valid, w_in, c_in,
                    sample_size, *, num_strata, out_capacity, child_of,
                    allocation, backend):
    """One WHS hierarchy level: sample, compact, route to parents.

    Runs through ``whs.level_tick`` — one fused Pallas kernel for the
    ``pallas_fused`` backend, the saturation passthrough for the rest —
    bit-identical to the unfused ``level_whsamp`` + ``level_compact``.
    """
    n_nodes = values.shape[0]
    keys = _level_keys(key, t, lvl, n_nodes)
    v_c, s_c, valid_c, meta, res = whs.level_tick(
        keys, values, strata, valid, w_in, c_in, sample_size, num_strata,
        out_capacity=out_capacity, allocation=allocation, backend=backend)
    present = _present_strata(s_c, valid_c, num_strata)
    packed_v, packed_s, n_deliv = _route_pack(v_c, s_c, valid_c, child_of)
    n_fwd = jnp.sum(valid_c, axis=1, dtype=jnp.int32)
    return (packed_v, packed_s, n_deliv,
            meta.weight, meta.count, present, n_fwd)


def _srs_level_core(key, t, lvl, values, strata, valid, w_in, c_in,
                    p_keep, *, num_strata, out_capacity, child_of):
    """One SRS hierarchy level: coin-flip keep, compact, route to parents."""
    from repro.core import srs

    n_nodes, capacity = values.shape
    out_cap = min(out_capacity, capacity)
    keys = _level_keys(key, t, lvl, n_nodes)
    selected = srs.level_srs_select(keys, valid, p_keep)
    v_c, s_c, n_sel = whs.pack_rows(values, strata, selected, out_cap)
    n_keep = jnp.minimum(n_sel, out_cap)
    valid_c = jnp.arange(out_cap)[None, :] < n_keep[:, None]
    present = _present_strata(s_c, valid_c, num_strata)
    packed_v, packed_s, n_deliv = _route_pack(v_c, s_c, valid_c, child_of)
    # SRS carries no sampler metadata: W/C sets pass through unchanged.
    return packed_v, packed_s, n_deliv, w_in, c_in, present, n_keep


# --------------------------------------------------------------------------
# Jitted per-node steps (loop engine — the bit-exact reference).
# The sticky W/C buffers are donated (argnums 6/7): their shapes/dtypes
# match the outgoing meta sets exactly, so XLA reuses the reservoir
# metadata buffers in place instead of copying them every tick.
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _node_step(capacity: int, num_strata: int, out_capacity: int,
               allocation: str, backend: str, lvl: int):
    @functools.partial(jax.jit, donate_argnums=(6, 7))
    def step(key, t, ix, values, strata, valid, w_in, c_in, sample_size):
        k = _node_key(key, t, lvl, ix)
        batch = IntervalBatch(values, strata, valid, StratumMeta(w_in, c_in))
        res = whs.whsamp(k, batch, sample_size, num_strata,
                         allocation=allocation, backend=backend,
                         max_reservoir=out_capacity)
        out = whs.compact_sample(batch, res, out_capacity)
        return (out.value, out.stratum, out.valid,
                out.meta.weight, out.meta.count, res.y)

    return step


@functools.lru_cache(maxsize=None)
def _root_step(capacity: int, num_strata: int, allocation: str, backend: str,
               lvl: int, budget: int, hist_bins: int = 64):
    @jax.jit
    def step(key, t, values, strata, valid, w_in, c_in, sample_size):
        outs, _ = _whs_root_core(key, t, lvl, values, strata, valid, w_in,
                                 c_in, sample_size, num_strata=num_strata,
                                 allocation=allocation, backend=backend,
                                 budget=budget, hist_bins=hist_bins)
        return outs

    return step


def _plan_root_step(plan, num_strata: int, allocation: str,
                    backend: str, lvl: int, budget: int):
    """Per-tree jitted root step for the ``level``/``loop`` engines when a
    query plan is registered: the host threads the sketch state through
    (donated — same shapes in and out, so XLA updates it in place)."""

    @functools.partial(jax.jit, donate_argnums=(7,))
    def step(key, t, values, strata, valid, w_in, c_in, qstate, sample_size):
        return _whs_root_core(key, t, lvl, values, strata, valid, w_in, c_in,
                              sample_size, num_strata=num_strata,
                              allocation=allocation, backend=backend,
                              budget=budget, plan=plan, qstate=qstate)

    return step


# --- SRS baseline (§IV-B): coin-flip keep at every node, HT estimate at root.
@functools.lru_cache(maxsize=None)
def _srs_node_step(capacity: int, num_strata: int, out_capacity: int, lvl: int):
    from repro.core import srs

    out_cap = min(out_capacity, capacity)

    @functools.partial(jax.jit, donate_argnums=(6, 7))
    def step(key, t, ix, values, strata, valid, w_in, c_in, p_keep):
        k = _node_key(key, t, lvl, ix)
        batch = IntervalBatch(values, strata, valid, StratumMeta(w_in, c_in))
        selected = srs.srs_select(k, batch, p_keep)
        # compact without weight bookkeeping (SRS carries no metadata)
        v_c, s_c, n_sel = whs.pack_rows(values[None, :], strata[None, :],
                                        selected[None, :], out_cap)
        slot_valid = jnp.arange(out_cap) < jnp.minimum(n_sel[0], out_cap)
        return v_c[0], s_c[0], slot_valid, w_in, c_in, n_sel[0]

    return step


@functools.lru_cache(maxsize=None)
def _srs_root_step(capacity: int, num_strata: int, lvl: int,
                   hist_bins: int = 64):
    @jax.jit
    def step(key, t, values, strata, valid, w_in, c_in, p_keep, f_total):
        return _srs_root_core(key, t, lvl, values, strata, valid, w_in, c_in,
                              p_keep, f_total, num_strata=num_strata,
                              hist_bins=hist_bins)

    return step


# --------------------------------------------------------------------------
# Jitted level steps (level-vectorized engine): one dispatch per level.
# Sticky W/C sets donated (argnums 5/6) — same shapes as the outgoing meta.
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _whs_level_step(n_nodes: int, capacity: int, num_strata: int,
                    out_capacity: int, n_parents: int, allocation: str,
                    backend: str, lvl: int):
    child_of = _child_routing(n_nodes, n_parents)

    @functools.partial(jax.jit, donate_argnums=(5, 6))
    def step(key, t, values, strata, valid, w_in, c_in, sample_size):
        return _whs_level_core(key, t, lvl, values, strata, valid, w_in, c_in,
                               sample_size, num_strata=num_strata,
                               out_capacity=out_capacity, child_of=child_of,
                               allocation=allocation, backend=backend)

    return step


@functools.lru_cache(maxsize=None)
def _srs_level_step(n_nodes: int, capacity: int, num_strata: int,
                    out_capacity: int, n_parents: int, lvl: int):
    child_of = _child_routing(n_nodes, n_parents)

    @functools.partial(jax.jit, donate_argnums=(5, 6))
    def step(key, t, values, strata, valid, w_in, c_in, p_keep):
        return _srs_level_core(key, t, lvl, values, strata, valid, w_in, c_in,
                               p_keep, num_strata=num_strata,
                               out_capacity=out_capacity, child_of=child_of)

    return step


# --------------------------------------------------------------------------
# Scan engine: the whole tree fused into one tree-step, T ticks per dispatch.
# --------------------------------------------------------------------------
def _append_rows(values, strata, fill, dropped, add_v, add_s, add_n,
                 empty: bool = False):
    """In-graph ``Window.deliver`` / ``LevelState.deliver_packed``: append
    each row's first ``add_n[r]`` incoming items at the row's fill offset,
    truncating at capacity (prefix rule — identical to the host buffers'
    backpressure behavior).

    ``empty=True`` is the static all-1-interval fast path: the receiving
    buffer is provably empty (it flushed last tick and receives exactly
    one message per tick), so the append is a plain prefix overwrite — no
    scatter. The message already arrives front-packed, so the buffer *is*
    the (zero-padded) message; slots past ``take`` are masked by ``fill``
    downstream either way."""
    n, cap = values.shape
    k = add_v.shape[1]
    add_n = add_n.astype(jnp.int32)
    if empty:
        take = jnp.minimum(add_n, cap)
        if k < cap:
            padv = jnp.zeros((n, cap - k), add_v.dtype)
            pads = jnp.zeros((n, cap - k), add_s.dtype)
            add_v = jnp.concatenate([add_v, padv], axis=1)
            add_s = jnp.concatenate([add_s, pads], axis=1)
        elif k > cap:
            add_v, add_s = add_v[:, :cap], add_s[:, :cap]
        return add_v, add_s, take, dropped + (add_n - take)
    take = jnp.minimum(add_n, cap - fill)
    j = jnp.arange(k, dtype=jnp.int32)[None, :]
    ok = j < take[:, None]
    pos = fill[:, None] + j
    row = jnp.arange(n, dtype=jnp.int32)[:, None]
    idx = jnp.where(ok, row * cap + pos, n * cap).reshape(-1)
    values = values.reshape(-1).at[idx].set(
        add_v.reshape(-1), mode="drop").reshape(n, cap)
    strata = strata.reshape(-1).at[idx].set(
        add_s.reshape(-1), mode="drop").reshape(n, cap)
    return values, strata, fill + take, dropped + (add_n - take)


def _fold_meta_graph(wc_acc, c_acc, seen, child_of: np.ndarray,
                     present, w_out, c_out):
    """In-graph ``LevelState.fold_meta``: fold each child's (W^out, C^out)
    message into its parent's interval accumulators, child slots in
    ascending order (a static unroll over the children-per-parent axis,
    so the f32 accumulation order bit-matches the host's sequential
    ``np.add.at``)."""
    n, x = w_out.shape
    pad = lambda a, dt: jnp.concatenate([a, jnp.zeros((1, x), dt)])
    wp = pad(w_out, w_out.dtype)
    cp = pad(c_out, c_out.dtype)
    prp = jnp.concatenate([present, jnp.zeros((1, x), bool)])
    gather = jnp.asarray(child_of)          # [P, cpp], sentinel row = n
    for k in range(child_of.shape[1]):
        ch = gather[:, k]
        pr = prp[ch]
        wc_acc = wc_acc + jnp.where(pr, wp[ch] * cp[ch], 0.0)
        c_acc = c_acc + jnp.where(pr, cp[ch], 0.0)
        seen = seen | pr
    return wc_acc, c_acc, seen


def _flush_meta(wc_acc, c_acc, seen, w_in, c_in):
    """In-graph ``flush`` metadata merge: fresh count-weighted-mean sets
    where metadata arrived this interval, sticky values elsewhere."""
    w_merged = wc_acc / jnp.maximum(c_acc, 1.0)
    w_eff = jnp.where(seen, w_merged, w_in)
    c_eff = jnp.where(seen, c_acc, c_in)
    return w_eff, c_eff


def _build_scan_tick(fanin, capacities, sample_sizes, interval_ticks,
                     num_strata, allocation, backend, mode, p_level,
                     fraction, trace_counter=None, plan=None,
                     telemetry=False):
    """Build the fused whole-tree tick: ``(state, key, t, budgets, ingest)
    → (state', per-tick outputs)``.

    Levels are chained in-graph exactly like ``_tick_level`` chains them on
    the host: level ``l`` flushes, samples, and its packed forwards are
    appended to level ``l+1``'s buffers *before* level ``l+1`` flushes, so
    one tick pushes data through the whole hierarchy. Levels whose interval
    has not elapsed are gated with ``where`` (their buffers keep
    accumulating); with all-1 intervals (the paper topology) the gates are
    static and the graph is branch-free.

    ``sample_sizes`` here are the *static maximum* per-level budgets —
    they size the forwarding buffers and partial selections. The budgets
    actually applied each tick arrive as the traced ``budgets`` f32
    [n_levels] argument, so the closed-loop ``BudgetController`` can move
    per-level sample sizes between epochs without a single retrace.

    ``plan`` is the compiled continuous-query plan (or ``None``): the
    root's standing queries evaluate inside this same traced tick, with
    their sketch state carried in ``state.qstate`` (donated with the
    rest of ``TreeState``).

    ``telemetry`` statically compiles the ``EpochTelemetry`` counter
    update in (or out). Every counter derives from quantities the tick
    already computes — flush occupancy, forwarded counts, the root
    sampler's per-stratum ``c``/``y`` — and telemetry consumes no PRNG,
    so sample state and window answers are bit-identical either way.
    """
    from repro.core.window import TreeState

    n_levels = len(fanin)
    child_tables = [_child_routing(fanin[l], fanin[l + 1])
                    for l in range(n_levels - 1)]

    def tick(state: "TreeState", key, t, budgets, ing_v, ing_s, ing_n):
        if trace_counter is not None:
            trace_counter["traces"] += 1
        lv = {f: list(getattr(state, f)) for f in TreeState.LEVEL_FIELDS}

        # Adaptive stratification: when a routing table rides in the state,
        # ingest stratum ids are *keys* gathered through the (traced,
        # host-editable) key→stratum table. The identity table is a
        # bitwise no-op; a split/merge between epochs is a same-shape edit
        # of the leaf (``repro.strata.StratumManager``) — zero retraces.
        if not isinstance(state.route, tuple):
            num_keys = state.route.shape[0]
            ing_s = state.route[jnp.clip(ing_s, 0, num_keys - 1)]

        # Source → level-0 delivery (one slice of the epoch's ingest batch).
        # With a 1-tick level-0 interval the buffer is empty here (it
        # flushed last tick), so the append is a scatter-free overwrite.
        # The named scopes (``ingest``, ``level{l}``) only tag the ops'
        # metadata, so a device trace can charge time to a tree level.
        with jax.named_scope("ingest"):
            (lv["values"][0], lv["strata"][0], lv["fill"][0],
             lv["dropped"][0]) = _append_rows(
                lv["values"][0], lv["strata"][0], lv["fill"][0],
                lv["dropped"][0], ing_v, ing_s, ing_n,
                empty=int(interval_ticks[0]) == 1)

        n_fwd_levels = []
        root_out = None
        tel_in, tel_kept = [], []
        root_strat = None
        for l in range(n_levels):
            iv = int(interval_ticks[l])
            is_root = l == n_levels - 1
            cap = capacities[l]
            fill = lv["fill"][l]
            if telemetry:
                # Items offered at this level's flush: the pre-flush
                # occupancy, zero on not-due ticks. Computed OUTSIDE the
                # cond from state the tick already holds.
                offered = jnp.sum(fill).astype(jnp.float32)
                tel_in.append(offered if iv == 1 else
                              jnp.where(t % iv == 0, offered, 0.0))

            def run_level(l=l, iv=iv, is_root=is_root, cap=cap, fill=fill):
                """Flush + sample + route + reset for a due level. Returns
                every state leaf the level touches plus its outputs, so a
                not-due tick can ``cond`` the whole body away."""
                valid = (jnp.arange(cap, dtype=jnp.int32)[None, :]
                         < fill[:, None])
                w_eff, c_eff = _flush_meta(lv["wc_acc"][l], lv["c_acc"][l],
                                           lv["seen"][l], lv["w_in"][l],
                                           lv["c_in"][l])
                values, strata = lv["values"][l], lv["strata"][l]
                # Interval reset (``flush``): clear occupancy +
                # accumulators, refresh stickies. Buffer contents are left
                # stale — every consumer masks by the valid range, exactly
                # as with zeroing.
                reset = (jnp.zeros_like(fill), jnp.zeros_like(lv["wc_acc"][l]),
                         jnp.zeros_like(lv["c_acc"][l]),
                         jnp.zeros_like(lv["seen"][l]), w_eff, c_eff)
                if is_root:
                    # Root: single node — squeeze node axis, run the query.
                    if mode == "srs":
                        outs = _srs_root_core(
                            key, t, l, values[0], strata[0], valid[0],
                            w_eff[0], c_eff[0], jnp.float32(p_level),
                            jnp.float32(fraction), num_strata=num_strata)
                        q_new = state.qstate
                    else:
                        outs, q_new = _whs_root_core(
                            key, t, l, values[0], strata[0], valid[0],
                            w_eff[0], c_eff[0], budgets[l],
                            num_strata=num_strata, allocation=allocation,
                            backend=backend, budget=int(sample_sizes[l]),
                            plan=plan, qstate=state.qstate,
                            telemetry=telemetry)
                    root_ok = jnp.sum(fill) > 0
                    return ((root_ok,) + outs, reset, q_new)
                if mode == "srs":
                    (packed_v, packed_s, n_deliv, w_out, c_out, present,
                     n_fwd) = _srs_level_core(
                        key, t, l, values, strata, valid, w_eff, c_eff,
                        jnp.float32(p_level), num_strata=num_strata,
                        out_capacity=int(sample_sizes[l]),
                        child_of=child_tables[l])
                else:
                    (packed_v, packed_s, n_deliv, w_out, c_out, present,
                     n_fwd) = _whs_level_core(
                        key, t, l, values, strata, valid, w_eff, c_eff,
                        budgets[l], num_strata=num_strata,
                        out_capacity=int(sample_sizes[l]),
                        child_of=child_tables[l],
                        allocation=allocation, backend=backend)
                # 1-tick intervals on both ends ⇒ exactly one message into
                # an empty parent buffer per tick ⇒ scatter-free overwrite.
                parent = _append_rows(
                    lv["values"][l + 1], lv["strata"][l + 1],
                    lv["fill"][l + 1], lv["dropped"][l + 1],
                    packed_v, packed_s, n_deliv,
                    empty=(iv == 1 and int(interval_ticks[l + 1]) == 1))
                parent_meta = _fold_meta_graph(
                    lv["wc_acc"][l + 1], lv["c_acc"][l + 1],
                    lv["seen"][l + 1], child_tables[l], present,
                    w_out, c_out)
                return (parent + parent_meta + (jnp.sum(n_fwd),)) + reset

            def skip_level(l=l, is_root=is_root, fill=fill):
                """Not-due tick: every touched leaf unchanged, null output."""
                keep = (fill, lv["wc_acc"][l], lv["c_acc"][l], lv["seen"][l],
                        lv["w_in"][l], lv["c_in"][l])
                if is_root:
                    f32 = lambda: jnp.zeros((), jnp.float32)
                    nul = (jnp.zeros((), bool), f32(), f32(), f32(), f32(),
                           jnp.zeros((), jnp.int32),
                           jnp.zeros((64,), jnp.float32))
                    if plan is not None:
                        nul = nul + (jnp.zeros((plan.n_out,), jnp.float32),
                                     jnp.zeros((plan.n_out,), jnp.float32))
                    if telemetry and mode != "srs":
                        nul = nul + (jnp.zeros((num_strata,), jnp.float32),
                                     jnp.zeros((num_strata,), jnp.float32))
                    return (nul, keep, state.qstate)
                nul = (lv["values"][l + 1], lv["strata"][l + 1],
                       lv["fill"][l + 1], lv["dropped"][l + 1],
                       lv["wc_acc"][l + 1], lv["c_acc"][l + 1],
                       lv["seen"][l + 1], jnp.zeros((), jnp.int32))
                return nul + keep

            with jax.named_scope(f"level{l}"):
                if iv == 1:
                    out = run_level()
                else:
                    # cond executes ONE branch at runtime: a level whose
                    # interval has not elapsed costs nothing — its buffers
                    # keep accumulating untouched.
                    out = jax.lax.cond(t % iv == 0, run_level, skip_level)

            if is_root:
                root_out, tail, q_out = out
                if telemetry and mode != "srs":
                    root_strat = root_out[-2:]
                    root_out = root_out[:-2]
                if telemetry:
                    tel_kept.append(root_out[5].astype(jnp.float32))
            else:
                (lv["values"][l + 1], lv["strata"][l + 1], lv["fill"][l + 1],
                 lv["dropped"][l + 1], lv["wc_acc"][l + 1],
                 lv["c_acc"][l + 1], lv["seen"][l + 1]) = out[:7]
                n_fwd_levels.append(out[7])
                if telemetry:
                    tel_kept.append(out[7].astype(jnp.float32))
                tail = out[8:]
            (lv["fill"][l], lv["wc_acc"][l], lv["c_acc"][l], lv["seen"][l],
             lv["w_in"][l], lv["c_in"][l]) = tail

        if telemetry:
            tel = state.telemetry
            d_in = jnp.stack(tel_in)
            d_kept = jnp.stack(tel_kept)
            flushed = d_in > 0
            root_ok = root_out[0]
            se, sv = root_out[1], root_out[2]
            new_tel = tel._replace(
                items_in=tel.items_in + d_in,
                items_kept=tel.items_kept + d_kept,
                flushes=tel.flushes + flushed.astype(jnp.int32),
                saturation_hits=tel.saturation_hits + (
                    flushed & (d_kept >= d_in)).astype(jnp.int32),
                windows=tel.windows + root_ok.astype(jnp.int32),
                root_sum=tel.root_sum + jnp.where(root_ok, se, 0.0),
                root_sum_var=tel.root_sum_var + jnp.where(root_ok, sv, 0.0),
            )
            if root_strat is not None:
                new_tel = new_tel._replace(
                    stratum_in=new_tel.stratum_in + root_strat[0],
                    stratum_kept=new_tel.stratum_kept + root_strat[1])
            if plan is not None:
                ans, bnd = root_out[7], root_out[8]
                rel = bnd / jnp.maximum(jnp.abs(ans), 1e-9)
                new_tel = new_tel._replace(
                    slot_rel_bound_sum=new_tel.slot_rel_bound_sum
                    + jnp.where(root_ok, rel, 0.0))
        else:
            new_tel = state.telemetry

        new_state = TreeState(
            **{f: tuple(lv[f]) for f in TreeState.LEVEL_FIELDS},
            qstate=q_out, telemetry=new_tel, route=state.route)
        out = root_out + (jnp.stack(n_fwd_levels),)
        return new_state, out

    return tick


def _build_epoch_fn(tick_fn, epoch_ticks: int):
    """One jitted dispatch per ``epoch_ticks``-tick epoch: ``lax.scan``
    over the fused tree-step, every ``TreeState`` buffer donated so the
    reservoir/window state is updated in place on device."""

    def epoch(state, key, t0, budgets, ing_v, ing_s, ing_n):
        ts = t0 + jnp.arange(epoch_ticks, dtype=jnp.int32)

        def body(st, xs):
            t, v, s, n = xs
            return tick_fn(st, key, t, budgets, v, s, n)

        return jax.lax.scan(body, state, (ts, ing_v, ing_s, ing_n))

    return jax.jit(epoch, donate_argnums=(0,))


def accumulate_epoch_accounting(tree, wall: float, counts, offered,
                                n_fwd) -> None:
    """Per-epoch accounting shared by ``HostTree.run_epoch`` and the
    compiled-pipeline driver (``launch.analytics._CompiledDriver``) —
    one implementation so the engines are compared under identical
    bookkeeping. A fused epoch cannot observe per-level time inside its
    single dispatch, so wall-time is attributed to levels proportionally
    to their buffer slots (``n_nodes × capacity`` — a static model of
    where the work is); ``offered`` is the pre-truncation ingest count
    (defaults to ``counts``); ``n_fwd`` is the stacked per-(tick, level)
    forwarded-item count."""
    import numpy as np

    tree.dispatch_count += 1
    slots = [n * c for n, c in zip(tree.fanin, tree.capacities)]
    total = float(sum(slots))
    for lvl, s in enumerate(slots):
        tree.level_time_s[lvl] += wall * s / total
    tree.items_ingested += int(
        np.asarray(counts if offered is None else offered).sum())
    for lvl in range(len(tree.fanin) - 1):
        tree.items_forwarded[lvl] += int(n_fwd[:, lvl].sum())


class HostTree:
    """Emulated edge topology (default geometry = the paper's testbed:
    8 sources → 4 edge nodes → 2 edge nodes → 1 root).

    ``mode="whs"`` runs the paper's weighted hierarchical sampler;
    ``mode="srs"`` runs the §IV-B coin-flip baseline (per-level keep
    probability ``p_level`` so the end-to-end fraction matches WHS's).

    ``engine`` selects the execution strategy (see module docstring):
    ``"level"`` issues one jitted dispatch per level per tick,
    ``"loop"`` one per node per tick, ``"scan"`` one per **epoch** of
    ``T`` ticks (drive it with ``run_epoch`` instead of
    ``ingest``/``tick``). ``dispatch_count`` tracks jitted step
    invocations so tests/benchmarks can verify the dispatch model.
    ``sampler_backend`` is threaded through to every WHSamp call.

    Donation caveat: the ``level``/``loop`` engines donate the sticky
    W/C metadata buffers into their steps, and the ``scan`` engine
    donates the *entire* ``TreeState``; callers must not hold references
    to state arrays across a tick/epoch (the tree itself never does —
    host flushes hand fresh copies to the steps).

    Per-level processing wall-time is accumulated in ``level_time_s``
    (drives the Fig. 9/10 latency model). The scan engine cannot observe
    per-level time inside its fused dispatch, so it attributes each
    epoch's device wall-time to levels proportionally to their buffer
    slots (``n_nodes × capacity``) — a static model of where the work
    is."""

    def __init__(
        self,
        fanin: list[int],                 # nodes per level, root last, e.g. [4, 2, 1]
        num_strata: int,
        capacity: int,
        sample_sizes: list[int],          # per level: interval budget
        interval_ticks: list[int] | None = None,
        allocation: str = "fair",
        seed: int = 0,
        mode: str = "whs",                # whs | srs
        fraction: float | None = None,    # srs: end-to-end sampling fraction
        engine: str = "level",            # level | loop
        # topk is bit-identical to the argsort reference (see core.sampling)
        # and ~1.7x faster on CPU — the tree defaults to it; the library
        # functions keep the argsort reference as their default.
        sampler_backend: str = "topk",
        # Continuous query plane: a QueryRegistry (or compiled plan) of
        # standing queries answered at the root every window, inside the
        # same dispatch(es). whs mode only (the plan needs WHS metadata).
        queries=None,
        # Static per-level budget ceilings for the closed-loop controller:
        # buffers/partial selections are provisioned for these, while
        # ``set_sample_sizes`` moves the applied budgets anywhere below
        # them between ticks/epochs with zero retraces. Defaults to
        # ``sample_sizes`` (fixed-budget operation).
        max_sample_sizes: list[int] | None = None,
        # Adaptive stratification (scan engine only): number of ingest
        # stratum *keys*. When set, ingest strata are routed through a
        # key→stratum table seeded to identity; ``set_route`` installs a
        # split/merge remap between epochs at zero retraces.
        route_keys: int | None = None,
    ):
        from repro.core.window import LevelState, TreeState, Window

        assert fanin[-1] == 1, "last level must be the single root"
        assert mode in ("whs", "srs")
        assert engine in ("level", "loop", "scan")
        assert route_keys is None or engine == "scan", \
            "adaptive stratum routing needs the scan engine"
        self.fanin = fanin
        self.num_strata = num_strata
        self.allocation = allocation
        self.sample_sizes = list(sample_sizes)
        self.max_sample_sizes = list(max_sample_sizes or sample_sizes)
        assert all(m >= s for m, s in zip(self.max_sample_sizes,
                                          self.sample_sizes)), \
            "max_sample_sizes must dominate the initial sample_sizes"
        self.mode = mode
        self.engine = engine
        self.sampler_backend = sampler_backend
        self.fraction = fraction
        if queries is not None and not hasattr(queries, "evaluate"):
            # Raw QueryRegistry: build the same slotted single-tenant
            # plan the API front door compiles, so legacy-constructed
            # trees stay bitwise interchangeable with spec-built ones
            # (same padded traced program, same compacted public rows).
            from repro.query.compiler import build_slotted_plan

            queries = build_slotted_plan((("default", queries.specs),),
                                         num_strata)
        self.plan = queries
        # Traced programs close over the name-free core when the plan is
        # slotted (tenant routing is host-side only).
        self._traced_plan = getattr(queries, "core", queries)
        assert self.plan is None or mode == "whs", \
            "the query plane needs WHS stratum metadata (mode='whs')"
        # SRS keeps items with the same probability at every level so the
        # compounded keep-rate equals the end-to-end ``fraction``.
        self.p_level = (float(fraction) ** (1.0 / len(fanin))
                        if fraction is not None else 1.0)
        interval_ticks = interval_ticks or [1] * len(fanin)
        # Exact arrival-bound buffer provisioning (see derive_capacities:
        # with globally-ticked intervals the bound is tight, so upper-level
        # buffers — and their sort/top-k passes — carry no 2x slack).
        self.capacities = derive_capacities(fanin, capacity,
                                            self.max_sample_sizes,
                                            interval_ticks)
        if engine == "loop":
            self.levels = [
                [Window(self.capacities[lvl], num_strata, interval_ticks[lvl])
                 for _ in range(n_nodes)]
                for lvl, n_nodes in enumerate(fanin)
            ]
        elif engine == "level":
            self.levels = [
                LevelState(n_nodes, self.capacities[lvl], num_strata,
                           interval_ticks[lvl])
                for lvl, n_nodes in enumerate(fanin)
            ]
        else:  # scan: whole-tree on-device state, one dispatch per epoch
            self.levels = None
            self._state = TreeState.create(
                fanin, self.capacities, num_strata,
                qstate=self.plan.init_state() if self.plan is not None
                else (),
                route=(jnp.arange(int(route_keys), dtype=jnp.int32)
                       if route_keys else ()))
            self._trace_counter = {"traces": 0}
            self._tick_fn = _build_scan_tick(
                fanin, self.capacities, self.max_sample_sizes, interval_ticks,
                num_strata, allocation, sampler_backend, mode, self.p_level,
                fraction, trace_counter=self._trace_counter,
                plan=self._traced_plan)
            self._epoch_fns: dict[int, object] = {}
        if engine != "scan" and self.plan is not None:
            # level/loop engines: host-threaded sketch state + a dedicated
            # root step closing over the plan.
            self._qstate = self.plan.init_state()
            self._plan_step = _plan_root_step(
                self.plan, num_strata, allocation, sampler_backend,
                len(fanin) - 1, int(self.max_sample_sizes[-1]))
        self._key = jax.random.PRNGKey(seed)
        self.items_forwarded = [0] * len(fanin)   # bandwidth accounting (Fig. 8)
        self.items_ingested = 0
        self.level_time_s = [0.0] * len(fanin)    # processing time (Fig. 9/10)
        self.dispatch_count = 0                   # jitted step invocations
        self.results: list[dict] = []

    @classmethod
    def from_spec(cls, spec, engine: str = "level") -> "HostTree":
        """Back-compat shim: build a ``HostTree`` from a declarative
        ``repro.api.PipelineSpec`` — the one front door. New code should
        use ``repro.api.compile(spec)`` (pure ``init``/``run_epoch``,
        explicit donated state); this constructor exists so the per-tick
        ``level``/``loop`` engines and legacy drivers consume the same
        job description. Resolution (sample sizes, ceilings, intervals,
        query plan) is shared with the API compiler, so the two paths
        are bit-identical."""
        from repro.api.spec import resolve

        r = resolve(spec)
        return cls(
            fanin=list(spec.topology.fanin),
            num_strata=spec.topology.num_strata,
            capacity=spec.topology.capacity,
            sample_sizes=list(r.sample_sizes),
            interval_ticks=list(r.interval_ticks),
            allocation=spec.sampler.allocation,
            seed=spec.seed,
            mode=spec.sampler.mode,
            fraction=spec.sampler.fraction,
            engine=engine,
            sampler_backend=spec.sampler.backend,
            queries=r.plan,
            max_sample_sizes=list(r.max_sample_sizes),
            route_keys=(spec.strata.num_keys or None)
            if engine == "scan" else None,
        )

    def ingest(self, node: int, values: np.ndarray, strata: np.ndarray) -> None:
        """Source → level-0 node delivery."""
        if self.engine == "scan":
            raise RuntimeError("engine='scan' ingests per epoch: use "
                               "run_epoch(t0, values, strata, counts)")
        self.items_ingested += len(values)
        if self.engine == "loop":
            self.levels[0][node].deliver(values, strata)
        else:
            self.levels[0].deliver(node, values, strata)

    def tick(self, t: int) -> None:
        """Advance one global tick: flush every due window, push upstream."""
        if self.engine == "scan":
            raise RuntimeError("engine='scan' advances per epoch: use "
                               "run_epoch(t0, values, strata, counts)")
        if self.engine == "loop":
            self._tick_loop(t)
        else:
            self._tick_level(t)

    # ------------------------------------------------------------- scan --
    def run_epoch(self, t0: int, values: np.ndarray, strata: np.ndarray,
                  counts: np.ndarray,
                  offered: np.ndarray | None = None) -> None:
        """Advance ``T`` ticks (``t0 .. t0+T-1``) in ONE jitted dispatch.

        ``values``/``strata`` are ``[T, fanin[0], width]`` tick-major
        padded ingest (see ``data.stream.batch_ingest``), ``counts`` the
        per-(tick, node) item counts. ``offered`` is the pre-truncation
        count for ``items_ingested`` accounting, so bandwidth fractions
        match the per-tick engines when a (tick, node) overflows the
        ingest width (defaults to ``counts``). The whole epoch's ingest
        moves host→device in one transfer; the tree state stays on
        device (donated) and only the stacked per-tick root results come
        back.
        """
        import time as _time

        assert self.engine == "scan", "run_epoch requires engine='scan'"
        epoch_ticks, n0, _ = values.shape
        assert n0 == self.fanin[0], "ingest rows must match level-0 nodes"
        fn = self._epoch_fns.get(epoch_ticks)
        if fn is None:
            fn = self._epoch_fns[epoch_ticks] = _build_epoch_fn(
                self._tick_fn, epoch_ticks)
        budgets = jnp.asarray([float(s) for s in self.sample_sizes],
                              jnp.float32)
        t_start = _time.perf_counter()
        self._state, outs = fn(
            self._state, self._key, jnp.int32(t0), budgets,
            jnp.asarray(values, jnp.float32), jnp.asarray(strata, jnp.int32),
            jnp.asarray(counts, jnp.int32))
        if self.plan is not None:
            (root_ok, se, sv, me, mv, nsel, hist, ans, bnd, n_fwd) = (
                np.asarray(o) for o in outs)      # one device→host sync
            if hasattr(self.plan, "compact"):
                ans, bnd = self.plan.compact(ans), self.plan.compact(bnd)
        else:
            (root_ok, se, sv, me, mv, nsel, hist, n_fwd) = (
                np.asarray(o) for o in outs)
            ans = bnd = None
        wall = _time.perf_counter() - t_start
        accumulate_epoch_accounting(self, wall, counts, offered, n_fwd)
        for i in range(epoch_ticks):
            if root_ok[i]:
                row = dict(
                    tick=t0 + i, sum=float(se[i]), sum_var=float(sv[i]),
                    mean=float(me[i]), mean_var=float(mv[i]),
                    n_sampled=int(nsel[i]), histogram=hist[i],
                )
                if ans is not None:
                    row["answers"], row["bounds"] = ans[i], bnd[i]
                self.results.append(row)

    def reset_query_state(self) -> None:
        """Reset the standing queries' sketch state to empty (drivers call
        this after warmup so continuous answers cover only measured
        ticks; windowed CLT answers are stateless and unaffected)."""
        if self.plan is None:
            return
        if self.engine == "scan":
            self._state = self._state._replace(qstate=self.plan.init_state())
        else:
            self._qstate = self.plan.init_state()

    def set_route(self, route) -> None:
        """Install a new key→stratum routing table (adaptive
        stratification). A same-shape leaf edit on the donated state —
        the next epoch runs the remapped strata with zero retraces."""
        assert self.engine == "scan", "routing lives in the scan state"
        assert not isinstance(self._state.route, tuple), \
            "tree was built without route_keys"
        r = jnp.asarray(route, jnp.int32)
        assert r.shape == self._state.route.shape, "route shape is static"
        self._state = self._state._replace(route=r)

    def set_sample_sizes(self, sizes) -> None:
        """Move the applied per-level sample budgets (closed-loop knob).

        Budgets are traced values in every engine, so this never
        recompiles; they are clamped to the provisioned
        ``max_sample_sizes`` ceilings (buffers upstream were sized for
        those — exceeding them would truncate forwards)."""
        assert len(sizes) == len(self.fanin)
        self.sample_sizes = [
            min(max(float(s), 1.0), float(m))
            for s, m in zip(sizes, self.max_sample_sizes)
        ]

    def _root_result(self, t: int, outs) -> dict:
        """Host-side result row from a root step's outputs (plan-aware)."""
        se, sv, me, mv, nsel, hist = outs[:6]
        row = dict(tick=t, sum=float(se), sum_var=float(sv),
                   mean=float(me), mean_var=float(mv), n_sampled=int(nsel),
                   histogram=np.asarray(hist))
        if len(outs) > 6:
            ans, bnd = np.asarray(outs[6]), np.asarray(outs[7])
            if self.plan is not None and hasattr(self.plan, "compact"):
                ans, bnd = self.plan.compact(ans), self.plan.compact(bnd)
            row["answers"], row["bounds"] = ans, bnd
        return row

    # ------------------------------------------------------------- loop --
    def _tick_loop(self, t: int) -> None:
        import time as _time

        for lvl, nodes in enumerate(self.levels):
            is_root = lvl == len(self.levels) - 1
            n_parents = self.fanin[lvl + 1] if not is_root else 1
            for ix, win in enumerate(nodes):
                if not win.due(t) or win.fill == 0:
                    continue
                values, strata, valid, w_in, c_in = win.flush()
                t0 = _time.perf_counter()
                if is_root:
                    if self.mode == "srs":
                        step = _srs_root_step(win.capacity, self.num_strata, lvl)
                        outs = step(
                            self._key, t, values, strata, valid, w_in, c_in,
                            jnp.float32(self.p_level), jnp.float32(self.fraction))
                    elif self.plan is not None:
                        outs, self._qstate = self._plan_step(
                            self._key, t, values, strata, valid, w_in, c_in,
                            self._qstate, jnp.float32(self.sample_sizes[lvl]))
                    else:
                        step = _root_step(win.capacity, self.num_strata,
                                          self.allocation, self.sampler_backend,
                                          lvl, int(self.max_sample_sizes[lvl]))
                        outs = step(
                            self._key, t, values, strata, valid, w_in, c_in,
                            jnp.float32(self.sample_sizes[lvl]))
                    self.dispatch_count += 1
                    row = self._root_result(t, outs)  # np.asarray syncs
                    self.level_time_s[lvl] += _time.perf_counter() - t0
                    self.results.append(row)
                else:
                    out_cap = self.max_sample_sizes[lvl]
                    if self.mode == "srs":
                        step = _srs_node_step(win.capacity, self.num_strata,
                                              out_cap, lvl)
                        ov, os_, oval, w_out, c_out, _ = step(
                            self._key, t, ix, values, strata, valid, w_in, c_in,
                            jnp.float32(self.p_level))
                    else:
                        step = _node_step(win.capacity, self.num_strata, out_cap,
                                          self.allocation, self.sampler_backend,
                                          lvl)
                        ov, os_, oval, w_out, c_out, _ = step(
                            self._key, t, ix, values, strata, valid, w_in, c_in,
                            jnp.float32(self.sample_sizes[lvl]))
                    self.dispatch_count += 1
                    ov, os_, oval = np.asarray(ov), np.asarray(os_), np.asarray(oval)
                    self.level_time_s[lvl] += _time.perf_counter() - t0
                    n = int(oval.sum())
                    self.items_forwarded[lvl] += n
                    parent = self.levels[lvl + 1][ix % n_parents]
                    parent.deliver(ov[:n], os_[:n], np.asarray(w_out), np.asarray(c_out))

    # ------------------------------------------------------------ level --
    def _tick_level(self, t: int) -> None:
        import time as _time

        for lvl, state in enumerate(self.levels):
            is_root = lvl == len(self.levels) - 1
            if not state.due(t) or int(state.fill.sum()) == 0:
                continue
            values, strata, valid, w_in, c_in = state.flush_all()
            t0 = _time.perf_counter()
            if is_root:
                # The root is always a single node: squeeze the node axis and
                # run the (shared) scalar root step — still one dispatch.
                if self.mode == "srs":
                    step = _srs_root_step(state.capacity, self.num_strata, lvl)
                    outs = step(
                        self._key, t, values[0], strata[0], valid[0],
                        w_in[0], c_in[0],
                        jnp.float32(self.p_level), jnp.float32(self.fraction))
                elif self.plan is not None:
                    outs, self._qstate = self._plan_step(
                        self._key, t, values[0], strata[0], valid[0],
                        w_in[0], c_in[0], self._qstate,
                        jnp.float32(self.sample_sizes[lvl]))
                else:
                    step = _root_step(state.capacity, self.num_strata,
                                      self.allocation, self.sampler_backend,
                                      lvl, int(self.max_sample_sizes[lvl]))
                    outs = step(
                        self._key, t, values[0], strata[0], valid[0],
                        w_in[0], c_in[0],
                        jnp.float32(self.sample_sizes[lvl]))
                self.dispatch_count += 1
                row = self._root_result(t, outs)  # np.asarray syncs
                self.level_time_s[lvl] += _time.perf_counter() - t0
                self.results.append(row)
            else:
                n_parents = self.fanin[lvl + 1]
                out_cap = self.max_sample_sizes[lvl]
                if self.mode == "srs":
                    step = _srs_level_step(state.n_nodes, state.capacity,
                                           self.num_strata, out_cap,
                                           n_parents, lvl)
                    outs = step(self._key, t, values, strata, valid, w_in, c_in,
                                jnp.float32(self.p_level))
                else:
                    step = _whs_level_step(state.n_nodes, state.capacity,
                                           self.num_strata, out_cap, n_parents,
                                           self.allocation,
                                           self.sampler_backend, lvl)
                    outs = step(self._key, t, values, strata, valid, w_in, c_in,
                                jnp.float32(self.sample_sizes[lvl]))
                self.dispatch_count += 1
                (packed_v, packed_s, n_deliv,
                 w_out, c_out, present, n_fwd) = (np.asarray(o) for o in outs)
                self.level_time_s[lvl] += _time.perf_counter() - t0
                self.items_forwarded[lvl] += int(n_fwd.sum())
                parent = self.levels[lvl + 1]
                parent.deliver_packed(packed_v, packed_s, n_deliv)
                parent_ix = np.arange(state.n_nodes) % n_parents
                parent.fold_meta(parent_ix, present, w_out, c_out)


# --------------------------------------------------------------------------
# In-graph SPMD hierarchy (pod-scale data plane).
# --------------------------------------------------------------------------
def spmd_local_then_root(
    key: jax.Array,
    batch: IntervalBatch,
    *,
    axis_name: str,
    num_strata: int,
    local_budget: int,
    root_budget: int,
    allocation: str = "fair",
    sampler_backend: str = sampling.DEFAULT_BACKEND,
) -> tuple[QueryResult, QueryResult]:
    """Two-level hierarchical sampling across a mesh axis.

    Level 1 (edge): each device samples its local interval batch and
    compacts to ``local_budget`` slots. Level 2 (root): the compacted
    reservoirs — not the raw stream — are all-gathered and re-sampled,
    then SUM/MEAN + error bounds are computed. Returns (sum, mean).

    Call under ``shard_map`` with ``axis_name`` bound, e.g. the "data"
    axis; every device computes the root stage redundantly (no single
    point of failure, no coordination — §III-E). ``sampler_backend``
    selects the selection engine at both stages; off TPU, with the
    Pallas backends, the enclosing ``shard_map`` passes
    ``check_vma=False`` (their kernels then run in the Pallas
    interpreter, which in JAX 0.9.0 mis-types its block slices under the
    vma check; see ``api.spmd._check_vma``).
    """
    # Local stage: per-device key. Root stage: the SAME key on every device
    # so the redundantly-computed root result is bit-identical (replicated).
    k_local = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
    k_root = jax.random.fold_in(key, 0x5F3759DF)
    res = whs.whsamp(k_local, batch, jnp.float32(local_budget), num_strata,
                     allocation=allocation, backend=sampler_backend,
                     max_reservoir=local_budget)
    compact = whs.compact_sample(batch, res, local_budget)

    g_val = jax.lax.all_gather(compact.value, axis_name, tiled=True)
    g_str = jax.lax.all_gather(compact.stratum, axis_name, tiled=True)
    g_vld = jax.lax.all_gather(compact.valid, axis_name, tiled=True)
    # Workers sample disjoint shards of each sub-stream (§III-E): the union
    # of their per-stratum reservoirs carries per-worker weights. Merging
    # parallel workers uses the count-weighted mean (the pool represents
    # Σ w_k·C_k originals over Σ C_k forwarded items) — see core/window.py
    # for why Eq. 5's max rule is path-only and biases parallel merges.
    g_c = jax.lax.psum(compact.meta.count, axis_name)
    g_w = (jax.lax.psum(compact.meta.weight * compact.meta.count, axis_name)
           / jnp.maximum(g_c, 1.0))
    # Strata empty across all workers: weight is irrelevant (no items) —
    # use 1 so the result stays replicated across the axis.
    g_w = jnp.where(g_c > 0.0, g_w, 1.0)

    root_batch = IntervalBatch(g_val, g_str, g_vld, StratumMeta(g_w, g_c))
    res_root = whs.whsamp(k_root, root_batch, jnp.float32(root_budget), num_strata,
                          allocation=allocation, backend=sampler_backend,
                          max_reservoir=root_budget)
    s = err.approx_sum(root_batch.value, root_batch.stratum, res_root.selected,
                       res_root.meta, num_strata)
    m = err.approx_mean(root_batch.value, root_batch.stratum, res_root.selected,
                        res_root.meta, num_strata)
    # The root stage is computed redundantly from all-gathered (identical)
    # data + an axis-invariant key, so results are replicated in value; a
    # scalar pmean re-types them as invariant for shard_map's vma check
    # (all_gather outputs stay `varying` under JAX's vma typing).
    rep = lambda t: jax.tree.map(lambda x: jax.lax.pmean(x, axis_name), t)
    return rep(s), rep(m)


def spmd_local_then_root_epoch(
    key: jax.Array,
    batches: IntervalBatch,
    *,
    axis_name: str,
    num_strata: int,
    local_budget: int,
    root_budget: int,
    allocation: str = "fair",
    sampler_backend: str = sampling.DEFAULT_BACKEND,
) -> tuple[QueryResult, QueryResult]:
    """Epoch-batched ``spmd_local_then_root``: ``T`` interval batches in
    one ``lax.scan``, one dispatch per epoch instead of one per interval.

    ``batches`` is an ``IntervalBatch`` whose array leaves carry a leading
    tick axis (``value[T, M]``, per-tick ``meta`` sets ``[T, X]``). Each
    tick ``i`` folds ``i`` into the epoch key, so results match ``T``
    separate calls with ``fold_in(key, i)`` keys bit-for-bit. Returns
    (sum, mean) ``QueryResult``s with ``[T]``-stacked leaves. Call under
    ``shard_map`` exactly like the per-interval function.
    """
    def body(i, batch):
        s, m = spmd_local_then_root(
            jax.random.fold_in(key, i), batch, axis_name=axis_name,
            num_strata=num_strata, local_budget=local_budget,
            root_budget=root_budget, allocation=allocation,
            sampler_backend=sampler_backend)
        return (s, m)

    t = batches.value.shape[0]
    _, outs = jax.lax.scan(
        lambda c, xs: (c, body(xs[0], xs[1])),
        0, (jnp.arange(t, dtype=jnp.int32), batches))
    return outs


def spmd_query_plane_tick(
    key: jax.Array,
    batch: IntervalBatch,
    qstate: tuple,
    plan,
    *,
    axis_name: str,
    budget: jnp.ndarray,
    max_budget: int,
    num_strata: int,
    allocation: str = "fair",
    sampler_backend: str = sampling.DEFAULT_BACKEND,
    hist_bins: int = 64,
):
    """One window of the distributed multi-tenant query plane (§III-E +
    the PR-3 query plane, merged by summaries).

    Every device WHS-samples its local shard of the window (``budget``
    is the TRACED applied sample budget, ``max_budget`` the static
    ceiling sizing the partial selections), then the window is answered
    from MERGED per-device summaries: the built-in workload (SUM/MEAN ±
    variance, sample count, histogram) merges via ``psum`` of per-shard
    moments, and the standing-query plan evaluates through
    ``CompiledQueryPlan.evaluate_spmd`` — local sketch updates,
    all-gathered O(sketch) summaries, one batched root evaluation per
    window. NO raw reservoir items cross the device boundary (contrast
    ``spmd_local_then_root``, which gathers the compacted reservoirs);
    cross-device traffic per window is the sketch buffers plus a
    handful of per-stratum scalars.

    ``key`` must be replicated across the axis. Returns
    ``(qstate', outs)`` with ``qstate'`` device-local and every leaf of
    ``outs`` replicated (re-typed axis-invariant via ``pmean``):
    ``(ok, sum, sum_var, mean, mean_var, n_sampled, histogram,
    answers, bounds)``.
    """
    k_local = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
    res = whs.whsamp(k_local, batch, budget, num_strata,
                     allocation=allocation, backend=sampler_backend,
                     max_reservoir=max_budget)
    sel = res.selected
    psum = lambda v: jax.lax.psum(v, axis_name)

    y, s1, s2 = err.stratum_moments(batch.value, batch.stratum, sel,
                                    num_strata)
    s_loc = err.approx_sum_from_moments(y, s1, s2, res.meta)
    m_loc = err.approx_mean_from_moments(y, s1, s2, res.meta)
    # Mean merges share-weighted: each shard's mean estimates ITS
    # sub-population's mean, so the union mean re-weights by the shard's
    # estimated population Σ c_src (same rule as evaluate_spmd's "mean").
    total_local = jnp.sum(y * res.meta.weight)
    share = total_local / jnp.maximum(psum(total_local), 1.0)
    se, sv = psum(s_loc.estimate), psum(s_loc.variance)
    me = psum(m_loc.estimate * share)
    mv = psum(m_loc.variance * share * share)
    n_sel = psum(jnp.sum(sel.astype(jnp.int32)))
    ok = psum(jnp.sum(batch.valid.astype(jnp.int32))) > 0

    # Built-in histogram: replicated data-dependent edges (pmin/pmax of
    # the per-shard sampled range — two scalars), then a psum of the
    # per-bin HT estimates (linear queries merge exactly).
    from repro.core import queries

    lo = jax.lax.pmin(jnp.min(jnp.where(sel, batch.value, jnp.inf)),
                      axis_name)
    hi = jax.lax.pmax(jnp.max(jnp.where(sel, batch.value, -jnp.inf)),
                      axis_name)
    edges = jnp.linspace(lo, hi + 1e-6, hist_bins + 1)
    hist = psum(queries.weighted_histogram(batch, res, num_strata,
                                           edges).estimate)

    if plan is None:
        qstate2, tail = qstate, ()
    else:
        qstate2, answers, bounds = plan.evaluate_spmd(
            key, batch, res, qstate, axis_name)
        # psum/pmin outputs are already axis-invariant, but the sketch-
        # derived answer slots descend from all_gathers, which stay
        # vma-typed `varying`; one pmean over the (replicated-in-value)
        # answer vectors re-types them for the shard_map out check.
        # Exact for power-of-two meshes (N·x/N); the psum-merged slots
        # are untouched collectives-wise — the vectors are [n_out] f32.
        rep = lambda v: jax.lax.pmean(v, axis_name)
        tail = (rep(answers), rep(bounds))
    return qstate2, (ok, se, sv, me, mv, n_sel, hist) + tail


def spmd_query_plane_epoch(
    key: jax.Array,
    t0: jnp.ndarray,
    budget: jnp.ndarray,
    batches: IntervalBatch,
    qstate: tuple,
    plan,
    *,
    axis_name: str,
    max_budget: int,
    num_strata: int,
    allocation: str = "fair",
    sampler_backend: str = sampling.DEFAULT_BACKEND,
    hist_bins: int = 64,
):
    """Epoch-batched ``spmd_query_plane_tick``: ``T`` windows in one
    ``lax.scan`` with the sketch state as the carry — one dispatch per
    epoch, per-device sketch state never leaving the device (only its
    per-window summaries do). Window ``i`` folds the GLOBAL tick
    ``t0 + i`` into the epoch key, so multi-epoch runs resume
    bit-identically to one long epoch (asserted in
    ``tests/test_spmd_query_plane.py``). ``budget`` is the traced
    applied level-0 budget — the closed-loop controller moves it between
    epochs with zero retraces."""
    t = batches.value.shape[0]

    def body(carry, xs):
        i, batch = xs
        return spmd_query_plane_tick(
            jax.random.fold_in(key, i), batch, carry, plan,
            axis_name=axis_name, budget=budget, max_budget=max_budget,
            num_strata=num_strata, allocation=allocation,
            sampler_backend=sampler_backend, hist_bins=hist_bins)

    ts = t0 + jnp.arange(t, dtype=jnp.int32)
    qfinal, outs = jax.lax.scan(body, qstate, (ts, batches))
    return qfinal, outs


def spmd_srs_epoch(
    key: jax.Array,
    batches: IntervalBatch,
    *,
    axis_name: str,
    fraction: float,
):
    """§IV-B coin-flip baseline on the mesh: each device keeps its shard's
    items with probability ``fraction`` (one flat stage — the SPMD path
    has no intermediate hops to compound through) and the HT SUM / sample
    MEAN merge from ``psum``-ed sample moments — like the WHS query
    plane, no item ever crosses the device boundary. Returns
    (sum, mean) ``QueryResult``s with ``[T]``-stacked leaves, same
    contract as ``spmd_local_then_root_epoch``."""
    from repro.core import srs

    p = jnp.float32(fraction)

    def tick(i, batch):
        k_local = jax.random.fold_in(jax.random.fold_in(key, i),
                                     jax.lax.axis_index(axis_name))
        sel = srs.srs_select(k_local, batch, p)
        x = jnp.where(sel, batch.value, 0.0)
        psum = lambda v: jax.lax.psum(v, axis_name)
        n = psum(jnp.sum(sel.astype(jnp.float32)))
        g1 = psum(jnp.sum(x))
        g2 = psum(jnp.sum(x * x))
        s = QueryResult(estimate=g1 / p, variance=g2 * (1.0 - p) / (p * p))
        mean = g1 / jnp.maximum(n, 1.0)
        s_sq = jnp.maximum(g2 - n * mean * mean, 0.0) / jnp.maximum(n - 1.0,
                                                                    1.0)
        m = QueryResult(estimate=mean, variance=s_sq / jnp.maximum(n, 1.0))
        return s, m

    t = batches.value.shape[0]
    _, outs = jax.lax.scan(
        lambda c, xs: (c, tick(xs[0], xs[1])),
        0, (jnp.arange(t, dtype=jnp.int32), batches))
    return outs
