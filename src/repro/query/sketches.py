"""Mergeable sketch state for standing queries — pure-JAX pytrees.

Both sketches are fixed-shape pytrees, so they ride along ``TreeState``
as donated device-resident leaves inside the scan engine's epoch
dispatch and update once per window with no host round-trip. Both are
*mergeable*: folding a batch in is the same operation as folding another
sketch's summary in, which is what lets one edge sample answer many
standing queries (and many tenants share one sketch pipeline).

``QuantileSketch`` — a true multi-level KLL compactor: ``L`` weighted
buffers of ``C`` points each (``kll_schedule``). A batch enters level 0;
any level that overflows its capacity compacts its buffer at randomized
equi-weight rank targets ``t_k = (k + u)·W/m`` and pushes the ``m = C/2``
survivors (weight ``W/m`` each) up one level, so heavy quanta live only
in the rarely-compacted top buffer (which compacts in place to ``C``
points). The randomized offset ``u`` makes every compaction's rank
perturbation zero-mean (KLL's core trick), so perturbations random-walk:
the sketch tracks ``err_q2 = Σ quantum²`` across its history and reports
``rank_error_bound = 2·√(err_q2)/W`` — each level-``h`` quantum covers
only that level's buffer weight, which is why the leveled bound beats
the collapsed single-buffer ``2·√U/C`` on long streams. While a level's
live points fit in ``C`` slots its fold is lossless, so a stream that
never exceeds level 0 is summarised exactly.

``HeavyHitterSketch`` — a weighted count-min sketch (``depth × width``,
multiply-shift hashing) plus a tracked top-``k`` candidate set. Batch
update: fold the batch into the counts (one ``cms_update`` kernel pass),
re-estimate all candidates (old top-k ∪ batch keys) against the fresh
counts, dedupe, and keep the best ``k``. Estimates only over-count
(collisions), by at most ``(2/width)·W`` per the standard CM bound.

Windowed / decayed variants (the serve plane's "last N minutes, not
stream-so-far" answers):

``WindowedQuantileSketch`` — a ring of ``R`` KLL sub-sketches, one per
root window. Each update writes a FRESH sub-sketch into the head slot
(evicting the slot written ``R`` windows ago) and advances the head, so
the ring always holds exactly the last ``R`` windows' summaries. A query
merges the ring through ``quantile_merge_stacked`` — one compaction
pass over all ``R`` slots — and answers from the merged summary with
its honest rank-error bound. Batches that fit the sub-sketch capacity
are summarised losslessly per window, so the only rank error is the
query-time merge's.

``hh_decayed_update`` — exponential decay on the SAME
``HeavyHitterSketch`` state: ``counts ← γ·counts + batch``, so an item
seen ``t`` windows ago contributes ``γ^t`` of its weight and the top-k
tracks the *recent* heavy hitters. Decay commutes with the linear CM
merge (``γ(A+B)+a+b = (γA+a)+(γB+b)``), so the distributed ``psum``
merge path is unchanged; the CM bound applies with the decayed total
weight ``Σ γ^t·W_t``.

Merge algebra (the §III-E distributed query plane rests on this): both
sketches close under ``merge`` — ``quantile_merge`` folds one summary's
weighted buffer into another (one compaction when over capacity, both
histories' compaction counts ride along in the bound), and ``hh_merge``
adds the linear CM tables and re-merges the top-k candidate union
against the merged counts. The ``*_stacked`` variants take a leading
stack axis (exactly what ``jax.lax.all_gather`` of per-device state
produces under ``shard_map``) and merge N summaries with ONE compaction
/ one candidate refresh, so the pod-scale path ships O(sketch) bytes
per window — never a reservoir. Properties (associativity/commutativity
up to answer equivalence, identity, merge ≡ concatenated stream) are
pinned in ``tests/test_sketch_merge.py``.

Heavy inner passes route through ``kernels.sketch_update.ops`` (Pallas
on TPU, jnp oracle elsewhere).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.sketch_update import ops as sk_ops
from repro.kernels.sketch_update.ref import hash_buckets

HH_EMPTY_KEY = jnp.int32(2**31 - 1)   # sentinel: unoccupied top-k slot


# --------------------------------------------------------------- quantile --
class QuantileSketch(NamedTuple):
    """``value``/``weight`` f32[L, C]: ``L`` level buffers of ``C`` slots
    (``kll_schedule``); weight 0 marks an empty slot. Each level's live
    slots are kept value-sorted and packed to the front. ``compactions``
    f32[] counts lossy compaction steps; ``err_q2`` f32[] accumulates the
    squared weight quantum of each — together they drive the reported
    rank-error bound (``rank_error_bound``), which a lossless (never
    overflowed) summary keeps at exactly 0."""

    value: jnp.ndarray
    weight: jnp.ndarray
    compactions: jnp.ndarray
    err_q2: jnp.ndarray

    @property
    def levels(self) -> int:
        return self.value.shape[0]

    @property
    def capacity(self) -> int:
        return self.value.shape[-1]

    @property
    def total_weight(self) -> jnp.ndarray:
        return jnp.sum(self.weight)

    @property
    def rank_error_bound(self) -> jnp.ndarray:
        """Current ±2σ rank-error bound (fraction of total weight).

        A compaction at buffer weight ``W_buf`` perturbs any rank by at
        most one weight quantum ``q = W_buf/m`` with a zero-mean
        randomized sign; independent perturbations random-walk, so
        ±2σ = ``2·√(Σ q²)/W``. Level-``h`` quanta cover only that level's
        buffer weight — far below total ``W`` for long streams — so this
        is strictly tighter than the collapsed one-buffer ``2·√U/C``
        whenever any compaction ran below the top level."""
        return jnp.where(
            self.compactions > 0.0,
            2.0 * jnp.sqrt(self.err_q2)
            / jnp.maximum(self.total_weight, 1e-30),
            0.0)


def kll_schedule(capacity: int) -> tuple[int, ...]:
    """Per-level slot capacities for a ``capacity``-point sketch.

    Uniform ``C`` slots per level: level 0 must hold ``capacity`` points
    so the ≤-capacity stream stays exact (`quantile_init`'s lossless
    contract), and equal upper levels keep every fold's argsort the same
    cost. Depth grows with capacity — tiny sketches don't benefit from
    levels they can never fill."""
    if capacity < 16:
        levels = 1
    elif capacity < 64:
        levels = 2
    else:
        levels = 4
    return (capacity,) * levels


def quantile_init(capacity: int) -> QuantileSketch:
    levels = len(kll_schedule(capacity))
    return QuantileSketch(value=jnp.zeros((levels, capacity), jnp.float32),
                          weight=jnp.zeros((levels, capacity), jnp.float32),
                          compactions=jnp.zeros((), jnp.float32),
                          err_q2=jnp.zeros((), jnp.float32))


def quantile_rank_error_bound(capacity: int, max_updates: int = 64) -> float:
    """Static planning bound: the rank error a ``capacity`` sketch stays
    within across ``max_updates`` batch folds, for any batch size.

    Runs the leveled schedule's weight bookkeeping on the host (no data,
    just per-level counts/weights) and takes the worst ``2·√(Σq²)/W``
    over a batch-size grid spanning under- to over-capacity batches —
    the quantum sum is monotone in how often low levels spill, which the
    grid's extremes bracket. Strictly tighter than the old collapsed
    ``2·√U/C`` whenever the schedule has >1 level. Validated empirically
    in ``tests/test_query_plane.py`` / ``benchmarks/fig8_accuracy.py``."""
    ks = kll_schedule(capacity)
    top = len(ks) - 1
    worst = 0.0
    for batch in sorted({max(capacity // 4, 1), capacity, 4 * capacity}):
        n = [0.0] * len(ks)
        w = [0.0] * len(ks)
        var = 0.0
        for _ in range(int(max_updates)):
            cv, cw = float(batch), float(batch)
            for h, k in enumerate(ks):
                n[h] += cv
                w[h] += cw
                if n[h] <= k:
                    break
                m = k if h == top else k // 2
                q = w[h] / m
                var += q * q
                if h == top:
                    n[h] = float(k)   # in-place compact, no spill
                    break
                cv, cw = float(m), w[h]
                n[h] = 0.0
                w[h] = 0.0
        total = float(max_updates) * float(batch)
        worst = max(worst, 2.0 * math.sqrt(var) / total)
    return worst


def _fold_level(key: jax.Array, lvl_v: jnp.ndarray, lvl_w: jnp.ndarray,
                add_v: jnp.ndarray, add_w: jnp.ndarray, *, m_up: int,
                impl: str):
    """Fold extra weighted points into one ``C``-slot level buffer.

    Returns ``(value[C], weight[C], carry_v[m_up], carry_w[m_up],
    did_compact, q2)``. While the live points fit, the fold is lossless
    (stable value-sorted live-first pack) and the carry is empty. On
    overflow the buffer compacts at randomized equi-weight rank targets:
    ``m_up > 0`` pushes the ``m_up`` survivors up as the carry and empties
    the level; ``m_up == 0`` (top level) compacts in place to ``C``
    points. ``q2`` is the squared weight quantum of the compaction."""
    cap = lvl_v.shape[0]
    m = cap if m_up == 0 else m_up
    v = jnp.concatenate([lvl_v, add_v])
    w = jnp.concatenate([lvl_w, jnp.maximum(add_w, 0.0)])
    order = jnp.argsort(v)
    v_s, w_s = v[order], w[order]
    cumw = jnp.cumsum(w_s)
    total = cumw[-1]
    n_live = jnp.sum(w_s > 0.0)
    # Under shard_map both cond branches must return the same varying-
    # manual-axes type. Every data-derived output varies over the axes of
    # (v, w, key), so the branches' constant outputs are cast to that set
    # here, before the cond (a no-op outside shard_map, where it is empty).
    vma = jax.typeof(v_s).vma | jax.typeof(w_s).vma | jax.typeof(key).vma

    def varying(x):
        axes = tuple(sorted(vma - jax.typeof(x).vma))
        return jax.lax.pcast(x, axes, to="varying") if axes else x

    zero_carry = varying(jnp.zeros((m_up,), jnp.float32))
    zero_cap = varying(jnp.zeros((cap,), jnp.float32))
    zero = varying(jnp.zeros((), jnp.float32))
    one = varying(jnp.ones((), jnp.float32))

    def exact():
        # Everything fits: pack live slots to the front (stable, so the
        # value ordering survives) — the fold is lossless.
        pack = jnp.argsort(jnp.where(w_s > 0.0, 0, 1), stable=True)
        return (v_s[pack][:cap], w_s[pack][:cap], zero_carry, zero_carry,
                zero, zero)

    def compact():
        u = jax.random.uniform(key, ())
        q = total / m
        t = (jnp.arange(m, dtype=jnp.float32) + u) * q
        cumw_prev = jnp.concatenate([jnp.zeros((1,), jnp.float32),
                                     cumw[:-1]])
        picked = sk_ops.quantile_compact(v_s, cumw_prev, cumw, t, impl=impl)
        # f32 rounding can push the last target(s) to >= total; rank-W is
        # the max live value by definition.
        vmax = jnp.max(jnp.where(w_s > 0.0, v_s, -jnp.inf))
        picked = jnp.where(t >= total, vmax, picked)
        pw = jnp.full((m,), q, jnp.float32)
        if m_up == 0:
            return (picked, pw, zero_carry, zero_carry, one, q * q)
        return (zero_cap, zero_cap, picked, pw, one, q * q)

    return jax.lax.cond(n_live <= cap, exact, compact)


def _fold_all(key: jax.Array, sk: QuantileSketch, incoming, *, impl: str
              ) -> QuantileSketch:
    """Cascade a per-level list of extra ``(value, weight)`` buffers (or
    ``None``) through the sketch, carrying each level's spill up."""
    levels, cap = sk.value.shape
    carry_v = jnp.zeros((0,), jnp.float32)
    carry_w = jnp.zeros((0,), jnp.float32)
    comp, err = sk.compactions, sk.err_q2
    rows_v, rows_w = [], []
    for h in range(levels):
        add_v, add_w = [carry_v], [carry_w]
        if incoming[h] is not None:
            add_v.append(incoming[h][0])
            add_w.append(incoming[h][1])
        m_up = 0 if h == levels - 1 else cap // 2
        nv, nw, carry_v, carry_w, did, q2 = _fold_level(
            jax.random.fold_in(key, h), sk.value[h], sk.weight[h],
            jnp.concatenate(add_v), jnp.concatenate(add_w),
            m_up=m_up, impl=impl)
        rows_v.append(nv)
        rows_w.append(nw)
        comp = comp + did
        err = err + q2
    return QuantileSketch(value=jnp.stack(rows_v), weight=jnp.stack(rows_w),
                          compactions=comp, err_q2=err)


@functools.partial(jax.jit, static_argnames=("impl",))
def quantile_update(key: jax.Array, sk: QuantileSketch, values: jnp.ndarray,
                    weights: jnp.ndarray, *, impl: str = "auto"
                    ) -> QuantileSketch:
    """Fold a weighted batch (weight 0 = excluded item) into the summary.

    The batch enters level 0; overflow cascades up the schedule, one
    (possible) compaction per level."""
    incoming = [(values, weights)] + [None] * (sk.levels - 1)
    return _fold_all(key, sk, incoming, impl=impl)


@jax.jit
def quantile_query(sk: QuantileSketch, qs: jnp.ndarray) -> jnp.ndarray:
    """f32[len(qs)] value estimates at quantiles ``qs`` (each in [0, 1]).

    All levels answer together: the flattened ``[L·C]`` weighted point
    set is one summary — level only matters for *where compaction error
    entered*, not for querying."""
    flat_v = sk.value.reshape(-1)
    flat_w = sk.weight.reshape(-1)
    order = jnp.argsort(flat_v)
    v_s, w_s = flat_v[order], flat_w[order]
    cumw = jnp.cumsum(w_s)
    total = cumw[-1]
    t = jnp.clip(qs, 0.0, 1.0) * total
    # first live slot with cumw > t; q == 1.0 maps to the max live value
    n = flat_v.shape[0]
    idx = jnp.searchsorted(cumw, t, side="right")
    vmax = jnp.max(jnp.where(w_s > 0.0, v_s, -jnp.inf))
    out = jnp.where(idx < n, v_s[jnp.minimum(idx, n - 1)], vmax)
    return jnp.where(total > 0.0, out, 0.0)


@functools.partial(jax.jit, static_argnames=("impl",))
def quantile_merge(key: jax.Array, a: QuantileSketch, b: QuantileSketch,
                   *, impl: str = "auto") -> QuantileSketch:
    """Merge two summaries into one with ``a``'s schedule.

    Same-schedule sketches merge level-wise — level-``h`` points carry
    level-``h`` quanta, so keeping them at their level preserves the
    leveled error accounting (mergeability by construction: each level
    fold is the batch-fold operation). A ``b`` with a different schedule
    flattens into level 0 like a batch. Both histories' ``compactions``
    and ``err_q2`` are added so the merged ``rank_error_bound`` stays
    honest (the two histories' rank errors random-walk independently —
    summing the variances upper-bounds the merged variance)."""
    base = a._replace(compactions=a.compactions + b.compactions,
                      err_q2=a.err_q2 + b.err_q2)
    if b.value.shape == a.value.shape:
        incoming = [(b.value[h], b.weight[h]) for h in range(a.levels)]
    else:
        incoming = ([(b.value.reshape(-1), b.weight.reshape(-1))]
                    + [None] * (a.levels - 1))
    return _fold_all(key, base, incoming, impl=impl)


@functools.partial(jax.jit, static_argnames=("impl",))
def quantile_merge_stacked(key: jax.Array, stacked: QuantileSketch,
                           *, impl: str = "auto") -> QuantileSketch:
    """Merge ``N`` stacked summaries (leaves ``[N, ...]`` — the layout an
    ``all_gather`` of per-device state produces) in one level-wise pass.

    Equivalent to a left fold of :func:`quantile_merge` up to answer
    equivalence, but each level compacts at most once for the whole
    merge (≤ ``L`` rank perturbations instead of up to ``L·(N − 1)``),
    so the merged bound is tighter."""
    levels, cap = stacked.value.shape[-2:]
    base = QuantileSketch(
        value=jnp.zeros((levels, cap), jnp.float32),
        weight=jnp.zeros((levels, cap), jnp.float32),
        compactions=jnp.sum(stacked.compactions),
        err_q2=jnp.sum(stacked.err_q2))
    incoming = [(stacked.value[..., h, :].reshape(-1),
                 stacked.weight[..., h, :].reshape(-1))
                for h in range(levels)]
    return _fold_all(key, base, incoming, impl=impl)


# ------------------------------------------------- windowed quantiles --
class WindowedQuantileSketch(NamedTuple):
    """Ring of ``R`` per-window KLL sub-sketches: ``value``/``weight``
    f32[R, L, C], ``compactions``/``err_q2`` f32[R] (per-slot histories),
    ``head`` i32[] — the next slot to overwrite. Slot ``head`` holds the
    oldest window; a query over the ring covers exactly the last ``R``
    updates."""

    value: jnp.ndarray
    weight: jnp.ndarray
    compactions: jnp.ndarray
    err_q2: jnp.ndarray
    head: jnp.ndarray

    @property
    def window(self) -> int:
        return self.value.shape[0]

    @property
    def capacity(self) -> int:
        return self.value.shape[-1]


def windowed_quantile_init(capacity: int, window: int
                           ) -> WindowedQuantileSketch:
    levels = len(kll_schedule(capacity))
    r = int(window)
    return WindowedQuantileSketch(
        value=jnp.zeros((r, levels, capacity), jnp.float32),
        weight=jnp.zeros((r, levels, capacity), jnp.float32),
        compactions=jnp.zeros((r,), jnp.float32),
        err_q2=jnp.zeros((r,), jnp.float32),
        head=jnp.zeros((), jnp.int32))


@functools.partial(jax.jit, static_argnames=("impl",))
def windowed_quantile_update(key: jax.Array, sk: WindowedQuantileSketch,
                             values: jnp.ndarray, weights: jnp.ndarray, *,
                             impl: str = "auto") -> WindowedQuantileSketch:
    """Summarise ONE window's weighted batch into the head slot (fresh
    sub-sketch — the slot's previous window falls out of scope) and
    advance the ring. A batch that fits the sub-sketch capacity is
    summarised exactly (the lossless fold contract)."""
    levels, cap = sk.value.shape[-2:]
    sub = QuantileSketch(value=jnp.zeros((levels, cap), jnp.float32),
                         weight=jnp.zeros((levels, cap), jnp.float32),
                         compactions=jnp.zeros((), jnp.float32),
                         err_q2=jnp.zeros((), jnp.float32))
    sub = quantile_update(key, sub, values, weights, impl=impl)
    i = sk.head
    return WindowedQuantileSketch(
        value=sk.value.at[i].set(sub.value),
        weight=sk.weight.at[i].set(sub.weight),
        compactions=sk.compactions.at[i].set(sub.compactions),
        err_q2=sk.err_q2.at[i].set(sub.err_q2),
        head=(i + 1) % sk.window)


@functools.partial(jax.jit, static_argnames=("impl",))
def windowed_quantile_merged(key: jax.Array, sk: WindowedQuantileSketch, *,
                             impl: str = "auto") -> QuantileSketch:
    """Merge the ring's live slots into one query-time summary — exactly
    ``quantile_merge_stacked`` over the ``[R, ...]`` stacked sub-sketches
    (empty slots carry zero weight and zero error history, so a not-yet-
    filled ring answers from the windows it has)."""
    stacked = QuantileSketch(value=sk.value, weight=sk.weight,
                             compactions=sk.compactions, err_q2=sk.err_q2)
    return quantile_merge_stacked(key, stacked, impl=impl)


# ---------------------------------------------------------- heavy hitters --
class HeavyHitterSketch(NamedTuple):
    """``counts`` f32[depth, width] weighted count-min state;
    ``key`` i32[k] / ``est`` f32[k] the tracked top-k candidates
    (``HH_EMPTY_KEY`` marks an unoccupied slot)."""

    counts: jnp.ndarray
    key: jnp.ndarray
    est: jnp.ndarray

    @property
    def depth(self) -> int:
        return self.counts.shape[0]

    @property
    def width(self) -> int:
        return self.counts.shape[1]

    @property
    def total_weight(self) -> jnp.ndarray:
        return jnp.sum(self.counts[0])


def hh_init(k: int, width: int, depth: int) -> HeavyHitterSketch:
    return HeavyHitterSketch(
        counts=jnp.zeros((depth, width), jnp.float32),
        key=jnp.full((k,), HH_EMPTY_KEY, jnp.int32),
        est=jnp.zeros((k,), jnp.float32),
    )


def hh_error_bound(width: int, total_weight: jnp.ndarray) -> jnp.ndarray:
    """CM over-count bound: est − true ≤ (2/width)·W w.h.p. (1 − 2^-depth)."""
    return (2.0 / float(width)) * total_weight


def hh_point_estimate(sk: HeavyHitterSketch, keys: jnp.ndarray) -> jnp.ndarray:
    """f32[M] count-min estimates (min over depth rows) for ``keys``."""
    buckets = hash_buckets(keys, sk.depth, sk.width)           # [D, M]
    per_row = jnp.take_along_axis(sk.counts, buckets, axis=1)  # [D, M]
    return jnp.min(per_row, axis=0)


def _refresh_topk(counts: jnp.ndarray, cand_key: jnp.ndarray,
                  k_slots: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Re-estimate a candidate-key pool against a CM table and keep the
    best ``k_slots``: dedupe by sorting (duplicates share one CM
    estimate, so which survives is irrelevant), then top-k by estimate.
    Shared by the batch update and the merge paths — the "top-k
    re-merge" is exactly a refresh over the union of candidate sets."""
    fresh = HeavyHitterSketch(counts=counts,
                              key=jnp.zeros((0,), jnp.int32),
                              est=jnp.zeros((0,), jnp.float32))
    cand_est = jnp.where(cand_key == HH_EMPTY_KEY, -1.0,
                         hh_point_estimate(fresh, cand_key))
    order = jnp.argsort(cand_key)
    ks, es = cand_key[order], cand_est[order]
    first = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
    score = jnp.where(first & (ks != HH_EMPTY_KEY), es, -1.0)
    top_est, top_ix = jax.lax.top_k(score, k_slots)
    occupied = top_est >= 0.0
    return (jnp.where(occupied, ks[top_ix], HH_EMPTY_KEY),
            jnp.maximum(top_est, 0.0))


def hh_update(sk: HeavyHitterSketch, keys: jnp.ndarray,
              weights: jnp.ndarray, *, impl: str = "auto"
              ) -> HeavyHitterSketch:
    """Fold a weighted key batch in and refresh the top-k candidate set."""
    k_slots = sk.key.shape[0]
    w = jnp.maximum(weights, 0.0)
    delta = sk_ops.cms_update(keys.astype(jnp.uint32), w, sk.depth, sk.width,
                              impl=impl)
    counts = sk.counts + delta
    cand_key = jnp.concatenate(
        [sk.key, jnp.where(w > 0.0, keys, HH_EMPTY_KEY)])
    key_out, est_out = _refresh_topk(counts, cand_key, k_slots)
    return HeavyHitterSketch(counts=counts, key=key_out, est=est_out)


def hh_decayed_update(sk: HeavyHitterSketch, keys: jnp.ndarray,
                      weights: jnp.ndarray, decay: float, *,
                      impl: str = "auto") -> HeavyHitterSketch:
    """Fold one window's weighted key batch into an exponentially decayed
    CM table: ``counts ← decay·counts + batch``, then refresh the top-k
    against the decayed counts. An item seen ``t`` windows ago weighs
    ``decay^t``, so the candidate set tracks the RECENT heavy hitters —
    a long-retired key's estimate shrinks geometrically until a current
    key overtakes it. Decay is linear, so the distributed psum merge of
    per-device tables stays exact (each device decays its own shard)."""
    k_slots = sk.key.shape[0]
    w = jnp.maximum(weights, 0.0)
    delta = sk_ops.cms_update(keys.astype(jnp.uint32), w, sk.depth, sk.width,
                              impl=impl)
    counts = jnp.float32(decay) * sk.counts + delta
    cand_key = jnp.concatenate(
        [sk.key, jnp.where(w > 0.0, keys, HH_EMPTY_KEY)])
    key_out, est_out = _refresh_topk(counts, cand_key, k_slots)
    return HeavyHitterSketch(counts=counts, key=key_out, est=est_out)


def hh_merge(a: HeavyHitterSketch, b: HeavyHitterSketch) -> HeavyHitterSketch:
    """Merge two sketches: CM tables are linear (counts add exactly —
    the merged table equals one table fed the concatenated stream), and
    the top-k re-merges as a candidate refresh over both key sets
    against the merged counts."""
    counts = a.counts + b.counts
    cand_key = jnp.concatenate([a.key, b.key])
    key_out, est_out = _refresh_topk(counts, cand_key, a.key.shape[0])
    return HeavyHitterSketch(counts=counts, key=key_out, est=est_out)


def hh_merge_stacked(stacked: HeavyHitterSketch) -> HeavyHitterSketch:
    """Merge ``N`` stacked sketches (leaves ``[N, ...]``, e.g. from an
    ``all_gather`` of per-device state) with one candidate refresh."""
    counts = jnp.sum(stacked.counts, axis=0)
    cand_key = stacked.key.reshape(-1)
    key_out, est_out = _refresh_topk(counts, cand_key,
                                     stacked.key.shape[-1])
    return HeavyHitterSketch(counts=counts, key=key_out, est=est_out)


def hh_item_key(values: jnp.ndarray) -> jnp.ndarray:
    """Default item→key map for value streams: round to the nearest int.

    IoT heavy hitters are "which readings dominate the stream"; rounding
    buckets the f32 payload into integer keys. Pipelines with a real key
    column should pass it directly to ``hh_update`` instead.
    """
    return jnp.round(values).astype(jnp.int32)
