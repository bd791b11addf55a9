"""Stratified reservoir sampling, adapted for TPU as *priority sampling*.

The paper's per-stratum reservoir sampling (Vitter's Algorithm R inside
Alg. 2, line 10) is inherently sequential: item ``i`` is kept with
probability ``N/i`` and evicts a random resident. Its *output
distribution*, however, is simply "a uniform random subset of size
``min(c, N)`` without replacement". We realize that distribution with a
branch-free, fully-parallel equivalent:

    draw an i.i.d. priority  u_k ~ U(0,1)  per item,
    keep the stratum's top-``N_i`` items by priority.

Equivalence: every size-``min(c,N)`` subset of a stratum is equally likely
under both schemes. Priority sampling additionally merges across shards
for free (top-``N`` of a union of priority-tagged samples is a valid
sample of the union — used for §III-E distributed execution), and lowers
to one sort + gathers on TPU instead of a data-dependent loop.

All shapes are static; the dynamic item count rides in ``valid``.

Selection is routed through a pluggable ``SamplerBackend`` so the same
WHSamp math can run on either of two equivalent realizations:

* ``argsort``  — one XLA sort over (stratum, priority) composite keys and
  a rank test (this module's ``stratified_priority_sample``).
* ``topk``     — exact per-stratum thresholds from a dense ``lax.top_k``
  (partial selection beats a full sort ~3× on CPU) with stable,
  position-ordered tie resolution, so its masks are bit-identical to
  ``argsort``'s.
* ``pallas``   — per-stratum counts via the fused ``stratified_stats``
  kernel, exact thresholds τ_i from ``kernels.sample_mask.ops``, then the
  fused ``sample_mask`` Pallas kernel for the threshold-select pass
  (compiled on TPU, interpret mode elsewhere).
* ``pallas_fused`` — the whole selection (counts, thresholds via a
  sort-free bisection on priority bit patterns, tie-exact keep mask) in
  ONE Pallas kernel (``kernels.fused_level_tick``); through
  ``whs.level_tick`` it additionally fuses the Alg. 2 weight update and
  the compaction into the same kernel with VMEM-resident reservoirs.

All produce identical keep-masks for identical priorities (exact f32
priority ties included — measure-zero for continuous draws); callers pick
one by name (``get_backend``) everywhere a sampler runs.
"""
from __future__ import annotations

from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp


def stratum_counts(stratum: jnp.ndarray, valid: jnp.ndarray, num_strata: int) -> jnp.ndarray:
    """``c_i``: number of valid items per stratum. f32[X]."""
    seg = jnp.where(valid, stratum, num_strata)
    return jnp.zeros((num_strata + 1,), jnp.float32).at[seg].add(1.0)[:num_strata]


def stratum_stds(
    values: jnp.ndarray, stratum: jnp.ndarray, valid: jnp.ndarray,
    num_strata: int,
) -> jnp.ndarray:
    """Per-stratum value standard deviation over valid items. f32[X].

    Feeds the ``neyman`` allocation policy (``N_i ∝ c_i·σ_i``); empty
    strata report 0 (their ``c_i·σ_i`` score is 0 anyway)."""
    seg = jnp.where(valid, stratum, num_strata)
    v = jnp.where(valid, values.astype(jnp.float32), 0.0)
    ones = valid.astype(jnp.float32)
    c = jnp.zeros((num_strata + 1,), jnp.float32).at[seg].add(ones)[:num_strata]
    s1 = jnp.zeros((num_strata + 1,), jnp.float32).at[seg].add(v)[:num_strata]
    s2 = jnp.zeros((num_strata + 1,), jnp.float32).at[seg].add(v * v)[:num_strata]
    safe = jnp.maximum(c, 1.0)
    var = jnp.maximum(s2 / safe - jnp.square(s1 / safe), 0.0)
    return jnp.sqrt(var)


def _exclusive_prefix(x: jnp.ndarray) -> jnp.ndarray:
    """Exclusive prefix sum of a 1-D f32 vector via an O(X²) comparison
    matrix. No ``cumsum``/1-D iota so it lowers inside Pallas TPU kernels
    (X = num_strata is small, so the quadratic matrix is free)."""
    n = x.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    below = jnp.where(jj < ii, jnp.broadcast_to(x[None, :], (n, n)), 0.0)
    return jnp.sum(below, axis=1)


def _settle(alloc, counts, active, budget):
    """Exact-conservation top-up: hand the not-yet-spent part of ``budget``
    to the lowest-indexed strata with headroom (a sequential fill expressed
    as one clip against the exclusive prefix of headroom), so that
    ``Σ alloc == budget`` holds exactly in f32 integer arithmetic."""
    alloc = jnp.where(active, jnp.minimum(alloc, counts), 0.0)
    head = jnp.where(active, counts - alloc, 0.0)
    leftover = budget - jnp.sum(alloc)
    give = jnp.clip(leftover - _exclusive_prefix(head), 0.0, head)
    return alloc + give


def allocate_reservoirs(
    sample_size: jnp.ndarray,
    counts: jnp.ndarray,
    *,
    policy: str = "fair",
    water_fill_iters: int = 4,
    stds: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """``getSampleSize`` (Alg. 2 line 7): split the interval budget across strata.

    Every policy conserves the budget exactly: ``Σ alloc ==
    min(sample_size, Σ counts)`` (floors and water-fill surpluses are
    settled deterministically onto strata with headroom, lowest index
    first), and ``alloc_i ≤ c_i`` always.

    ``fair`` (default): equal share per *active* stratum, with water-filling —
    capacity unused by small strata (``c_i < share``) is iteratively
    redistributed to the rest. This is what gives ApproxIoT its skew
    robustness (§V-E): a stratum with 0.01% of the items still gets a full
    share of the reservoir.

    ``proportional``: ``N_i ∝ c_i`` (what SRS approximates in expectation),
    largest-remainder rounded so rare strata keep their fractional claim;
    kept for ablations.

    ``neyman``: ``N_i ∝ c_i·σ_i`` (minimum-variance allocation for the
    stratified SUM estimator), water-filled like ``fair``. Requires
    ``stds`` — the per-stratum value standard deviations.

    ``proportional`` and ``neyman`` both RESERVE one row per non-empty
    stratum before splitting the remainder. Without the reserve a rare
    stratum's quota/score rounds to zero and its items are dropped with
    no weight — a BIAS, not just variance (under ``SKEW_SHARES`` one
    stratum-D item can carry most of the window's mass). ``fair`` gets
    the same guarantee from its equal shares.
    """
    counts = counts.astype(jnp.float32)
    active = counts > 0
    n_active = jnp.maximum(jnp.sum(active.astype(jnp.float32)), 1.0)
    sample_size = jnp.asarray(sample_size, jnp.float32)
    # The spendable budget: strata can never absorb more than their counts.
    budget = jnp.minimum(sample_size, jnp.sum(counts))

    if policy in ("proportional", "neyman"):
        # One-row unbiasedness reserve; the sequential clip caps it at the
        # budget (index order) when budget < #active — same trick as
        # ``_settle``, Pallas-safe.
        one = jnp.minimum(counts, 1.0)
        reserve = jnp.clip(budget - _exclusive_prefix(one), 0.0, one)
        rem_budget = budget - jnp.sum(reserve)
        rem_counts = counts - reserve

    if policy == "proportional":
        total = jnp.maximum(jnp.sum(rem_counts), 1.0)
        quota = rem_budget * rem_counts / total  # q_i ≤ c_i−r_i: budget ≤ Σc
        base = jnp.floor(quota)
        frac = jnp.where(rem_counts > 0, quota - base, -1.0)
        n_extra = jnp.round(rem_budget - jnp.sum(base))
        # Largest-remainder (Hamilton) rounding without a sort: rank_i =
        # |{j : frac_j > frac_i, ties to the lower index}|, Pallas-safe.
        n = counts.shape[0]
        ii = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        fr_j = jnp.broadcast_to(frac[None, :], (n, n))
        fr_i = jnp.broadcast_to(frac[:, None], (n, n))
        ahead = (fr_j > fr_i) | ((fr_j == fr_i) & (jj < ii))
        rank = jnp.sum(ahead.astype(jnp.float32), axis=1)
        alloc = reserve + base + jnp.where((rem_counts > 0)
                                           & (rank < n_extra), 1.0, 0.0)
        return _settle(alloc, counts, active, budget)

    if policy == "neyman":
        if stds is None:
            raise ValueError("neyman allocation requires per-stratum stds")
        sigma = jnp.maximum(stds.astype(jnp.float32), 1e-6)
        score = jnp.where(active, counts * sigma, 0.0)

        def neyman_body(_, alloc):
            # Strata already at capacity drop out; the unspent budget is
            # re-split ∝ c·σ among the rest.
            uncapped = active & (alloc < counts)
            s = jnp.where(uncapped, score, 0.0)
            s_tot = jnp.maximum(jnp.sum(s), 1e-30)
            spare = budget - jnp.sum(alloc)
            return jnp.minimum(alloc + jnp.floor(spare * s / s_tot), counts)

        s_tot0 = jnp.maximum(jnp.sum(score), 1e-30)
        alloc0 = jnp.minimum(reserve + jnp.floor(rem_budget * score / s_tot0),
                             counts)
        alloc = jax.lax.fori_loop(0, water_fill_iters, neyman_body, alloc0)
        return _settle(alloc, counts, active, budget)

    if policy != "fair":
        raise ValueError(f"unknown allocation policy: {policy}")

    def body(_, alloc):
        # alloc: current per-stratum cap. Strata smaller than their cap
        # release the surplus; it is re-split among the still-capped strata.
        used = jnp.minimum(alloc, counts)
        surplus = jnp.sum(alloc - used)
        capped = active & (counts > alloc)
        n_capped = jnp.maximum(jnp.sum(capped.astype(jnp.float32)), 1.0)
        bump = jnp.where(capped, jnp.floor(surplus / n_capped), 0.0)
        return jnp.where(active, used + bump, 0.0)

    share = jnp.where(active, jnp.floor(budget / n_active), 0.0)
    alloc = jax.lax.fori_loop(0, water_fill_iters, body, share)
    # N_i > c_i and N_i = c_i are equivalent (all items kept, weight 1), so
    # clamping to c_i loses nothing and makes Y_i = N_i hold when saturated;
    # the settle pass then restores the division remainder and any
    # water-fill surplus dropped by the floors.
    return _settle(alloc, counts, active, budget)


def stratified_priority_sample(
    key: jax.Array,
    stratum: jnp.ndarray,
    valid: jnp.ndarray,
    reservoirs: jnp.ndarray,
    num_strata: int,
    priorities: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Select per-stratum top-``N_i``-by-priority items. Returns bool[M].

    Exactly reproduces per-stratum reservoir sampling's output law
    (uniform w/o replacement, size ``min(c_i, N_i)``).
    """
    m = stratum.shape[0]
    if priorities is None:
        priorities = jax.random.uniform(key, (m,))
    # Lexicographic sort [stratum asc, priority desc]; invalid items are
    # banished to a sentinel stratum that sorts last. Two full-precision
    # keys (not one packed float key): packing seg into the exponent bits
    # ties nearby priorities once seg grows, which breaks the exact
    # per-node ≡ level-flattened equivalence the engine relies on.
    seg = jnp.where(valid, stratum, num_strata)
    order = jnp.lexsort((jnp.where(valid, -priorities, 0.5), seg))

    counts_ext = jnp.zeros((num_strata + 2,), jnp.int32).at[
        jnp.where(valid, stratum, num_strata)
    ].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts_ext)[:-1]])

    seg_sorted = jnp.where(valid, stratum, num_strata)[order]
    rank = jnp.arange(m, dtype=jnp.int32) - starts[seg_sorted]
    res_ext = jnp.concatenate([reservoirs.astype(jnp.int32), jnp.zeros((2,), jnp.int32)])
    keep_sorted = rank < res_ext[seg_sorted]

    return jnp.zeros((m,), bool).at[order].set(keep_sorted) & valid


# --------------------------------------------------------------------------
# Pluggable sampler backends.
# --------------------------------------------------------------------------
@runtime_checkable
class SamplerBackend(Protocol):
    """The two operations WHSamp needs from a selection engine.

    Implementations must agree on the output *law*: ``counts`` returns
    exact per-stratum valid-item counts, and ``select`` keeps exactly the
    per-stratum top-``N_i`` items by priority (ties broken arbitrarily).
    Given identical ``priorities`` all backends return identical masks, so
    they are interchangeable mid-pipeline and testable against each other.
    """

    name: str

    def counts(self, stratum: jnp.ndarray, valid: jnp.ndarray,
               num_strata: int) -> jnp.ndarray:
        """Valid items per stratum. f32[X]."""
        ...

    def select(self, key, stratum: jnp.ndarray, valid: jnp.ndarray,
               reservoirs: jnp.ndarray, num_strata: int, *,
               priorities: jnp.ndarray | None = None,
               max_reservoir: int | None = None,
               batch_hint: int = 1) -> jnp.ndarray:
        """Per-stratum top-``N_i``-by-priority keep mask. bool[M].

        ``max_reservoir`` is an optional *static* upper bound on every
        ``N_i`` (e.g. the level's interval budget); backends may exploit
        it (``topk`` sizes its partial selection with it) or ignore it.
        ``batch_hint`` tells the backend how many sibling problems are
        being vmapped over this call (the level engine passes its node
        count) so memory guards can account for the whole batch.
        """
        ...


class ArgsortBackend:
    """Reference backend: one XLA lexsort + rank test (always available)."""

    name = "argsort"

    def counts(self, stratum, valid, num_strata):
        return stratum_counts(stratum, valid, num_strata)

    def select(self, key, stratum, valid, reservoirs, num_strata, *,
               priorities=None, max_reservoir=None, batch_hint=1):
        return stratified_priority_sample(
            key, stratum, valid, reservoirs, num_strata, priorities=priorities
        )


class TopKBackend:
    """Threshold backend: τ_i from a dense per-stratum ``lax.top_k``.

    Densifies priorities to ``[X, M]`` (invalid/foreign slots → −1), takes
    the top ``max_reservoir`` per stratum, and reads τ_i = the ``N_i``-th
    largest. Items with ``u > τ`` are kept outright; items with ``u == τ``
    (exact f32 ties) are kept in buffer-position order until the reservoir
    is full — the same (priority desc, position asc) law as the stable
    lexsort, so masks are **bit-identical** to ``argsort``'s. Partial
    selection is ~3× cheaper than the full sort on CPU; the dense matrix
    costs ``X·M`` memory **per vmapped sibling** (``batch_hint`` of them
    under the level engine), so selection falls back to ``argsort`` when
    the whole batch exceeds ``_DENSE_LIMIT`` or no static
    ``max_reservoir`` is known.
    """

    name = "topk"
    _DENSE_LIMIT = 1 << 22  # elements of the densified [X, M] matrices

    def counts(self, stratum, valid, num_strata):
        return stratum_counts(stratum, valid, num_strata)

    def select(self, key, stratum, valid, reservoirs, num_strata, *,
               priorities=None, max_reservoir=None, batch_hint=1):
        m = stratum.shape[0]
        if priorities is None:
            priorities = jax.random.uniform(key, (m,))
        if (max_reservoir is None
                or max(int(batch_hint), 1) * num_strata * m > self._DENSE_LIMIT):
            return stratified_priority_sample(
                key, stratum, valid, reservoirs, num_strata,
                priorities=priorities,
            )
        k = int(min(m, max(int(max_reservoir), 1)))
        p_eff = jnp.where(valid, priorities, -1.0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (num_strata, m), 0)
        onrow = stratum[None, :] == cols
        dense = jnp.where(onrow, p_eff[None, :], -1.0)
        topv = jax.lax.top_k(dense, k)[0]                       # [X, k] desc
        n_int = reservoirs.astype(jnp.int32)
        tau = jnp.take_along_axis(
            topv, jnp.clip(n_int - 1, 0, k - 1)[:, None], axis=1)[:, 0]
        # N_i ≤ 0 keeps nothing (τ above any priority); τ == −1 (stratum
        # smaller than its reservoir) keeps every valid item.
        tau = jnp.where(n_int <= 0, 2.0, tau)
        seg_tau = tau[stratum]
        strict = valid & (priorities > seg_tau)
        m_strict = jnp.zeros((num_strata,), jnp.int32).at[stratum].add(
            strict.astype(jnp.int32))
        slack = n_int - m_strict
        tie = valid & (priorities == seg_tau)
        tie_rank = jnp.cumsum(
            jnp.where(onrow, tie[None, :].astype(jnp.int32), 0), axis=1)
        rank_at = tie_rank[stratum, jnp.arange(m)]
        return strict | (tie & (rank_at <= slack[stratum]))


class PallasBackend:
    """TPU-native backend built on the two Pallas kernels.

    ``counts`` is the count column of the fused ``stratified_stats`` pass;
    ``select`` finds exact per-stratum thresholds τ_i (tiny sort) and runs
    the fused ``sample_mask`` threshold kernel over the item buffer. On
    non-TPU hosts the kernels execute in interpret mode, so the backend is
    selectable (and bit-checked against ``argsort``) everywhere.

    ``flatten_for_level = True``: the level engine flattens a level into
    one composite-stratum problem for this backend (one kernel sweep per
    level) instead of vmapping per node — vmapping a ``pallas_call`` adds
    a grid dimension, which interpret mode handles poorly.
    """

    name = "pallas"
    flatten_for_level = True

    def counts(self, stratum, valid, num_strata):
        from repro.kernels.stratified_stats import ops as ss_ops

        stats = ss_ops.stratified_stats(
            jnp.zeros(stratum.shape, jnp.float32), stratum, valid, num_strata,
            impl="pallas",
        )
        return stats[:, 0]

    def select(self, key, stratum, valid, reservoirs, num_strata, *,
               priorities=None, max_reservoir=None, batch_hint=1):
        from repro.kernels.sample_mask import ops as sm_ops

        if priorities is None:
            priorities = jax.random.uniform(key, (stratum.shape[0],))
        tau = sm_ops.thresholds_from_reservoirs(
            priorities, stratum, valid, reservoirs, num_strata
        )
        keep, _ = sm_ops.sample_mask(
            priorities, stratum, valid, tau,
            jnp.ones((num_strata,), jnp.float32), impl="pallas",
        )
        return keep


class PallasFusedBackend:
    """Single-kernel backend: the whole sampling tick fused in VMEM.

    ``select`` runs the ``fused_level_tick`` kernel's selection stage —
    per-stratum counts, an exact bitwise binary search for each τ_i (no
    in-kernel sort), and the strict/tie keep decomposition — in ONE
    Pallas pass with the item buffer VMEM-resident, so its masks are
    **bit-identical** to ``argsort``'s even on exact f32 priority ties
    (unlike ``pallas``, which keeps extras on ties). The level engine
    additionally routes whole-level ticks through the fused kernel (see
    ``whs.level_tick``), collapsing sample + weight-update + compaction
    into one kernel launch per level.

    The one-hot working set is ``O(M·X)`` VMEM per problem; on TPU the
    kernel compiles within ``kernels.fused_level_tick``'s envelope, and
    ``api.spec.validate`` refuses specs outside it. ``select`` checks its
    own shape when traced (a mesh shard, the mesh root's gathered
    batch) and raises the same ``SpecError`` outside the envelope.
    """

    name = "pallas_fused"
    flatten_for_level = True
    fused_level_tick = True

    def counts(self, stratum, valid, num_strata):
        from repro.kernels.stratified_stats import ops as ss_ops

        stats = ss_ops.stratified_stats(
            jnp.zeros(stratum.shape, jnp.float32), stratum, valid, num_strata,
            impl="pallas",
        )
        return stats[:, 0]

    def select(self, key, stratum, valid, reservoirs, num_strata, *,
               priorities=None, max_reservoir=None, batch_hint=1):
        from repro.api.spec import check_fused_envelope
        from repro.kernels.fused_level_tick import ops as ft_ops

        check_fused_envelope(stratum.shape[0], num_strata,
                             jax.default_backend(),
                             "this selection (one node's buffer, one "
                             "device's mesh shard or the mesh root's "
                             "gathered reservoirs)")
        if priorities is None:
            priorities = jax.random.uniform(key, (stratum.shape[0],))
        return ft_ops.fused_select(priorities, stratum, valid, reservoirs,
                                   num_strata, impl="pallas")


_BACKENDS: dict[str, SamplerBackend] = {}


def register_backend(backend: SamplerBackend) -> None:
    _BACKENDS[backend.name] = backend


register_backend(ArgsortBackend())
register_backend(TopKBackend())
register_backend(PallasBackend())
register_backend(PallasFusedBackend())

DEFAULT_BACKEND = "argsort"


def get_backend(backend: str | SamplerBackend) -> SamplerBackend:
    """Resolve a backend by name (or pass an instance through)."""
    if isinstance(backend, str):
        try:
            return _BACKENDS[backend]
        except KeyError:
            raise ValueError(
                f"unknown sampler backend {backend!r}; "
                f"registered: {sorted(_BACKENDS)}"
            ) from None
    return backend


def merge_priority_samples(
    priorities_a: jnp.ndarray, priorities_b: jnp.ndarray
) -> jnp.ndarray:
    """§III-E merge helper: union of two priority-tagged shard samples.

    Because selection is "top-N by i.i.d. priority", two workers' local
    reservoirs merge by concatenation + re-selection — no coordination.
    Returns the concatenated priority vector (caller re-runs selection).
    """
    return jnp.concatenate([priorities_a, priorities_b], axis=0)
