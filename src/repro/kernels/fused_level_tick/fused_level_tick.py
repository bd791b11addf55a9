"""Pallas TPU kernel: the ENTIRE per-level WHS sampling tick, fused.

One grid step = one node of the hierarchy level. The node's interval
buffer (values / strata / valid / priorities) is loaded into VMEM once
and every stage of Alg. 2 runs on it without an HBM round-trip:

    counts     c_i            one-hot [X, cap] lane reduce
    allocation N_i            fair water-filling (same fori_loop as
                              ``core.sampling.allocate_reservoirs``)
    threshold  τ_i            bitwise binary search for the N_i-th
                              largest priority (31 fixed iterations on
                              the monotone IEEE-754 order; no in-kernel
                              sort needed)
    keep mask                 strict/tie decomposition — bit-identical
                              to the stable-lexsort law (``argsort``)
    weight update             Alg. 2 lines 12-20 + Eq. 9 (``_whs_meta``)
    compaction                prefix-sum destination + one-hot MXU
                              scatter

Layout: items run along lanes. Every buffer is a ``(1, cap)`` row and
every per-stratum view an ``[X, cap]`` one-hot (item k is lane k, stratum
i is sublane i), so no stage moves an item vector between lanes and
sublanes. Pallas TPU has no ``cumsum``; prefix sums are log-step shifted
adds (``pltpu.roll``), exact on integer-valued data.

The previous pallas backend ran this as three kernels
(``stratified_stats`` → threshold sort → ``sample_mask``) plus an XLA
compaction, with the item buffer leaving and re-entering HBM between
each stage. Here reservoirs and the per-stratum accumulators stay
VMEM-resident for the whole tick.

Saturation fast path (fraction ≥ 1.0): when every stratum's reservoir
covers its count, the keep mask is provably ``valid`` — the threshold
search and tie resolution are skipped under ``pl.when``, and when the
buffer is additionally front-packed the compaction collapses to a
truncating copy. This is what removes the exact-path overhead at
sampling fraction 1.0 (the sampler never loses when it samples
nothing).

Tie law (the bit-identity recipe, same as ``TopKBackend``): items with
``u > τ`` are kept outright; items with ``u == τ`` (exact f32
collisions) are kept in buffer-position order until the reservoir is
full — exactly the (priority desc, position asc) order of the stable
lexsort, so masks match ``argsort`` bit-for-bit.

VMEM envelope: ``cap ≤ MAX_CAPACITY`` items and ``X ≤ MAX_STRATA``
strata per node. The one-hot views are ``O(X·cap)`` and the compaction
scatters ``_SCATTER_ELEMS``-bounded ``[out_capacity, chunk]`` tiles, so
a node fits a core's default scoped VMEM. ``api.spec.validate`` refuses
a ``pallas_fused`` spec outside the envelope on TPU; nothing falls back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sampling import allocate_reservoirs
from repro.core.whs import _whs_meta
from repro.kernels import out_struct, row_spec

# Binary-search iterations: priorities live in [0, 1), whose IEEE-754
# payloads span [0, 0x3F800000) ⊂ [0, 2^30) — 31 halvings pin the
# threshold to an exact item priority (extra iterations are no-ops).
_SEARCH_ITERS = 31

# The envelope the kernel is compiled and checked for on TPU (per node).
MAX_CAPACITY = 8192
MAX_STRATA = 32

# f32 elements of one compaction scatter tile [out_capacity, chunk]
# (~4 MiB): the scatter walks the buffer in lane chunks of this budget
# instead of materializing the O(cap·out_capacity) matrix at once.
_SCATTER_ELEMS = 1 << 20
_LANES = 128
# Scoped VMEM the kernel may use (of a v5e core's 128 MiB): a cap-8192
# node spills its (1, cap) rows and [X, cap] one-hots past the 16 MiB
# default.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024

_HIGHEST = jax.lax.Precision.HIGHEST


def _prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along the lane axis of a 2-D array:
    ``log2(n)`` shifted adds (Hillis–Steele). Exact for the integer-
    valued counts it is used on."""
    n = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = 1
    while shift < n:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, 1),
                          jnp.zeros_like(x))
        shift *= 2
    return x


def _onehot(s: jnp.ndarray, m: jnp.ndarray, num_strata: int) -> jnp.ndarray:
    """bool[X, cap]: row i marks the valid items of stratum i."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (num_strata, s.shape[1]), 0)
    return (rows == s) & m


def _stratum_sums(x: jnp.ndarray, onehot_f: jnp.ndarray) -> jnp.ndarray:
    """f32[X]: Σ_k x_k over each stratum's valid items, as a (1, cap) ×
    [X, cap]ᵀ MXU contraction whose (1, X) result is already lane-major
    (a lane reduction of the one-hot would leave a column that the
    per-stratum [X] math cannot use without a relayout)."""
    return jax.lax.dot_general(
        x, onehot_f, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)[0]


def _column_count(mask: jnp.ndarray) -> jnp.ndarray:
    """f32[X, 1]: per-row count of a bool [X, cap] (exact below 2^24)."""
    return jnp.sum(mask.astype(jnp.float32), axis=1, keepdims=True)


def _search_tau(u, onehot, reservoirs, counts):
    """Exact per-stratum thresholds as an f32[X, 1] column: τ_i = the
    ``N_i``-th largest valid priority of stratum i, found by binary
    search on the IEEE-754 bit pattern (monotone for non-negative
    floats). Sentinels match
    ``kernels.sample_mask.ops.thresholds_from_reservoirs``:
    keep-nothing (N ≤ 0) → +2.0, keep-everything (c ≤ N) → −1.0."""
    num_strata = reservoirs.shape[0]
    n_int = reservoirs.astype(jnp.int32)[:, None]            # i32[X, 1]
    c_int = counts.astype(jnp.int32)[:, None]
    # Effective rank: only searched when 0 < N < c (sentinels otherwise).
    n_eff = jnp.clip(jnp.minimum(n_int, c_int), 1, None)
    u_bits = jax.lax.bitcast_convert_type(u, jnp.int32)     # (1, cap)

    def body(_, state):
        lo, hi = state                                      # i32[X, 1]
        mid = (lo + hi) // 2
        # F(mid) per stratum: its valid items with bits(u) ≥ mid.
        cnt = jnp.sum((onehot & (u_bits >= mid)).astype(jnp.int32),
                      axis=1, keepdims=True)
        ok = cnt >= n_eff                                   # mid feasible
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo0 = jnp.zeros((num_strata, 1), jnp.int32)             # F(0) = c ≥ n_eff
    hi0 = jnp.full((num_strata, 1), 0x3F800001, jnp.int32)  # > bits(max u)
    lo, _ = jax.lax.fori_loop(0, _SEARCH_ITERS, body, (lo0, hi0))
    tau = jax.lax.bitcast_convert_type(lo, jnp.float32)
    return jnp.where(n_int <= 0, 2.0,
                     jnp.where(c_int <= n_int, -1.0, tau))


def _select_block(u, onehot, reservoirs, counts):
    """bool (1, cap) keep mask for one VMEM-resident buffer — τ search +
    the strict/tie decomposition that reproduces the stable lexsort
    bit-for-bit. Each item takes part only in its own stratum's row."""
    tau = _search_tau(u, onehot, reservoirs, counts)        # [X, 1]
    strict = onehot & (u > tau)                             # [X, cap]
    slack = reservoirs[:, None] - _column_count(strict)     # int-valued
    tie = onehot & (u == tau)
    # Position-ordered tie rank within each stratum row.
    ranks = _prefix_sum(tie.astype(jnp.float32))
    keep = strict | (tie & (ranks <= slack))
    return jnp.max(keep.astype(jnp.int32), axis=0, keepdims=True) > 0


def _scatter_chunk(cap: int, out_capacity: int) -> int:
    """Lanes per compaction step: the widest whole-vreg divisor of ``cap``
    whose [out_capacity, chunk] one-hot tile stays within
    ``_SCATTER_ELEMS``."""
    vregs = cap // _LANES
    fit = max(1, _SCATTER_ELEMS // (out_capacity * _LANES))
    return _LANES * max(d for d in range(1, min(vregs, fit) + 1)
                        if vregs % d == 0)


def _compact(values_ref, strata_ref, dest_ref, keep, out_capacity: int):
    """Scatter the kept items of a (1, cap) buffer to the front of a
    (1, out_capacity) row, in position order: a prefix sum gives each
    kept item its destination (−1 = not moved, staged in ``dest_ref``),
    and a one-hot [out_capacity, chunk] matrix per lane chunk moves it on
    the MXU (exact: one hit per destination, f32 contraction). The chunk
    loop is a ``fori_loop``, so one tile is live at a time."""
    cap = keep.shape[1]
    keep_i = keep.astype(jnp.int32)
    dest = _prefix_sum(keep_i) - 1
    dest_ref[...] = jnp.where(keep & (dest < out_capacity), dest, -1)
    chunk = _scatter_chunk(cap, out_capacity)
    dims = (((1,), (1,)), ((), ()))
    slots = jax.lax.broadcasted_iota(jnp.int32, (out_capacity, chunk), 0)

    def body(i, acc):
        vals, strc = acc
        lanes = pl.ds(pl.multiple_of(i * chunk, _LANES), chunk)
        dmat = jnp.where(slots == dest_ref[:, lanes], 1.0, 0.0)
        vals = vals + jax.lax.dot_general(
            values_ref[:, lanes], dmat, dims, precision=_HIGHEST,
            preferred_element_type=jnp.float32)
        # Stratum ids are small ints (≪ 2^24): the f32 scatter is exact.
        strc = strc + jax.lax.dot_general(
            strata_ref[:, lanes].astype(jnp.float32), dmat, dims,
            precision=_HIGHEST, preferred_element_type=jnp.float32)
        return vals, strc

    zeros = jnp.zeros((1, out_capacity), jnp.float32)
    vals, strc = jax.lax.fori_loop(0, cap // chunk, body, (zeros, zeros))
    return vals, strc.astype(jnp.int32), jnp.sum(keep_i)


def _kernel(values_ref, strata_ref, valid_ref, prio_ref, win_ref, cin_ref,
            size_ref, keep_ref, vals_ref, strc_ref, nk_ref, c_ref, res_ref,
            y_ref, w_ref, cout_ref, dest_ref, *, num_strata: int,
            out_capacity: int, allocation: str, async_calibration: bool):
    v = values_ref[...]                                      # (1, cap)
    s = strata_ref[...]
    m = valid_ref[...]
    u = prio_ref[...]
    cap = v.shape[1]
    onehot = _onehot(s, m, num_strata)                       # [X, cap]
    onehot_f = onehot.astype(jnp.float32)

    # --- counts + reservoir allocation (VMEM-resident accumulators) ------
    c = _stratum_sums(jnp.ones_like(v), onehot_f)            # f32[X]
    stds = None
    if allocation == "neyman":
        # Per-stratum value moments on the MXU: invalid items contribute
        # nothing (their one-hot column is all-zero).
        s1 = _stratum_sums(v, onehot_f)
        s2 = _stratum_sums(v * v, onehot_f)
        safe = jnp.maximum(c, 1.0)
        var = jnp.maximum(s2 / safe - jnp.square(s1 / safe), 0.0)
        stds = jnp.sqrt(var)
    reservoirs = allocate_reservoirs(size_ref[0, 0], c, policy=allocation,
                                     stds=stds)
    c_ref[0, :] = c
    res_ref[0, :] = reservoirs

    # --- weight update (Alg. 2 lines 12-20 + Eq. 9) ----------------------
    y, meta = _whs_meta(c, reservoirs, win_ref[0, :], cin_ref[0, :],
                        async_calibration)
    y_ref[0, :] = y
    w_ref[0, :] = meta.weight
    cout_ref[0, :] = meta.count

    # --- selection, with the saturation fast path ------------------------
    saturated = jnp.all(reservoirs >= c)

    @pl.when(saturated)
    def _keep_all():
        # N_i ≥ c_i everywhere: τ sinks below every priority, ties resolve
        # to "keep all" — the mask is provably ``valid``. Skips the whole
        # threshold search (the fraction-1.0 exact path).
        keep_ref[...] = m

    @pl.when(jnp.logical_not(saturated))
    def _select():
        keep_ref[...] = _select_block(u, onehot, reservoirs, c)

    # --- compaction ------------------------------------------------------
    keep = keep_ref[...]
    n_valid = jnp.sum(m.astype(jnp.int32))
    iota_cap = jax.lax.broadcasted_iota(jnp.int32, (1, cap), 1)
    front_packed = jnp.all(m == (iota_cap < n_valid))
    out_iota = jax.lax.broadcasted_iota(jnp.int32, (1, out_capacity), 1)

    @pl.when(saturated & front_packed)
    def _passthrough():
        # Everything valid is kept and already front-packed: compaction is
        # a truncating copy (zeros beyond the kept range, matching the
        # scatter path bit-for-bit).
        n_keep = jnp.minimum(n_valid, out_capacity)
        live = out_iota < n_keep
        vals_ref[...] = jnp.where(live, v[:, :out_capacity], 0.0)
        strc_ref[...] = jnp.where(live, s[:, :out_capacity], 0)
        nk_ref[...] = jnp.full((1, 1), n_valid, jnp.int32)

    @pl.when(jnp.logical_not(saturated & front_packed))
    def _pack():
        vals, strc, n_keep = _compact(values_ref, strata_ref, dest_ref, keep,
                                      out_capacity)
        vals_ref[...] = vals
        strc_ref[...] = strc
        nk_ref[...] = jnp.full((1, 1), n_keep, jnp.int32)


def _pad_lanes(x: jnp.ndarray) -> jnp.ndarray:
    """Pad the item axis to a whole number of 128-lane vregs (padding is
    invalid, so it is never counted, kept or moved)."""
    pad = (-x.shape[-1]) % _LANES
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


@functools.partial(
    jax.jit,
    static_argnames=("num_strata", "out_capacity", "allocation",
                     "async_calibration", "interpret"))
def fused_level_tick(
    values: jnp.ndarray,      # f32[n, cap]
    strata: jnp.ndarray,      # i32[n, cap]
    valid: jnp.ndarray,       # bool[n, cap]
    priorities: jnp.ndarray,  # f32[n, cap]
    w_in: jnp.ndarray,        # f32[n, X]
    c_in: jnp.ndarray,        # f32[n, X]
    sample_size: jnp.ndarray,  # f32[] level budget
    num_strata: int,
    out_capacity: int,
    *,
    allocation: str = "fair",
    async_calibration: bool = True,
    interpret: bool = True,
):
    """Run the fused WHS tick over a stacked level (one grid step/node).

    Returns ``(keep, values_c, strata_c, n_keep, c, reservoirs, y, w_out,
    c_out)`` — the keep mask ``bool[n, cap]``, the compacted forwarding
    buffers ``[n, out_capacity]`` + per-node kept counts ``i32[n]``, and
    the per-stratum ``f32[n, X]`` accumulators (counts, reservoirs, Y,
    W^out, C^out).
    """
    n, cap = values.shape
    x = w_in.shape[-1]
    size2 = jnp.broadcast_to(
        jnp.asarray(sample_size, jnp.float32).reshape(1, 1), (1, 1))
    # One node's [1, ·] rows per grid step (``kernels.row_spec``).
    values, strata, valid, priorities = (
        _pad_lanes(a).reshape(n, 1, -1)
        for a in (values, strata, valid, priorities))
    cap_p = values.shape[-1]
    operands = (values, strata, valid, priorities, w_in.reshape(n, 1, x),
                c_in.reshape(n, 1, x), size2)

    outs = pl.pallas_call(
        functools.partial(_kernel, num_strata=x, out_capacity=out_capacity,
                          allocation=allocation,
                          async_calibration=async_calibration),
        grid=(n,),
        in_specs=[row_spec(cap_p)] * 4 + [row_spec(x)] * 2
        + [pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=[row_spec(cap_p), row_spec(out_capacity),
                   row_spec(out_capacity), row_spec(1)]
        + [row_spec(x)] * 5,
        out_shape=[out_struct(shape, dt, *operands) for shape, dt in (
            [((n, 1, cap_p), jnp.bool_),
             ((n, 1, out_capacity), jnp.float32),
             ((n, 1, out_capacity), jnp.int32),
             ((n, 1, 1), jnp.int32)] + [((n, 1, x), jnp.float32)] * 5)],
        scratch_shapes=[pltpu.VMEM((1, cap_p), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)
    keep, vals_c, strata_c, nk, c, res, y, w_out, c_out = (
        o[:, 0] for o in outs)
    return (keep[:, :cap], vals_c, strata_c, nk[:, 0], c, res, y, w_out,
            c_out)


def _select_kernel(prio_ref, strata_ref, valid_ref, res_ref, keep_ref, *,
                   num_strata: int):
    u = prio_ref[...]
    m = valid_ref[...]
    onehot = _onehot(strata_ref[...], m, num_strata)
    c = _stratum_sums(jnp.ones_like(u), onehot.astype(jnp.float32))
    reservoirs = res_ref[0, :]
    saturated = jnp.all(reservoirs >= c)

    @pl.when(saturated)
    def _keep_all():
        keep_ref[...] = m

    @pl.when(jnp.logical_not(saturated))
    def _select():
        keep_ref[...] = _select_block(u, onehot, reservoirs, c)


@functools.partial(jax.jit, static_argnames=("num_strata", "interpret"))
def fused_select(
    priorities: jnp.ndarray,  # f32[M]
    strata: jnp.ndarray,      # i32[M]
    valid: jnp.ndarray,       # bool[M]
    reservoirs: jnp.ndarray,  # f32[X]
    num_strata: int,
    *,
    interpret: bool = True,
) -> jnp.ndarray:
    """Selection-only entry (the ``SamplerBackend.select`` contract):
    caller-provided reservoirs, same τ search + tie law, bool[M] mask."""
    m_items = priorities.shape[0]
    u, s, m = (_pad_lanes(a.reshape(1, -1))
               for a in (priorities, strata, valid))
    return pl.pallas_call(
        functools.partial(_select_kernel, num_strata=num_strata),
        out_shape=out_struct(u.shape, jnp.bool_, u, s, m, reservoirs),
        interpret=interpret,
    )(u, s, m, reservoirs.reshape(1, -1))[0, :m_items]
