"""Pallas TPU kernels: sketch update passes for the continuous query plane.

Two kernels, one per mergeable sketch in ``repro.query.sketches``:

``cms_update`` — count-min accumulation. Each VMEM tile of (key, weight)
pairs hashes its keys once per depth row (multiply-shift over uint32) and
hits the MXU with a one-hot bucket matrix instead of a scatter per item
(gathers/scatters are VPU-serial on TPU, one-hot matmuls are not):

    counts[d, :] += weightᵀ @ one_hot(h_d(key))          f32[1,B]@[B,W]

``quantile_compact`` — the compaction gather of the KLL-style quantile
compactor. Stage 1 (XLA: sort + cumsum) produces value-sorted summary
slots with exclusive/inclusive cumulative weights; this kernel streams
the slots once and extracts, for each of the ``C`` equi-weight rank
targets, the value of the slot whose weight interval covers it:

    picked[k] = Σ_i value_i · 1[cumw_prev_i ≤ t_k < cumw_i]

— a [B, C] interval-membership matrix contracted against the value tile
on the MXU. Intervals partition [0, W) exactly (cumw_prev is the shifted
cumsum, not ``cumw − w``, so f32 rounding cannot double- or zero-assign
a target); zero-weight slots have empty intervals and capture nothing.

The grid walks item tiles sequentially (TPU grid order), accumulating
into the same output block — the standard revisiting-output reduction
pattern, as in ``kernels/stratified_stats``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import out_struct, row_spec

_BLOCK_ITEMS = 4096
# f32 elements of in-kernel one-hot tile a kernel may materialize
# per grid step (~4 MiB) — well under a TPU core's ~16 MiB VMEM once the
# item tiles and the [depth, width] accumulator are co-resident.
_ONEHOT_BUDGET_ELEMS = 1 << 20

# Odd multiply-shift constants (xxhash/Murmur finalization primes plus
# golden-ratio mixes): h_d(x) = (A[d]·x mod 2³²) >> (32 − log₂ width).
HASH_MULTIPLIERS = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F,
                    0x165667B1, 0xD3A2646D)


def _cms_kernel(keys_ref, w_ref, mult_ref, out_ref, *, depth: int,
                width: int, shift: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    k = keys_ref[0, :]                                     # u32[B]
    w = w_ref[0, :]                                        # f32[B]
    b = k.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, width), 1)
    for d in range(depth):                                 # static, small
        bucket = jax.lax.shift_right_logical(
            k * mult_ref[0, d], jnp.uint32(shift)).astype(jnp.int32)
        onehot = jnp.where(bucket[:, None] == cols, 1.0, 0.0)
        # f32 precision: the default TPU contraction may round the
        # summed weights to bf16.
        row = jax.lax.dot_general(                         # [1,B] @ [B,W]
            w[None, :], onehot,
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        out_ref[d, :] += row[0, :]


@functools.partial(jax.jit, static_argnames=("depth", "width", "interpret"))
def cms_update(
    keys: jnp.ndarray,
    weights: jnp.ndarray,
    depth: int,
    width: int,
    *,
    interpret: bool = True,
) -> jnp.ndarray:
    """f32[depth, width] of weighted bucket increments for a key batch.

    ``keys`` u32[M], ``weights`` f32[M] (0 = masked-out item). ``width``
    must be a power of two; the caller adds the returned delta into its
    running count-min state (the pass is mergeable by construction).
    """
    assert width & (width - 1) == 0, "count-min width must be a power of 2"
    assert depth <= len(HASH_MULTIPLIERS)
    shift = 32 - (width - 1).bit_length()
    m_items = keys.shape[0]
    # The kernel's [block, width] one-hot tile must fit VMEM alongside the
    # item tiles and the [depth, width] accumulator: cap it at ~4 MiB of
    # f32 and shrink the item block as width grows (width 1024 → block
    # 1024), instead of letting block×width scale unbounded.
    block = min(_BLOCK_ITEMS, max(256, _ONEHOT_BUDGET_ELEMS // width),
                m_items)
    pad = (-m_items) % block
    if pad:
        keys = jnp.pad(keys, (0, pad))
        weights = jnp.pad(weights, (0, pad))
    n = keys.shape[0] // block
    mult = jnp.asarray(HASH_MULTIPLIERS[:depth], jnp.uint32).reshape(1, depth)

    row = row_spec(block)
    return pl.pallas_call(
        functools.partial(_cms_kernel, depth=depth, width=width, shift=shift),
        grid=(n,),
        in_specs=[row, row, pl.BlockSpec((1, depth), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((depth, width), lambda i: (0, 0)),
        out_shape=out_struct((depth, width), jnp.float32, keys, weights),
        interpret=interpret,
    )(keys.reshape(n, 1, block), weights.reshape(n, 1, block), mult)


def _compact_kernel(vals_ref, cwp_ref, cw_ref, tgt_ref, out_ref, *,
                    n_targets: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    v = vals_ref[0, :]                                     # f32[B]
    lo = cwp_ref[0, :]                                     # f32[B]
    hi = cw_ref[0, :]                                      # f32[B]
    t = tgt_ref[0, :]                                      # f32[C]
    hit = jnp.where((lo[:, None] <= t[None, :]) & (t[None, :] < hi[:, None]),
                    1.0, 0.0)                              # f32[B, C]
    # f32 precision: the picked value must come back bit-for-bit.
    picked = jax.lax.dot_general(                          # [1,B] @ [B,C]
        v[None, :], hit, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    out_ref[...] += picked


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantile_compact(
    values: jnp.ndarray,
    cumw_prev: jnp.ndarray,
    cumw: jnp.ndarray,
    targets: jnp.ndarray,
    *,
    interpret: bool = True,
) -> jnp.ndarray:
    """f32[C]: value of the slot whose weight interval covers each target.

    ``values``/``cumw_prev``/``cumw`` f32[P] are value-sorted summary
    slots with exclusive/inclusive cumulative weights; ``targets`` f32[C]
    are rank targets in [0, W). Targets at or beyond W hit no interval
    and come back 0 — the caller substitutes the max summary value.
    """
    p_items = values.shape[0]
    n_targets = targets.shape[0]
    # Same VMEM cap as the cms kernel, on the [block, C] membership tile.
    block = min(_BLOCK_ITEMS, max(256, _ONEHOT_BUDGET_ELEMS // n_targets),
                p_items)
    pad = (-p_items) % block
    if pad:
        # padded slots get an empty interval at the very top: lo == hi == W
        top = cumw[-1]
        values = jnp.pad(values, (0, pad))
        cumw_prev = jnp.pad(cumw_prev, (0, pad), constant_values=0.0)
        cumw_prev = cumw_prev.at[p_items:].set(top)
        cumw = jnp.pad(cumw, (0, pad))
        cumw = cumw.at[p_items:].set(top)
    n = values.shape[0] // block

    row = row_spec(block)
    return pl.pallas_call(
        functools.partial(_compact_kernel, n_targets=n_targets),
        grid=(n,),
        in_specs=[row, row, row,
                  pl.BlockSpec((1, n_targets), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, n_targets), lambda i: (0, 0)),
        out_shape=out_struct((1, n_targets), jnp.float32, values,
                             cumw_prev, cumw, targets),
        interpret=interpret,
    )(values.reshape(n, 1, block), cumw_prev.reshape(n, 1, block),
      cumw.reshape(n, 1, block), targets.reshape(1, n_targets))[0]
