"""Pallas TPU kernel: fused threshold-select + weight materialization.

Second stage of the TPU-native reservoir sampler: stage 1 (tiny, XLA sort
over per-stratum priorities) finds each stratum's ``N_i``-th largest
priority τ_i; this kernel then streams the item buffer once, emitting the
keep-mask and per-item weight. Lookup tables (τ, W) are broadcast to every
grid step and resolved against an [X, B] one-hot (items along lanes) by a
masked sum over its X sublanes instead of a dynamic gather — gathers are
serial on TPU, and the sum is exact (one hit per item).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import out_struct, row_spec

_BLOCK_ITEMS = 4096


def _kernel(prio_ref, strata_ref, valid_ref, tau_ref, w_ref, keep_ref, wout_ref,
            *, num_strata: int):
    u = prio_ref[0, :]
    s = strata_ref[...]                                          # i32[1, B]
    m = valid_ref[0, :]

    b = u.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (num_strata, b), 0)
    onehot = rows == s                                          # [X, B]
    tau_i = jnp.sum(jnp.where(onehot, tau_ref[...].T, 0.0), axis=0)  # [B]
    w_i = jnp.sum(jnp.where(onehot, w_ref[...].T, 0.0), axis=0)      # [B]

    keep = m & (u >= tau_i)
    keep_ref[0, :] = keep
    wout_ref[0, :] = jnp.where(keep, w_i, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sample_mask(
    priorities: jnp.ndarray,
    strata: jnp.ndarray,
    valid: jnp.ndarray,
    tau: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    interpret: bool = True,
):
    m_items = priorities.shape[0]
    num_strata = tau.shape[0]
    block = min(_BLOCK_ITEMS, m_items)
    pad = (-m_items) % block
    if pad:
        priorities = jnp.pad(priorities, (0, pad))
        strata = jnp.pad(strata, (0, pad))
        valid = jnp.pad(valid, (0, pad))
    n = priorities.shape[0] // block
    row = row_spec(block)
    table = pl.BlockSpec((1, num_strata), lambda i: (0, 0))

    keep, w = pl.pallas_call(
        functools.partial(_kernel, num_strata=num_strata),
        grid=(n,),
        in_specs=[row, row, row, table, table],
        out_specs=[row, row],
        out_shape=[out_struct((n, 1, block), dt, priorities, strata, valid,
                              tau, weights) for dt in (jnp.bool_, jnp.float32)],
        interpret=interpret,
    )(
        priorities.reshape(n, 1, block),
        strata.reshape(n, 1, block),
        valid.reshape(n, 1, block),
        tau.reshape(1, num_strata),
        weights.reshape(1, num_strata),
    )
    return keep.reshape(-1)[:m_items], w.reshape(-1)[:m_items]
