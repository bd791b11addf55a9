"""Pallas TPU kernels of the sampler and the query plane. Each package
holds the kernel, an ``ops.py`` dispatch and a ``ref.py`` jnp oracle."""
import jax
from jax.experimental import pallas as pl


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A ``pallas_call`` output that varies over the mesh axes its
    operands vary over. ``shard_map(check_vma=True)`` needs this declared
    on every kernel output; outside ``shard_map`` the set is empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def row_spec(width: int) -> pl.BlockSpec:
    """One ``(1, width)`` row of an ``[n, 1, width]`` array per grid
    step. The TPU lowering takes a block whose last two dims are not
    (8, 128)-aligned only where they equal the array's, which a ``(1, ·)``
    row of an ``[n, width]`` array does not; the unit middle axis makes
    them equal."""
    return pl.BlockSpec((None, 1, width), lambda i: (i, 0, 0))
