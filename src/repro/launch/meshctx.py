"""Mesh context: logical-axis sharding constraints that degrade gracefully.

Model code annotates activations with *logical* axes ("batch", "model",
"seq", ...). When a mesh is installed (launch/dry-run), these resolve to
``with_sharding_constraint`` over physical axes; in single-device smoke
tests they are no-ops. Batch maps to ``("pod","data")`` when a pod axis
exists, so the same model code serves both production meshes.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

# logical name -> candidate physical axes, first present in the mesh win(s).
_LOGICAL = {
    "batch": ("pod", "data"),       # all present axes combined
    "fsdp": ("data",),              # weight-shard axis
    "fsdp_pod": ("pod", "data"),    # weight-shard incl. pod (ZeRO across pods)
    "model": ("model",),
    "expert": ("model",),
    None: (),
}


def current_mesh() -> Mesh | None:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    # The constraints below are Auto-mode sharding; ``jax.make_mesh``
    # builds Explicit axes, whose arrays carry sharding in their types
    # and refuse ``with_sharding_constraint``. Install the same devices
    # and axes as Auto.
    auto = jax.sharding.AxisType.Auto
    if any(t != auto for t in mesh.axis_types):
        mesh = Mesh(mesh.devices, mesh.axis_names,
                    axis_types=(auto,) * len(mesh.axis_names))
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        with mesh:  # legacy Mesh context (axis-name resolution for pjit)
            yield mesh
    finally:
        _state.mesh = prev


def resolve_spec(*logical: str | None) -> P:
    """Translate logical axis names into a PartitionSpec for current mesh."""
    mesh = current_mesh()
    names = set(mesh.axis_names) if mesh is not None else set()
    out = []
    for ax in logical:
        if ax is None:
            out.append(None)
            continue
        phys = tuple(a for a in _LOGICAL.get(ax, (ax,)) if a in names)
        if not phys:
            out.append(None)
        elif len(phys) == 1:
            out.append(phys[0])
        else:
            out.append(phys)
    return P(*out)


def shard(x: jax.Array, *logical: str | None) -> jax.Array:
    """Constrain activation sharding; identity when no mesh installed."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, resolve_spec(*logical))
    )
