"""The main path's Pallas kernels, compiled for a TPU v5e that is described
and not attached.

JAX's TPU compiler runs here without a chip: lowering and compiling a
kernel with ``interpret=False`` for a device of a described ``v5e:2x2``
topology raises what the chip's compiler would raise (block shapes the
lowering refuses, primitives Mosaic lacks, VMEM overruns). Each test
compiles one kernel at the shapes the system runs it at and asserts the
program carries the Mosaic kernel (``tpu_custom_call``); where a shape is
outside a kernel's envelope, spec validation must refuse it instead.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU library, and it compiles in
its own process.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.api.spec import SpecError, check_kernel_envelope
from repro.kernels.fused_level_tick import fused_level_tick as ft
from repro.kernels.sample_mask import sample_mask as sm
from repro.kernels.sketch_update import sketch_update as sk
from repro.kernels.stratified_stats import stratified_stats as ss

X = 4                      # the paper's four sub-streams
NODES = 4                  # level-0 nodes of the paper tree
ROOT_ITEMS = 34_610        # root buffer of the paper testbed at fraction 0.1


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip cannot be read back from the
    # persistent cache, so keep these compiles out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _compiled_text(one_chip, fn, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_cms_update_compiles_at_root_width(one_chip):
    """The decayed heavy hitters' count-min (depth 4, width 256) over one
    root window — the kernel the serve loop reaches on TPU."""
    text = _compiled_text(
        one_chip, lambda k, w: sk.cms_update(k, w, 4, 256, interpret=False),
        ((ROOT_ITEMS,), jnp.uint32), ((ROOT_ITEMS,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("targets", [128, 256])
def test_quantile_compact_compiles_at_root_width(one_chip, targets):
    """A capacity-256 KLL level folding a root window: 128 survivors
    pushed up, or 256 kept in place at the top level."""
    p = ROOT_ITEMS + 256
    text = _compiled_text(
        one_chip, lambda v, a, c, t: sk.quantile_compact(v, a, c, t,
                                                         interpret=False),
        ((p,), jnp.float32), ((p,), jnp.float32), ((p,), jnp.float32),
        ((targets,), jnp.float32))
    assert "tpu_custom_call" in text


def test_stratified_stats_compiles_for_a_flattened_level(one_chip):
    """The ``pallas`` backend's counts pass over one level flattened into
    composite strata: 4 nodes × 8192 items × 4 strata."""
    m, x = NODES * ft.MAX_CAPACITY, NODES * X
    text = _compiled_text(
        one_chip, lambda v, s, k: ss.stratified_stats(v, s, k, x,
                                                      interpret=False),
        ((m,), jnp.float32), ((m,), jnp.int32), ((m,), jnp.bool_))
    assert "tpu_custom_call" in text


def test_sample_mask_compiles_for_a_flattened_level(one_chip):
    m, x = NODES * ft.MAX_CAPACITY, NODES * X
    text = _compiled_text(
        one_chip, lambda u, s, k, t, w: sm.sample_mask(u, s, k, t, w,
                                                       interpret=False),
        ((m,), jnp.float32), ((m,), jnp.int32), ((m,), jnp.bool_),
        ((x,), jnp.float32), ((x,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("allocation,x,fraction", [
    ("fair", X, 0.1),                  # the paper tree's level 0
    ("neyman", ft.MAX_STRATA, 1.0),    # the envelope's widest corner
])
def test_fused_level_tick_compiles_at_its_envelope(one_chip, allocation, x,
                                                  fraction):
    """4 nodes × cap 8192: the one-hot views and the chunked compaction
    fit the kernel's VMEM limit up to X=32 and out_capacity == cap."""
    n, cap = NODES, ft.MAX_CAPACITY
    text = _compiled_text(
        one_chip,
        lambda *a: ft.fused_level_tick(*a, x, int(cap * fraction),
                                       allocation=allocation,
                                       interpret=False),
        ((n, cap), jnp.float32), ((n, cap), jnp.int32),
        ((n, cap), jnp.bool_), ((n, cap), jnp.float32),
        ((n, x), jnp.float32), ((n, x), jnp.float32), ((), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("x", [X, ft.MAX_STRATA])
def test_fused_select_compiles_at_its_envelope(one_chip, x):
    m = ft.MAX_CAPACITY
    text = _compiled_text(
        one_chip, lambda u, s, k, r: ft.fused_select(u, s, k, r, x,
                                                     interpret=False),
        ((m,), jnp.float32), ((m,), jnp.int32), ((m,), jnp.bool_),
        ((x,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backend", ["pallas", "pallas_fused"])
@pytest.mark.parametrize("tenants", [True, False])
def test_mesh_epoch_compiles_with_vma_check(v5e, monkeypatch, backend,
                                            tenants):
    """The SPMD epoch with a Pallas sampler backend, its kernels compiled
    (not interpreted), on a described 4-chip ``data`` mesh: on TPU the
    ``shard_map`` keeps its varying-axes check on, and every kernel
    output's declared vma passes it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.api import spmd
    from repro.core.types import IntervalBatch, StratumMeta
    from repro.kernels.fused_level_tick import ops as ft_ops
    from repro.kernels.sample_mask import ops as sm_ops
    from repro.kernels.sketch_update import ops as sk_ops
    from repro.kernels.stratified_stats import ops as ss_ops
    from repro.query.registry import QueryRegistry

    for ops in (ft_ops, sm_ops, sk_ops, ss_ops):
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(v5e.devices[:4]), ("data",))
    reg = (QueryRegistry().register_sum().register_count().register_mean()
           .register_quantile("q", (0.5,), capacity=64)
           .register_heavy_hitters("hh", k=4, width=64, depth=2))
    m, t = NODES * 2048, 2
    pipe = api.compile(api.PipelineSpec(
        topology=api.TopologySpec(capacity=m // NODES, num_strata=X),
        sampler=api.SamplerSpec(fraction=0.1, backend=backend),
        tenants=(api.TenantSpec.from_registry("a", reg),) if tenants
        else ()), mesh=mesh)
    rep, dev = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    item = NamedSharding(mesh, P(None, "data"))
    sds = lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
    f32 = lambda shape, sh: jax.ShapeDtypeStruct(shape, jnp.float32,
                                                 sharding=sh)
    batch = IntervalBatch(
        f32((t, m), item), jax.ShapeDtypeStruct((t, m), jnp.int32,
                                                sharding=item),
        jax.ShapeDtypeStruct((t, m), jnp.bool_, sharding=item),
        StratumMeta(f32((t, X), rep), f32((t, X), rep)))
    key = sds(jax.eval_shape(lambda: pipe.default_key), rep)
    if tenants:
        st = jax.eval_shape(pipe.init)
        state = st._replace(tick=sds(st.tick, rep), qstate=jax.tree.map(
            lambda a: sds(a, dev), st.qstate))
        lowered = pipe._fn.lower(state, key, f32((), rep), batch)
    else:
        lowered = pipe._fn.lower(key, batch)
    assert spmd._check_vma(backend)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_fused_selection_outside_its_envelope_is_refused_on_tpu(monkeypatch):
    """A ``pallas_fused`` selection over more items than the kernel
    compiles for — one device's shard of a 1-device mesh over the four
    nodes' buffers — raises the SpecError when traced on TPU instead of
    reaching Mosaic; inside the envelope it traces."""
    from repro.core import sampling

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    be = sampling.get_backend("pallas_fused")

    def select(m, x):
        return jax.eval_shape(
            lambda s, v: be.select(jax.random.key(0), s, v,
                                   jnp.ones((x,), jnp.float32), x,
                                   priorities=jnp.zeros(s.shape)),
            jax.ShapeDtypeStruct((m,), jnp.int32),
            jax.ShapeDtypeStruct((m,), jnp.bool_))

    with pytest.raises(SpecError, match="pallas_fused.*8192 items"):
        select(NODES * ft.MAX_CAPACITY, X)
    with pytest.raises(SpecError, match="at most 32 strata"):
        select(ft.MAX_CAPACITY, ft.MAX_STRATA + 1)


def test_fused_backend_outside_its_envelope_is_refused_on_tpu():
    """A ``pallas_fused`` spec whose level-0 buffer exceeds the kernel's
    envelope is refused with an actionable SpecError on TPU — never
    rerouted to another sampler — and stays valid where the kernel runs
    in the interpreter."""
    def spec(capacity, num_strata=X):
        return api.PipelineSpec(
            topology=api.TopologySpec(capacity=capacity,
                                      num_strata=num_strata),
            sampler=api.SamplerSpec(backend="pallas_fused", fraction=0.1))

    check_kernel_envelope(spec(ft.MAX_CAPACITY), "tpu")
    with pytest.raises(SpecError, match="pallas_fused.*8192 items"):
        check_kernel_envelope(spec(2 * ft.MAX_CAPACITY), "tpu")
    with pytest.raises(SpecError, match="at most 32 strata"):
        check_kernel_envelope(spec(1024, ft.MAX_STRATA + 1), "tpu")
    check_kernel_envelope(spec(2 * ft.MAX_CAPACITY), "cpu")
