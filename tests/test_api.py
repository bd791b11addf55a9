"""Declarative pipeline API: spec validation + serialization, compiled
``init``/``step``/``run_epoch`` bit-equivalence with every legacy
``HostTree`` engine, multi-tenant answer routing ≡ isolated runs,
checkpoint/resume bitwise identity, the back-compat shim, and the SPMD
lowering of the same spec."""
import jax
import numpy as np
import pytest

from repro import api
from repro.api import (BudgetSpec, PipelineSpec, SamplerSpec, SpecError,
                       TenantSpec, TopologySpec)
from repro.core.tree import HostTree
from repro.data import stream as S
from repro.query.registry import QueryRegistry, QuerySpec

X = 3


def _spec(mode="whs", backend="topk", tenants=(), iv=None, seed=5,
          sizes=(96, 96, 96), capacity=768, max_sizes=None):
    return PipelineSpec(
        topology=TopologySpec(fanin=(4, 2, 1), capacity=capacity,
                              interval_ticks=iv, num_strata=X),
        sampler=SamplerSpec(mode=mode, backend=backend,
                            fraction=0.25 if mode == "srs" else None),
        tenants=tuple(tenants),
        budget=BudgetSpec(sample_sizes=sizes, max_sample_sizes=max_sizes),
        seed=seed,
    )


def _legacy_tree(spec: PipelineSpec, engine: str) -> HostTree:
    """The old constructor path (NOT from_spec) — what pre-API callers
    wrote, for shim equivalence checks."""
    return HostTree(
        fanin=list(spec.topology.fanin), num_strata=X,
        capacity=spec.topology.capacity,
        sample_sizes=list(spec.budget.sample_sizes),
        interval_ticks=(list(spec.topology.interval_ticks)
                        if spec.topology.interval_ticks else None),
        seed=spec.seed, mode=spec.sampler.mode,
        fraction=spec.sampler.fraction, engine=engine,
        sampler_backend=spec.sampler.backend,
        queries=(QueryRegistry(list(spec.tenants[0].queries))
                 if spec.tenants else None),
        max_sample_sizes=(list(spec.budget.max_sample_sizes)
                          if spec.budget.max_sample_sizes else None))


def _ingest(ticks, n0=4, width=400, seed=11):
    rng = np.random.default_rng(seed)
    vals = rng.normal(50, 9, (ticks, n0, width)).astype(np.float32)
    strs = rng.integers(0, X, (ticks, n0, width)).astype(np.int32)
    counts = rng.integers(100, width, (ticks, n0)).astype(np.int32)
    return vals, strs, counts


def _run_sequential(tree, vals, strs, counts):
    ticks, n0, _ = vals.shape
    for t in range(1, ticks + 1):
        for node in range(n0):
            c = counts[t - 1, node]
            tree.ingest(node, vals[t - 1, node, :c], strs[t - 1, node, :c])
        tree.tick(t)


def _assert_rows_equal(rows, ref_results):
    assert len(rows) == len(ref_results) > 0
    for ra, rb in zip(rows, ref_results):
        for k in ("tick", "sum", "sum_var", "mean", "mean_var", "n_sampled"):
            assert ra[k] == rb[k], k
        np.testing.assert_array_equal(ra["histogram"], rb["histogram"])
        if "answers" in rb:
            np.testing.assert_array_equal(ra["answers"], rb["answers"])
            np.testing.assert_array_equal(ra["bounds"], rb["bounds"])


def _reg_a():
    return (QueryRegistry().register_sum().register_mean()
            .register_quantile("q", (0.5, 0.9), capacity=64))


def _reg_b():
    return (QueryRegistry().register_count()
            .register_histogram("h", 0.0, 100.0, 8)
            .register_heavy_hitters("hh", k=4, width=256))


# ------------------------------------------------- old ≡ new, bitwise --
@pytest.mark.parametrize("engine,mode,backend", [
    ("loop", "whs", "topk"),
    ("level", "whs", "topk"),
    ("scan", "whs", "topk"),
    ("loop", "srs", "topk"),
    ("scan", "srs", "topk"),
    ("scan", "whs", "argsort"),
    ("level", "whs", "argsort"),
])
def test_compiled_matches_host_tree(engine, mode, backend):
    """compile(spec).run_epoch ≡ the pre-refactor HostTree engines, to
    the bit (results, forwarded counts) on identical ingest."""
    vals, strs, counts = _ingest(4)
    spec = _spec(mode=mode, backend=backend)
    ref = _legacy_tree(spec, engine)
    if engine == "scan":
        ref.run_epoch(1, vals, strs, counts)
    else:
        _run_sequential(ref, vals, strs, counts)
    pipe = api.compile(spec)
    state, wa = pipe.run_epoch(pipe.init(), pipe.default_key, vals, strs,
                               counts)
    _assert_rows_equal(pipe.rows(wa), ref.results)
    n_fwd = np.asarray(wa.n_forwarded)
    fwd = [int(n_fwd[:, l].sum()) for l in range(len(pipe.fanin) - 1)] + [0]
    assert fwd == ref.items_forwarded


def test_compiled_matches_host_tree_async_intervals():
    vals, strs, counts = _ingest(6)
    spec = _spec(iv=(1, 2, 3))
    ref = _legacy_tree(spec, "loop")
    _run_sequential(ref, vals, strs, counts)
    pipe = api.compile(spec)
    _, wa = pipe.run_epoch(pipe.init(), pipe.default_key, vals, strs, counts)
    _assert_rows_equal(pipe.rows(wa), ref.results)


def test_compiled_sample_state_matches_scan_engine():
    """The donated PipelineState.tree is bit-identical to the HostTree
    scan engine's TreeState after the same epoch."""
    vals, strs, counts = _ingest(4)
    spec = _spec()
    ref = _legacy_tree(spec, "scan")
    ref.run_epoch(1, vals, strs, counts)
    pipe = api.compile(spec)
    state, _ = pipe.run_epoch(pipe.init(), pipe.default_key, vals, strs,
                              counts)
    for la, lb in zip(jax.tree.leaves(state.tree),
                      jax.tree.leaves(ref._state)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_fig_config_pipeline_matches_host_tree():
    """The fig7/fig8 configuration (paper_gaussian + the standing-query
    registry through run_pipeline's spec builder): compiled answers ≡
    the HostTree scan engine bitwise."""
    from repro.launch.analytics import build_spec

    reg = (QueryRegistry().register_sum().register_count()
           .register_quantile("q", (0.5, 0.9, 0.99), capacity=128))
    streams = S.paper_gaussian(rates=(300, 300, 300, 300))
    spec = build_spec(streams, fraction=0.1, seed=7, queries=reg)
    sources = [S.StreamSource(streams, seed=7 * 977 + i) for i in range(8)]
    b = S.batch_ingest(sources, 5, 4, spec.topology.capacity)

    ref = HostTree.from_spec(spec, engine="scan")
    ref.run_epoch(1, b.values, b.strata, b.counts, offered=b.offered)
    pipe = api.compile(spec)
    _, wa = pipe.run_epoch(pipe.init(), pipe.default_key, b.values,
                           b.strata, b.counts)
    _assert_rows_equal(pipe.rows(wa), ref.results)


def test_step_equals_run_epoch():
    """T single-tick step() calls ≡ one T-tick run_epoch (same fused
    tick at the level/loop dispatch granularity)."""
    vals, strs, counts = _ingest(3)
    pipe = api.compile(_spec())
    sa = pipe.init()
    rows_stepped = []
    for t in range(3):
        sa, wa = pipe.step(sa, pipe.default_key, vals[t], strs[t], counts[t])
        rows_stepped.extend(pipe.rows(wa))
    sb, wb = pipe.run_epoch(pipe.init(), pipe.default_key, vals, strs,
                            counts)
    _assert_rows_equal(rows_stepped, pipe.rows(wb))
    for la, lb in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_budgets_are_traced_zero_retrace():
    """Moving per-level budgets between epochs reuses the compiled
    program (the closed-loop controller's zero-retrace contract)."""
    vals, strs, counts = _ingest(2)
    pipe = api.compile(_spec(sizes=(64, 64, 64), max_sizes=(96, 96, 96)))
    st = pipe.init()
    st, _ = pipe.run_epoch(st, pipe.default_key, vals, strs, counts)
    traces = pipe.trace_counter["traces"]
    st, _ = pipe.run_epoch(st, pipe.default_key, vals, strs, counts,
                           budgets=[96, 80, 72])
    assert pipe.trace_counter["traces"] == traces
    # ...and clamped to the provisioned ceilings
    assert pipe.clamp_budgets([500, 0.2, 80]) == [96.0, 1.0, 80.0]


# ------------------------------------------------------- multi-tenant --
def test_two_tenants_match_isolated_single_tenant_runs():
    """A 2-tenant spec returns per-tenant answers matching isolated
    single-tenant runs bitwise, while sharing ONE tree dispatch per
    epoch (identical sample state, one fused answer vector)."""
    vals, strs, counts = _ingest(4)
    both = api.compile(_spec(tenants=(_reg_a().as_tenant("alpha"),
                                      _reg_b().as_tenant("beta"))))
    alpha = api.compile(_spec(tenants=(_reg_a().as_tenant("alpha"),)))
    beta = api.compile(_spec(tenants=(_reg_b().as_tenant("beta"),)))

    run = lambda p: p.run_epoch(p.init(), p.default_key, vals, strs, counts)
    s2, w2 = run(both)
    sa, wa = run(alpha)
    sb, wb = run(beta)
    for t, w1 in (("alpha", wa), ("beta", wb)):
        np.testing.assert_array_equal(
            both.tenant_answers(np.asarray(w2.answers), t),
            np.asarray(w1.answers))
        np.testing.assert_array_equal(
            both.tenant_answers(np.asarray(w2.bounds), t),
            np.asarray(w1.bounds))
    # shared tree: sample state identical with 0, 1, or 2 tenants
    for la, lb in zip(jax.tree.leaves(s2.tree._replace(qstate=())),
                      jax.tree.leaves(sa.tree._replace(qstate=()))):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # per-tenant routing by name, and per-tenant error attribution
    lay = both.query_layout()
    assert "alpha/sum" in lay and "beta/count" in lay
    row_a, row_b = np.asarray(w2.answers)[-1], np.asarray(w2.bounds)[-1]
    rel = both.tenant_rel_errors(row_a, row_b)
    assert set(rel) == {"alpha", "beta"}
    assert rel["alpha"] > 0.0          # CLT queries vote
    assert rel["beta"] == 0.0          # count/hist/hh: no CLT vote


def test_error_budget_spec_defaults_growable_ceiling():
    """target_rel_error without an explicit ceiling provisions the full
    window (max_fraction=1.0, the legacy driver default) — otherwise
    the accuracy controller's ceiling would equal the initial budget
    and the grow loop could never move."""
    spec = PipelineSpec(
        topology=TopologySpec(fanin=(4, 2, 1), capacity=1000, num_strata=X),
        sampler=SamplerSpec(fraction=0.01),
        budget=BudgetSpec(target_rel_error=0.02))
    r = api.resolve(spec)
    assert r.sample_sizes == (10, 10, 10)
    assert r.max_sample_sizes == (1000, 1000, 1000)


def test_worst_tenant_arbiter_moves_budget_for_worst():
    from repro.runtime.budget import BudgetConfig, WorstTenantArbiter

    arb = WorstTenantArbiter(
        BudgetConfig(min_size=8, max_size=512, target_rel_error=0.02),
        initial_size=64)
    size = arb.update({"quiet": 0.001, "noisy": 0.2})
    assert arb.last_tenant == "noisy"
    assert size > 64                   # grows for the worst-off tenant
    for _ in range(30):
        size = arb.update({"quiet": 0.001, "noisy": 0.001})
    assert size < 512                  # shrinks only when all are under


# ------------------------------------------------ serialization + spec --
def test_spec_round_trip_and_hashable():
    import json

    spec = _spec(tenants=(_reg_a().as_tenant("alpha"),
                          _reg_b().as_tenant("beta")), iv=(1, 2, 4))
    spec2 = PipelineSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert spec2 == spec
    assert hash(spec2) == hash(spec)
    assert api.compile(spec) is api.compile(spec2)   # compile cache hit


@pytest.mark.parametrize("build,needle", [
    (lambda: TopologySpec(fanin=(4, 2)), "single root"),
    (lambda: TopologySpec(interval_ticks=(1, 2)), "one entry per level"),
    (lambda: SamplerSpec(mode="srs", fraction=1.7), "fraction must be in"),
    (lambda: SamplerSpec(backend="cuda"), "sampler.backend"),
    (lambda: PipelineSpec(sampler=SamplerSpec(mode="srs", fraction=0.2),
                          tenants=(_reg_a().as_tenant("a"),)),
     "WHS stratum metadata"),
    (lambda: PipelineSpec(
        topology=TopologySpec(fanin=(2, 1), capacity=64, num_strata=X),
        budget=BudgetSpec(sample_sizes=(128, 16))), "exceeds the level-0"),
    (lambda: PipelineSpec(       # pinned UPPER-level budget overflows its
        topology=TopologySpec(fanin=(4, 2, 1), capacity=1024, num_strata=X),
        budget=BudgetSpec(sample_sizes=(8, 500, 8))), "exceeds the level-1"),
    (lambda: PipelineSpec(
        budget=BudgetSpec(sample_sizes=(64,) * 3,
                          max_sample_sizes=(32,) * 3)), "dominate"),
    (lambda: PipelineSpec(tenants=(TenantSpec("a", (QuerySpec("s", "sum"),)),
                                   TenantSpec("a", (QuerySpec("c", "count"),)))),
     "duplicate tenant"),
    (lambda: PipelineSpec.from_dict({"topology": {"bogus": 3}}),
     "unknown keys"),
    (lambda: PipelineSpec.from_dict({"version": 9}), "version"),
])
def test_spec_errors_are_actionable(build, needle):
    with pytest.raises(SpecError, match=needle):
        build()


# --------------------------------------------------------- checkpoint --
def test_checkpoint_resume_bitwise_identical(tmp_path):
    """save → restore → continue ≡ an uninterrupted run, to the bit
    (answers AND every state leaf), across a fresh compile from the
    serialized spec."""
    vals, strs, counts = _ingest(6)
    spec = _spec(tenants=(_reg_a().as_tenant("alpha"),))

    pipe = api.compile(spec)
    st = pipe.init()
    st, wa1 = pipe.run_epoch(st, pipe.default_key, vals[:3], strs[:3],
                             counts[:3])
    api.save_state(tmp_path / "ck", 1, st, spec=spec)
    st, wa2 = pipe.run_epoch(st, pipe.default_key, vals[3:], strs[3:],
                             counts[3:])
    rows_uninterrupted = pipe.rows(wa2)

    pipe2 = api.compile(PipelineSpec.from_dict(spec.to_dict()))
    st2, meta = api.restore_state(tmp_path / "ck", pipe2)
    assert meta["pipeline_spec"] == spec.to_dict()
    st2, wb2 = pipe2.run_epoch(st2, pipe2.default_key, vals[3:], strs[3:],
                               counts[3:])
    _assert_rows_equal(pipe2.rows(wb2), rows_uninterrupted)
    for la, lb in zip(jax.tree.leaves(st), jax.tree.leaves(st2)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_checkpoint_restore_rejects_wrong_spec(tmp_path):
    spec = _spec()
    pipe = api.compile(spec)
    api.save_state(tmp_path / "ck", 1, pipe.init(), spec=spec)
    other = api.compile(_spec(seed=6))
    with pytest.raises(SpecError, match="different PipelineSpec"):
        api.restore_state(tmp_path / "ck", other)


# --------------------------------------------------------------- shim --
def test_host_tree_from_spec_shim_smoke():
    """HostTree.from_spec(spec) ≡ the legacy keyword constructor, and
    the legacy build_tree wrapper still stands."""
    from repro.launch.analytics import build_tree

    vals, strs, counts = _ingest(3)
    spec = _spec(tenants=(_reg_a().as_tenant("alpha"),))
    old = _legacy_tree(spec, "level")
    new = HostTree.from_spec(spec, engine="level")
    _run_sequential(old, vals, strs, counts)
    _run_sequential(new, vals, strs, counts)
    _assert_rows_equal(new.results, old.results)

    t = build_tree(X, 768, 0.125, engine="level")
    assert t.sample_sizes == [96, 96, 96]   # fraction × capacity


def test_run_pipeline_accepts_explicit_spec():
    from repro.launch.analytics import build_spec, run_pipeline

    streams = S.paper_gaussian(rates=(120,) * 4)
    spec = build_spec(streams, fraction=0.2, seed=3)
    a = run_pipeline(streams, pipeline_spec=spec, ticks=4, engine="scan")
    b = run_pipeline(streams, fraction=0.2, seed=3, ticks=4, engine="scan")
    assert a["approx_sum"] == b["approx_sum"]
    assert a["dispatches"] == 1


# --------------------------------------------------------------- spmd --
def test_compile_with_mesh_matches_spmd_epoch():
    """compile(spec, mesh=...) ≡ direct per-interval
    spmd_local_then_root calls on a 1-device mesh, bit-for-bit."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.tree import spmd_local_then_root
    from repro.core.types import IntervalBatch, StratumMeta

    m, ticks = 256, 3
    rng = np.random.default_rng(0)
    batches = IntervalBatch(
        value=jnp.asarray(rng.normal(100, 10, (ticks, m)), jnp.float32),
        stratum=jnp.asarray(rng.integers(0, X, (ticks, m)), jnp.int32),
        valid=jnp.ones((ticks, m), bool),
        meta=StratumMeta(jnp.ones((ticks, X)), jnp.zeros((ticks, X))))
    mesh = jax.make_mesh((1,), ("data",))
    spec = _spec(sizes=(32, 32, 64))
    pipe = api.compile(spec, mesh=mesh)
    assert pipe.local_budget == 32 and pipe.root_budget == 64
    state, (s_t, m_t) = pipe.run_epoch(pipe.init(), pipe.default_key,
                                       batches)

    spec1 = IntervalBatch(P("data"), P("data"), P("data"),
                          StratumMeta(P(), P()))
    one = jax.jit(jax.shard_map(
        lambda k, b: spmd_local_then_root(
            k, b, axis_name="data", num_strata=X, local_budget=32,
            root_budget=64, allocation="fair", sampler_backend="topk"),
        mesh=mesh, in_specs=(P(), spec1), out_specs=(P(), P())))
    for i in range(ticks):
        b = IntervalBatch(batches.value[i], batches.stratum[i],
                          batches.valid[i],
                          StratumMeta(batches.meta.weight[i],
                                      batches.meta.count[i]))
        s1, m1 = one(jax.random.fold_in(pipe.default_key, i), b)
        assert float(s1.estimate) == float(s_t.estimate[i])
        assert float(m1.estimate) == float(m_t.estimate[i])


def test_compile_with_mesh_accepts_tenants_and_srs():
    """The PR-5 lowering: tenant specs and the srs baseline now compile
    onto the mesh (formerly SpecError rejections); genuinely unsupported
    shapes keep actionable errors."""
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(SpecError, match="no axis"):
        api.compile(_spec(), mesh=mesh, axis_name="model")
    srs = api.compile(_spec(mode="srs"), mesh=mesh)
    assert srs.plan is None and srs.init() == ()
    tenanted = api.compile(_spec(tenants=(_reg_a().as_tenant("a"),)),
                           mesh=mesh)
    assert tenanted.plan is not None
    assert tenanted.tenant_names == ("a",)
    assert int(tenanted.init().tick) == 0
