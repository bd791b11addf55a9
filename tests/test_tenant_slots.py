"""PR-7 padded-slot control plane: admit/retire as state edits.

The contracts under test:
  * admit/retire equivalence — a churned pipeline's answers are bitwise
    what a fresh compile of the same live set produces (local and mesh
    paths): slots and masking are invisible in the public vector;
  * masked-slot invariance — retired slots never perturb active
    tenants' answers, bounds, or error attribution;
  * the PR-4 two-tenant bitwise law survives any bucket size (slots
    padded by churn, then masked);
  * zero-retrace churn — recycling slots inside a bucket traces
    nothing; only crossing a bucket boundary compiles (one program per
    bucket, cached);
  * checkpoint slot manifests — restoring into a differently-churned
    pipeline is an actionable ``SpecError``, not silent mis-routing.
"""
import jax
import numpy as np
import pytest

from repro import api
from repro.api import (BudgetSpec, PipelineSpec, SamplerSpec, SpecError,
                       TenantSpec, TopologySpec)
from repro.query.registry import QueryRegistry

X = 3


def _spec(tenants, seed=5):
    return PipelineSpec(
        topology=TopologySpec(fanin=(4, 2, 1), capacity=768, num_strata=X),
        sampler=SamplerSpec(mode="whs", backend="topk"),
        tenants=tuple(tenants),
        budget=BudgetSpec(sample_sizes=(96, 96, 96)),
        seed=seed,
    )


def _reg_a():
    return (QueryRegistry().register_sum().register_mean()
            .register_quantile("q", (0.5, 0.9), capacity=64))


def _reg_b():
    return (QueryRegistry().register_count()
            .register_histogram("h", 0.0, 100.0, 8)
            .register_heavy_hitters("hh", k=4, width=256))


def _tenant(name, reg):
    return TenantSpec.from_registry(name, reg)


def _ingest(ticks=3, n0=4, width=400, seed=11):
    rng = np.random.default_rng(seed)
    vals = rng.normal(50, 9, (ticks, n0, width)).astype(np.float32)
    strs = rng.integers(0, X, (ticks, n0, width)).astype(np.int32)
    counts = rng.integers(100, width, (ticks, n0)).astype(np.int32)
    return vals, strs, counts


def _epoch(pipe, data, state=None):
    state = pipe.init() if state is None else state
    return pipe.run_epoch(state, pipe.default_key, *data)


# ---------------------------------------------------- churn equivalence --
def test_admit_equivalence_local():
    """compile({a}) + admit(b) + admit(c) ≡ compile({a,b,c}), bitwise —
    c shares a's signature, so its admit grows a slot bucket (1→2)
    rather than opening a group; the public vector must not notice."""
    data = _ingest()
    a, b = _tenant("alpha", _reg_a()), _tenant("beta", _reg_b())
    c = _tenant("gamma", _reg_a())

    fresh = api.compile(_spec((a, b, c)))
    _, w_fresh = _epoch(fresh, data)

    pipe = api.compile(_spec((a,)))
    state = pipe.init()
    pipe, state = pipe.admit(state, b)
    pipe, state = pipe.admit(state, c)
    state, w_churn = pipe.run_epoch(state, pipe.default_key, *data)

    assert pipe.tenant_names == fresh.tenant_names
    np.testing.assert_array_equal(np.asarray(w_churn.answers),
                                  np.asarray(w_fresh.answers))
    np.testing.assert_array_equal(np.asarray(w_churn.bounds),
                                  np.asarray(w_fresh.bounds))
    # churn edited the spec too: the clone is recompilable as-is
    assert tuple(t.name for t in pipe.spec.tenants) == (
        "alpha", "beta", "gamma")


def test_retire_equivalence_local():
    """compile({a,b,c}) + retire(b) answers ≡ compile({a,c}) answers,
    bitwise — b's slot stays allocated but masked, and the compacted
    public vector carries exactly the live tenants' blocks."""
    data = _ingest()
    a, b = _tenant("alpha", _reg_a()), _tenant("beta", _reg_b())
    c = _tenant("gamma", _reg_a())

    pipe = api.compile(_spec((a, b, c)))
    state = pipe.init()
    pipe, state = pipe.retire(state, "beta")
    state, w_churn = pipe.run_epoch(state, pipe.default_key, *data)

    fresh = api.compile(_spec((a, c)))
    _, w_fresh = _epoch(fresh, data)

    assert pipe.tenant_names == ("alpha", "gamma")
    np.testing.assert_array_equal(np.asarray(w_churn.answers),
                                  np.asarray(w_fresh.answers))
    np.testing.assert_array_equal(np.asarray(w_churn.bounds),
                                  np.asarray(w_fresh.bounds))
    with pytest.raises(SpecError):
        pipe.retire(state, "nope")


def test_admit_retire_equivalence_mesh():
    """The same churn law on the SPMD path (1-device mesh in-process):
    admit + retire are sharded-state edits and the merged-summary
    answers match a fresh compile of the live set bitwise."""
    mesh = jax.make_mesh((1,), ("data",))
    a, b = _tenant("alpha", _reg_a()), _tenant("beta", _reg_b())
    c = _tenant("gamma", _reg_a())
    rng = np.random.default_rng(3)
    T, N0, W = 3, 4, 128
    rows = (rng.normal(50, 9, (T, N0, W)).astype(np.float32),
            rng.integers(0, X, (T, N0, W)).astype(np.int32),
            np.full((T, N0), W, np.int32))

    def mesh_spec(tenants):
        return PipelineSpec(
            topology=TopologySpec(fanin=(N0, 2, 1), capacity=N0 * W,
                                  num_strata=X),
            sampler=SamplerSpec(mode="whs", backend="topk", fraction=0.25),
            tenants=tuple(tenants), seed=0)

    pipe = api.compile(mesh_spec((a, b)), mesh=mesh)
    state = pipe.init()
    pipe, state = pipe.admit(state, c)
    pipe, state = pipe.retire(state, "beta")
    state, w_churn = pipe.run_epoch(state, pipe.default_key, *rows)

    fresh = api.compile(mesh_spec((a, c)), mesh=mesh)
    _, w_fresh = fresh.run_epoch(fresh.init(), fresh.default_key, *rows)

    assert pipe.tenant_names == ("alpha", "gamma")
    np.testing.assert_array_equal(np.asarray(w_churn.answers),
                                  np.asarray(w_fresh.answers))
    np.testing.assert_array_equal(np.asarray(w_churn.bounds),
                                  np.asarray(w_fresh.bounds))


# ------------------------------------------------ masked-slot invariance --
def test_masked_slots_never_affect_active_tenants():
    """A retired neighbour (frozen sketch state, mask off) is invisible:
    the surviving tenants' per-window answers, bounds, and per-tenant
    error attribution are bitwise those of a never-churned pipeline."""
    from repro.runtime.budget import aggregate_tenant_rel_errors

    data = _ingest()
    a, b = _tenant("alpha", _reg_a()), _tenant("beta", _reg_b())
    c = _tenant("gamma", _reg_a())

    # run an epoch WITH gamma live (its sketches absorb data), then
    # retire it — the frozen non-empty slot state must not leak
    pipe = api.compile(_spec((a, b, c)))
    state, _ = _epoch(pipe, data)
    pipe, state = pipe.retire(state, "gamma")
    state, w_churn = pipe.run_epoch(state, pipe.default_key, *data)

    ref = api.compile(_spec((a, b)))
    st_ref, _ = _epoch(ref, data)
    st_ref, w_ref = ref.run_epoch(st_ref, ref.default_key, *data)

    np.testing.assert_array_equal(np.asarray(w_churn.answers),
                                  np.asarray(w_ref.answers))
    np.testing.assert_array_equal(np.asarray(w_churn.bounds),
                                  np.asarray(w_ref.bounds))
    # arbitration sees only live tenants
    per = aggregate_tenant_rel_errors(pipe.plan, pipe.rows(w_churn))
    assert set(per) == {"alpha", "beta"}


def test_two_tenant_law_survives_any_bucket():
    """The PR-4 bitwise law (multi-tenant answers ≡ isolated runs) with
    slots padded well past the live count: grow alpha's group to bucket
    4 via same-signature admits, retire them all, and the padded+masked
    plan must still answer exactly like the isolated single-tenant
    pipelines."""
    data = _ingest()
    a, b = _tenant("alpha", _reg_a()), _tenant("beta", _reg_b())

    pipe = api.compile(_spec((a, b)))
    state = pipe.init()
    for i in range(3):  # alpha's group: bucket 1 → 4
        pipe, state = pipe.admit(state, _tenant(f"pad{i}", _reg_a()))
    for i in range(3):
        pipe, state = pipe.retire(state, f"pad{i}")
    assert sum(n for _, n in pipe.plan.core.groups) >= 5
    state, w2 = pipe.run_epoch(state, pipe.default_key, *data)

    for t, reg in (("alpha", _reg_a()), ("beta", _reg_b())):
        solo = api.compile(_spec((_tenant(t, reg),)))
        _, w1 = _epoch(solo, data)
        np.testing.assert_array_equal(
            pipe.plan.tenant_answers(np.asarray(w2.answers), t),
            np.asarray(w1.answers))
        np.testing.assert_array_equal(
            pipe.plan.tenant_answers(np.asarray(w2.bounds), t),
            np.asarray(w1.bounds))


# ------------------------------------------------- zero-retrace churn --
def test_zero_retrace_churn_inside_bucket():
    """Slot recycling inside a bucket traces nothing: the tick program
    is keyed on the canonical (name-free) core, so retire + admit of
    same-signature tenants reuses the jitted executable."""
    from repro.api.pipeline import program_cache_stats

    data = _ingest(ticks=2)
    regs = [_tenant(f"t{i}", _reg_a()) for i in range(8)]
    pipe = api.compile(_spec(tuple(regs)))
    state, _ = _epoch(pipe, data)
    t0 = pipe.trace_counter["traces"]
    m0 = program_cache_stats()["misses"]

    for i in range(4):
        pipe, state = pipe.retire(state, f"t{i}")
    for i in range(4):
        pipe, state = pipe.admit(state, _tenant(f"new{i}", _reg_a()))
    state, _ = pipe.run_epoch(state, pipe.default_key, *data)

    assert pipe.trace_counter["traces"] == t0
    assert program_cache_stats()["misses"] == m0


def test_one_trace_per_bucket_boundary():
    """Crossing a bucket boundary compiles exactly one new program;
    every admit until the NEXT boundary is then free."""
    from repro.api.pipeline import program_cache_stats

    data = _ingest(ticks=2)
    regs = [_tenant(f"t{i}", _reg_a()) for i in range(2)]
    pipe = api.compile(_spec(tuple(regs)))
    state, _ = _epoch(pipe, data)  # bucket 2 program traced
    m0 = program_cache_stats()["misses"]

    pipe, state = pipe.admit(state, _tenant("t2", _reg_a()))  # 2 → 4
    state, _ = pipe.run_epoch(state, pipe.default_key, *data)
    assert program_cache_stats()["misses"] == m0 + 1

    pipe, state = pipe.admit(state, _tenant("t3", _reg_a()))  # inside 4
    state, _ = pipe.run_epoch(state, pipe.default_key, *data)
    assert program_cache_stats()["misses"] == m0 + 1


# ---------------------------------------------------- checkpoint slots --
def test_restore_rejects_differently_churned_pipeline(tmp_path):
    """A checkpoint written under one slot configuration must not load
    into a pipeline that churned differently — the slot manifest rides
    the checkpoint and the mismatch is an actionable SpecError."""
    data = _ingest(ticks=2)
    a, b = _tenant("alpha", _reg_a()), _tenant("beta", _reg_b())

    pipe = api.compile(_spec((a, b)))
    state, _ = _epoch(pipe, data)
    api.save_state(tmp_path, 1, state, pipeline=pipe)

    # same live set, same spec — restores bitwise
    again = api.compile(_spec((a, b)))
    restored, _ = api.restore_state(tmp_path, again, 1)
    for la, lb in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    # differently-churned: same live set reached via churn, different
    # slot allocation (gamma grew alpha's bucket) → actionable rejection
    churned = api.compile(_spec((a, b)))
    churned, st2 = churned.admit(churned.init(), _tenant("gamma", _reg_a()))
    churned, st2 = churned.retire(st2, "gamma")
    with pytest.raises(SpecError, match="tenant-slot configuration"):
        api.restore_state(tmp_path, churned, 1)
