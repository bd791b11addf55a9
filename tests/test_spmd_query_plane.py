"""SPMD query plane: multi-device equivalence harness.

The acceptance property of the mesh lowering (``repro.api.compile(spec,
mesh=...)`` with tenants): on 2/4/8 simulated devices the per-tenant
``WindowAnswers`` agree with the single-device run on the same total
stream — EXACT queries (the HT count, variance 0 by construction)
bitwise, CLT queries within their published ±2σ bounds, sketch queries
within their published rank/CM bounds — and only sketch summaries
(never raw reservoirs) cross a device boundary, asserted against the
traced collectives' operand shapes.

Multi-device checks run in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main test
process must keep the default 1-device backend); one subprocess run
feeds every assertion via a module-scoped fixture. Dispatch/donation/
retrace and CLT-coverage properties need no second device and run
in-process on a 1-device mesh.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

# ---------------------------------------------------------------------------
# the multi-device worker: every device-count run + ground truth in one go
# ---------------------------------------------------------------------------
_HARNESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.api.spec import (PipelineSpec, SamplerSpec, TenantSpec,
                                TopologySpec)
    from repro.data import stream as S
    from repro.query.registry import QueryRegistry

    X, T, M = 3, 4, 8192
    HEAVY = np.array([7, 13, 29, 101], np.int64)

    rng = np.random.default_rng(0)
    vals = np.where(
        rng.random((T, M)) < 0.55,
        rng.choice(HEAVY, p=[0.5, 0.3, 0.15, 0.05], size=(T, M)),
        np.round(rng.normal(50.0, 9.0, (T, M)))).astype(np.float32)
    strs = rng.integers(0, X, (T, M)).astype(np.int32)
    counts = np.full((T,), M, np.int64)
    batches = S.rows_to_interval_batch(vals, strs, counts, X)

    def tenants():
        a = (QueryRegistry().register_sum().register_count()
             .register_mean()
             .register_quantile("q", (0.5, 0.9), capacity=64)
             .register_heavy_hitters("hh", k=4, width=64, depth=2))
        b = (QueryRegistry().register_count("n")
             .register_histogram("h", 0.0, 128.0, 16))
        return (TenantSpec.from_registry("a", a),
                TenantSpec.from_registry("b", b))

    def make_spec(fraction, mode="whs", with_tenants=True):
        return PipelineSpec(
            topology=TopologySpec(fanin=(4, 2, 1), capacity=M // 8,
                                  num_strata=X),
            sampler=SamplerSpec(mode=mode, backend="topk",
                                fraction=fraction),
            tenants=tenants() if with_tenants else (),
            seed=0)

    def mesh_of(n):
        return jax.make_mesh((n,), ("data",), devices=jax.devices()[:n])

    out = {"exact": {
        "sum": [float(v.sum()) for v in vals],
        "count": float(M),
        "mean": [float(v.mean()) for v in vals],
    }}

    # ---- tenant runs across device counts (sampled regime) ---------------
    spec = make_spec(0.25)
    runs = {}
    for n in (1, 2, 4, 8):
        pipe = api.compile(spec, mesh=mesh_of(n))
        st, wa = pipe.run_epoch(pipe.init(), pipe.default_key, batches)
        runs[n] = dict(answers=np.asarray(wa.answers).tolist(),
                       bounds=np.asarray(wa.bounds).tolist(),
                       n_sampled=np.asarray(wa.n_sampled).tolist(),
                       ok=np.asarray(wa.ok).tolist(),
                       tick=np.asarray(wa.tick).tolist())
    out["tenant_runs"] = runs
    out["layout"] = {k: list(v) for k, v in
                     api.compile(spec, mesh=mesh_of(1)).plan.layout()
                     .items()}
    out["local_budget"] = api.compile(spec, mesh=mesh_of(1)).local_budget

    # quantile ground truth: rank of each answered value on the stream
    # the continuous sketch has absorbed so far (windows 0..t)
    def ranks_so_far(values_row, t):
        seen = vals[:t + 1].reshape(-1)
        return [float((seen <= v).mean()) for v in values_row]
    lay = {k: v for k, v in out["layout"].items()}
    qo, qw, _ = lay["a/q"]
    out["q_ranks"] = {
        n: [ranks_so_far(np.asarray(runs[n]["answers"])[t, qo:qo + qw], t)
            for t in range(T)] for n in runs}
    # heavy-hitter ground truth: cumulative rounded-key counts after
    # each window (the continuous sketch spans windows 0..t)
    out["hh_true_cum"] = []
    for t in range(T):
        keys_seen = np.round(vals[:t + 1].reshape(-1)).astype(np.int64)
        uniq, cnt = np.unique(keys_seen, return_counts=True)
        out["hh_true_cum"].append(
            {int(k): int(c) for k, c in zip(uniq, cnt)})
    out["hh_heavy"] = [int(k) for k in HEAVY]

    # ---- exact regime: fraction 1.0 on 8 devices (budget == shard) -------
    # single stratum: fair allocation then covers every item (per-stratum
    # caps keep multi-strata fraction-1.0 merely near-exact), so every
    # weight is exactly 1 and the sketch holds the raw stream
    spec_exact = PipelineSpec(
        topology=TopologySpec(fanin=(4, 2, 1), capacity=M // 8,
                              num_strata=1),
        sampler=SamplerSpec(mode="whs", backend="topk", fraction=1.0),
        tenants=tenants(), seed=0)
    batches1 = S.rows_to_interval_batch(vals, np.zeros_like(strs), counts, 1)
    pipe1 = api.compile(spec_exact, mesh=mesh_of(8))
    assert pipe1.local_budget == M // 8
    st, wa = pipe1.run_epoch(pipe1.init(), pipe1.default_key, batches1)
    out["exact_regime"] = dict(answers=np.asarray(wa.answers).tolist(),
                               bounds=np.asarray(wa.bounds).tolist())
    out["exact_regime_q_ranks"] = [
        ranks_so_far(np.asarray(wa.answers)[t, qo:qo + qw], t)
        for t in range(T)]

    # ---- multi-epoch resume (4 devices): 2+2 ticks ≡ 4 ticks -------------
    pipe = api.compile(spec, mesh=mesh_of(4))
    stA, waA = pipe.run_epoch(pipe.init(), pipe.default_key,
                              jax.tree.map(lambda v: v[:2], batches))
    stA, waB = pipe.run_epoch(stA, pipe.default_key,
                              jax.tree.map(lambda v: v[2:], batches))
    two = np.concatenate([np.asarray(waA.answers), np.asarray(waB.answers)])
    one = np.asarray(runs[4]["answers"])
    out["resume"] = dict(
        bitwise=bool((two == one).all()),
        max_abs_diff=float(np.max(np.abs(two - one))),
        ticks=np.concatenate([np.asarray(waA.tick),
                              np.asarray(waB.tick)]).tolist())

    # ---- srs baseline on the mesh (no tenants) ---------------------------
    srs = {}
    for n in (1, 8):
        pipe = api.compile(make_spec(0.25, mode="srs", with_tenants=False),
                           mesh=mesh_of(n))
        _, (sq, mq) = pipe.run_epoch(pipe.init(), pipe.default_key, batches)
        srs[n] = dict(sum=np.asarray(sq.estimate).tolist(),
                      sum_var=np.asarray(sq.variance).tolist(),
                      mean=np.asarray(mq.estimate).tolist(),
                      mean_var=np.asarray(mq.variance).tolist())
    out["srs_runs"] = srs

    # ---- collectives audit: what actually crosses the mesh ---------------
    COLL = ("all_gather", "psum", "pmin", "pmax", "pmean", "all_to_all",
            "ppermute", "reduce_scatter")
    def walk(jaxpr, acc):
        for eqn in jaxpr.eqns:
            if any(c in eqn.primitive.name for c in COLL):
                elems = max(int(np.prod(v.aval.shape) or 1)
                            for v in eqn.invars if hasattr(v, "aval"))
                acc.append([eqn.primitive.name, elems])
            for v in eqn.params.values():
                for j in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = getattr(j, "jaxpr", None)
                    if inner is not None and hasattr(inner, "eqns"):
                        walk(inner, acc)
                    elif hasattr(j, "eqns"):
                        walk(j, acc)
    pipe = api.compile(spec, mesh=mesh_of(8))
    closed = jax.make_jaxpr(
        lambda st, k, b, bt: pipe._fn(st, k, b, bt))(
        pipe.init(), pipe.default_key, jnp.float32(pipe.local_budget),
        batches)
    acc = []
    walk(closed.jaxpr, acc)
    out["collectives"] = acc
    out["shard_items"] = M // 8
    out["n_devices_seen"] = len(jax.devices())
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def harness():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _HARNESS],
                         capture_output=True, text=True, timeout=900,
                         env=env, cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _slice(r, lay, name):
    o, w, _ = lay[name]
    a = np.asarray(r["answers"])[..., o:o + w]
    b = np.asarray(r["bounds"])[..., o:o + w]
    return a, b


def test_eight_devices_simulated(harness):
    assert harness["n_devices_seen"] == 8
    for n, r in harness["tenant_runs"].items():
        assert np.asarray(r["ok"]).all()
        assert r["tick"] == [0, 1, 2, 3]


def test_exact_queries_bitwise_across_device_counts(harness):
    """Both tenants' HT counts (variance 0 by construction) are
    bitwise-identical on 1, 2, 4, and 8 devices — the merged answer is
    a sum of exact per-shard integers, independent of the split."""
    lay = harness["layout"]
    ref = harness["tenant_runs"]["1"]
    for name in ("a/count", "b/n"):
        want, _ = _slice(ref, lay, name)
        np.testing.assert_array_equal(want[:, 0],
                                      np.asarray(harness["exact"]["count"]))
        for n in ("2", "4", "8"):
            got, bb = _slice(harness["tenant_runs"][n], lay, name)
            np.testing.assert_array_equal(got, want), (name, n)
            np.testing.assert_array_equal(bb, 0.0)


def test_clt_answers_within_published_bounds(harness):
    """Per-tenant SUM/MEAN on every device count stay within their own
    reported ±2σ of the exact stream aggregate (fixed seeds; 2x slack
    on the 2σ bound keeps the deterministic check off the 5% tail)."""
    lay = harness["layout"]
    exact_sum = np.asarray(harness["exact"]["sum"])
    exact_mean = np.asarray(harness["exact"]["mean"])
    for n, r in harness["tenant_runs"].items():
        a, b = _slice(r, lay, "a/sum")
        assert np.all(np.abs(a[:, 0] - exact_sum) <= 2 * b[:, 0] + 1e-3), n
        assert np.all(b[:, 0] > 0.0), n
        a, b = _slice(r, lay, "a/mean")
        assert np.all(np.abs(a[:, 0] - exact_mean) <= 2 * b[:, 0] + 1e-3), n


def test_histogram_tenant_merges_exactly_at_full_mass(harness):
    """Tenant b's static-edge histogram: total estimated mass across the
    bins equals the HT count (the per-bin linear queries psum-merge
    without loss) on every device count."""
    lay = harness["layout"]
    for n, r in harness["tenant_runs"].items():
        h, _ = _slice(r, lay, "b/h")
        np.testing.assert_allclose(h.sum(axis=-1),
                                   harness["exact"]["count"],
                                   rtol=1e-4), n


def test_quantile_answers_within_published_rank_bounds(harness):
    """The merged compactor's answers, ranked on the exact stream it has
    absorbed so far, stay within the reported rank-error bound plus the
    sampling slack (the sketch summarizes an HT-weighted sample)."""
    lay = harness["layout"]
    for n, r in harness["tenant_runs"].items():
        _, b = _slice(r, lay, "a/q")
        ranks = np.asarray(harness["q_ranks"][n])        # [T, 2]
        targets = np.asarray([0.5, 0.9])
        slack = 0.06  # CLT slack of the ~(budget·devices)-item sample
        assert np.all(np.abs(ranks - targets) <= b + slack), (n, ranks, b)


def test_exact_regime_is_tight(harness):
    """fraction 1.0 on 8 devices (budget == shard): every weight is 1,
    so SUM is the exact integer sum, the quantile ranks meet the bound
    with NO sampling slack, and heavy-hitter estimates obey the pure CM
    bound (only over-count) against true stream counts."""
    lay = harness["layout"]
    r = harness["exact_regime"]
    a, b = _slice(r, lay, "a/sum")
    np.testing.assert_array_equal(a[:, 0],
                                  np.asarray(harness["exact"]["sum"]))
    ranks = np.asarray(harness["exact_regime_q_ranks"])
    _, qb = _slice(r, lay, "a/q")
    assert np.all(np.abs(ranks - np.asarray([0.5, 0.9]))
                  <= qb + 1e-6), (ranks, qb)
    hh_a, hh_b = _slice(r, lay, "a/hh")
    for t in range(hh_a.shape[0]):
        keys, ests = hh_a[t, :4].astype(np.int64), hh_a[t, 4:]
        bound = hh_b[t, 4]
        true = {int(k): v for k, v in harness["hh_true_cum"][t].items()}
        for k, e in zip(keys, ests):
            tk = true.get(int(k), 0)
            assert tk - 1e-3 <= e <= tk + bound + 1e-3, (t, k, e, tk, bound)


def test_heavy_hitters_found_on_every_device_count(harness):
    """The top-k re-merge surfaces the true heavy keys regardless of how
    the stream was sharded, and estimates stay within the CM bound plus
    HT sampling slack of the true counts."""
    lay = harness["layout"]
    heavy = set(harness["hh_heavy"])
    true = {int(k): v for k, v in harness["hh_true_cum"][-1].items()}
    for n, r in harness["tenant_runs"].items():
        hh_a, hh_b = _slice(r, lay, "a/hh")
        keys = set(hh_a[-1, :4].astype(np.int64).tolist())
        assert keys == heavy, (n, keys)
        w_total = sum(true.values())
        for k, e in zip(hh_a[-1, :4].astype(np.int64), hh_a[-1, 4:]):
            # CM bound + 4σ-ish HT slack of the sampled fold-in
            assert abs(e - true[int(k)]) <= hh_b[-1, 4] + 0.05 * w_total, \
                (n, k, e, true[int(k)])


def test_multi_epoch_resume_bitwise(harness):
    """Two 2-tick epochs through the donated state produce bitwise the
    answers of one 4-tick epoch — global-tick key folding plus carried
    sketch state make the epoch boundary invisible."""
    assert harness["resume"]["ticks"] == [0, 1, 2, 3]
    assert harness["resume"]["bitwise"], harness["resume"]
    assert harness["resume"]["max_abs_diff"] == 0.0


def test_srs_baseline_on_mesh_within_bounds(harness):
    """whs is not alone on the mesh: the §IV-B coin-flip baseline also
    lowers (HT from psum-ed moments), agreeing with the exact stream and
    with its own single-device run within combined ±2σ bounds."""
    exact_sum = np.asarray(harness["exact"]["sum"])
    exact_mean = np.asarray(harness["exact"]["mean"])
    for n, r in harness["srs_runs"].items():
        est = np.asarray(r["sum"])
        sig = np.sqrt(np.asarray(r["sum_var"]))
        assert np.all(np.abs(est - exact_sum) <= 3 * sig), n
        m = np.asarray(r["mean"])
        ms = np.sqrt(np.asarray(r["mean_var"]))
        assert np.all(np.abs(m - exact_mean) <= 3 * ms + 1e-3), n
    d = np.abs(np.asarray(harness["srs_runs"]["1"]["sum"])
               - np.asarray(harness["srs_runs"]["8"]["sum"]))
    comb = 2 * (np.sqrt(np.asarray(harness["srs_runs"]["1"]["sum_var"]))
                + np.sqrt(np.asarray(harness["srs_runs"]["8"]["sum_var"])))
    assert np.all(d <= comb)


def test_only_sketch_summaries_cross_devices(harness):
    """The communicated-bytes audit: every cross-device collective in
    the traced epoch moves at most a sketch-sized operand — strictly
    smaller than one device's compacted reservoir, let alone its shard
    of raw items. The reservoir never crosses."""
    from repro.query.sketches import kll_schedule

    colls = harness["collectives"]
    assert colls, "no collectives traced — the audit went blind"
    sizes = {}
    for name, elems in colls:
        sizes[name] = max(sizes.get(name, 0), elems)
    max_elems = max(sizes.values())
    # largest legitimate summary: the leveled KLL value/weight gather
    # (levels x capacity per leaf — 4x64 here), then the 2x64 CM table
    # psum (=128). At capacity 64 the leveled state matches the
    # compacted reservoir's per-leaf footprint, so the sharp claim is
    # against the RAW shard: no operand ever approaches one device's
    # window of raw items, and the reservoir leaves themselves (values,
    # weights, strata, validity at budget width) never cross.
    legit = max(128, len(kll_schedule(64)) * 64)
    assert max_elems <= legit, sizes
    assert max_elems < harness["shard_items"], sizes
    assert any("all_gather" in n for n in sizes), sizes
    assert any("psum" in n for n in sizes), sizes


# ---------------------------------------------------------------------------
# 1-device in-process properties: dispatch model, donation, traced budgets,
# CLT coverage (mirrors test_scan_engine's assertions for the SPMD epoch)
# ---------------------------------------------------------------------------
X = 3


def _tenant_spec(capacity=1024, fraction=0.25, seed=0):
    from repro.api.spec import (BudgetSpec, PipelineSpec, SamplerSpec,
                                TenantSpec, TopologySpec)
    from repro.query.registry import QueryRegistry

    a = (QueryRegistry().register_sum().register_count().register_mean()
         .register_quantile("q", (0.5, 0.9), capacity=64)
         .register_heavy_hitters("hh", k=4, width=64, depth=2))
    b = QueryRegistry().register_count("n")
    return PipelineSpec(
        topology=TopologySpec(fanin=(4, 2, 1), capacity=capacity,
                              num_strata=X),
        sampler=SamplerSpec(mode="whs", backend="topk", fraction=fraction),
        tenants=(TenantSpec.from_registry("a", a),
                 TenantSpec.from_registry("b", b)),
        # ceiling above the initial budget: the controller (and the
        # zero-retrace test) must have room to move the traced budget
        budget=BudgetSpec(max_fraction=1.0),
        seed=seed)


def _batches(ticks=2, m=2048, seed=0):
    from repro.data import stream as S

    rng = np.random.default_rng(seed)
    vals = np.round(rng.normal(50, 9, (ticks, m))).astype(np.float32)
    strs = rng.integers(0, X, (ticks, m)).astype(np.int32)
    return S.rows_to_interval_batch(vals, strs, np.full((ticks,), m), X)


def _mesh1():
    import jax

    return jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])


def test_spmd_epoch_one_dispatch_donated_zero_retrace():
    """One jitted dispatch per epoch; the whole state (tick + qstate
    leaves) donated; moving the traced budget never retraces."""
    import jax

    from repro import api

    pipe = api.compile(_tenant_spec(), mesh=_mesh1())
    batches = _batches(2)
    s0 = pipe.init()
    q_before = s0.qstate
    s1, wa1 = pipe.run_epoch(s0, pipe.default_key, batches)
    traces = pipe.trace_counter["traces"]
    assert traces == 1
    n_small = int(np.asarray(wa1.n_sampled)[-1])
    # donated: the previous epoch's sketch buffers are invalidated
    with pytest.raises(RuntimeError):
        np.asarray(jax.tree.leaves(q_before)[0])
    # budget move: bigger sample, same executable (budgets are traced)
    s2, wa2 = pipe.run_epoch(s1, pipe.default_key, _batches(2, seed=1),
                             budgets=[pipe.max_local_budget])
    assert pipe.trace_counter["traces"] == traces, "budget move retraced!"
    assert int(np.asarray(wa2.n_sampled)[-1]) > n_small
    # clamped to the provisioned ceiling
    assert pipe.clamp_budgets([10 ** 9]) == float(pipe.max_local_budget)
    # executable reuse: epoch 1 compiles once; epoch 2 may re-lower once
    # (shard_map canonicalizes the carried state's sharding) but from
    # then on every epoch reuses the cached executable — and the fused
    # tick never re-traces
    cache_after_two = pipe._fn._cache_size()
    assert cache_after_two <= 2
    pipe.run_epoch(s2, pipe.default_key, _batches(2, seed=2),
                   budgets=[64])
    assert pipe._fn._cache_size() == cache_after_two
    assert pipe.trace_counter["traces"] == traces


def test_spmd_budgets_rejected_without_tenants():
    from repro import api
    from repro.api.spec import SpecError

    spec = _tenant_spec()
    import dataclasses

    plain = dataclasses.replace(spec, tenants=())
    pipe = api.compile(plain, mesh=_mesh1())
    with pytest.raises(SpecError, match="tenant"):
        pipe.run_epoch(pipe.init(), pipe.default_key, _batches(1),
                       budgets=[64])


def test_spmd_rejects_indivisible_item_axis():
    """Actionable error for a genuinely unsupported layout: the item
    axis must shard evenly over the mesh."""
    import jax

    from repro import api
    from repro.api.spec import SpecError

    if len(jax.devices()) < 2:
        # build the 2-way mesh error by padding to an odd width on 1 dev
        pipe = api.compile(_tenant_spec(), mesh=_mesh1())
        pipe.n_devices = 2   # simulate the check's arithmetic
        with pytest.raises(SpecError, match="divide evenly"):
            pipe._check_batches(_batches(1, m=2049))
    else:  # pragma: no cover — multi-device hosts check the real path
        mesh = jax.make_mesh((2,), ("data",), devices=jax.devices()[:2])
        pipe = api.compile(_tenant_spec(), mesh=mesh)
        with pytest.raises(SpecError, match="divide evenly"):
            pipe.run_epoch(pipe.init(), pipe.default_key,
                           _batches(1, m=2049))


def test_local_compile_unchanged_by_spmd_lowering():
    """Regression guard for the satellite 'compile(spec) without a mesh
    is bit-identical before/after this PR': the local compiled pipeline
    still bit-matches the per-node loop oracle on a tenant spec (the
    SPMD lowering shares the plan/compiler code — it must not perturb
    the local path)."""
    from repro import api
    from repro.core.tree import HostTree

    spec = _tenant_spec(capacity=768, fraction=0.125)
    batches = _batches(3, m=700)
    vals = np.asarray(batches.value)
    strs = np.asarray(batches.stratum)

    pipe = api.compile(spec)
    # local runtime consumes [T, n0, width] node-major ingest
    n0 = spec.topology.fanin[0]
    width = 700 // n0
    v4 = vals[:, :n0 * width].reshape(3, n0, width)
    s4 = strs[:, :n0 * width].reshape(3, n0, width)
    c4 = np.full((3, n0), width)
    state, wa = pipe.run_epoch(pipe.init(), pipe.default_key, v4, s4, c4)
    rows = pipe.rows(wa)

    ref = HostTree.from_spec(spec, engine="loop")
    for t in range(1, 4):
        for node in range(n0):
            ref.ingest(node, v4[t - 1, node], s4[t - 1, node])
        ref.tick(t)
    assert len(rows) == len(ref.results) > 0
    for ra, rb in zip(rows, ref.results):
        for k in ("sum", "sum_var", "mean", "mean_var", "n_sampled"):
            assert ra[k] == rb[k], k
        np.testing.assert_array_equal(ra["answers"], rb["answers"])
        np.testing.assert_array_equal(ra["bounds"], rb["bounds"])


def test_spmd_clt_coverage_vmapped():
    """Satellite: vmapped multi-seed run — for each tenant's sum/mean on
    the merged root, the measured ±2σ coverage stays at/above the
    nominal-minus-noise threshold (CLT ≈ 95%; 90% floors the
    200-draw binomial wobble)."""
    import jax
    import jax.numpy as jnp

    from repro import api

    from jax.sharding import PartitionSpec as P

    from repro.core import tree as T
    from repro.core.types import IntervalBatch, StratumMeta

    pipe = api.compile(_tenant_spec(fraction=0.125), mesh=_mesh1())
    plan = pipe.plan
    batches = _batches(1, m=2048, seed=7)
    batch = jax.tree.map(lambda v: v[0], batches)    # one window
    exact_sum = float(np.asarray(batches.value).sum())
    exact_mean = float(np.asarray(batches.value).mean())
    n_draws = 200

    # the vmap runs INSIDE the shard-mapped program (vmapped collectives
    # batch fine; vmap-over-shard_map would fight the replication check)
    def many(keys, b):
        def one(k):
            _, outs = T.spmd_query_plane_tick(
                k, b, plan.init_state(), plan, axis_name="data",
                budget=jnp.float32(pipe.local_budget),
                max_budget=pipe.max_local_budget, num_strata=X,
                allocation="fair", sampler_backend="topk")
            return outs[7], outs[8]                  # answers, bounds
        return jax.vmap(one)(keys)

    item = P("data")
    specs = IntervalBatch(item, item, item, StratumMeta(P(), P()))
    fn = jax.shard_map(many, mesh=_mesh1(), in_specs=(P(), specs),
                       out_specs=(P(), P()))
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n_draws))
    ans, bnd = jax.jit(fn)(keys, batch)
    ans, bnd = np.asarray(ans), np.asarray(bnd)
    lay = pipe.plan.layout()
    for name, exact in (("a/sum", exact_sum), ("a/mean", exact_mean)):
        o = lay[name][0]
        hits = np.abs(ans[:, o] - exact) <= bnd[:, o]
        assert hits.mean() >= 0.90, (name, hits.mean())
        assert bnd[:, o].min() > 0.0
    # the exact count is covered trivially but must be *exact*
    o = lay["a/count"][0]
    np.testing.assert_array_equal(ans[:, o], 2048.0)
    o = lay["b/n"][0]
    np.testing.assert_array_equal(ans[:, o], 2048.0)
