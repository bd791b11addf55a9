"""Continuous query plane: registry/compiler correctness, sketch accuracy,
K=8 queries answered in the scan engine's single epoch dispatch with
bit-identical sample state, dynamic budgets with zero retraces, and the
closed-loop error-budget controller."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import whs
from repro.core.tree import HostTree
from repro.core.types import IntervalBatch, SampleResult, StratumMeta
from repro.core.window import TreeState
from repro.query import sketches as sk
from repro.query.registry import QueryRegistry, QuerySpec

X = 3


def _k8_registry():
    return (QueryRegistry()
            .register_sum()
            .register_count()
            .register_mean()
            .register_histogram("hist_lo", 0.0, 80.0, 16)
            .register_histogram("hist_hi", 0.0, 120.0, 8)
            .register_quantile("quant", (0.5, 0.9, 0.99), capacity=128)
            .register_quantile("median", (0.5,), capacity=64)
            .register_heavy_hitters("hh", k=8, width=512, depth=4))


def _tree(engine, queries=None, seed=5, **kw):
    return HostTree(fanin=[4, 2, 1], num_strata=X, capacity=768,
                    sample_sizes=[96, 96, 96], seed=seed, engine=engine,
                    sampler_backend="topk", queries=queries, **kw)


def _ingest_arrays(ticks, n0=4, width=400, seed=11):
    rng = np.random.default_rng(seed)
    vals = rng.normal(50, 9, (ticks, n0, width)).astype(np.float32)
    strs = rng.integers(0, X, (ticks, n0, width)).astype(np.int32)
    counts = rng.integers(100, width, (ticks, n0)).astype(np.int32)
    return vals, strs, counts


def _full_batch(m=512, seed=0, strata=X):
    rng = np.random.default_rng(seed)
    vals = rng.normal(50, 9, m).astype(np.float32)
    strs = rng.integers(0, strata, m).astype(np.int32)
    return IntervalBatch(jnp.asarray(vals), jnp.asarray(strs),
                         jnp.ones((m,), bool), StratumMeta.identity(strata))


# ------------------------------------------------------------- registry --
def test_registry_layout_and_k():
    plan = _k8_registry().compile(X)
    assert plan.k == 8
    lay = plan.layout()
    assert lay["sum"] == (0, 1, "sum")
    assert lay["hist_lo"][1] == 16 and lay["quant"][1] == 3
    assert lay["hh"][1] == 16                      # k keys + k estimates
    assert plan.n_out == sum(w for _, w, _ in lay.values())


def test_registry_rejects_duplicates_and_bad_kinds():
    reg = QueryRegistry().register_sum()
    with pytest.raises(ValueError):
        reg.register_sum()
    with pytest.raises(ValueError):
        QuerySpec("x", "p99")
    with pytest.raises(ValueError):
        QuerySpec("q", "quantile")                 # no qs
    with pytest.raises(ValueError):
        QuerySpec("h", "heavy_hitters", width=1000)  # not 2^n


def test_registry_from_tokens_roundtrip():
    reg = QueryRegistry.from_tokens(
        "sum,count,mean,hist:0:100:8,q:0.5:0.99,hh:4")
    kinds = [s.kind for s in reg.specs]
    assert kinds == ["sum", "count", "mean", "histogram", "quantile",
                     "heavy_hitters"]
    assert reg.specs[3].bins == 8 and reg.specs[4].qs == (0.5, 0.99)
    assert reg.specs[5].k == 4


# ------------------------------------------------------------- compiler --
def test_compiled_clt_queries_match_reference_functions():
    """Fused evaluation ≡ the standalone queries.* / error.* functions."""
    from repro.core import queries as Q

    batch = _full_batch()
    res = whs.whsamp(jax.random.PRNGKey(3), batch, jnp.float32(128), X)
    plan = (QueryRegistry().register_sum().register_count().register_mean()
            .register_histogram("h", 0.0, 80.0, 16)).compile(X)
    _, ans, bnd = plan.evaluate(jax.random.PRNGKey(9), batch, res,
                                plan.init_state())
    s = Q.weighted_sum(batch, res, X)
    c = Q.weighted_count(batch, res, X)
    m = Q.weighted_mean(batch, res, X)
    h = Q.weighted_histogram(batch, res, X, jnp.linspace(0.0, 80.0, 17))
    np.testing.assert_array_equal(plan.answer(ans, "sum"),
                                  [float(s.estimate)])
    np.testing.assert_allclose(plan.answer(ans, "count"),
                               [float(c.estimate)], rtol=1e-6)
    np.testing.assert_array_equal(plan.answer(ans, "mean"),
                                  [float(m.estimate)])
    np.testing.assert_array_equal(plan.answer(ans, "h"),
                                  np.asarray(h.estimate))
    np.testing.assert_array_equal(plan.answer(bnd, "sum"),
                                  [float(s.bound(2.0))])
    np.testing.assert_array_equal(plan.answer(bnd, "h"),
                                  np.asarray(h.bound(2.0)))


def test_fraction_one_quantile_and_hh_exact():
    """At fraction 1.0 every weight is 1: the quantile summary under its
    capacity is lossless and heavy-hitter estimates equal true counts."""
    m = 100
    rng = np.random.default_rng(4)
    vals = np.round(rng.normal(20, 3, m)).astype(np.float32)
    batch = IntervalBatch(jnp.asarray(vals),
                          jnp.zeros((m,), jnp.int32),
                          jnp.ones((m,), bool), StratumMeta.identity(1))
    res = whs.whsamp(jax.random.PRNGKey(0), batch, jnp.float32(m), 1)
    plan = (QueryRegistry()
            .register_quantile("q", (0.25, 0.5, 0.75), capacity=256)
            .register_heavy_hitters("hh", k=4, width=1024)).compile(1)
    _, ans, _ = plan.evaluate(jax.random.PRNGKey(1), batch, res,
                              plan.init_state())
    qv = plan.answer(ans, "q")
    srt = np.sort(vals)
    for q, v in zip((0.25, 0.5, 0.75), qv):
        # lossless summary ⇒ exactly the order statistic at rank ⌊q·m⌋
        assert v == srt[int(np.floor(q * m))]
    hh = plan.answer(ans, "hh")
    keys, ests = hh[:4].astype(np.int64), hh[4:]
    true = {k: (np.round(vals).astype(np.int64) == k).sum() for k in keys}
    for k, e in zip(keys, ests):
        assert e == true[k], (k, e, true[k])


def test_count_is_the_integer_item_count_despite_weight_rounding():
    """13 of 1000 items sampled carry the f32 weight 1000/13, and
    13 · fl(1000/13) is not 1000 in f32; the HT count answers the 1000
    items exactly, with bound 0 (TPU division, which is not correctly
    rounded, leaves such residues on every window)."""
    c, n = 1000, 13
    w = np.float32(c) / np.float32(n)
    assert np.float32(w * np.float32(n)) != np.float32(c)
    batch = IntervalBatch(jnp.ones((n,), jnp.float32),
                          jnp.zeros((n,), jnp.int32), jnp.ones((n,), bool),
                          StratumMeta.identity(1))
    meta = StratumMeta(jnp.asarray([w], jnp.float32),
                       jnp.asarray([n], jnp.float32))
    res = SampleResult(batch.valid, meta, jnp.asarray([c], jnp.float32),
                           jnp.asarray([n], jnp.float32),
                           jnp.asarray([n], jnp.float32))
    plan = QueryRegistry().register_count("n").compile(1)
    _, ans, bnd = plan.evaluate(jax.random.PRNGKey(0), batch, res,
                                plan.init_state())
    assert float(plan.answer(ans, "n")[0]) == float(c)
    assert float(plan.answer(bnd, "n")[0]) == 0.0


# ----------------------------------------------------------- scan engine --
def test_k8_single_dispatch_and_bit_identical_sample_state():
    """THE acceptance property: K=8 standing queries answered per window
    in the same single dispatch per epoch, and every sample/reservoir
    state leaf bit-identical to a run with no queries registered."""
    vals, strs, counts = _ingest_arrays(4)
    plain = _tree("scan")
    plain.run_epoch(1, vals, strs, counts)
    reg = _k8_registry()
    qt = _tree("scan", queries=reg)
    assert qt.plan.k == 8
    qt.run_epoch(1, vals, strs, counts)

    assert qt.dispatch_count == 1 == plain.dispatch_count
    for f in TreeState.LEVEL_FIELDS:
        for a, b in zip(getattr(plain._state, f), getattr(qt._state, f)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(qt.results) == len(plain.results) > 0
    for ra, rb in zip(plain.results, qt.results):
        for k in ("tick", "sum", "sum_var", "mean", "mean_var", "n_sampled"):
            assert ra[k] == rb[k], k
        assert rb["answers"].shape == (qt.plan.n_out,)
        assert rb["bounds"].shape == (qt.plan.n_out,)


def test_engines_agree_on_answers_bitwise():
    """scan ≡ level ≡ loop on the full K=8 answer vectors (same key
    folding, same math, three execution strategies)."""
    vals, strs, counts = _ingest_arrays(3)
    scan = _tree("scan", queries=_k8_registry())
    scan.run_epoch(1, vals, strs, counts)
    for engine in ("level", "loop"):
        other = _tree(engine, queries=_k8_registry())
        for t in range(1, 4):
            for node in range(4):
                c = counts[t - 1, node]
                other.ingest(node, vals[t - 1, node, :c],
                             strs[t - 1, node, :c])
            other.tick(t)
        assert len(other.results) == len(scan.results)
        for ra, rb in zip(scan.results, other.results):
            np.testing.assert_array_equal(ra["answers"], rb["answers"])
            np.testing.assert_array_equal(ra["bounds"], rb["bounds"])


def test_sketch_state_rides_tree_state_and_is_donated():
    vals, strs, counts = _ingest_arrays(2)
    qt = _tree("scan", queries=_k8_registry())
    # slotted layout: one (mask, stacked-per-spec) group, leaves carrying
    # a leading slot axis (a raw registry is one single-slot group)
    (mask, stacked), = qt._state.qstate
    assert mask.shape == (1,) and bool(np.asarray(mask)[0])
    assert len(stacked) == 8
    qt.run_epoch(1, vals, strs, counts)
    # donated: the old sketch buffers are invalidated with the rest
    with pytest.raises(RuntimeError):
        np.asarray(stacked[5].value)
    # quantile sketch accumulated the windows' weighted mass
    (_, stacked2), = qt._state.qstate
    total = float(np.asarray(stacked2[5].weight).sum())
    assert total > 0.0


def test_dynamic_budgets_no_retrace_and_monotone_sample():
    """set_sample_sizes moves budgets between epochs with ZERO retraces
    (budgets are traced inputs), and a bigger budget keeps more items."""
    vals, strs, counts = _ingest_arrays(6)
    tree = _tree("scan", max_sample_sizes=[256, 256, 256])
    tree.run_epoch(1, vals[:2], strs[:2], counts[:2])
    traces = tree._trace_counter["traces"]
    n_small = tree.results[-1]["n_sampled"]
    tree.set_sample_sizes([256, 256, 256])
    tree.run_epoch(3, vals[2:4], strs[2:4], counts[2:4])
    n_big = tree.results[-1]["n_sampled"]
    tree.set_sample_sizes([40, 40, 40])
    tree.run_epoch(5, vals[4:], strs[4:], counts[4:])
    n_tiny = tree.results[-1]["n_sampled"]
    assert tree._trace_counter["traces"] == traces, "budget change retraced!"
    assert tree.dispatch_count == 3
    assert n_tiny < n_small < n_big
    # clamped to the provisioned ceiling
    tree.set_sample_sizes([9999, 9999, 9999])
    assert tree.sample_sizes == [256.0, 256.0, 256.0]


def test_closed_loop_reaches_target_within_20_epochs():
    """run_pipeline's error-budget loop: starting far under-budgeted, the
    controller reaches the target relative error within 20 epochs."""
    from repro.data import stream as S
    from repro.launch.analytics import run_pipeline

    target = 0.05
    r = run_pipeline(S.paper_gaussian(rates=(300, 300, 300, 300)),
                     fraction=0.01, ticks=80, epoch_ticks=4, seed=3,
                     engine="scan", warmup_ticks=1,
                     target_rel_error=target, max_fraction=0.8)
    traj = r["controller"]
    assert len(traj) == 20
    hit = [t["step"] for t in traj if t["rel_error"] <= target * 1.1]
    assert hit and hit[0] < 20, traj
    # and it stays in the neighbourhood once there (no blow-up)
    assert traj[-1]["rel_error"] <= target * 1.6


def test_plan_requires_whs_mode():
    with pytest.raises(AssertionError):
        _tree("scan", queries=_k8_registry(), mode="srs", fraction=0.25)


# -------------------------------------------------------------- sketches --
def test_quantile_sketch_exact_under_capacity():
    rng = np.random.default_rng(0)
    data = rng.normal(10, 2, 200).astype(np.float32)
    q = sk.quantile_init(256)
    for chunk in np.split(data, 4):
        b = jnp.asarray(chunk)
        q = sk.quantile_update(jax.random.PRNGKey(1), q, b,
                               jnp.ones_like(b))
    est = np.asarray(sk.quantile_query(q, jnp.asarray([0.0, 0.5, 1.0])))
    srt = np.sort(data)
    assert est[0] == srt[0] and est[2] == srt[-1]
    assert abs((data <= est[1]).mean() - 0.5) <= 1.0 / len(data) + 1e-6
    np.testing.assert_allclose(float(q.total_weight), len(data), rtol=1e-6)


def test_quantile_sketch_rank_error_within_bound():
    """Compacting 40k items through a C=256 summary keeps measured rank
    error within the configured bound (the fig8 acceptance property)."""
    rng = np.random.default_rng(7)
    data = rng.lognormal(3.0, 1.0, 40_000).astype(np.float32)
    q = sk.quantile_init(256)
    key = jax.random.PRNGKey(0)
    for i, chunk in enumerate(np.split(data, 40)):
        b = jnp.asarray(chunk)
        q = sk.quantile_update(jax.random.fold_in(key, i), q, b,
                               jnp.ones_like(b))
    qs = (0.1, 0.5, 0.9, 0.99)
    est = np.asarray(sk.quantile_query(q, jnp.asarray(qs)))
    bound = sk.quantile_rank_error_bound(256)
    for target, v in zip(qs, est):
        rank = (data <= v).mean()
        assert abs(rank - target) <= bound, (target, rank)


def test_quantile_sketch_weighted_update():
    """Weight-2 items count twice: matches duplicating the items."""
    rng = np.random.default_rng(3)
    data = rng.normal(0, 1, 300).astype(np.float32)
    a = sk.quantile_update(jax.random.PRNGKey(5), sk.quantile_init(1024),
                           jnp.asarray(data),
                           jnp.full((300,), 2.0, jnp.float32))
    dup = np.repeat(data, 2)
    b = sk.quantile_update(jax.random.PRNGKey(5), sk.quantile_init(1024),
                           jnp.asarray(dup), jnp.ones((600,), jnp.float32))
    qs = jnp.asarray([0.25, 0.5, 0.75])
    np.testing.assert_allclose(np.asarray(sk.quantile_query(a, qs)),
                               np.asarray(sk.quantile_query(b, qs)),
                               atol=1e-5)


def test_heavy_hitters_tracks_skewed_stream():
    rng = np.random.default_rng(1)
    pop = np.array([7, 13, 29, 101, 555])
    keys = rng.choice(pop, p=[0.45, 0.3, 0.15, 0.07, 0.03], size=8000)
    h = sk.hh_init(4, 512, 4)
    for chunk in np.split(keys.astype(np.float32), 8):
        b = jnp.asarray(chunk)
        h = sk.hh_update(h, sk.hh_item_key(b), jnp.ones_like(b))
    got = set(int(k) for k in np.asarray(h.key))
    assert got == {7, 13, 29, 101}
    bound = float(sk.hh_error_bound(512, h.total_weight))
    for k, e in zip(np.asarray(h.key), np.asarray(h.est)):
        true = (keys == k).sum()
        assert true <= e <= true + bound + 1e-4   # CM only over-counts


def test_heavy_hitters_masked_items_ignored():
    h = sk.hh_init(2, 256, 2)
    keys = jnp.asarray([4, 4, 9], jnp.int32)
    w = jnp.asarray([1.0, 1.0, 0.0], jnp.float32)
    h = sk.hh_update(h, keys, w)
    assert int(h.key[0]) == 4 and float(h.est[0]) == 2.0
    assert int(h.key[1]) == int(sk.HH_EMPTY_KEY)  # 9 carried no weight
    np.testing.assert_allclose(float(h.total_weight), 2.0)
