"""Scan engine: bit-equivalence with the per-node loop oracle across all
sampler backends, the one-dispatch-per-epoch execution model, donated
on-device state, and the batched ingest path."""
import jax
import numpy as np
import pytest

from repro.core.tree import HostTree
from repro.data import stream as S
from repro.launch.analytics import run_pipeline

X = 3


def _tree(engine, mode="whs", backend="topk", iv=None, seed=5):
    return HostTree(fanin=[4, 2, 1], num_strata=X, capacity=768,
                    sample_sizes=[96, 96, 96], seed=seed, mode=mode,
                    fraction=0.25 if mode == "srs" else None,
                    interval_ticks=iv, engine=engine,
                    sampler_backend=backend)


def _ingest_arrays(ticks, n0=4, width=400, seed=11):
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


def _assert_same_results(a: HostTree, b: HostTree):
    assert len(a.results) == len(b.results) > 0
    for ra, rb in zip(a.results, b.results):
        for k in ("tick", "sum", "sum_var", "mean", "mean_var", "n_sampled"):
            assert ra[k] == rb[k], k
        np.testing.assert_array_equal(ra["histogram"], rb["histogram"])
    assert a.items_forwarded == b.items_forwarded


# ---------------------------------------------------------- equivalence --
@pytest.mark.parametrize("backend", ["argsort", "topk", "pallas", "pallas_fused"])
def test_scan_matches_loop_oracle_all_backends(backend):
    """One fused epoch dispatch ≡ per-node per-tick dispatches, to the bit
    (same (tick, level, node) key folding, same f32 metadata math)."""
    vals, strs, counts = _ingest_arrays(4)
    ref = _tree("loop", backend=backend)
    _run_sequential(ref, vals, strs, counts)
    scan = _tree("scan", backend=backend)
    scan.run_epoch(1, vals, strs, counts)
    _assert_same_results(ref, scan)


@pytest.mark.parametrize("mode", ["whs", "srs"])
def test_scan_matches_loop_oracle_modes(mode):
    vals, strs, counts = _ingest_arrays(5)
    ref = _tree("loop", mode=mode)
    _run_sequential(ref, vals, strs, counts)
    scan = _tree("scan", mode=mode)
    scan.run_epoch(1, vals, strs, counts)
    _assert_same_results(ref, scan)


def test_scan_matches_loop_async_intervals():
    """Interval gating (due/not-due levels accumulate in place) agrees
    with the host engines' per-level due checks."""
    vals, strs, counts = _ingest_arrays(6)
    ref = _tree("loop", iv=[1, 2, 3])
    _run_sequential(ref, vals, strs, counts)
    scan = _tree("scan", iv=[1, 2, 3])
    scan.run_epoch(1, vals, strs, counts)
    _assert_same_results(ref, scan)


def test_scan_multi_epoch_continues_stream():
    """Two epochs chain through the donated state exactly like one: sticky
    metadata and tick indices carry across the epoch boundary."""
    vals, strs, counts = _ingest_arrays(6)
    ref = _tree("loop")
    _run_sequential(ref, vals, strs, counts)
    scan = _tree("scan")
    scan.run_epoch(1, vals[:3], strs[:3], counts[:3])
    scan.run_epoch(4, vals[3:], strs[3:], counts[3:])
    _assert_same_results(ref, scan)


def test_scan_ingest_accounting_matches_under_overload():
    """A (tick, node) offering more items than the level-0 buffer holds:
    items_ingested counts the OFFERED items (pre-truncation) on every
    engine, so bandwidth fractions agree."""
    kw = dict(fraction=0.5, ticks=3, seed=3, capacity=512, warmup_ticks=0)
    a = run_pipeline(S.paper_gaussian(), engine="level", **kw)
    b = run_pipeline(S.paper_gaussian(), engine="scan", **kw)
    assert a["items_ingested"] == b["items_ingested"]
    assert a["items_forwarded"] == b["items_forwarded"]
    np.testing.assert_allclose(a["bandwidth_fraction"],
                               b["bandwidth_fraction"], rtol=0)


def test_scan_matches_level_via_pipeline():
    """Full driver path (batched ingest generation included) agrees with
    the level engine on the fig7 workload."""
    kw = dict(fraction=0.2, ticks=4, seed=2, warmup_ticks=0)
    a = run_pipeline(S.paper_gaussian(), engine="level", **kw)
    b = run_pipeline(S.paper_gaussian(), engine="scan", **kw)
    np.testing.assert_allclose(a["approx_sum"], b["approx_sum"], rtol=1e-6)
    np.testing.assert_allclose(a["bound_2sigma"], b["bound_2sigma"], rtol=1e-6)
    assert a["items_forwarded"] == b["items_forwarded"]
    assert b["dispatches"] == 1


# ------------------------------------------------------------ dispatches --
def test_one_compiled_dispatch_per_epoch():
    """An epoch is ONE jitted call: the epoch fn compiles once, every
    subsequent epoch reuses the executable, and no per-tick/per-level
    dispatches happen (the tree-step traces exactly as often as the scan
    program compiles — never per executed tick)."""
    vals, strs, counts = _ingest_arrays(4)
    tree = _tree("scan")
    tree.run_epoch(1, vals, strs, counts)
    traces_after_first = tree._trace_counter["traces"]
    assert tree.dispatch_count == 1
    tree.run_epoch(5, vals, strs, counts)
    assert tree.dispatch_count == 2
    # same epoch length → same executable, zero retracing
    assert tree._trace_counter["traces"] == traces_after_first
    assert tree._epoch_fns[4]._cache_size() == 1


def test_scan_state_is_donated():
    """The epoch dispatch donates the whole TreeState: the previous
    epoch's buffers are invalidated, not copied."""
    vals, strs, counts = _ingest_arrays(2)
    tree = _tree("scan")
    state_before = tree._state
    tree.run_epoch(1, vals, strs, counts)
    with pytest.raises(RuntimeError):
        np.asarray(state_before.values[0])


def test_scan_rejects_per_tick_api():
    tree = _tree("scan")
    with pytest.raises(RuntimeError):
        tree.ingest(0, np.ones(3, np.float32), np.zeros(3, np.int32))
    with pytest.raises(RuntimeError):
        tree.tick(1)


# ---------------------------------------------------------- spmd epoch --
def test_spmd_epoch_matches_per_interval():
    """spmd_local_then_root_epoch over T stacked batches ≡ T per-interval
    calls with fold_in(key, i) keys, bit-for-bit (1-device mesh)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.tree import (spmd_local_then_root,
                                 spmd_local_then_root_epoch)
    from repro.core.types import IntervalBatch, StratumMeta

    m, ticks = 256, 3
    rng = np.random.default_rng(0)
    batches = IntervalBatch(
        value=jnp.asarray(rng.normal(100, 10, (ticks, m)), jnp.float32),
        stratum=jnp.asarray(rng.integers(0, X, (ticks, m)), jnp.int32),
        valid=jnp.ones((ticks, m), bool),
        meta=StratumMeta(jnp.ones((ticks, X)), jnp.zeros((ticks, X))))
    mesh = jax.make_mesh((1,), ("data",))
    key = jax.random.PRNGKey(0)
    kw = dict(axis_name="data", num_strata=X, local_budget=32,
              root_budget=64)

    specs_t = IntervalBatch(P(None, "data"), P(None, "data"),
                            P(None, "data"), StratumMeta(P(), P()))
    s_t, m_t = jax.jit(jax.shard_map(
        lambda k, b: spmd_local_then_root_epoch(k, b, **kw),
        mesh=mesh, in_specs=(P(), specs_t),
        out_specs=(P(), P())))(key, batches)

    spec1 = IntervalBatch(P("data"), P("data"), P("data"),
                          StratumMeta(P(), P()))
    one = jax.jit(jax.shard_map(lambda k, b: spmd_local_then_root(k, b, **kw),
                                mesh=mesh, in_specs=(P(), spec1),
                                out_specs=(P(), P())))
    for i in range(ticks):
        b = IntervalBatch(batches.value[i], batches.stratum[i],
                          batches.valid[i],
                          StratumMeta(batches.meta.weight[i],
                                      batches.meta.count[i]))
        s1, m1 = one(jax.random.fold_in(key, i), b)
        assert float(s1.estimate) == float(s_t.estimate[i])
        assert float(m1.estimate) == float(m_t.estimate[i])


# -------------------------------------------------------- batched ingest --
def test_batch_ingest_matches_sequential_generation():
    """batch_ingest consumes the source RNGs exactly like the sequential
    drivers and packs per (tick, node) in source order."""
    specs = S.paper_gaussian(rates=(50, 50, 50, 50))
    seq = [S.StreamSource(specs, seed=i) for i in range(4)]
    bat = [S.StreamSource(specs, seed=i) for i in range(4)]
    b = S.batch_ingest(bat, ticks=3, n_nodes=2, width=2048)
    exact = 0.0
    for t in range(3):
        fill = [0, 0]
        for i, src in enumerate(seq):
            v, s = src.tick()
            exact += float(v.sum())
            node, f = i % 2, fill[i % 2]
            np.testing.assert_array_equal(b.values[t, node, f:f + len(v)], v)
            np.testing.assert_array_equal(b.strata[t, node, f:f + len(v)], s)
            fill[node] = f + len(v)
        assert list(b.counts[t]) == fill
    assert b.exact_sum == exact


def test_stream_source_batch_matches_ticks():
    specs = S.paper_gaussian(rates=(40, 40, 40, 40))
    a = S.StreamSource(specs, seed=9)
    bsrc = S.StreamSource(specs, seed=9)
    values, strata, counts = bsrc.batch(3)
    for t in range(3):
        v, s = a.tick()
        assert counts[t] == len(v)
        np.testing.assert_array_equal(values[t, :len(v)], v)
        np.testing.assert_array_equal(strata[t, :len(v)], s)
