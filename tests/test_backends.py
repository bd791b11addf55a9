"""Sampler-backend equivalence: the ``pallas`` (interpret-mode), ``topk``
and ``argsort`` backends must produce identical keep-masks and weight sets
for the same key — across strata counts, skew, empty strata, and
degenerate reservoir allocations. No hypothesis dependency: the sweeps are
explicit so these run everywhere tier-1 runs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sampling, whs
from repro.core.types import IntervalBatch, StratumMeta

BACKENDS = ("argsort", "topk", "pallas", "pallas_fused")
ALT_BACKENDS = ("topk", "pallas", "pallas_fused")   # compared against the argsort reference


def _batch(seed, m, x, skew=None, valid_frac=1.0):
    rng = np.random.default_rng(seed)
    if skew is None:
        strata = rng.integers(0, x, m).astype(np.int32)
    else:
        # heavily skewed stratum shares, e.g. (0.9, 0.09, 0.009, ...)
        probs = np.asarray(skew, np.float64)
        strata = rng.choice(x, size=m, p=probs / probs.sum()).astype(np.int32)
    vals = rng.normal(100, 25, m).astype(np.float32)
    valid = rng.random(m) < valid_frac
    return IntervalBatch(jnp.asarray(vals), jnp.asarray(strata),
                         jnp.asarray(valid), StratumMeta.identity(x))


@pytest.mark.parametrize("alt", ALT_BACKENDS)
@pytest.mark.parametrize("m,x,budget", [
    (256, 1, 64), (512, 4, 100), (4096, 16, 500), (333, 3, 7), (1000, 7, 999),
])
def test_whsamp_backends_identical(alt, m, x, budget):
    batch = _batch(m + x, m, x, valid_frac=0.9)
    key = jax.random.PRNGKey(budget)
    a = whs.whsamp(key, batch, jnp.float32(budget), x, backend="argsort",
                   max_reservoir=budget)
    p = whs.whsamp(key, batch, jnp.float32(budget), x, backend=alt,
                   max_reservoir=budget)
    assert (np.asarray(a.selected) == np.asarray(p.selected)).all()
    np.testing.assert_array_equal(np.asarray(a.meta.weight),
                                  np.asarray(p.meta.weight))
    np.testing.assert_array_equal(np.asarray(a.meta.count),
                                  np.asarray(p.meta.count))
    np.testing.assert_array_equal(np.asarray(a.c), np.asarray(p.c))


@pytest.mark.parametrize("alt", ALT_BACKENDS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_whsamp_backends_identical_under_skew(alt, seed):
    """0.01%-share strata (the paper's §V-E setting) select identically."""
    x = 4
    batch = _batch(seed, 4096, x, skew=(0.80, 0.1989, 0.001, 0.0001))
    key = jax.random.PRNGKey(seed)
    a = whs.whsamp(key, batch, jnp.float32(400), x, backend="argsort",
                   max_reservoir=400)
    p = whs.whsamp(key, batch, jnp.float32(400), x, backend=alt,
                   max_reservoir=400)
    assert (np.asarray(a.selected) == np.asarray(p.selected)).all()
    np.testing.assert_array_equal(np.asarray(a.meta.weight),
                                  np.asarray(p.meta.weight))


@pytest.mark.parametrize("alt", ALT_BACKENDS)
def test_backends_identical_with_empty_strata(alt):
    """Strata with zero items must select nothing and keep sticky meta on
    every backend."""
    m, x = 512, 6
    rng = np.random.default_rng(5)
    strata = rng.integers(0, 2, m).astype(np.int32)   # strata 2..5 empty
    batch = IntervalBatch(jnp.asarray(rng.normal(0, 1, m), jnp.float32),
                          jnp.asarray(strata), jnp.ones((m,), bool),
                          StratumMeta.identity(x))
    key = jax.random.PRNGKey(9)
    a = whs.whsamp(key, batch, jnp.float32(64), x, backend="argsort",
                   max_reservoir=64)
    p = whs.whsamp(key, batch, jnp.float32(64), x, backend=alt,
                   max_reservoir=64)
    assert (np.asarray(a.selected) == np.asarray(p.selected)).all()
    np.testing.assert_array_equal(np.asarray(a.meta.weight),
                                  np.asarray(p.meta.weight))
    assert (np.asarray(a.meta.weight)[2:] == 1.0).all()  # sticky identity


def test_topk_matches_argsort_on_priority_ties():
    """At m ≈ 44k, f32 uniform draws collide (24-bit resolution) — the
    topk backend's position-ordered tie resolution must reproduce the
    stable lexsort law bit-for-bit."""
    m, x, budget = 44032, 8, 1104
    rng = np.random.default_rng(0)
    batch = IntervalBatch(jnp.asarray(rng.normal(100, 10, m), jnp.float32),
                          jnp.asarray(rng.integers(0, x, m), jnp.int32),
                          jnp.ones((m,), bool), StratumMeta.identity(x))
    key = jax.random.PRNGKey(m)
    u = np.asarray(jax.random.uniform(key, (m,)))
    assert m - len(np.unique(u)) > 0, "test needs priority collisions"
    a = whs.whsamp(key, batch, jnp.float32(budget), x, backend="argsort",
                   max_reservoir=budget)
    t = whs.whsamp(key, batch, jnp.float32(budget), x, backend="topk",
                   max_reservoir=budget)
    assert (np.asarray(a.selected) == np.asarray(t.selected)).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_select_zero_reservoir_keeps_nothing(backend):
    """N_i = 0 with c_i > 0 must keep zero items (regression: the threshold
    path used to clip τ to the stratum max and keep one)."""
    m, x = 128, 2
    be = sampling.get_backend(backend)
    strata = jnp.asarray(np.arange(m) % x, jnp.int32)
    sel = be.select(jax.random.PRNGKey(0), strata, jnp.ones((m,), bool),
                    jnp.asarray([0.0, 5.0]), x, max_reservoir=5)
    sel = np.asarray(sel)
    assert sel[::2].sum() == 0      # stratum 0: reservoir 0
    assert sel[1::2].sum() == 5     # stratum 1: reservoir 5


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_counts_exact(backend):
    m, x = 777, 5
    rng = np.random.default_rng(3)
    strata = rng.integers(0, x, m).astype(np.int32)
    valid = rng.random(m) < 0.6
    be = sampling.get_backend(backend)
    got = np.asarray(be.counts(jnp.asarray(strata), jnp.asarray(valid), x))
    want = np.bincount(strata[valid], minlength=x).astype(np.float32)
    np.testing.assert_array_equal(got, want)


def test_same_priorities_same_mask_across_backends():
    """The backend contract: identical priorities ⇒ identical masks."""
    m, x = 2048, 8
    rng = np.random.default_rng(11)
    strata = jnp.asarray(rng.integers(0, x, m), jnp.int32)
    valid = jnp.asarray(rng.random(m) < 0.85)
    prio = jnp.asarray(rng.random(m), jnp.float32)
    res = jnp.asarray(rng.integers(0, 60, x), jnp.float32)
    masks = [
        np.asarray(sampling.get_backend(b).select(
            jax.random.PRNGKey(0), strata, valid, res, x, priorities=prio,
            max_reservoir=60))
        for b in BACKENDS
    ]
    for other in masks[1:]:
        assert (masks[0] == other).all()


def test_get_backend_unknown_name():
    with pytest.raises(ValueError, match="unknown sampler backend"):
        sampling.get_backend("quantum")


@pytest.mark.parametrize("backend,check_vma", [
    # the Pallas interpreter mis-types its block slices under check_vma
    ("topk", True), ("pallas", False),
])
def test_spmd_path_backend_selectable(backend, check_vma):
    """sampler_backend is honored end-to-end through the shard_map data
    plane (1-device mesh)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.tree import spmd_local_then_root

    mesh = jax.make_mesh((1,), ("data",))
    rng = np.random.default_rng(0)
    m, x = 1024, 4
    batch = IntervalBatch(jnp.asarray(rng.normal(100, 10, m), jnp.float32),
                          jnp.asarray(rng.integers(0, x, m), jnp.int32),
                          jnp.ones((m,), bool), StratumMeta.identity(x))

    def f(key, b):
        s, _ = spmd_local_then_root(key, b, axis_name="data", num_strata=x,
                                    local_budget=256, root_budget=128,
                                    sampler_backend=backend)
        return s.estimate

    fn = jax.shard_map(f, mesh=mesh,
                       in_specs=(P(), IntervalBatch(P("data"), P("data"),
                                                    P("data"),
                                                    StratumMeta(P(), P()))),
                       out_specs=P(), check_vma=check_vma)
    est = float(fn(jax.random.PRNGKey(0), batch))
    exact = float(np.asarray(batch.value).sum())
    assert abs(est - exact) / exact < 0.1
