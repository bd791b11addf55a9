"""HostTree end-to-end: the paper's topology, accuracy, bandwidth, skew."""
import numpy as np
import pytest

from repro.data import stream as S
from repro.launch.analytics import run_pipeline


def test_pipeline_accuracy_within_bounds_gaussian():
    r = run_pipeline(S.paper_gaussian(), fraction=0.2, ticks=10, seed=1)
    assert r["accuracy_loss"] < 0.02
    assert r["within_2sigma"] or r["accuracy_loss"] < 0.005


def test_bandwidth_saving_tracks_fraction():
    """Fig. 8: items forwarded from layer 0 ≈ sampling fraction."""
    r = run_pipeline(S.paper_gaussian(), fraction=0.1, ticks=8, seed=2)
    assert r["bandwidth_fraction"] < 0.2
    r2 = run_pipeline(S.paper_gaussian(), fraction=0.5, ticks=8, seed=2)
    assert r2["bandwidth_fraction"] > r["bandwidth_fraction"]


def test_skew_whs_beats_srs_style_allocation():
    """Fig. 11c: under heavy skew, stratified allocation is orders of
    magnitude more accurate than the SRS coin-flip baseline.

    Proportional allocation used to be SRS-like here because it rounded
    rare stratum D down to ZERO reservoir rows — dropped mass, i.e. bias.
    The one-row unbiasedness reserve in ``allocate_reservoirs`` fixed
    that, so proportional is now merely higher-variance than fair (it
    over-spends budget on the bulk strata) while both stratified policies
    crush true SRS, which misses stratum D entirely."""
    specs = S.paper_poisson(rates=tuple(4000 * s for s in S.SKEW_SHARES),
                            skewed=True)
    errs = {}
    for alloc in ("fair", "proportional"):
        losses = [run_pipeline(specs, fraction=0.1, ticks=6, seed=s,
                               allocation=alloc)["accuracy_loss"]
                  for s in range(3)]
        errs[alloc] = np.mean(losses)
    srs = np.mean([run_pipeline(specs, fraction=0.1, ticks=6, seed=s,
                                mode="srs")["accuracy_loss"]
                   for s in range(3)])
    assert errs["fair"] < errs["proportional"], errs
    assert errs["fair"] * 100 < srs, (errs, srs)
    assert errs["proportional"] * 100 < srs, (errs, srs)


def test_async_intervals_stay_unbiased():
    """§III-C: different interval lengths per level still give accurate
    results thanks to Eq. 9 calibration."""
    r = run_pipeline(S.paper_gaussian(), fraction=0.3, ticks=12,
                     interval_ticks=[1, 2, 3], seed=3)
    assert r["accuracy_loss"] < 0.03, r["accuracy_loss"]


def test_spmd_hierarchy_single_device():
    """In-graph two-level hierarchy under shard_map on a 1-device mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.tree import spmd_local_then_root
    from repro.core.types import IntervalBatch, StratumMeta

    mesh = jax.make_mesh((1,), ("data",))
    rng = np.random.default_rng(0)
    m, x = 1024, 4
    batch = IntervalBatch(
        value=jnp.asarray(rng.normal(100, 10, m), jnp.float32),
        stratum=jnp.asarray(rng.integers(0, x, m), jnp.int32),
        valid=jnp.ones((m,), bool),
        meta=StratumMeta.identity(x),
    )

    def f(key, b):
        s, mn = spmd_local_then_root(key, b, axis_name="data", num_strata=x,
                                     local_budget=256, root_budget=128)
        return s.estimate, s.variance, mn.estimate

    batch_specs = IntervalBatch(P("data"), P("data"), P("data"),
                                StratumMeta(P(), P()))
    fn = jax.shard_map(f, mesh=mesh,
                       in_specs=(P(), batch_specs),
                       out_specs=(P(), P(), P()))
    est, var, mean = fn(jax.random.PRNGKey(0), batch)
    exact = float(np.asarray(batch.value).sum())
    assert abs(float(est) - exact) / exact < 0.1
    assert float(var) >= 0
