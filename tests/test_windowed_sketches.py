"""Windowed / time-decayed sketch acceptance tests (ISSUE 9).

The load-bearing law: over a drifting stream, the recency variants
(sliding-window KLL ring, exponentially decayed count-min) track the
RECENT distribution where the stream-so-far sketches provably do not —
pinned both at the sketch layer and end-to-end through the compiled
query plan (distribution shift mid-run).
"""
from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import api  # noqa: E402
from repro.data import stream as S  # noqa: E402
from repro.query import sketches  # noqa: E402
from repro.query.registry import QueryRegistry, QuerySpec  # noqa: E402


# ------------------------------------------------------------ sketch layer --


def test_windowed_quantile_tracks_recent_where_plain_lags():
    key = jax.random.PRNGKey(0)
    plain = sketches.quantile_init(128)
    ring = sketches.windowed_quantile_init(128, window=4)
    rng = np.random.default_rng(1)
    # 12 windows at μ=10, then 8 windows at μ=100
    for w in range(20):
        mu = 10.0 if w < 12 else 100.0
        v = jnp.asarray(rng.normal(mu, 1.0, 200).astype(np.float32))
        ones = jnp.ones_like(v)
        kw = jax.random.fold_in(key, w)
        plain = sketches.quantile_update(kw, plain, v, ones)
        ring = sketches.windowed_quantile_update(kw, ring, v, ones)
    q = jnp.asarray([0.5])
    plain_med = float(sketches.quantile_query(plain, q)[0])
    merged = sketches.windowed_quantile_merged(key, ring)
    ring_med = float(sketches.quantile_query(merged, q)[0])
    # the ring only remembers the last 4 windows — all post-shift
    assert abs(ring_med - 100.0) < 5.0
    # the stream-so-far sketch still answers from the 12 old windows
    assert abs(plain_med - 100.0) > 20.0


def test_windowed_quantile_ring_evicts_in_fifo_order():
    key = jax.random.PRNGKey(2)
    ring = sketches.windowed_quantile_init(64, window=2)
    ones = jnp.ones((64,), jnp.float32)
    for w, mu in enumerate([1.0, 2.0, 3.0, 4.0]):
        ring = sketches.windowed_quantile_update(
            jax.random.fold_in(key, w), ring,
            jnp.full((64,), mu, jnp.float32), ones)
    merged = sketches.windowed_quantile_merged(key, ring)
    med = float(sketches.quantile_query(merged, jnp.asarray([0.5]))[0])
    # windows 1.0 and 2.0 were evicted; only 3.0 / 4.0 remain
    assert med in (3.0, 4.0)
    assert int(ring.head) == 0          # wrapped twice
    assert ring.window == 2 and ring.capacity == 64


def test_decayed_counts_flip_to_new_heavy_key_where_plain_does_not():
    old = jnp.full((64,), 7.0, jnp.float32)
    new = jnp.full((16,), 42.0, jnp.float32)
    ones_old = jnp.ones_like(old)
    ones_new = jnp.ones_like(new)
    plain = sketches.hh_init(k=2, width=256, depth=4)
    dec = sketches.hh_init(k=2, width=256, depth=4)
    # 10 heavy windows of key 7, then 6 light windows of key 42
    for _ in range(10):
        plain = sketches.hh_update(plain, sketches.hh_item_key(old),
                                   ones_old)
        dec = sketches.hh_decayed_update(dec, sketches.hh_item_key(old),
                                         ones_old, decay=0.5)
    for _ in range(6):
        plain = sketches.hh_update(plain, sketches.hh_item_key(new),
                                   ones_new)
        dec = sketches.hh_decayed_update(dec, sketches.hh_item_key(new),
                                         ones_new, decay=0.5)
    # stream-so-far: 640 of key 7 vs 96 of key 42 — old key stays on top
    assert int(plain.key[0]) == 7
    # decayed: old mass halved every window since the shift — new key wins
    assert int(dec.key[0]) == 42
    # decayed total weight reflects the decayed stream, not the raw count
    assert float(dec.total_weight) < float(plain.total_weight)


def test_decayed_update_is_linear_in_the_counts():
    # γ·(A+B) + a + b == (γ·A + a) + (γ·B + b): the decayed CM stays
    # psum-mergeable across devices
    ka = jnp.full((8,), 3.0, jnp.float32)
    kb = jnp.full((8,), 9.0, jnp.float32)
    ones = jnp.ones((8,), jnp.float32)
    merged = sketches.hh_init(k=2, width=128, depth=4)
    a = sketches.hh_init(k=2, width=128, depth=4)
    b = sketches.hh_init(k=2, width=128, depth=4)
    for _ in range(3):
        merged = sketches.hh_decayed_update(
            merged, sketches.hh_item_key(jnp.concatenate([ka, kb])),
            jnp.ones((16,), jnp.float32), decay=0.7)
        a = sketches.hh_decayed_update(a, sketches.hh_item_key(ka), ones,
                                       decay=0.7)
        b = sketches.hh_decayed_update(b, sketches.hh_item_key(kb), ones,
                                       decay=0.7)
    np.testing.assert_allclose(np.asarray(merged.counts),
                               np.asarray(a.counts) + np.asarray(b.counts),
                               rtol=1e-6)


# ---------------------------------------------------------------- registry --


def test_registry_windowed_and_decayed_specs():
    reg = (QueryRegistry()
           .register_windowed_quantile("wq", qs=(0.5, 0.9), capacity=64,
                                       window=3)
           .register_decayed_heavy_hitters("dhh", k=4, width=256,
                                           decay=0.8))
    wq, dhh = reg.specs
    assert wq.out_width == 2 and wq.window == 3
    assert dhh.out_width == 8 and dhh.decay == 0.8
    with pytest.raises(ValueError, match="window"):
        QuerySpec("bad", "windowed_quantile", qs=(0.5,), window=0)
    with pytest.raises(ValueError, match="decay"):
        QuerySpec("bad", "decayed_heavy_hitters", decay=1.0)
    with pytest.raises(ValueError, match="qs"):
        QuerySpec("bad", "windowed_quantile")
    with pytest.raises(ValueError, match="2\\^n"):
        QuerySpec("bad", "decayed_heavy_hitters", width=100)


def test_registry_token_language_parses_new_kinds():
    reg = QueryRegistry.from_tokens("wq:0.5:0.99,dhh:4:0.7,sum")
    wq, dhh, _ = reg.specs
    assert wq.kind == "windowed_quantile" and wq.qs == (0.5, 0.99)
    assert dhh.kind == "decayed_heavy_hitters"
    assert dhh.k == 4 and dhh.decay == 0.7
    with pytest.raises(ValueError, match="malformed query token"):
        QueryRegistry.from_tokens("wq:not-a-number")


def test_spec_roundtrip_keeps_new_fields():
    spec = api.PipelineSpec(
        topology=api.TopologySpec(fanin=(2, 1), capacity=128, num_strata=2),
        sampler=api.SamplerSpec(mode="whs", backend="topk", fraction=1.0),
        tenants=(QueryRegistry()
                 .register_windowed_quantile("wq", qs=(0.5,), window=5)
                 .register_decayed_heavy_hitters("dhh", decay=0.75)
                 .as_tenant("t"),), seed=0)
    assert api.PipelineSpec.from_dict(spec.to_dict()) == spec


# ------------------------------------------------- end-to-end (compiled) --


def test_pipeline_drift_regression_recent_vs_stream_so_far():
    """The ISSUE 9 acceptance regression: a mid-run distribution shift.
    The windowed quantile and decayed top-k track the NEW regime; the
    stream-so-far quantile and plain top-k provably answer from the old
    one."""
    reg = (QueryRegistry()
           .register_quantile("q_all", qs=(0.5,), capacity=64)
           .register_windowed_quantile("q_recent", qs=(0.5,), capacity=64,
                                       window=4)
           .register_heavy_hitters("hh_all", k=2, width=256)
           .register_decayed_heavy_hitters("hh_recent", k=2, width=256,
                                           decay=0.5))
    spec = api.PipelineSpec(
        topology=api.TopologySpec(fanin=(2, 1), capacity=128, num_strata=2),
        sampler=api.SamplerSpec(mode="whs", backend="topk", fraction=1.0),
        tenants=(reg.as_tenant("t"),), seed=0)
    pipe = api.compile(spec)
    state = pipe.init()
    rng = np.random.default_rng(0)
    ticks = []
    for t in range(24):
        # 16 windows around key 10, then 8 around key 100
        mu = 10.0 if t < 16 else 100.0
        v = rng.normal(mu, 0.5, 48).astype(np.float32)
        s = (np.arange(48) % 2).astype(np.int32)
        ticks.append((v, s))
    batch = S.ticks_to_ingest(ticks, n_nodes=2, width=128)
    state, wa = pipe.run_epoch(state, pipe.default_key, batch.values,
                               batch.strata, batch.counts)
    last = pipe.rows(wa)[-1]
    q_all = float(pipe.answer(last["answers"], "q_all")[0])
    q_recent = float(pipe.answer(last["answers"], "q_recent")[0])
    hh_all = float(pipe.answer(last["answers"], "hh_all")[0])
    hh_recent = float(pipe.answer(last["answers"], "hh_recent")[0])
    # recency queries live in the new regime...
    assert abs(q_recent - 100.0) < 5.0
    assert abs(hh_recent - 100.0) <= 1.0
    # ...stream-so-far queries still answer from the old one
    assert abs(q_all - 10.0) < 5.0
    assert abs(hh_all - 10.0) <= 1.0
    # the windowed bound is the merged summary's live rank error
    assert float(pipe.answer(last["bounds"], "q_recent")[0]) >= 0.0


def test_windowed_and_decayed_lower_through_spmd_plan():
    """The same kinds answer on the mesh path (single-device mesh run:
    exercises the all-gather/psum merge lowering)."""
    from repro.launch.analytics import make_data_mesh

    reg = (QueryRegistry()
           .register_windowed_quantile("wq", qs=(0.5,), capacity=64,
                                       window=2)
           .register_decayed_heavy_hitters("dhh", k=2, width=256,
                                           decay=0.5))
    spec = api.PipelineSpec(
        topology=api.TopologySpec(fanin=(1, 1), capacity=128, num_strata=2),
        sampler=api.SamplerSpec(mode="whs", backend="topk", fraction=1.0),
        tenants=(reg.as_tenant("t"),), seed=0)
    pipe = api.compile(spec, mesh=make_data_mesh(1))
    rng = np.random.default_rng(3)
    rows_v = np.zeros((8, 64), np.float32)
    rows_s = np.zeros((8, 64), np.int32)
    counts = np.full((8,), 64, np.int32)
    for t in range(8):
        mu = 5.0 if t < 6 else 50.0
        rows_v[t] = rng.normal(mu, 0.5, 64).astype(np.float32)
        rows_s[t] = np.arange(64) % 2
    state = pipe.init()
    state, wa = pipe.run_epoch(state, pipe.default_key, rows_v[:, None],
                               rows_s[:, None], counts[:, None])
    last = pipe.rows(wa)[-1]
    wq = float(pipe.answer(last["answers"], "wq")[0])
    dhh = float(pipe.answer(last["answers"], "dhh")[0])
    assert abs(wq - 50.0) < 5.0          # ring holds the last 2 windows
    assert abs(dhh - 50.0) <= 1.0        # decayed top-1 is the new key
