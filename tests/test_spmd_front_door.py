"""The mesh pipeline's front door: ``compile(spec, mesh=...)`` takes the
local pipeline's ``run_epoch(state, key, values, strata, counts,
budgets)`` on ``[T, fanin[0], width]`` node rows, numbers windows from
tick 1, exposes ``fanin``, and ``StreamingExecutor`` runs over it
unchanged.

Every check runs on four simulated devices in a SUBPROCESS (the device
count is fixed when JAX starts, and the test process keeps its default
one-device backend); one run feeds every assertion through a module
fixture.
"""
import json
import math
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_WORKER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.api.spec import SpecError
    from jax.sharding import PartitionSpec as P

    from repro.core import tree as TR
    from repro.core.types import IntervalBatch, StratumMeta
    from repro.data import stream as S
    from repro.launch.analytics import default_capacity
    from repro.query.registry import QueryRegistry
    from repro.serve import StreamingExecutor, SyntheticSource

    N_SRC, FANIN, EPOCH, EPOCHS = 8, (4, 2, 1), 8, 3
    specs = S.paper_gaussian(rates=(50,) * 4)
    X = len(specs)
    cap = default_capacity(specs, N_SRC, FANIN)

    def spec(fanin=FANIN):
        reg = (QueryRegistry().register_count("n").register_sum("total")
               .register_mean("avg")
               .register_quantile("q", (0.5, 0.9), capacity=64))
        return api.PipelineSpec(
            topology=api.TopologySpec(fanin=fanin, capacity=cap,
                                      num_strata=X),
            sampler=api.SamplerSpec(mode="whs", backend="topk",
                                    fraction=0.25),
            tenants=(reg.as_tenant("dash"),), seed=11)

    mesh = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
    pipe = api.compile(spec(), mesh=mesh)
    out = {"fanin": list(pipe.fanin),
           "layout_equal": pipe.query_layout()
           == api.compile(spec()).query_layout()}

    # ---- node rows vs the core epoch on interval batches laid out by hand
    rng = np.random.default_rng(5)
    T, n0 = 3, FANIN[0]
    counts = rng.integers(cap // 2, cap, (T, n0)).astype(np.int32)
    live = np.arange(cap)[None, None, :] < counts[..., None]
    values = np.where(live, rng.normal(50.0, 9.0, (T, n0, cap)),
                      0.0).astype(np.float32)
    strata = np.where(live, rng.integers(0, X, (T, n0, cap)),
                      0).astype(np.int32)
    s1, wa = pipe.run_epoch(pipe.init(), pipe.default_key, values, strata,
                            counts)
    flat = lambda a: jnp.asarray(a.reshape(T, n0 * cap))
    batch = IntervalBatch(flat(values), flat(strata), flat(live),
                          StratumMeta(jnp.ones((T, X), jnp.float32),
                                      jnp.zeros((T, X), jnp.float32)))
    item, rep = P(None, "data"), P()

    def core_epoch(key, budget, batch, q):
        qf, outs = TR.spmd_query_plane_epoch(
            key, jnp.int32(1), budget, batch,
            jax.tree.map(lambda v: v[0], q), pipe.plan.core,
            axis_name="data", max_budget=pipe.max_local_budget,
            num_strata=X, allocation=pipe.spec.sampler.allocation,
            sampler_backend=pipe.spec.sampler.backend)
        return jax.tree.map(lambda v: v[None], qf), outs

    q0 = jax.tree.map(lambda v: jnp.stack([v] * 4), pipe.plan.init_state())
    qf, outs = jax.jit(jax.shard_map(
        core_epoch, mesh=mesh,
        in_specs=(rep, rep, IntervalBatch(item, item, item,
                                          StratumMeta(rep, rep)), P("data")),
        out_specs=(P("data"), rep)))(
        pipe.default_key, jnp.float32(pipe.local_budget), batch, q0)
    ok, se, sv, me, mv, nsel, hist, ans, bnd = outs
    want = dict(ok=ok, sum=se, sum_var=sv, mean=me, mean_var=mv,
                n_sampled=nsel, histogram=hist,
                answers=pipe.plan.compact(ans), bounds=pipe.plan.compact(bnd))
    out["bitwise"] = {f: bool(np.array_equal(np.asarray(getattr(wa, f)),
                                             np.asarray(v)))
                      for f, v in want.items()}
    out["state_bitwise"] = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(s1.qstate), jax.tree.leaves(qf)))
    out["ticks"] = np.asarray(wa.tick).tolist()
    out["count_rows"] = np.asarray(
        pipe.answer(np.asarray(wa.answers), "n"))[:, 0].tolist()
    out["count_exact"] = counts.sum(axis=1).tolist()
    _, wc = pipe.run_epoch(s1, pipe.default_key, values, strata, counts)
    out["next_ticks"] = np.asarray(wc.tick).tolist()
    out["local_ticks"] = np.asarray(api.compile(spec()).run_epoch(
        api.compile(spec()).init(), pipe.default_key, values, strata,
        counts)[1].tick).tolist()

    # ---- a level-0 node count the mesh axis does not divide ------------
    odd = api.compile(spec((2, 1)), mesh=mesh)
    try:
        odd.run_epoch(odd.init(), odd.default_key, values[:, :2],
                      strata[:, :2], counts[:, :2])
        out["odd_error"] = None
    except SpecError as e:
        out["odd_error"] = str(e)

    # ---- the streaming executor over the mesh pipeline -----------------
    log = []

    class Recorded:
        def __init__(self, src):
            self.src = src

        def subscribe(self, deliver):
            def tee(shard, v, s):
                log[-1].append(np.asarray(v, np.float64))
                deliver(shard, v, s)
            self.src.subscribe(tee)

        def pump(self, now):
            self.src.pump(now)

    sources = [Recorded(SyntheticSource(i // (N_SRC // n0), specs=specs,
                                        seed=700 + i))
               for i in range(N_SRC)]
    ex = StreamingExecutor(epoch_ticks=EPOCH, width=cap,
                           queue_capacity=2 * cap, policy="block", seed=3)
    ex.start(pipe, sources)
    for _ in range(EPOCHS * EPOCH):
        log.append([])
        ex.pump()
    stats = ex.stop()
    lay = pipe.query_layout()
    windows = []
    for w in ex.published:
        a, b = np.asarray(w.answers), np.asarray(w.bounds)
        windows.append({"tick": w.tick, "partial": bool(w.partial),
                        "n_sampled": int(w.n_sampled),
                        "answers": a.tolist(), "bounds": b.tolist()})
    out["executor"] = {
        "windows": windows, "ticks": [np.concatenate(p).tolist()
                                      for p in log],
        "shed": stats["queue_items_dropped"] + stats["queue_deferred"]
        + stats["truncated_items"],
        "layout": {k: list(v) for k, v in lay.items()},
        "epoch": EPOCH}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def door():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _WORKER],
                         capture_output=True, text=True, timeout=900,
                         env=env, cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_node_rows_are_bitwise_the_interval_batch_step(door):
    """Node ``d``'s rows on device ``d``, mask and source metadata built
    on the device: every answer and the carried sketch state are bitwise
    what the core's interval-batch epoch (``spmd_query_plane_epoch``)
    gives on the same items laid out by hand, item axis split over the
    mesh."""
    assert all(door["bitwise"].values()), door["bitwise"]
    assert door["state_bitwise"]
    assert door["count_rows"] == door["count_exact"]


def test_fresh_state_numbers_windows_from_one_as_the_local_pipeline(door):
    assert door["ticks"] == [1, 2, 3] == door["local_ticks"]
    assert door["next_ticks"] == [4, 5, 6]
    assert door["layout_equal"]
    assert door["fanin"] == [4, 2, 1]


def test_a_fanin_the_mesh_axis_does_not_divide_is_refused(door):
    assert door["odd_error"] is not None
    assert "multiple of mesh axis" in door["odd_error"]


def _max_misses(n: int, p: float = 0.0455, alpha: float = 1e-3) -> int:
    """The most of ``n`` windows a calibrated 2-sigma bound may miss by
    chance: the smallest k with P(Binomial(n, p) > k) <= alpha."""
    tail = 1.0
    for k in range(n + 1):
        tail -= math.comb(n, k) * p ** k * (1.0 - p) ** (n - k)
        if tail <= alpha:
            return k
    return n


def test_the_executor_publishes_every_window_of_the_mesh(door):
    ex = door["executor"]
    ticks = [w["tick"] for w in ex["windows"]]
    assert ticks == list(range(1, len(ex["ticks"]) + 1))
    assert ex["shed"] == 0
    assert not any(w["partial"] for w in ex["windows"])


@pytest.mark.parametrize("query", ["n", "total", "avg", "q"])
def test_executor_windows_against_a_numpy_reference(door, query):
    """Each published window against the exact items that entered its
    tick: the count exact; sum and mean inside their +-2 sigma at the
    coverage a calibrated bound reaches; the continuous quantiles, at
    each epoch's last window, ranked among every item so far, inside
    their rank bound plus 2/sqrt(items sampled so far)."""
    ex = door["executor"]
    o, w, _ = ex["layout"][query]
    wins = ex["windows"]
    items = [np.asarray(v) for v in ex["ticks"]]
    a = np.asarray([x["answers"] for x in wins])[:, o:o + w]
    b = np.asarray([x["bounds"] for x in wins])[:, o:o + w]
    if query == "n":
        np.testing.assert_array_equal(a[:, 0], [v.size for v in items])
        np.testing.assert_array_equal(b[:, 0], 0.0)
    elif query in ("total", "avg"):
        exact = np.asarray([v.sum() if query == "total" else v.mean()
                            for v in items])
        ratio = np.abs(a[:, 0] - exact) / b[:, 0]
        assert np.all(b[:, 0] > 0.0)
        assert np.sum(ratio > 1.0) <= _max_misses(ratio.size), ratio
    else:
        n_sampled = np.asarray([x["n_sampled"] for x in wins], np.float64)
        epoch = ex["epoch"]
        for last in range(epoch - 1, len(wins), epoch):
            seen = np.sort(np.concatenate(items[:last + 1]))
            slack = 2.0 / np.sqrt(n_sampled[:last + 1].sum())
            for q, v, bound in zip((0.5, 0.9), a[last], b[last]):
                rank = np.searchsorted(seen, v, side="right") / seen.size
                assert abs(rank - q) <= bound + slack, (last, q, rank)
