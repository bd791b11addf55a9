"""Streaming serve plane (repro.serve) acceptance tests.

The load-bearing laws (ISSUE 9):

* bounded-queue drop accounting: ``items_in == items_out +
  items_dropped + depth`` under every backpressure policy, with every
  drop counted (never silent);
* straggler window ≡ on-time window BITWISE when no shard is late —
  the executor path adds nothing to a synchronous ``run_epoch`` run;
* a window with a late shard publishes a *partial* answer whose Eq. 9
  calibrated estimate covers the true value and whose bounds are
  widened by 1/α ≥ 1 (partial bound ≥ full bound), and the late data
  folds into the next window (Σ raw counts conserves every item);
* ``stop()`` drains: no queued items remain, accounting still closes;
* the ``repro_serve_*`` metric families render and Prometheus-parse;
* the executor's ``executor.*`` spans: one per stage per pump, nested
  as documented, epoch-tagged, in the profiler's trace under the
  caller's annotation, and neutral — windows are bitwise the same with
  the tracer off.

All tests run with an injected fake clock and deterministic sources.
"""
from __future__ import annotations

import collections

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro import api  # noqa: E402
from repro.obs.metrics import (metrics_text,  # noqa: E402
                               parse_prometheus_text)
from repro.query.registry import QueryRegistry  # noqa: E402
from repro.serve import (BoundedShardQueue, ConstantSource,  # noqa: E402
                         DoubleBuffer, LateShardSource, StreamingExecutor,
                         WindowPublisher)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float):
        self.t += dt


def _registry() -> QueryRegistry:
    return (QueryRegistry().register_count("n").register_sum("s")
            .register_mean("m"))


def _spec(fraction: float = 1.0) -> api.PipelineSpec:
    return api.PipelineSpec(
        topology=api.TopologySpec(fanin=(2, 1), capacity=256, num_strata=2),
        sampler=api.SamplerSpec(mode="whs", backend="topk",
                                fraction=fraction),
        tenants=(_registry().as_tenant("t"),), seed=0)


def _executor(clock, **kw) -> StreamingExecutor:
    kw.setdefault("epoch_ticks", 4)
    kw.setdefault("width", 64)
    kw.setdefault("queue_capacity", 256)
    return StreamingExecutor(clock=clock, **kw)


def _run(ex, clock, ticks, dt=1.0):
    for _ in range(ticks):
        clock.advance(dt)
        ex.pump()


# ---------------------------------------------------------------- queues --


@pytest.mark.parametrize("policy", ["block", "drop_oldest", "degrade"])
def test_queue_accounting_law(policy):
    q = BoundedShardQueue(capacity=16, policy=policy, seed=3)
    rng = np.random.default_rng(0)
    for step in range(50):
        n = int(rng.integers(0, 8))
        q.put(rng.normal(size=n), np.zeros(n, np.int32), float(step))
        q.get_many(int(rng.integers(0, 6)))
        assert q.accounting_ok, q.stats()
    assert q.high_watermark <= q.capacity


def test_queue_block_defers_overflow():
    q = BoundedShardQueue(capacity=4, policy="block")
    accepted = q.put(np.arange(10.0), np.zeros(10, np.int32), 0.0)
    assert accepted == 4 and q.deferred == 6 and q.depth == 4
    assert q.items_dropped == 0 and q.accounting_ok


def test_queue_drop_oldest_keeps_freshest():
    q = BoundedShardQueue(capacity=4, policy="drop_oldest")
    q.put(np.arange(10.0), np.zeros(10, np.int32), 0.0)
    assert q.items_dropped == 6 and q.depth == 4
    values, _, _ = q.get_many(10)
    np.testing.assert_array_equal(values, [6.0, 7.0, 8.0, 9.0])
    assert q.accounting_ok


def test_queue_degrade_sheds_proportionally_and_deterministically():
    def fill(seed):
        q = BoundedShardQueue(capacity=32, policy="degrade", seed=seed)
        for step in range(8):
            q.put(np.arange(16.0), np.zeros(16, np.int32), float(step))
        return q
    a, b = fill(7), fill(7)
    assert a.items_dropped == b.items_dropped > 0   # deterministic, shedding
    assert a.depth == b.depth and a.accounting_ok
    # an empty queue accepts everything (p_drop = 0)
    q = BoundedShardQueue(capacity=32, policy="degrade", seed=7)
    assert q.put(np.arange(8.0), np.zeros(8, np.int32), 0.0) == 8


def test_queue_rejects_bad_policy_and_capacity():
    with pytest.raises(ValueError, match="policy"):
        BoundedShardQueue(capacity=4, policy="shrug")
    with pytest.raises(ValueError, match="capacity"):
        BoundedShardQueue(capacity=0)


class _ItemQueue:
    """The oracle: the bounded queue kept as a FIFO of single
    ``(value, stratum, arrival)`` items, one Python step per item."""

    def __init__(self, capacity: int, policy: str = "block", seed: int = 0):
        self.capacity = int(capacity)
        self.policy = policy
        self._q: collections.deque = collections.deque()
        self._rng = np.random.default_rng(seed)
        self.items_in = 0
        self.items_out = 0
        self.items_dropped = 0
        self.deferred = 0
        self.high_watermark = 0

    def put(self, values, strata, now: float) -> int:
        values = np.asarray(values, np.float32)
        strata = np.asarray(strata, np.int32)
        offered = int(values.size)
        if offered == 0:
            return 0
        if self.policy == "block":
            take = min(offered, self.capacity - len(self._q))
            self.deferred += offered - take
            self.items_in += take
            for i in range(take):
                self._q.append((float(values[i]), int(strata[i]), now))
            accepted = take
        elif self.policy == "drop_oldest":
            self.items_in += offered
            for i in range(offered):
                self._q.append((float(values[i]), int(strata[i]), now))
            while len(self._q) > self.capacity:
                self._q.popleft()
                self.items_dropped += 1
            accepted = offered
        else:  # degrade
            self.items_in += offered
            p_drop = len(self._q) / self.capacity
            keep = self._rng.random(offered) >= p_drop
            self.items_dropped += int(offered - keep.sum())
            for i in np.flatnonzero(keep):
                self._q.append((float(values[i]), int(strata[i]), now))
            while len(self._q) > self.capacity:
                self._q.popleft()
                self.items_dropped += 1
            accepted = int(keep.sum())
        self.high_watermark = max(self.high_watermark, len(self._q))
        return accepted

    def get_many(self, max_records: int):
        n = min(int(max_records), len(self._q))
        values = np.empty(n, np.float32)
        strata = np.empty(n, np.int32)
        arrivals = np.empty(n, np.float64)
        for i in range(n):
            values[i], strata[i], arrivals[i] = self._q.popleft()
        self.items_out += n
        return values, strata, arrivals

    @property
    def depth(self) -> int:
        return len(self._q)


_COUNTERS = ("items_in", "items_out", "items_dropped", "deferred",
             "high_watermark", "depth")


@pytest.mark.parametrize("policy", ["block", "drop_oldest", "degrade"])
def test_chunked_queue_matches_the_per_item_queue(policy):
    """Same calls, same answers: every drain returns the oracle's items,
    strata and arrivals in its order, and every counter agrees after
    every step, batches larger than the capacity included."""
    rng = np.random.default_rng(11)
    q = BoundedShardQueue(capacity=16, policy=policy, seed=5)
    ref = _ItemQueue(capacity=16, policy=policy, seed=5)
    for step in range(400):
        if rng.random() < 0.5:
            n = int(rng.integers(0, 41))
            values = rng.normal(size=n)
            strata = rng.integers(0, 4, size=n)
            now = float(step) + 0.25
            assert q.put(values, strata, now) == ref.put(values, strata, now)
        else:
            k = int(rng.integers(0, 51))
            for got, want in zip(q.get_many(k), ref.get_many(k)):
                assert got.dtype == want.dtype
                np.testing.assert_array_equal(got, want)
        for name in _COUNTERS:
            assert getattr(q, name) == getattr(ref, name), (step, name)
        assert q.accounting_ok


def test_get_many_split_keeps_the_tail_in_order_with_its_arrival():
    q = BoundedShardQueue(capacity=64, policy="block")
    q.put(np.arange(5.0), np.arange(5), 1.0)
    q.put(np.arange(5.0, 8.0), np.arange(5, 8), 2.0)
    values, strata, arrivals = q.get_many(3)
    np.testing.assert_array_equal(values, [0.0, 1.0, 2.0])
    np.testing.assert_array_equal(arrivals, [1.0, 1.0, 1.0])
    assert q.chunks_split == 1 and q.depth == 5
    values, strata, arrivals = q.get_many(4)
    np.testing.assert_array_equal(values, [3.0, 4.0, 5.0, 6.0])
    np.testing.assert_array_equal(strata, [3, 4, 5, 6])
    np.testing.assert_array_equal(arrivals, [1.0, 1.0, 2.0, 2.0])
    assert q.chunks_touched == 3 and q.chunks_split == 2
    values, _, arrivals = q.get_many(10)
    np.testing.assert_array_equal(values, [7.0])
    np.testing.assert_array_equal(arrivals, [2.0])
    assert q.depth == 0 and q.accounting_ok


def test_drop_oldest_trims_across_a_chunk_boundary():
    q = BoundedShardQueue(capacity=6, policy="drop_oldest")
    q.put(np.arange(3.0), np.zeros(3, np.int32), 1.0)
    q.put(np.arange(3.0, 6.0), np.zeros(3, np.int32), 2.0)
    # 4 more: the first chunk goes whole, the second loses its head.
    q.put(np.arange(6.0, 10.0), np.zeros(4, np.int32), 3.0)
    assert q.items_dropped == 4 and q.depth == 6
    values, _, arrivals = q.get_many(10)
    np.testing.assert_array_equal(values, [4.0, 5.0, 6.0, 7.0, 8.0, 9.0])
    np.testing.assert_array_equal(arrivals, [2.0, 2.0, 3.0, 3.0, 3.0, 3.0])
    assert q.accounting_ok


@pytest.mark.parametrize("policy", ["block", "drop_oldest", "degrade"])
def test_put_stores_a_copy(policy):
    q = BoundedShardQueue(capacity=16, policy=policy)
    values = np.arange(4, dtype=np.float32)
    strata = np.arange(4, dtype=np.int32)
    q.put(values, strata, 0.0)
    values[:] = -1.0
    strata[:] = -1
    got_v, got_s, _ = q.get_many(4)
    np.testing.assert_array_equal(got_v, [0.0, 1.0, 2.0, 3.0])
    np.testing.assert_array_equal(got_s, [0, 1, 2, 3])


# --------------------------------------------------------------- staging --


def test_double_buffer_packs_truncates_and_zeroes_on_swap():
    buf = DoubleBuffer(epoch_ticks=2, n_nodes=1, width=4)
    assert buf.stage(0, 0, np.arange(3.0), np.zeros(3, np.int32),
                     arrival=5.0) == 3
    assert buf.stage(0, 0, np.arange(3.0), np.zeros(3, np.int32),
                     arrival=2.0) == 1          # only one slot left
    assert buf.truncated_total == 2 and buf.staged_total == 4
    assert buf.first_arrival(0) == 2.0
    epoch = buf.swap()
    np.testing.assert_array_equal(epoch.counts, [[4], [0]])
    np.testing.assert_array_equal(epoch.values[0, 0], [0, 1, 2, 0])
    assert epoch.offered[0, 0] == 6
    # the newly active buffer is clean
    assert buf.first_arrival(0) == np.inf
    assert buf.swap().counts.sum() == 0


# --------------------------------------------- bitwise on-time equivalence --


def test_on_time_run_is_bitwise_equal_to_synchronous_epochs():
    pipe = api.compile(_spec())
    clock = FakeClock()
    ex = _executor(clock)
    ex.start(pipe, [ConstantSource(0, rate=6, value=2.0, stratum=0),
                    ConstantSource(1, rate=6, value=3.0, stratum=1)],
             warmup=False)
    _run(ex, clock, 8)            # two full epochs
    ex.stop()
    assert all(not w.partial and w.alpha == 1.0 for w in ex.published)

    # the same ingest, run synchronously through the bare pipeline with
    # the executor's per-epoch key schedule
    values = np.zeros((4, 2, 64), np.float32)
    strata = np.zeros((4, 2, 64), np.int32)
    counts = np.full((4, 2), 6, np.int32)
    values[:, 0, :6] = 2.0
    values[:, 1, :6] = 3.0
    strata[:, 1, :6] = 1
    state = pipe.init()
    rows = []
    for epoch in range(2):
        key = jax.random.fold_in(pipe.default_key, epoch)
        state, wa = pipe.run_epoch(state, key, values, strata, counts)
        rows.extend(pipe.rows(wa))
    assert len(rows) == len(ex.published) == 8
    for row, win in zip(rows, ex.published):
        assert row["tick"] == win.tick
        # published complete windows pass the arrays through UNTOUCHED
        np.testing.assert_array_equal(row["answers"], win.answers)
        np.testing.assert_array_equal(row["bounds"], win.bounds)
        assert row["sum"] == win.sum and row["mean"] == win.mean
        np.testing.assert_array_equal(row["histogram"], win.histogram)


# ------------------------------------------- straggler / partial windows --


def test_late_shard_publishes_partial_then_folds_into_next_window():
    pipe = api.compile(_spec())
    clock = FakeClock()
    ex = _executor(clock)
    # shard 1 is late for its pump ticks [4, 6) -> global ticks 5..6
    ex.start(pipe, [ConstantSource(0, rate=8, value=2.0),
                    LateShardSource(ConstantSource(1, rate=8, value=2.0),
                                    4, 6)], warmup=False)
    _run(ex, clock, 12)
    summary = ex.stop()

    partials = [w for w in ex.published if w.partial]
    assert len(partials) == 2 and summary["windows_partial"] == 2
    n = lambda vec, w=None: float(pipe.answer(vec, "n")[0])
    for w in partials:
        assert w.tick in (5, 6)
        # Eq. 9: α = 8/16, raw answer covers only the arrived shard,
        # calibrated answer recovers the TRUE full-window value exactly
        # (constant source, fraction 1.0, exact EWMA rate)
        assert w.alpha == pytest.approx(0.5)
        assert n(w.raw["answers"]) == pytest.approx(8.0)
        assert n(w.answers) == pytest.approx(16.0)
        truth_sum = 2.0 * 16
        assert w.sum == pytest.approx(truth_sum)
        # widened bounds dominate the raw ones: bound' = bound / α
        raw_b = np.asarray(w.raw["bounds"], np.float64)
        np.testing.assert_allclose(np.asarray(w.bounds, np.float64),
                                   raw_b / w.alpha, rtol=1e-6)
        assert (np.asarray(w.bounds) >= np.asarray(w.raw["bounds"])).all()
    # the late data folds into the NEXT window (global tick 7): its raw
    # count carries this window's 16 plus the 16 withheld items
    by_tick = {w.tick: w for w in ex.published}
    assert n(by_tick[7].raw["answers"]) == pytest.approx(32.0)
    assert not by_tick[7].partial
    # conservation: nothing was dropped — every admitted item is counted
    # in exactly one window's RAW answer
    total_raw = sum(n(w.raw["answers"]) for w in ex.published)
    assert total_raw == pytest.approx(summary["queue_items_in"])
    assert summary["queue_items_dropped"] == 0
    # the monitor accounted the late shard-windows
    assert ex.monitor.late_shards_total == 2
    assert ex.monitor.widened_windows_total == 2


def test_partial_window_bound_covers_truth_under_sampling():
    # fraction < 1: the calibrated estimate is noisy; truth must sit
    # inside estimate ± widened bound for the linear queries
    pipe = api.compile(_spec(fraction=0.5))
    clock = FakeClock()
    ex = _executor(clock)
    ex.start(pipe, [ConstantSource(0, rate=24, value=2.0),
                    LateShardSource(ConstantSource(1, rate=24, value=2.0),
                                    4, 6)], warmup=False)
    _run(ex, clock, 12)
    ex.stop()
    partials = [w for w in ex.published if w.partial]
    assert partials
    for w in partials:
        truth = 2.0 * 48                      # both shards' items
        s = float(pipe.answer(w.answers, "s")[0])
        b = float(pipe.answer(w.bounds, "s")[0])
        assert abs(s - truth) <= b + 1e-5


def test_drops_widen_bounds_too():
    # degrade policy sheds load under pressure; shed items count into α
    # so even with NO late shard the window publishes partial
    pipe = api.compile(_spec())
    clock = FakeClock()
    ex = _executor(clock, policy="degrade", queue_capacity=32)
    ex.start(pipe, [ConstantSource(0, rate=48, value=2.0),
                    ConstantSource(1, rate=48, value=2.0)], warmup=False)
    _run(ex, clock, 8)
    summary = ex.stop()
    assert summary["queue_items_dropped"] > 0
    partials = [w for w in ex.published if w.partial]
    assert partials and all(w.alpha < 1.0 for w in partials)


# ------------------------------------------------------- drain-on-stop --


def test_stop_drains_queues_clean():
    pipe = api.compile(_spec())
    clock = FakeClock()
    # max_records < rate: queues accumulate a backlog during the run
    ex = _executor(clock, max_records=4)
    ex.start(pipe, [ConstantSource(0, rate=8, value=1.0),
                    ConstantSource(1, rate=8, value=1.0)], warmup=False)
    _run(ex, clock, 6)
    assert any(q.depth > 0 for q in ex._queues)
    summary = ex.stop()
    assert summary["queue_depth"] == [0, 0]
    assert summary["queue_items_in"] == summary["queue_items_out"]
    assert all(q.accounting_ok for q in ex._queues)
    # everything drained lands in a window: raw counts conserve items
    total_raw = sum(float(pipe.answer(w.raw["answers"], "n")[0])
                    for w in ex.published)
    assert total_raw == pytest.approx(summary["queue_items_in"])
    with pytest.raises(RuntimeError, match="not started"):
        ex.stop()


def test_restart_after_stop():
    pipe = api.compile(_spec())
    clock = FakeClock()
    ex = _executor(clock)
    ex.start(pipe, [ConstantSource(0, rate=4), ConstantSource(1, rate=4)],
             warmup=False)
    with pytest.raises(RuntimeError, match="already started"):
        ex.start(pipe, [])
    _run(ex, clock, 4)
    ex.stop()
    ex.start(pipe, [ConstantSource(0, rate=4), ConstantSource(1, rate=4)],
             warmup=False)
    _run(ex, clock, 4)
    assert ex.stop()["windows_published"] == 4


# ------------------------------------------------------------- metrics --


def test_serve_metric_families_roundtrip():
    pipe = api.compile(_spec())
    clock = FakeClock()
    ex = _executor(clock)
    ex.start(pipe, [ConstantSource(0, rate=8, value=2.0),
                    LateShardSource(ConstantSource(1, rate=8, value=2.0),
                                    4, 6)], warmup=False)
    _run(ex, clock, 12)
    ex.stop()
    text = metrics_text(pipeline=pipe, state=ex.state,
                        straggler=ex.monitor, executor=ex)
    fams = parse_prometheus_text(text)
    for name in ("repro_serve_queue_depth",
                 "repro_serve_queue_high_watermark",
                 "repro_serve_queue_items_total",
                 "repro_serve_queue_dropped_total",
                 "repro_serve_queue_deferred_total",
                 "repro_serve_staged_items_total",
                 "repro_serve_truncated_items_total",
                 "repro_serve_ingest_overlap_fraction",
                 "repro_serve_windows_published_total",
                 "repro_serve_windows_partial_total",
                 "repro_serve_window_latency_seconds"):
        assert name in fams, name
    assert fams["repro_serve_windows_partial_total"]["samples"][()] == 2.0
    assert fams["repro_serve_queue_depth"]["samples"][
        (("shard", "0"),)] == 0.0
    samples = fams["repro_serve_window_latency_seconds"]["samples"]
    assert (("quantile", "p50"),) in samples


# -------------------------------------------------------------- spans --

# Where each executor span opens: the span it nests directly inside.
SPAN_PARENTS = {
    "executor.tick": {None, "executor.drain"},
    "executor.enqueue": {"executor.tick"},
    "executor.dequeue": {"executor.tick"},
    "executor.stage": {"executor.tick"},
    "executor.account": {"executor.tick"},
    "executor.flush": {"executor.tick", None},
    "executor.wait_device": {"executor.flush"},
    "executor.swap": {"executor.flush"},
    "executor.dispatch": {"executor.flush"},
    "executor.collect": {"executor.flush", None},
    "executor.drain": {None},
}


def _two_epochs(monkeypatch, tracer, fraction=1.0, pump=None):
    """Two 4-tick epochs and ``stop()`` on the small pipeline, with
    ``tracer`` as the process's default; ``pump`` wraps each pump (a
    profiler annotation in the trace test)."""
    from repro.obs import trace as trace_mod

    monkeypatch.setattr(trace_mod, "_GLOBAL", tracer)
    pipe = api.compile(_spec(fraction))
    clock = FakeClock()
    ex = _executor(clock)
    ex.start(pipe, [ConstantSource(0, rate=6, value=2.0, stratum=0),
                    ConstantSource(1, rate=6, value=3.0, stratum=1)],
             warmup=False)
    for _ in range(8):
        clock.advance(1.0)
        if pump is None:
            ex.pump()
        else:
            with pump():
                ex.pump()
    ex.stop()
    return ex


def test_executor_spans_name_and_nest_each_stage(monkeypatch):
    from repro.obs.trace import SpanTracer

    tr = SpanTracer()
    _two_epochs(monkeypatch, tr)
    assert {ev.name for ev in tr.events} == set(SPAN_PARENTS)
    for ev in tr.events:
        assert ev.parent in SPAN_PARENTS[ev.name], ev
    assert tr.well_formed()
    # One span per stage per pump, never per item or per shard.
    for name in ("executor.tick", "executor.enqueue", "executor.dequeue",
                 "executor.stage", "executor.account"):
        assert tr.calls[name] == 8, name
    assert tr.calls["executor.flush"] == tr.calls["executor.dispatch"] == 2
    assert tr.calls["executor.swap"] == 2
    assert tr.calls["executor.wait_device"] == 1
    assert tr.calls["executor.collect"] == 2
    assert tr.calls["executor.drain"] == 1
    enq = [ev.meta["items"] for ev in tr.events
           if ev.name == "executor.enqueue"]
    assert enq == [12] * 8


def test_dispatch_and_collect_carry_the_same_epoch(monkeypatch):
    from repro.obs.trace import SpanTracer

    tr = SpanTracer()
    ex = _two_epochs(monkeypatch, tr)
    dispatched = [ev.meta["epoch"] for ev in tr.events
                  if ev.name == "executor.dispatch"]
    collected = [ev for ev in tr.events if ev.name == "executor.collect"]
    assert dispatched == [ev.meta["epoch"] for ev in collected] == [0, 1]
    assert sum(ev.meta["windows"] for ev in collected) == len(ex.published)
    assert all(isinstance(ev.meta["waited"], bool) for ev in collected)
    # The flush that dispatches epoch 1 first waits for epoch 0.
    assert [ev.meta["epoch"] for ev in tr.events
            if ev.name == "executor.wait_device"] == [0]


def test_executor_counters(monkeypatch):
    from repro.obs.trace import SpanTracer

    tr = SpanTracer()
    _two_epochs(monkeypatch, tr)
    # Per epoch: values f32[4,2,64], strata i32[4,2,64], counts i32[4,2].
    assert tr.counters["executor_h2d_bytes"] == 2 * (4 * 2 * 64 * 8 + 32)
    waited = [ev for ev in tr.events if ev.name == "executor.collect"
              and ev.meta["waited"]]
    assert tr.counters.get("executor_collect_waited", 0) == len(waited)
    fams = parse_prometheus_text(metrics_text(tracer=tr))
    assert fams["repro_executor_h2d_bytes_total"]["samples"][()] == \
        tr.counters["executor_h2d_bytes"]
    assert (("span", "executor.dispatch"),) in \
        fams["repro_span_seconds_total"]["samples"]


def _split_drains(monkeypatch, tracer):
    """Eight pumps of 6 items per shard drained 4 at a time: per shard
    the drains touch 1, 2, 1, 1, 2, 1, 1, 2 chunks and slice one in all
    but the third and sixth."""
    from repro.obs import trace as trace_mod

    monkeypatch.setattr(trace_mod, "_GLOBAL", tracer)
    clock = FakeClock()
    ex = _executor(clock, max_records=4)
    ex.start(api.compile(_spec()),
             [ConstantSource(0, rate=6, value=2.0, stratum=0),
              ConstantSource(1, rate=6, value=3.0, stratum=1)],
             warmup=False)
    _run(ex, clock, 8)
    return ex


def test_dequeue_span_reads_chunks_and_the_split_counter(monkeypatch):
    from repro.obs.trace import SpanTracer

    tr = SpanTracer()
    _split_drains(monkeypatch, tr)
    chunks = [ev.meta["chunks"] for ev in tr.events
              if ev.name == "executor.dequeue"]
    assert chunks == [2 * c for c in (1, 2, 1, 1, 2, 1, 1, 2)]
    assert tr.counters["executor_queue_split_chunks"] == 2 * 6
    fams = parse_prometheus_text(metrics_text(tracer=tr))
    assert fams["repro_executor_queue_split_chunks_total"]["samples"][()] \
        == 12

    off = SpanTracer(enabled=False)
    ex = _split_drains(monkeypatch, off)
    assert not off.events and not off.counters
    assert [q.chunks_split for q in ex._queues] == [6, 6]


def test_a_disabled_tracer_records_nothing_from_the_executor(monkeypatch):
    from repro.obs.trace import SpanTracer

    tr = SpanTracer(enabled=False)
    ex = _two_epochs(monkeypatch, tr)
    assert len(ex.published) == 8
    assert not tr.events and not tr.calls and not tr.counters


def test_windows_are_bitwise_identical_with_the_tracer_on_and_off(monkeypatch):
    from repro.obs.trace import SpanTracer

    on = _two_epochs(monkeypatch, SpanTracer(), fraction=0.5).published
    off = _two_epochs(monkeypatch, SpanTracer(enabled=False),
                      fraction=0.5).published
    assert len(on) == len(off) == 8
    for a, b in zip(on, off):
        assert a.tick == b.tick and a.partial == b.partial
        np.testing.assert_array_equal(a.answers, b.answers)
        np.testing.assert_array_equal(a.bounds, b.bounds)
        assert a.sum == b.sum and a.mean == b.mean
        assert a.n_sampled == b.n_sampled
        np.testing.assert_array_equal(a.histogram, b.histogram)


def test_a_profiler_trace_holds_every_executor_span_under_the_pump(
        tmp_path, monkeypatch):
    import glob

    from repro.obs.trace import SpanTracer

    tr = SpanTracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _two_epochs(monkeypatch, tr,
                    pump=lambda: jax.profiler.TraceAnnotation("pump"))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               dict(ev.stats))
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name == "pump" or ev.name.startswith("executor.")]
    names = {e[0] for e in events}
    assert names == set(SPAN_PARENTS) | {"pump"}
    pumps = [e for e in events if e[0] == "pump"]
    assert len(pumps) == 8
    # Every span of a pump opens and closes inside the benchmark's pump.
    ticks = [e for e in events if e[0] == "executor.tick"]
    assert len(ticks) == 8
    inside = lambda e: any(p[1] <= e[1] and e[2] <= p[2] for p in pumps)
    for e in events:
        if e[0] in ("executor.enqueue", "executor.dequeue",
                    "executor.stage", "executor.account", "executor.tick"):
            assert inside(e), e
    # Metadata rides on the trace's events: at open, and set in the block.
    dispatch = [e[3] for e in events if e[0] == "executor.dispatch"]
    collect = [e[3] for e in events if e[0] == "executor.collect"]
    assert [d["epoch"] for d in dispatch] == [0, 1]
    assert all(d["bytes"] == 4 * 2 * 64 * 8 + 32 for d in dispatch)
    assert [c["epoch"] for c in collect] == [0, 1]
    assert sum(c["windows"] for c in collect) == 8
    assert all(e[3]["items"] == 12 for e in events
               if e[0] == "executor.enqueue")
    assert {e[3]["tick"] for e in ticks} == set(range(1, 9))


# ---------------------------------------------------------- publisher --


class _StubPipeline:
    plan = object()

    def query_layout(self):
        return {"c": (0, 1, "count"), "m": (1, 1, "mean"),
                "q": (2, 2, "quantile"), "hh": (4, 4, "heavy_hitters")}


def test_publisher_widening_rules_per_kind():
    pub = WindowPublisher(_StubPipeline())
    row = dict(tick=3, sum=10.0, sum_var=4.0, mean=5.0, mean_var=1.0,
               n_sampled=7, histogram=np.array([1.0, 3.0]),
               answers=np.array([8.0, 5.0, 1.5, 2.5, 11.0, 12.0, 40.0,
                                 60.0], np.float32),
               bounds=np.arange(8, dtype=np.float32))
    win = pub.publish(row, alpha=0.5, partial=True, publish_time=9.0,
                      first_arrival=7.0)
    assert win.latency == 2.0 and win.partial and win.alpha == 0.5
    # linear slots scale by 1/α; mean and quantile VALUES do not; the
    # heavy-hitter key half does not, its estimate half does
    np.testing.assert_allclose(
        win.answers, [16.0, 5.0, 1.5, 2.5, 11.0, 12.0, 80.0, 120.0])
    np.testing.assert_allclose(win.bounds, np.arange(8) * 2.0)
    assert win.sum == 20.0 and win.sum_var == 16.0
    assert win.mean == 5.0 and win.mean_var == 4.0
    np.testing.assert_allclose(win.histogram, [2.0, 6.0])
    # complete windows pass through untouched — the same objects
    full = pub.publish(row, alpha=1.0, partial=False, publish_time=9.0,
                       first_arrival=7.0)
    assert full.answers is row["answers"] and full.bounds is row["bounds"]
    assert full.sum == 10.0 and full.histogram is row["histogram"]
