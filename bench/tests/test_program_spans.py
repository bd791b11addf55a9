"""The readers of the program's own spans, on hand-made spans and
traces: queue time per pump, publish lag, and every per-layer reader
with and without the program's spans present."""
from __future__ import annotations

import types

import pytest

import run
from harness import cells, program_spans as P
from harness import tracing as T
from harness.drivers import Run
from harness.reference import Windows


def sp(name, t0, t1, depth=0, tid=1, **meta):
    from repro.obs.trace import Span

    return Span(name, t0, t1, depth, tid, meta)


def ev(name, start, dur, **stats):
    return T.Event(name, start, dur, stats)


def pump_spans(k, t0, *, flush_epoch=None, collect_at=None):
    """Pump ``k`` from ``t0`` (program clock): 10 ms enqueue holding a
    4 ms nested span, 5 ms dequeue, 20 ms stage; with ``flush_epoch``
    a flush that dispatches that epoch at ``t0 + 0.04`` and collects the
    one before at ``collect_at``."""
    out = [sp("executor.tick", t0, t0 + 0.2, tick=k + 1, drain=False),
           sp("executor.enqueue", t0, t0 + 0.010, 1, items=8),
           sp("source.pump", t0 + 0.002, t0 + 0.006, 2),
           sp("executor.dequeue", t0 + 0.010, t0 + 0.015, 1, items=8),
           sp("executor.stage", t0 + 0.015, t0 + 0.035, 1, items=8)]
    if flush_epoch is not None:
        out += [sp("executor.flush", t0 + 0.04, t0 + 0.19, 1,
                   epoch=flush_epoch),
                sp("executor.dispatch", t0 + 0.04, t0 + 0.05, 2,
                   epoch=flush_epoch, bytes=64)]
        if collect_at is not None:
            out.append(sp("executor.collect", collect_at, collect_at + 0.01,
                          2, epoch=flush_epoch - 1, waited=False,
                          windows=4))
    return out


def test_queue_time_is_self_time_of_enqueue_and_dequeue_per_pump():
    spans = pump_spans(0, 1.0) + pump_spans(1, 2.0)
    # A drain tick's dequeue is not a pump's.
    spans += [sp("executor.drain", 3.0, 3.5),
              sp("executor.tick", 3.0, 3.1, 1, drain=True),
              sp("executor.dequeue", 3.0, 3.09, 2, items=1)]
    spans = P.window(spans, 0.0, 10.0)
    # (10 - 4) ms of enqueue's own time and 5 ms of dequeue per pump.
    assert P.queue_ms_per_pump(spans) == pytest.approx(11.0)
    assert P.queue_ms_per_pump(P.window(spans, 1.5, 10.0)) == \
        pytest.approx(11.0)
    assert P.queue_ms_per_pump([]) is None


def test_self_time_and_parents_follow_each_thread():
    spans = P.window([sp("a", 0.0, 1.0), sp("b", 0.1, 0.4, 1),
                      sp("c", 0.2, 0.3, 2), sp("d", 0.5, 0.9, 1),
                      sp("a", 0.0, 1.0, tid=2), sp("b", 0.0, 0.5, 1, tid=2)],
                     0.0, 1.0)
    names = [s.name for s in spans]
    up = [None if p is None else names[p] for p in P.parents(spans)]
    assert list(zip(names, up)) == [("a", None), ("b", "a"), ("c", "b"),
                                    ("d", "a"), ("a", None), ("b", "a")]
    own = dict(zip([(s.name, s.tid) for s in spans], P.self_seconds(spans)))
    assert own[("a", 1)] == pytest.approx(0.3)
    assert own[("b", 1)] == pytest.approx(0.2)
    assert own[("a", 2)] == pytest.approx(0.5)


def test_window_keeps_the_spans_that_open_and_close_inside_it():
    spans = [sp("x", 0.0, 1.0), sp("y", 2.0, 3.0), sp("z", 4.0, 6.0)]
    assert [s.name for s in P.window(spans, 1.5, 7.0)] == ["y", "z"]
    assert [s.name for s in P.window(spans, 0.0, 5.0)] == ["x", "y"]


def test_spans_in_refuses_a_window_the_ring_has_overflowed(monkeypatch,
                                                             capsys):
    from repro.obs import trace as trace_mod

    tracer = trace_mod.SpanTracer(capacity=3)
    monkeypatch.setattr(trace_mod, "_GLOBAL", tracer)
    tracer.events.extend([sp("w", 0.0, 0.5), sp("x", 1.0, 2.0)])
    assert [s.name for s in P.spans_in(0.8, 5.0)] == ["x"]
    # Full, but every span it dropped closed before the window opened.
    tracer.events.extend([sp("y", 2.0, 3.0), sp("z", 3.0, 4.0)])
    assert [s.name for s in P.spans_in(2.5, 5.0)] == ["z"]
    assert capsys.readouterr().err == ""
    # It dropped "x", which closed inside the window: no reading.
    assert P.spans_in(1.5, 5.0) is None
    assert "overflowed" in capsys.readouterr().err


def _eight_pumps():
    """Pumps 0-7 at 1.0, 1.3, 1.6, 1.9, 5.0, ... (program clock); pump 3
    dispatches epoch 0 at 1.94, pump 7 epoch 1 at 5.94 and collects
    epoch 0 at 5.96."""
    spans = []
    for k in range(8):
        spans += pump_spans(k, 1.0 + 0.3 * (k % 4) + 4.0 * (k // 4),
                            flush_epoch={3: 0, 7: 1}.get(k),
                            collect_at=5.96 if k == 7 else None)
    return spans


# On the trace's clock (program + 100 s) epoch 0 runs 1.95-2.35 and
# epoch 1 5.95-6.45, each after a small key-derivation program.
MODULES = [ev("jit_fold_in(1)", 101.94, 0.001),
           ev("jit_epoch(7)", 101.95, 0.40),
           ev("jit_fold_in(1)", 105.94, 0.001),
           ev("jit_epoch(7)", 105.95, 0.50)]


def test_publish_lag_pairs_each_collect_with_its_epochs_execution():
    # stop() collects epoch 1 at 6.20, before it ended at 6.45: it
    # waited, and counts no lag.
    spans = P.window(_eight_pumps() + [
        sp("executor.collect", 6.20, 6.46, epoch=1, waited=True,
           windows=4)], 0.0, 10.0)
    pumps = [ev("pump", 100.0 + s.t0 - 1e-6, 0.2) for s in spans
             if s.name == "executor.tick"]
    assert P.trace_offset(spans, pumps) == pytest.approx(100.0 - 1e-6)
    lag = P.publish_lag_ms(spans, pumps, MODULES)
    # Epoch 0: 5.96 - 2.35 = 3.61 s; epoch 1: 0.
    assert lag == pytest.approx(1e3 * 3.61 / 2, abs=1e-2)
    # A window holding fewer ticks than the trace holds pumps still
    # pairs them by the end: the offset is the same.
    assert P.trace_offset(spans[12:], pumps) == pytest.approx(100.0 - 1e-6)
    assert P.publish_lag_ms(spans, [], MODULES) is None
    assert P.publish_lag_ms([], pumps, MODULES) is None


# ------------------------------------------ every reader, spans or none --

CMS = ("%cms_update.10 = f32[4,256]{1,0} custom-call(u32[9,1,4096]{2,1,0} "
       "%a, f32[9,1,4096]{2,1,0} %b, u32[1,4]{1,0} %c), "
       "custom_call_target=\"tpu_custom_call\"")


def _trace(with_program_spans: bool, spans) -> T.Trace:
    host = [ev("pump", 100.0 + s.t0 - 1e-6, 0.2) for s in spans
            if s.name == "executor.tick"]
    if with_program_spans:      # as a reduction that kept them would
        host += [ev(s.name, 100.0 + s.t0, s.t1 - s.t0, **s.meta)
                 for s in spans]
    ops = [ev(CMS, 101.95, 0.01), ev("%fusion.1 = f32[8]{0} fusion()",
                                     101.96, 0.3),
           ev(CMS, 105.95, 0.01)]
    return T.Trace(ops=[ops], modules=[MODULES], host=host)


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  cells.benchmark()["workloads"]])
def test_existing_readers_read_the_same_with_and_without_program_spans(
        monkeypatch, cell):
    from repro.obs import trace as trace_mod

    spans = _eight_pumps()
    windows = Windows(*[[]] * 4)
    the_run = Run(windows, due=8, items=64, t0=0.5, t_end=7.0,
                  failed_ticks=set(), pump_s=[0.2] * 8, lag_s=[0.001] * 8)
    device = types.SimpleNamespace(device_kind="TPU v5 lite")
    _, config, _ = cells.cell(cell)
    new = {"publish_lag_ms.latency", "queue_host_ms.items"}

    def read(with_spans: bool) -> dict:
        tracer = trace_mod.SpanTracer()
        if with_spans:
            tracer.events.extend(spans)
        monkeypatch.setattr(trace_mod, "_GLOBAL", tracer)
        metrics, _, _ = run._per_layer(cell, config, the_run,
                                       _trace(with_spans, spans), 7.0,
                                       device)
        return {k: v["value"] for k, v in metrics.items()}

    bare, spanned = read(False), read(True)
    assert {k: v for k, v in spanned.items() if k not in new} == bare
    assert not new & set(bare)
    mine = {m["name"] for m in cells.metrics_of(cell, "per_layer")}
    assert set(spanned) - set(bare) == new & mine
