"""Observability plane (repro.obs) acceptance tests.

The load-bearing laws:

* telemetry is FREE and NEUTRAL — with ``TelemetrySpec(enabled=True)``
  the sample state and window answers are bit-identical to the
  telemetry-off run, and the epoch dispatch count is unchanged;
* the SPMD byte counter obeys the static per-window model:
  ``merge_bytes == windows x summary_bytes_per_window`` (the same
  number the PR-5 collectives audit bounds);
* the span tracer emits a well-formed span tree and schema-valid
  Chrome/Perfetto JSON;
* the Prometheus-text renderer and parser are strict inverses (CI's
  smoke step leans on the parser rejecting malformed text);
* the straggler monitor folds host-side deadline accounting into the
  same telemetry leaves.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import api  # noqa: E402
from repro import obs  # noqa: E402
from repro.api.spec import (PipelineSpec, SamplerSpec,  # noqa: E402
                            TelemetrySpec, TopologySpec)
from repro.data import stream as S  # noqa: E402
from repro.obs.metrics import (MetricsRegistry,  # noqa: E402
                               metrics_text, parse_prometheus_text,
                               render_pipeline_metrics)
from repro.obs.trace import SpanTracer  # noqa: E402
from repro.query.registry import QueryRegistry  # noqa: E402


FANIN = (4, 2, 1)
CAPACITY = 256
TICKS = 12
NUM_STRATA = 2


def _registry() -> QueryRegistry:
    return (QueryRegistry().register_sum().register_mean()
            .register_quantile("q", (0.5, 0.9), capacity=64))


def _spec(telemetry: bool) -> PipelineSpec:
    return PipelineSpec(
        topology=TopologySpec(fanin=FANIN, capacity=CAPACITY,
                              num_strata=NUM_STRATA),
        sampler=SamplerSpec(mode="whs", backend="topk", fraction=0.2),
        tenants=(_registry().as_tenant("acme"),),
        telemetry=TelemetrySpec(enabled=telemetry),
        seed=3,
    )


def _ingest(seed=0, ticks=TICKS):
    rng = np.random.default_rng(seed)
    vals = rng.normal(50.0, 9.0,
                      (ticks, FANIN[0], CAPACITY)).astype(np.float32)
    strs = rng.integers(0, NUM_STRATA,
                        (ticks, FANIN[0], CAPACITY)).astype(np.int32)
    counts = np.full((ticks, FANIN[0]), CAPACITY, np.int64)
    return vals, strs, counts


def _run(telemetry: bool):
    pipe = api.compile(_spec(telemetry))
    state = pipe.init()
    vals, strs, counts = _ingest()
    state, wa = pipe.run_epoch(state, pipe.default_key, vals, strs, counts)
    return pipe, state, wa


@pytest.fixture(scope="module")
def on_off():
    pipe_on, state_on, wa_on = _run(telemetry=True)
    pipe_off, state_off, wa_off = _run(telemetry=False)
    return (pipe_on, state_on, wa_on), (pipe_off, state_off, wa_off)


# ---------------------------------------------------------------------------
# law 1: telemetry-on is bitwise-neutral and costs no extra dispatch
# ---------------------------------------------------------------------------

def test_sample_state_bitwise_identical_on_off(on_off):
    (_, s_on, _), (_, s_off, _) = on_off
    tree_on = s_on.tree._replace(telemetry=())
    tree_off = s_off.tree._replace(telemetry=())
    for leaf_on, leaf_off in zip(jax.tree.leaves(tree_on),
                                 jax.tree.leaves(tree_off)):
        np.testing.assert_array_equal(np.asarray(leaf_on),
                                      np.asarray(leaf_off))


def test_window_answers_bitwise_identical_on_off(on_off):
    (_, _, wa_on), (_, _, wa_off) = on_off
    for leaf_on, leaf_off in zip(jax.tree.leaves(wa_on),
                                 jax.tree.leaves(wa_off)):
        np.testing.assert_array_equal(np.asarray(leaf_on),
                                      np.asarray(leaf_off))


def test_epoch_dispatch_count_unchanged(on_off):
    (pipe_on, _, _), (pipe_off, _, _) = on_off
    # one traced program each: telemetry rides the scan carry, it is not
    # an extra output or a second dispatch
    assert pipe_on.trace_counter["traces"] == 1
    assert pipe_off.trace_counter["traces"] == 1


def test_off_state_carries_zero_extra_leaves(on_off):
    (_, s_on, _), (_, s_off, _) = on_off
    assert s_off.tree.telemetry == ()
    extra = (len(jax.tree.leaves(s_on.tree))
             - len(jax.tree.leaves(s_off.tree)))
    assert extra == len(obs.EpochTelemetry._fields)


# ---------------------------------------------------------------------------
# snapshot semantics
# ---------------------------------------------------------------------------

def test_snapshot_levels_and_windows(on_off):
    (pipe, state, wa), _ = on_off
    snap = obs.snapshot(state)
    assert snap is not None
    assert len(snap["levels"]) == len(FANIN)
    assert snap["windows"] == len(pipe.rows(wa))
    for lv in snap["levels"]:
        assert lv["items_in"] >= lv["items_kept"] > 0
        assert 0.0 < lv["effective_fraction"] <= 1.0
    assert len(snap["strata"]) == NUM_STRATA


def test_snapshot_bound_matches_adhoc_recompute(on_off):
    """bound_2sigma is THE one place the ±2σ math lives: it must equal
    the ad-hoc host recompute the examples used to do."""
    (pipe, state, wa), _ = on_off
    snap = obs.snapshot(state)
    rows = pipe.rows(wa)
    adhoc = 2.0 * float(np.sqrt(sum(r["sum_var"] for r in rows)))
    assert snap["bound_2sigma"] == pytest.approx(adhoc, rel=1e-4)
    assert snap["sum_estimate"] == pytest.approx(
        float(sum(r["sum"] for r in rows)), rel=1e-4)


def test_snapshot_none_when_disabled(on_off):
    _, (_, s_off, _) = on_off
    assert obs.snapshot(s_off) is None


def test_tenant_rel_bounds(on_off):
    (pipe, state, _), _ = on_off
    per = obs.telemetry.tenant_rel_bounds(pipe, state)
    assert set(per) == {"acme"}
    assert 0.0 < per["acme"] < 1.0


def test_reset_zeroes_counters(on_off):
    (pipe, state, _), _ = on_off
    state0 = obs.reset(state)
    snap = obs.snapshot(state0)
    assert snap["windows"] == 0
    assert snap["sum_estimate"] == 0.0
    for lv in snap["levels"]:
        assert lv["items_in"] == 0.0
    # shape-preserving: resuming a same-length epoch from the reset
    # state retraces nothing
    n0 = pipe.trace_counter["traces"]
    vals, strs, counts = _ingest(seed=1)
    pipe.run_epoch(state0, pipe.default_key, vals, strs, counts)
    assert pipe.trace_counter["traces"] == n0


# ---------------------------------------------------------------------------
# law 2: SPMD byte counter + bitwise neutrality on the mesh
# ---------------------------------------------------------------------------

_SPMD_HARNESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    import numpy as np

    from repro import api
    from repro.api.spec import (PipelineSpec, SamplerSpec, TelemetrySpec,
                                TenantSpec, TopologySpec)
    from repro.obs.trace import get_tracer
    from repro.query.registry import QueryRegistry

    T, M, X, N0 = 6, 512, 2, 4

    def spec(telemetry):
        reg = (QueryRegistry().register_sum().register_mean()
               .register_quantile("q", (0.5, 0.9), capacity=64))
        return PipelineSpec(
            topology=TopologySpec(fanin=(N0, 1), capacity=M,
                                  num_strata=X),
            sampler=SamplerSpec(mode="whs", backend="topk",
                                fraction=0.25),
            tenants=(reg.as_tenant("acme"),),
            telemetry=TelemetrySpec(enabled=telemetry),
            seed=5)

    rng = np.random.default_rng(0)
    # node rows: level-0 node d (a quarter of each tick) on device d
    vals = rng.normal(40.0, 8.0, (T, N0, M // N0)).astype(np.float32)
    strs = rng.integers(0, X, (T, N0, M // N0)).astype(np.int32)
    counts = np.full((T, N0), M // N0, np.int32)
    mesh = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])

    out = {}
    answers = {}
    for tel in (True, False):
        pipe = api.compile(spec(tel), mesh=mesh)
        state = pipe.init()
        counter0 = get_tracer().counters["spmd_merge_bytes"]
        state, wa = pipe.run_epoch(state, pipe.default_key, vals, strs,
                                   counts)
        answers[tel] = [np.asarray(x).tolist()
                        for x in jax.tree.leaves(wa)]
        if tel:
            snap = pipe.telemetry_snapshot(state)
            out["windows"] = snap["windows"]
            out["merge_bytes"] = snap["merge_bytes"]
            out["bytes_per_window"] = pipe.summary_bytes_per_window
            out["merge_counter"] = (get_tracer().counters["spmd_merge_bytes"]
                                    - counter0)
            out["ticks"] = T
            n0 = pipe.trace_counter["traces"]
            state, _ = pipe.run_epoch(state, pipe.default_key, vals, strs,
                                      counts)
            out["retraced"] = pipe.trace_counter["traces"] - n0
    out["bitwise"] = answers[True] == answers[False]
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def spmd():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SPMD_HARNESS],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_spmd_answers_bitwise_identical_on_off(spmd):
    assert spmd["bitwise"]


def test_spmd_merge_bytes_law(spmd):
    """The sketch-merge byte counter equals windows x the static
    per-window summary model — the same all-gather payload the PR-5
    collectives audit bounds."""
    assert spmd["windows"] > 0
    assert spmd["merge_bytes"] == pytest.approx(
        spmd["windows"] * spmd["bytes_per_window"])
    # The tracer's counter is host arithmetic over every dispatched
    # window, flushed or not: it never reads the device.
    assert spmd["merge_counter"] == spmd["ticks"] * spmd["bytes_per_window"]


def test_spmd_second_epoch_no_retrace(spmd):
    assert spmd["retraced"] == 0


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_span_tree_well_formed_and_aggregated():
    tr = SpanTracer()
    with tr.span("epoch_dispatch", ticks=4):
        with tr.span("ingest"):
            pass
        with tr.span("block_until_ready"):
            pass
    with tr.span("checkpoint", op="save"):
        pass
    assert tr.well_formed()
    assert tr.calls["epoch_dispatch"] == 1
    assert tr.calls["ingest"] == 1
    assert tr.durations["epoch_dispatch"] >= tr.durations["ingest"]


def test_chrome_trace_schema(tmp_path):
    tr = SpanTracer()
    with tr.span("outer", epoch=1):
        with tr.span("inner"):
            pass
    doc = tr.chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert {e["name"] for e in evs} == {"outer", "inner"}
    for e in evs:
        assert e["ph"] == "X" and e["cat"] == "repro"
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["dur"] >= 0.0
    inner = next(e for e in evs if e["name"] == "inner")
    outer = next(e for e in evs if e["name"] == "outer")
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"]["epoch"] == 1
    # save round-trips through json
    path = tmp_path / "trace.json"
    tr.save(str(path))
    assert json.loads(path.read_text())["traceEvents"]


def test_disabled_tracer_records_nothing():
    tr = SpanTracer(enabled=False)
    with tr.span("x") as meta:
        meta["items"] = 3
    tr.count("x_total")
    assert not tr.events and not tr.calls and not tr.counters


def test_span_nesting_is_per_thread():
    import threading

    tr = SpanTracer()
    opened = threading.Barrier(2)

    def work(tag):
        with tr.span(f"outer.{tag}"):
            opened.wait()        # both threads hold an open span here
            with tr.span(f"inner.{tag}"):
                opened.wait()

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    by_name = {ev.name: ev for ev in tr.events}
    for tag in "ab":
        inner, outer = by_name[f"inner.{tag}"], by_name[f"outer.{tag}"]
        assert (inner.depth, inner.parent) == (1, f"outer.{tag}")
        assert (outer.depth, outer.parent) == (0, None)
        assert inner.tid == outer.tid
    assert by_name["inner.a"].tid != by_name["inner.b"].tid
    assert tr.well_formed()


def test_chrome_trace_records_each_spans_parent():
    tr = SpanTracer()
    with tr.span("executor.tick", tick=1):
        with tr.span("executor.flush", epoch=0):
            with tr.span("executor.dispatch", epoch=0):
                pass
    args = {e["name"]: e["args"] for e in tr.chrome_trace()["traceEvents"]}
    assert args["executor.tick"]["parent"] is None
    assert args["executor.flush"]["parent"] == "executor.tick"
    assert args["executor.dispatch"] == {"epoch": 0, "depth": 2,
                                         "parent": "executor.flush"}


def test_span_metadata_reaches_the_trace_annotation(monkeypatch):
    from repro.obs import trace as trace_mod

    made = []

    class Recorder:
        def __init__(self, name, **kwargs):
            self.name, self.kwargs, self.late = name, kwargs, {}
            made.append(self)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **kwargs):
            self.late.update(kwargs)

    monkeypatch.setattr(trace_mod, "_TraceAnnotation", Recorder)
    tr = SpanTracer()
    with tr.span("executor.collect", epoch=4, waited=True,
                 rows=np.zeros(3)) as meta:
        meta["windows"] = 8
    (ann,) = made
    # Scalars go to the annotation when it opens, keys set inside the
    # block when it closes; arrays stay in the ring only.
    assert ann.name == "executor.collect"
    assert ann.kwargs == {"epoch": 4, "waited": True}
    assert ann.late == {"windows": 8}
    assert tr.events[0].meta["windows"] == 8


def test_counter_round_trips_through_prometheus_text():
    tr = SpanTracer()
    tr.count("executor_h2d_bytes", 1024)
    tr.count("executor_h2d_bytes", 512)
    tr.count("executor_collect_waited")
    fams = parse_prometheus_text(metrics_text(tracer=tr))
    assert fams["repro_executor_h2d_bytes_total"]["samples"][()] == 1536
    assert fams["repro_executor_collect_waited_total"]["samples"][()] == 1
    with pytest.raises(ValueError, match="snake_case"):
        tr.count("executor.h2d_bytes")


# ---------------------------------------------------------------------------
# metrics registry + Prometheus text
# ---------------------------------------------------------------------------

def test_prometheus_render_parse_round_trip():
    reg = MetricsRegistry()
    reg.counter("repro_items_in_total", 128.0, help_="items offered",
                level="0")
    reg.counter("repro_items_in_total", 64.5, level="1")
    reg.gauge("repro_effective_fraction", 0.25, help_="kept/in")
    text = reg.to_text()
    fams = parse_prometheus_text(text)
    assert fams["repro_items_in_total"]["type"] == "counter"
    samples = fams["repro_items_in_total"]["samples"]
    assert samples[(("level", "0"),)] == 128.0
    assert samples[(("level", "1"),)] == 64.5
    assert fams["repro_effective_fraction"]["samples"][()] == 0.25
    # idempotent: parse(render(parse(x))) == parse(x)
    reg2 = MetricsRegistry()
    for name, fam in fams.items():
        for labels, v in fam["samples"].items():
            getattr(reg2, fam["type"])(name, v, **dict(labels))
    assert parse_prometheus_text(reg2.to_text()) == fams


@pytest.mark.parametrize("bad", [
    "", "repro_x{unclosed=\"1\" 3\n",
    "just some words\n", "repro_y 1 2 3 4\n",
])
def test_prometheus_parser_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_prometheus_text(bad)


def test_render_pipeline_metrics_end_to_end(on_off):
    (pipe, state, _), _ = on_off
    tr = SpanTracer()
    with tr.span("epoch_dispatch"):
        pass
    text = metrics_text(pipeline=pipe, state=state, tracer=tr)
    fams = parse_prometheus_text(text)
    for name in ("repro_items_in_total", "repro_items_kept_total",
                 "repro_effective_fraction", "repro_windows_total",
                 "repro_realized_bound_2sigma", "repro_tenant_rel_bound",
                 "repro_program_cache_misses_total",
                 "repro_plan_cache_builds_total",
                 "repro_span_seconds_total"):
        assert name in fams, f"{name} missing from exposition"
    n_levels = len({k for k in
                    fams["repro_items_in_total"]["samples"]})
    assert n_levels == len(FANIN)
    assert fams["repro_tenant_rel_bound"]["samples"][
        (("tenant", "acme"),)] > 0.0


# ---------------------------------------------------------------------------
# straggler wiring (ROADMAP item 1's signal)
# ---------------------------------------------------------------------------

def test_straggler_monitor_folds_into_telemetry(on_off):
    (pipe, state, _), _ = on_off
    mon = obs.StragglerMonitor(num_shards=4)
    before = obs.snapshot(state)
    # 12 on-time windows to build the deadline estimate, then one
    # window with a straggling shard
    for _ in range(12):
        present = mon.observe([1.0, 1.0, 1.0, 1.0])
        assert present.all()
    present = mon.observe([1.0, 1.0, 1.0, 1e6])
    assert present.sum() == 3 and not present[3]
    assert mon.late_shards_total == 1
    assert mon.widened_windows_total == 1
    state2 = mon.fold_into(state)
    snap = obs.snapshot(state2)
    assert snap["late_shards"] == before["late_shards"] + 1
    assert snap["widened_windows"] == before["widened_windows"] + 1
    # Eq. 9 recalibration: arrived shards' weights scale by 1/alpha
    w = np.ones(4, np.float64)
    w2 = mon.calibrate(w, present)
    assert w2[:3] == pytest.approx(4.0 / 3.0)
    # folding is idempotent once the deltas drain
    assert mon.fold_into(state2) is state2


def test_straggler_totals_in_metrics(on_off):
    (pipe, state, _), _ = on_off
    mon = obs.StragglerMonitor(num_shards=2)
    for _ in range(12):
        mon.observe([1.0, 1.0])
    mon.observe([1.0, 1e6])
    text = metrics_text(pipeline=pipe, state=state, straggler=mon)
    fams = parse_prometheus_text(text)
    assert fams["repro_straggler_monitor_late_shards_total"][
        "samples"][()] == 1.0
    assert fams["repro_straggler_monitor_widened_windows_total"][
        "samples"][()] == 1.0


# ---------------------------------------------------------------------------
# spec plumbing + benchmark provenance/regression gate
# ---------------------------------------------------------------------------

def test_spec_round_trip_with_telemetry():
    spec = _spec(telemetry=True)
    d = spec.to_dict()
    assert d["telemetry"] == {"enabled": True}
    back = PipelineSpec.from_dict(d)
    assert back.telemetry.enabled is True
    # specs serialized before the telemetry section default to off
    d2 = spec.to_dict()
    del d2["telemetry"]
    assert PipelineSpec.from_dict(d2).telemetry.enabled is False


def test_telemetry_spec_rejects_non_bool():
    with pytest.raises(Exception):
        TelemetrySpec(enabled=1)


def test_run_metadata_and_compare_gate():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import common

    meta = common.run_metadata(telemetry={"windows": 3})
    assert meta["git_sha"] and len(meta["git_sha"]) == 40
    assert meta["device"]["platform"]
    assert meta["telemetry"] == {"windows": 3}

    base = {"meta": meta, "fig7": {"ok": True, "rows": [
        {"fraction": 0.1, "engine": "scan", "whs_items_s": 1000.0},
        {"fraction": 0.2, "engine": "scan", "whs_items_s": 2000.0}]}}
    good = {"fig7": {"ok": True, "rows": [
        {"fraction": 0.1, "engine": "scan", "whs_items_s": 950.0},
        {"fraction": 0.2, "engine": "scan", "whs_items_s": 2500.0}]}}
    bad = {"fig7": {"ok": True, "rows": [
        {"fraction": 0.1, "engine": "scan", "whs_items_s": 800.0}]}}
    assert common.compare_reports(base, good, tol=0.10) == []
    regs = common.compare_reports(base, bad, tol=0.10)
    assert len(regs) == 1 and regs[0]["column"] == "whs_items_s"
    assert regs[0]["drop_pct"] == pytest.approx(20.0)
    # a failed module never gates
    assert common.compare_reports(
        base, {"fig7": {"ok": False}}, tol=0.10) == []
