"""The trace reduction, on hand-made events and on a small trace
recorded on the CPU."""
from __future__ import annotations

import pytest

from harness import cells
from harness import tracing as T


def ev(name, start, dur, **stats):
    return T.Event(name, start, dur, stats)


def test_union_and_busy_share():
    trace = T.Trace(ops=[[ev("a", 0.0, 1.0), ev("b", 0.5, 1.0),
                          ev("c", 3.0, 0.5)]], modules=[[]], host=[])
    assert T.union([(0.0, 1.0), (0.5, 1.5), (3.0, 3.5)]) == [[0.0, 1.5],
                                                            [3.0, 3.5]]
    assert T.busy_seconds(trace) == pytest.approx(2.0)


def test_idle_gaps_are_named_by_the_host_span_that_overlaps_most():
    trace = T.Trace(ops=[[ev("a", 1.0, 1.0), ev("b", 5.0, 1.0)]],
                    modules=[[]],
                    host=[ev("pump", 2.0, 2.5), ev("collect", 4.5, 0.4)])
    gaps = T.idle_gaps(trace, 0.0, 6.0)
    assert gaps[0] == ["host: pump", pytest.approx(3.0)]
    assert gaps[1] == ["host: no span", pytest.approx(1.0)]


def test_top_ops_and_module_time():
    loop = "%while.1 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %t)"
    sort = "%sort.2 = (f32[4,8]{1,0}, s32[4,8]{1,0}) sort(f32[4,8]{1,0} %a)"
    fus = "%fusion.1 = f32[4,8]{1,0} fusion(f32[4,8]{1,0} %b), kind=kLoop"
    trace = T.Trace(ops=[[ev(loop, 0, 9.0), ev(fus, 0, 1.0),
                          ev(sort, 1, 3.0), ev(fus, 4, 1.5)]],
                    modules=[[ev("jit_epoch(7)", 0, 2.0),
                              ev("jit_epoch(7)", 3, 4.0),
                              ev("jit_fold_in", 8, 1.0)]], host=[])
    assert T.top_ops(trace) == [["sort.2 sort f32[4,8]", 3.0],
                                ["fusion.1 fusion f32[4,8]", 2.5]]
    assert T.module_ms(trace, "epoch") == pytest.approx(3000.0)
    assert T.module_ms(trace, "nothing") is None


# An operation's name on the TPU is its HLO text.
CMS = ("%cms_update.10 = f32[4,256]{1,0:T(4,128)S(1)} custom-call("
       "u32[9,1,4096]{2,1,0:T(1,128)S(1)} %bitcast.1903, "
       "f32[9,1,4096]{2,1,0:T(1,128)S(1)} %bitcast.1905, "
       "u32[1,4]{1,0:T(1,128)S(1)} %copy-done.155), "
       "custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
       "{u32[9,1,4096]{2,1,0}, f32[9,1,4096]{2,1,0}, u32[1,4]{1,0}}")


def test_operand_shapes_and_kernel_roofline():
    e = ev(CMS, 0.0, 1e-3)
    assert T.operand_shapes(e) == [("f32", (4, 256)), ("u32", (9, 1, 4096)),
                                   ("f32", (9, 1, 4096)), ("u32", (1, 4))]
    other = ev("%fusion.7 = f32[4,256]{1,0} fusion(f32[4,256]{1,0} "
               "%cms_update.10), kind=kLoop", 0, 1.0)
    trace = T.Trace(ops=[[e, other]], modules=[[]], host=[])
    assert T.kernel_events(trace, "cms_update") == [e]
    cost = cells.load_module("costs", "cms_update").cost
    ops, nbytes = cost(T.operand_shapes(e))
    assert ops == 3 * 9 * 4096 * 4
    assert nbytes == 8 * 9 * 4096 + 4 * 4 * 256
    peak = {"flops_per_s": 1e12, "bytes_per_s": 1e9}
    pct = T.roofline_pct(trace, "cms_update", cost, peak)
    assert pct == pytest.approx(100.0 * (nbytes / 1e9) / 1e-3)
    assert T.roofline_pct(trace, "_no_such_kernel", cost, peak) is None


def test_quantile_compact_cost():
    cost = cells.load_module("costs", "quantile_compact").cost
    shapes = [("f32", (1, 128)), ("f32", (9, 1, 4096)),
              ("f32", (9, 1, 4096)), ("f32", (9, 1, 4096)),
              ("f32", (1, 128))]
    assert cost(shapes) == (9 * 4096 + 128, 12 * 9 * 4096 + 8 * 128)


def test_peaks_are_keyed_by_device_kind_with_a_source():
    peaks = cells.load_json(cells.BENCH / "peaks.json")
    v5e = peaks["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]


def test_a_cpu_trace_reads_back(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("pump"):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    # On the CPU the host plane holds the XLA operations too.
    trace = T.load(path, device_prefix="/host:CPU", ops_line="tf_XLA")
    assert [h.name for h in trace.host] == ["pump"]
    dots = [e for ops in trace.ops for e in ops
            if "dot" in str(e.stats.get("hlo_op", ""))]
    assert len(dots) >= 3
    assert T.busy_seconds(trace) > 0.0


AR = ("%all-reduce.3 = f32[4]{0} all-reduce(f32[4]{0} %p), channel_id=1, "
      "replica_groups={{0,1,2,3}}, to_apply=%add")
AG_START = ("%all-gather-start.1 = (f32[2]{0}, f32[8]{0}) all-gather-start("
            "f32[2]{0} %q), channel_id=2, dimensions={0}")
AG_DONE = ("%all-gather-done.1 = f32[8]{0} all-gather-done((f32[2]{0}, "
           "f32[8]{0}) %all-gather-start.1)")
FUSION = ("%fusion.4 = f32[4]{0} fusion(f32[4]{0} %all-reduce.3), "
          "kind=kLoop, calls=%fused_computation.4")


def test_opcode_from_hlo_text_or_instruction_name():
    assert T.opcode(ev(AR, 0, 1)) == "all-reduce"
    assert T.opcode(ev(AG_START, 0, 1)) == "all-gather-start"
    assert T.opcode(ev(FUSION, 0, 1)) == "fusion"
    assert T.opcode(ev("collective-permute-done.2", 0, 1)) == \
        "collective-permute-done"
    assert T.opcode(ev("%reduce-scatter.7", 0, 1)) == "reduce-scatter"


def test_four_chips_busy_each_and_collectives_averaged():
    # Chip d computes for 1 + d seconds, then waits in collectives; the
    # fusion that reads a collective's result is no collective.
    chips = []
    for d in range(4):
        ops = [ev(FUSION, 0.0, 1.0 + d), ev(AR, 5.0, 0.5),
               ev(AG_START, 6.0, 0.25), ev(AG_DONE, 6.1, 0.25 * d),
               ev("all-to-all.9", 8.0, 0.1)]
        chips.append(ops)
    trace = T.Trace(ops=chips, modules=[[]] * 4, host=[])
    busy = [1.0 + d + 0.5 + max(0.25, 0.1 + 0.25 * d) + 0.1
            for d in range(4)]
    assert T.busy_per_chip(trace) == pytest.approx([1.85, 2.95, 4.2, 5.45])
    assert T.busy_per_chip(trace) == pytest.approx(busy)
    assert T.busy_seconds(trace) == pytest.approx(sum(busy) / 4)
    # Per chip: 0.5 + the union of the all-gather halves + 0.1.
    per = [0.5 + max(0.25, 0.1 + 0.25 * d) + 0.1 for d in range(4)]
    assert T.collective_ms(trace) == pytest.approx(1e3 * sum(per) / 4)
    one = T.Trace(ops=[[ev(FUSION, 0.0, 1.0)]], modules=[[]], host=[])
    assert T.collective_ms(one) is None


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Ev:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns = name, start_ns
        self.duration_ns, self.stats = duration_ns, []


def test_load_keeps_one_op_list_per_chip_in_chip_order(monkeypatch):
    from jax import profiler

    def plane(d):
        return _Plane(f"/device:TPU:{d}", [
            _Line("XLA Ops", [_Ev(f"op{d}", 10, 5 * (d + 1))]),
            _Line("XLA Modules", [_Ev(f"jit_epoch{d}", 0, 100)])])

    planes = [plane(2), plane(0), _Plane("/host:CPU", [
        _Line("python", [_Ev("pump", 0, 50), _Ev("other", 0, 5)])]),
        plane(3), plane(1), _Plane("/device:TPU:4", [])]
    monkeypatch.setattr(profiler, "ProfileData", type(
        "PD", (), {"from_file": staticmethod(
            lambda path: type("P", (), {"planes": planes})())}))
    trace = T.load("unused.xplane.pb")
    assert [ops[0].name for ops in trace.ops] == ["op0", "op1", "op2", "op3"]
    assert [m[0].name for m in trace.modules] == [
        "jit_epoch0", "jit_epoch1", "jit_epoch2", "jit_epoch3"]
    assert [h.name for h in trace.host] == ["pump"]
    assert T.busy_per_chip(trace) == pytest.approx([5e-9, 10e-9, 15e-9,
                                                    20e-9])
