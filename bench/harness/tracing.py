"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

A device's operations are the events of its ``XLA Ops`` line; its
program executions those of ``XLA Modules``. Busy time is the union of
the operation intervals, idle the rest of the traced window. A kernel's
events are the operations that name it; its work comes from the
operand shapes the event carries, through ``bench/costs/<kernel>.py``.
Idle gaps are attributed to the host span (the benchmark's own
``TraceAnnotation``s and the program's ``obs.span`` names) that
overlaps them most.

A cell on several chips traces one plane per chip, kept in chip order.
Busy time and collective time are read on every chip and averaged;
the breakdown (``top_ops``, ``idle_gaps``) and the per-program and
per-kernel readings (``module_ms``, ``kernel_events``) are chip 0's,
since every chip runs the same SPMD program.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("run_epoch", "collect", "pump", "stop", "ingest",
              "epoch_dispatch", "block_until_ready")


@dataclasses.dataclass
class Event:
    name: str
    start: float              # seconds
    dur: float                # seconds
    stats: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: list                 # per device: [Event]
    modules: list             # per device: [Event]
    host: list                # [Event] host spans by name


def _events(line) -> list:
    out = []
    for ev in line.events:
        stats = {}
        for k, v in ev.stats:
            stats[k] = v
        out.append(Event(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                         stats))
    return out


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _chip_index(plane_name: str, prefix: str) -> int:
    m = re.match(r"\d+", plane_name[len(prefix):])
    return int(m.group()) if m else 0


def load(path: str, device_prefix: str = DEVICE_PREFIX,
         ops_line: str = OPS_LINE, modules_line: str = MODULES_LINE
         ) -> Trace:
    """The trace's device planes that ran operations, in chip order
    (``/device:TPU:0`` first), and the host spans named in
    ``HOST_SPANS``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            dev_ops, dev_modules = [], []
            for line in plane.lines:
                if line.name.startswith(ops_line):
                    dev_ops.extend(_events(line))
                elif line.name == modules_line:
                    dev_modules.extend(_events(line))
            if dev_ops:
                chips.append((_chip_index(plane.name, device_prefix),
                              sorted(dev_ops, key=lambda e: e.start),
                              dev_modules))
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e.name in HOST_SPANS)
    chips.sort(key=lambda c: c[0])
    return Trace([c[1] for c in chips], [c[2] for c in chips], host)


def union(intervals) -> list:
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_per_chip(trace: Trace) -> list:
    """Seconds in which an operation ran, on each device."""
    return [float(sum(e - s for s, e in union((ev.start, ev.end)
                                              for ev in ops)))
            for ops in trace.ops]


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    per = busy_per_chip(trace)
    return float(np.mean(per)) if per else 0.0


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_COLLECTIVE = re.compile(r"^(%s)(-start|-done)?$"
                         % "|".join(map(re.escape, COLLECTIVES)))


def opcode(ev: Event) -> str:
    """An operation's HLO opcode: from its HLO text
    (``%all-reduce.3 = f32[4] all-reduce(...)``), or from its
    instruction name (``all-reduce-start.1``) where the event carries
    no text."""
    if " = " in ev.name:
        m = _OPCODE.search(ev.name.split(" = ", 1)[1])
        return m.group(1) if m else ""
    return re.sub(r"\.\d+$", "", ev.name.lstrip("%"))


def collective_ms(trace: Trace) -> float | None:
    """Milliseconds in which a collective operation (``COLLECTIVES``,
    with their ``-start``/``-done`` halves) ran, on each chip over the
    traced window, averaged over the chips. None where the trace holds
    none."""
    per = [sum(e - s for s, e in union((ev.start, ev.end) for ev in ops
                                       if _COLLECTIVE.match(opcode(ev))))
           for ops in trace.ops]
    return 1e3 * float(np.mean(per)) if any(per) else None


def module_ms(trace: Trace, pattern: str) -> float | None:
    """Mean milliseconds of one execution of the programs whose name
    matches ``pattern`` (on chip 0: every chip runs the same program)."""
    rx = re.compile(pattern)
    durs = [ev.dur for ev in (trace.modules[0] if trace.modules else [])
            if rx.search(ev.name)]
    return 1e3 * float(np.mean(durs)) if durs else None


def kernel_events(trace: Trace, kernel: str) -> list:
    """Executions of the Pallas kernel ``kernel`` on chip 0 (every chip
    runs the same program): on the TPU an operation's name is its HLO
    text, and a ``pallas_call`` is the custom call named after the
    kernel's function (``%cms_update.10 = f32[4,256]...
    custom-call(...)``)."""
    rx = re.compile(r"^%" + re.escape(kernel) + r"(\.\d+)? = .*custom-call\(")
    return [ev for ev in (trace.ops[0] if trace.ops else [])
            if rx.match(ev.name)]


_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|"
                    r"f64)\[([0-9,]*)\]")


def operand_shapes(ev: Event) -> list | None:
    """``[(dtype, dims), ...]`` of an operation's result then operands,
    parsed from its HLO text (the event's name on the TPU) up to the
    end of the operand list, or None where the event carries none."""
    text = ev.name
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", text)
    if " = " not in text or m is None:
        return None
    depth, end = 0, len(text)
    for i in range(m.end() - 1, len(text)):
        depth += {"(": 1, ")": -1}.get(text[i], 0)
        if depth == 0:
            end = i
            break
    shapes = [(dt, tuple(int(d) for d in dims.split(",") if d))
              for dt, dims in _SHAPE.findall(text[:end])]
    return shapes or None


def short_name(text: str) -> str:
    """``fusion.12 fusion f32[4,173056]`` from an operation's HLO text:
    instruction, opcode and result shape."""
    if " = " not in text:
        return text[:120]
    lhs, rhs = text.split(" = ", 1)
    m = _OPCODE.search(rhs)
    shape = _SHAPE.search(rhs)
    parts = [lhs.lstrip("%")]
    if m:
        parts.append(m.group(1))
    if shape:
        parts.append(f"{shape.group(1)}[{shape.group(2)}]")
    return " ".join(parts)[:120]


def idle_gaps(trace: Trace, t_start: float, t_stop: float, top: int = 10
              ) -> list:
    """The longest idle gaps of chip 0 in the traced window, each named
    by the host span that overlaps it most."""
    if not trace.ops:
        return []
    busy = union((ev.start, ev.end) for ev in trace.ops[0])
    edges = [t_start] + [x for iv in busy for x in iv] + [t_stop]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        best, name = 0.0, "host: no span"
        for h in trace.host:
            ov = min(e, h.end) - max(s, h.start)
            if ov > best:
                best, name = ov, f"host: {h.name}"
        out.append([name, e - s])
    return out


_CONTAINERS = re.compile(r"^\S+ = .*\b(while|conditional|call)\(")


def top_ops(trace: Trace, top: int = 10) -> list:
    """The device operations that took most time on chip 0, by
    instruction, with the control-flow operations that contain others
    left out."""
    total: dict = {}
    for ev in (trace.ops[0] if trace.ops else []):
        if _CONTAINERS.match(ev.name):
            continue
        name = short_name(ev.name)
        total[name] = total.get(name, 0.0) + ev.dur
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:top]]


def roofline_pct(trace: Trace, kernel: str, cost, peak: dict
                 ) -> float | None:
    """Share of the roofline a kernel reached: over all its events, the
    least time the chip could take for the work ``cost(shapes)`` counts
    (the larger of operations over peak FLOP/s and bytes over peak
    bytes/s) over the time the events took. None where the trace holds
    no such kernel or no shapes for it."""
    least = took = 0.0
    for ev in kernel_events(trace, kernel):
        shapes = operand_shapes(ev)
        if shapes is None:
            return None
        ops, nbytes = cost(shapes)
        least += max(ops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
        took += ev.dur
    return 100.0 * least / took if took > 0.0 else None
