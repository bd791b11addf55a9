"""The three ways a cell drives the system under test.

* ``bulk``  — a backlog replayed into ``CompiledPipeline.run_epoch``
  back to back, one epoch in flight: the host queue is bypassed.
* ``flat``  — sources replayed through ``StreamingExecutor``
  ``start``/``pump``/``stop``, pumped back to back.
* ``paced`` — the same served path, pumped open loop on the wall clock
  once per tick; a late pump is made at once and never skipped.

Each driver warms every shape its cell uses before the window, and
returns what the window published with the benchmark's own clock
readings. The benchmark annotates its calls into the program
(``run_epoch``, ``collect``, ``pump``, ``stop``) so that a device trace
can attribute idle gaps to them.

A pipeline on N chips (``api.compile(spec, mesh=...)``) is driven
through the same calls as one on a single chip, with nothing converted
on the side: ``Bulk`` calls ``run_epoch(state, key, values[T, n0,
width], strata, counts)``, and ``Served`` hands the pipeline to
``StreamingExecutor.start``, which reads ``fanin``. That front door is
the harness's contract for N chips.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import numpy as np

from harness.reference import Windows

clock = time.perf_counter


@dataclasses.dataclass
class Run:
    windows: Windows
    due: int                  # ticks offered in the window
    items: int                # items offered in the window
    t0: float                 # start of the window (benchmark clock)
    t_end: float              # every answer of the window on the host
    failed_ticks: set         # ticks whose items were shed or partial
    pump_s: list = dataclasses.field(default_factory=list)
    lag_s: list = dataclasses.field(default_factory=list)
    latency_s: np.ndarray | None = None
    epochs: int = 0
    pumps: list = dataclasses.field(default_factory=list)


class PumpClock:
    """What one ``pump()`` spent besides its wall time: the calling
    thread's CPU seconds, the whole process's (the runtime's own threads
    with it), and the seconds the garbage collector ran. Read around
    every pump, so that a pump that stalls shows whether it worked,
    collected garbage, or sat off the CPU."""

    def __init__(self):
        self.gc_s, self._gc_t0 = 0.0, None
        gc.callbacks.append(self._on_gc)

    def close(self):
        gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = clock()
        elif self._gc_t0 is not None:
            self.gc_s += clock() - self._gc_t0
            self._gc_t0 = None

    def read(self):
        return time.thread_time(), time.process_time(), self.gc_s

    @staticmethod
    def since(a, b) -> dict:
        return {"cpu_ms": 1e3 * (b[0] - a[0]),
                "process_cpu_ms": 1e3 * (b[1] - a[1]),
                "gc_ms": 1e3 * (b[2] - a[2])}


class Annotations:
    """``jax.profiler.TraceAnnotation`` spans when tracing, and nothing
    otherwise."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


def pump_summary(pumps: list[dict]) -> dict:
    """The window's pumps in a few numbers, and the five slowest in
    full (``before_ms`` is the time since the previous pump ended: the
    pacer's sleep, or whatever held the loop between pumps)."""
    wall = np.asarray([p["wall_ms"] for p in pumps])
    flushed = np.asarray([p["flushed"] for p in pumps])
    lag = np.asarray([p["lag_ms"] for p in pumps])
    slowest = sorted(pumps, key=lambda p: -p["wall_ms"])[:5]
    return {"pumps": len(pumps), "pump_ms_mean": float(wall.mean()),
            "pump_ms_p95": float(np.percentile(wall, 95)),
            "flush_pump_ms_mean": float(wall[flushed].mean())
            if flushed.any() else None,
            "lag_ms_max": float(lag.max()),
            "pumps_over_100ms_late": int((lag > 100.0).sum()),
            "gc_ms": float(sum(p["gc_ms"] for p in pumps)),
            "slowest_pumps": slowest}


def _host_windows(rows: list) -> Windows:
    tick = np.concatenate([r[0] for r in rows]) if rows else np.zeros(0)
    return Windows(
        tick=tick.astype(np.int64),
        answers=np.concatenate([r[1] for r in rows]),
        bounds=np.concatenate([r[2] for r in rows]),
        n_sampled=np.concatenate([r[3] for r in rows]).astype(np.int64))


# ------------------------------------------------------------------ bulk --
class Bulk:
    def __init__(self, pipe, traffic: dict, ring, cap: int):
        import jax

        self.pipe = pipe
        self.T = int(traffic["epoch_ticks"])
        self.epochs = [ring.packed(r * self.T, self.T, cap)
                       for r in range(len(ring) // self.T)]
        self.items = [int(e[2].sum()) for e in self.epochs]
        self._fold = lambda i: jax.random.fold_in(pipe.default_key, i)

    def warm(self):
        """Compile (or load) the epoch program and the host-side ops on a
        scratch state, then start from a fresh one."""
        scratch, wa = self.pipe.run_epoch(self.pipe.init(), self._fold(0),
                                          *self.epochs[0])
        self._host(wa)
        del scratch
        self.state = self.pipe.init()

    @staticmethod
    def _host(wa):
        ok = np.asarray(wa.ok)
        return (np.asarray(wa.tick)[ok] - 1, np.asarray(wa.answers)[ok],
                np.asarray(wa.bounds)[ok], np.asarray(wa.n_sampled)[ok])

    def run(self, seconds: float, ann: Annotations) -> Run:
        rows, pending, i, items = [], None, 0, 0
        t0 = clock()
        while True:
            with ann("run_epoch"):
                self.state, wa = self.pipe.run_epoch(
                    self.state, self._fold(i),
                    *self.epochs[i % len(self.epochs)])
            items += self.items[i % len(self.epochs)]
            if pending is not None:
                with ann("collect"):
                    rows.append(self._host(pending))
            pending = wa
            i += 1
            if clock() - t0 >= seconds:
                break
        with ann("collect"):
            rows.append(self._host(pending))
        t_end = clock()
        return Run(_host_windows(rows), due=i * self.T, items=items, t0=t0,
                   t_end=t_end, failed_ticks=set(), epochs=i)


# ---------------------------------------------------------------- served --
class RingSource:
    """Source ``i`` of the ring: pump ``k`` delivers ring tick
    ``k mod len(ring)``. It is the tee too: it knows exactly what
    entered the executor in each pump."""

    def __init__(self, ring, i: int):
        self.ring, self.i, self.k = ring, i, 0
        self.shard = ring.node_of(i)
        self._deliver = None

    def subscribe(self, deliver):
        self._deliver = deliver

    def pump(self, now: float):
        v, s = self.ring.ticks[self.k % len(self.ring)][self.i]
        self.k += 1
        if v.size:
            self._deliver(self.shard, v, s)


class Served:
    def __init__(self, pipe, config: dict, traffic: dict, ring, cap: int,
                 seed: int):
        from repro.serve import StreamingExecutor

        self.pipe, self.ring = pipe, ring
        q = config["queue"]
        self.ex = StreamingExecutor(
            epoch_ticks=int(traffic["epoch_ticks"]), width=cap,
            queue_capacity=int(q["capacity_factor"]) * cap,
            policy=q["policy"], seed=int(seed) % (2 ** 31))
        self.tick_s = traffic.get("tick_seconds")

    def warm(self):
        """``start`` pre-warms the epoch program; the per-epoch key
        derivation is warmed here too."""
        import jax

        jax.random.fold_in(self.pipe.default_key, 0).block_until_ready()
        self.sources = [RingSource(self.ring, i)
                        for i in range(self.ring.num_sources)]
        self.ex.start(self.pipe, self.sources)

    def _shed(self) -> int:
        qs = self.ex._queues
        return (sum(q.deferred + q.items_dropped for q in qs)
                + self.ex._staging.truncated_total)

    def run(self, seconds: float, ann: Annotations, paced: bool) -> Run:
        seen, failed = {}, set()
        pump_s, lag_s, pumps = [], [], []
        shed0 = self._shed()
        k = 0
        pc = PumpClock()
        t0 = prev_end = clock()
        n_ticks = int(round(seconds / self.tick_s)) if paced else None
        while (k < n_ticks) if paced else (clock() - t0 < seconds):
            if paced:
                due = t0 + k * self.tick_s
                while clock() < due:
                    rest = due - clock()
                    if rest > 2e-3:
                        time.sleep(rest - 1e-3)
            epoch, r0 = self.ex._epoch, pc.read()
            start = clock()
            if paced:
                lag_s.append(start - due)
            with ann("pump"):
                got = self.ex.pump()
            end = clock()
            pump_s.append(end - start)
            pumps.append({"k": k, "wall_ms": 1e3 * (end - start),
                          "lag_ms": 1e3 * lag_s[-1] if paced else 0.0,
                          "before_ms": 1e3 * (start - prev_end),
                          "flushed": self.ex._epoch != epoch,
                          **pc.since(r0, pc.read())})
            prev_end = end
            for w in got:
                seen[w.tick - 1] = (w, end)
            shed = self._shed()
            if shed != shed0:
                failed.add(k)
                shed0 = shed
            k += 1
        with ann("stop"):
            self.ex.stop()
        t_end = clock()
        pc.close()
        for w in self.ex.published:
            if w.tick - 1 not in seen:
                seen[w.tick - 1] = (w, t_end)
        ticks = sorted(t for t in seen if t < k)
        rows = [(np.asarray([t]), np.asarray(seen[t][0].answers)[None],
                 np.asarray(seen[t][0].bounds)[None],
                 np.asarray([seen[t][0].n_sampled])) for t in ticks]
        failed |= {t for t in ticks if seen[t][0].partial}
        latency = None
        if paced:
            latency = np.asarray([seen[t][1] - (t0 + t * self.tick_s)
                                  for t in ticks])
        items = sum(self.ring.items(t % len(self.ring)) for t in range(k))
        windows = (_host_windows(rows) if rows else
                   Windows(np.zeros(0, np.int64), np.zeros((0, 0)),
                           np.zeros((0, 0)), np.zeros(0, np.int64)))
        return Run(windows, due=k, items=items, t0=t0, t_end=t_end,
                   failed_ticks=failed, pump_s=pump_s,
                   lag_s=lag_s, latency_s=latency, epochs=self.ex._epoch,
                   pumps=pumps)
