#!/usr/bin/env python3
"""Chip smoke test: the main path of the system on a TPU, checked against
exact references.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the mesh phase, on four chips

One chip runs two phases:

* ``kernels`` — every Pallas kernel, compiled for the chip, against its
  ``ref.py`` oracle at the main path's widths, plus a probe of whether a
  default-precision f32 one-hot matmul is exact on this chip.
* ``main_path`` — the paper testbed through the entry points a user
  calls: ``PipelineSpec`` → ``repro.api.compile`` →
  ``StreamingExecutor.start/pump/stop``. Eight sources feed a (4, 2, 1)
  tree with four gaussian sub-streams at 16,000 items per tick each per
  source (the rate that saturates the root in ``benchmarks/
  fig9_latency.py``), sampled at fraction 0.1 with the default sampler
  backend, and the serve dashboard's standing queries
  (``launch.serve.serve_registry``) answered every window. After the
  pre-warm, a few 64-tick epochs run and every window is compared with a
  numpy exact reference of the same seeded stream: the HT count must be
  exact; SUM and MEAN no further than twice their published ±2σ in any
  window, and outside ±2σ in no more windows than a calibrated bound
  misses by chance (``max_misses``); quantiles inside their rank bound
  plus the sampling slack.

``--chips 4`` runs only the mesh phase: the same spec and tenants
compiled onto a 4-device ``data`` mesh and onto a 1-device mesh, fed the
same total stream, compared with each other and with the exact stream.

The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU the script exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# The paper testbed (§V-A): 8 sources → 4 → 2 → 1, four gaussian
# sub-streams, each source offering 16,000 items per tick per sub-stream.
NUM_SOURCES = 8
FANIN = (4, 2, 1)
RATE = 16_000
FRACTION = 0.1
EPOCH_TICKS = 64
EPOCHS = 3
MESH_TICKS = 8           # per epoch; the mesh phase runs two epochs


# A calibrated ±2σ bound misses P(|Z| > 2) of the windows.
NOMINAL_MISS = 0.0455
# The chance that a calibrated bound fails the coverage check by luck.
COVERAGE_ALPHA = 1e-3


class CheckFailed(RuntimeError):
    """An answer outside its bound, or a kernel that disagrees with its
    oracle."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def _log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def max_misses(n: int) -> int:
    """The most of ``n`` windows a calibrated ±2σ bound may miss: the
    smallest ``k`` with P(Binomial(n, NOMINAL_MISS) > k) ≤
    COVERAGE_ALPHA (windows draw independent samples). 192 windows may
    miss 19 (coverage ≥ 0.901), 16 windows 4 (≥ 0.75)."""
    tail = 1.0
    for k in range(n + 1):
        tail -= (math.comb(n, k) * NOMINAL_MISS ** k
                 * (1.0 - NOMINAL_MISS) ** (n - k))
        if tail <= COVERAGE_ALPHA:
            return k
    return n


def _check_clt(name: str, err: np.ndarray, bound: np.ndarray) -> dict:
    """SUM or MEAN over a run's windows against the exact reference: no
    window outside twice its published ±2σ (about 4σ), and no more
    windows outside ±2σ than a calibrated bound misses by chance."""
    _check(bool(np.all(bound > 0.0)), f"{name}: a window has no bound")
    ratio = err / bound
    misses = int(np.sum(ratio > 1.0))
    _check(bool(np.all(ratio <= 2.0)),
           f"{name}: a window is {float(ratio.max())}x its ±2σ bound away "
           f"from the exact answer (limit 2x)")
    _check(misses <= max_misses(ratio.size),
           f"{name}: {misses} of {ratio.size} windows outside ±2σ (a "
           f"calibrated bound misses at most {max_misses(ratio.size)})")
    return {"worst_sigma2": float(ratio.max()), "misses": misses,
            "windows": int(ratio.size),
            "coverage": 1.0 - misses / ratio.size}


# ---------------------------------------------------------------- kernels --
def _precision_probe(interpret: bool) -> bool:
    """Does a DEFAULT-precision f32 one-hot matmul inside a Pallas kernel
    return its operand bit-for-bit on this chip? (The kernels pin
    ``Precision.HIGHEST`` either way; this records why.)"""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    n = 512
    v = jnp.asarray(np.random.default_rng(7).normal(1e5, 5e3, (1, n)),
                    jnp.float32)

    def kernel(v_ref, o_ref):
        eye = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
                        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1),
                        1.0, 0.0)
        o_ref[...] = jax.lax.dot_general(
            v_ref[...], eye, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    out = pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(v.shape,
                                                                v.dtype),
                         interpret=interpret)(v)
    return bool(np.array_equal(np.asarray(out), np.asarray(v)))


def _compare(name, got, want, *, rtol=0.0, atol=0.0) -> dict:
    got = [np.asarray(g) for g in (got if isinstance(got, tuple) else (got,))]
    want = [np.asarray(w) for w in
            (want if isinstance(want, tuple) else (want,))]
    exact = all(np.array_equal(g, w) for g, w in zip(got, want))
    diff = max(float(np.max(np.abs(g.astype(np.float64)
                                   - w.astype(np.float64)), initial=0.0))
               for g, w in zip(got, want))
    ok = exact or all(np.allclose(g, w, rtol=rtol, atol=atol)
                      for g, w in zip(got, want))
    _check(ok, f"kernel {name} disagrees with its oracle (max |diff| "
               f"{diff})")
    return {"exact": exact, "max_abs_diff": diff}


def kernel_phase(root_items: int, *, seed: int, fused_cap: int | None = None,
                 interpret: bool = False) -> dict:
    """Each Pallas kernel compiled (``interpret=False``) vs its oracle.
    Sums over the MXU are held to the repo's kernel-test tolerances
    (``tests/test_kernels.py``); lookups, masks and compactions must be
    bit-exact."""
    import jax
    import jax.numpy as jnp

    from repro.data import stream as S
    from repro.kernels.fused_level_tick import fused_level_tick as ft
    from repro.kernels.fused_level_tick import ref as ft_ref
    from repro.kernels.sample_mask import ops as sm_ops
    from repro.kernels.sample_mask import ref as sm_ref
    from repro.kernels.sample_mask import sample_mask as sm
    from repro.kernels.sketch_update import ref as sk_ref
    from repro.kernels.sketch_update import sketch_update as sk
    from repro.kernels.stratified_stats import ref as ss_ref
    from repro.kernels.stratified_stats import stratified_stats as ss

    rng = np.random.default_rng(seed)
    x = len(S.GAUSSIAN)
    m = root_items
    strata = rng.integers(0, x, m).astype(np.int32)
    mu = np.asarray([g[0] for g in S.GAUSSIAN])[strata]
    sd = np.asarray([g[1] for g in S.GAUSSIAN])[strata]
    vals = jnp.asarray(rng.normal(mu, sd), jnp.float32)
    strata = jnp.asarray(strata)
    valid = jnp.asarray(rng.random(m) < 0.9)
    prio = jnp.asarray(rng.random(m), jnp.float32)
    out = {"default_f32_dot_exact": _precision_probe(interpret)}

    out["stratified_stats"] = _compare(
        "stratified_stats",
        jax.jit(lambda *a: ss.stratified_stats(*a, x, interpret=interpret))(
            vals, strata, valid),
        ss_ref.stratified_stats(vals, strata, valid, x),
        rtol=1e-4, atol=1e-2)

    res = jnp.asarray(rng.integers(1, m // x, x), jnp.float32)
    w = jnp.asarray(rng.random(x) * 10, jnp.float32)
    tau = sm_ops.thresholds_from_reservoirs(prio, strata, valid, res, x)
    out["sample_mask"] = _compare(
        "sample_mask",
        jax.jit(lambda *a: sm.sample_mask(*a, interpret=interpret))(
            prio, strata, valid, tau, w),
        sm_ref.sample_mask(prio, strata, valid, tau, w))

    # The decayed heavy hitters' count-min shape in serve_registry().
    keys = jnp.round(vals).astype(jnp.int32).astype(jnp.uint32)
    wts = jnp.where(valid, prio * 10.0, 0.0)
    got = jax.jit(lambda k, ww: sk.cms_update(k, ww, 4, 256,
                                              interpret=interpret))(keys, wts)
    out["cms_update"] = _compare("cms_update", got,
                                 sk_ref.cms_update(keys, wts, 4, 256),
                                 rtol=1e-5, atol=1e-3)

    # A capacity-256 KLL level folding one root window, compacting to
    # 128 survivors (the dashboard quantile's level-0 shape).
    p = m + 256
    qv = jnp.asarray(np.sort(rng.normal(0.0, 1e4, p)), jnp.float32)
    qw = rng.random(p) * (rng.random(p) > 0.2)
    cumw = jnp.asarray(np.cumsum(qw), jnp.float32)
    prev = jnp.concatenate([jnp.zeros((1,), jnp.float32), cumw[:-1]])
    tgt = (jnp.arange(128, dtype=jnp.float32) + 0.37) * (cumw[-1] / 128)
    out["quantile_compact"] = _compare(
        "quantile_compact",
        jax.jit(lambda *a: sk.quantile_compact(*a, interpret=interpret))(
            qv, prev, cumw, tgt),
        sk_ref.quantile_compact(qv, prev, cumw, tgt))

    # The fused tick at the edge of its envelope: 4 nodes × 8192 × 4
    # strata, 10% budget, holes in the buffer (the scatter path).
    n, cap = 4, fused_cap or ft.MAX_CAPACITY
    budget, out_cap = cap // 10, cap // 10
    lv = jnp.asarray(rng.normal(1e4, 5e2, (n, cap)), jnp.float32)
    ls = jnp.asarray(rng.integers(0, x, (n, cap)), jnp.int32)
    lm = jnp.asarray(rng.random((n, cap)) < 0.9)
    lu = jnp.asarray(rng.random((n, cap)), jnp.float32)
    w_in = jnp.asarray(np.abs(rng.normal(1, 0.2, (n, x))), jnp.float32)
    c_in = jnp.asarray(rng.integers(0, 500, (n, x)), jnp.float32)
    size = jnp.float32(budget)
    out["fused_level_tick"] = _compare(
        "fused_level_tick",
        jax.jit(lambda *a: ft.fused_level_tick(*a, x, out_cap,
                                               interpret=interpret))(
            lv, ls, lm, lu, w_in, c_in, size),
        ft_ref.fused_level_tick(lv, ls, lm, lu, w_in, c_in, size, x,
                                out_cap))
    out["fused_select"] = _compare(
        "fused_select",
        jax.jit(lambda *a: ft.fused_select(*a, x, interpret=interpret))(
            lu[0], ls[0], lm[0], jnp.full((x,), budget / x, jnp.float32)),
        ft_ref.fused_select(lu[0], ls[0], lm[0],
                            jnp.full((x,), budget / x, jnp.float32), x))
    return out


# -------------------------------------------------------------- main path --
def paper_spec(rate: int, fraction: float):
    """The paper testbed as a ``PipelineSpec``: level-0 buffers sized by
    ``launch.analytics.default_capacity`` for the offered load, the serve
    dashboard as the one tenant, the default sampler backend."""
    from repro import api
    from repro.data import stream as S
    from repro.launch.analytics import default_capacity
    from repro.launch.serve import serve_registry

    specs = S.paper_gaussian(rates=(rate,) * len(S.GAUSSIAN))
    capacity = default_capacity(specs, NUM_SOURCES, FANIN)
    spec = api.PipelineSpec(
        topology=api.TopologySpec(fanin=FANIN, capacity=capacity,
                                  num_strata=len(specs)),
        sampler=api.SamplerSpec(mode="whs", fraction=fraction),
        tenants=(serve_registry().as_tenant("dashboard"),))
    return spec, specs


class _Recorded:
    """A source that keeps, per pump, the values it delivered — the
    exact reference is built from exactly what entered the executor."""

    def __init__(self, source, log: list):
        self._src, self._log = source, log

    def subscribe(self, deliver):
        def tee(shard, values, strata):
            self._log[-1].append(np.asarray(values, np.float32))
            deliver(shard, values, strata)
        self._src.subscribe(tee)

    def pump(self, now: float):
        self._src.pump(now)


def _rank(sorted_values: np.ndarray, v: float) -> float:
    return float(np.searchsorted(sorted_values, v, side="right")
                 / max(sorted_values.size, 1))


def _check_windows(layout, windows, ticks_values, tick0: int,
                   epoch_ticks: int) -> dict:
    """Every published window against the exact reference of its tick:
    count exact, SUM/MEAN held to their ±2σ by ``_check_clt``, the
    quantiles at each epoch's last window inside their rank bound plus a
    4σ binomial slack of the sample behind them."""
    off = {name: (o, w) for name, (o, w, _) in layout.items()}
    clt = {"latency_total_ms": ([], []), "latency_mean_ms": ([], [])}
    q_excess = -1.0
    n_sampled = []
    for win in windows:
        k = win.tick - tick0
        vals = ticks_values[k]
        a, b = np.asarray(win.answers), np.asarray(win.bounds)
        n_sampled.append(win.n_sampled)
        exact_n = vals.size
        exact_sum = float(vals.astype(np.float64).sum())
        o, _ = off["requests"]
        _check(float(a[o]) == float(exact_n) and float(b[o]) == 0.0,
               f"tick {k}: count {a[o]} != exact {exact_n}")
        for name, exact in (("latency_total_ms", exact_sum),
                            ("latency_mean_ms", exact_sum / exact_n)):
            o, _ = off[name]
            clt[name][0].append(abs(float(a[o]) - exact))
            clt[name][1].append(float(b[o]))
        o, w = off["hot_latency_keys"]
        _check(bool(np.all(np.isfinite(a[o:o + w]))),
               f"tick {k}: heavy hitters not finite")
    for e in range(len(windows) // epoch_ticks):
        last = (e + 1) * epoch_ticks - 1
        win = windows[last]
        a, b = np.asarray(win.answers), np.asarray(win.bounds)
        for name, first in (("latency_q_ms", 0),
                            ("latency_q_recent_ms", max(0, last - 3))):
            seen = np.sort(np.concatenate(ticks_values[first:last + 1]))
            sampled = float(sum(n_sampled[first:last + 1]))
            slack = 2.0 / np.sqrt(sampled)
            o, w = off[name]
            for q, v, bound in zip((0.5, 0.99), a[o:o + w], b[o:o + w]):
                excess = abs(_rank(seen, float(v)) - q) - float(bound)
                _check(excess <= slack,
                       f"window {last}: {name} q={q} answer {v} has rank "
                       f"{_rank(seen, float(v))}, outside bound {bound} + "
                       f"slack {slack}")
                q_excess = max(q_excess, excess)
    out = {name: _check_clt(name, np.asarray(e), np.asarray(b))
           for name, (e, b) in clt.items()}
    out["q_rank_excess"] = q_excess
    return out


def _lowered_text(fn, *args) -> str:
    import jax

    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          args)
    return fn.lower(*shapes).as_text()


def main_path_phase(*, seed: int, rate: int = RATE, epochs: int = EPOCHS,
                    epoch_ticks: int = EPOCH_TICKS,
                    expect_kernels: bool = True) -> dict:
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.serve import StreamingExecutor, SyntheticSource

    spec, specs = paper_spec(rate, FRACTION)
    cap = spec.topology.capacity
    n0 = FANIN[0]
    t = time.perf_counter()
    pipe = api.compile(spec)
    if expect_kernels:
        # The sketch kernels run as Pallas on TPU: the epoch program must
        # carry them, not a silent reference.
        text = _lowered_text(
            pipe._epoch_fn(epoch_ticks), pipe.init(), pipe.default_key,
            jnp.zeros((len(FANIN),), jnp.float32),
            np.zeros((epoch_ticks, n0, cap), np.float32),
            np.zeros((epoch_ticks, n0, cap), np.int32),
            np.zeros((epoch_ticks, n0), np.int32))
        _check("tpu_custom_call" in text,
               "the epoch program carries no Pallas kernel")
    log: list[list] = []
    sources = [_Recorded(SyntheticSource(i // (NUM_SOURCES // n0),
                                         specs=specs, seed=seed * 100 + i),
                         log)
               for i in range(NUM_SOURCES)]
    ex = StreamingExecutor(epoch_ticks=epoch_ticks, width=cap,
                           queue_capacity=2 * cap, policy="block", seed=seed)
    lower_s = time.perf_counter() - t
    t = time.perf_counter()
    ex.start(pipe, sources)            # pre-warm: compiles the epoch
    prewarm_s = time.perf_counter() - t
    epoch_s = []
    for _ in range(epochs):
        t = time.perf_counter()
        for _ in range(epoch_ticks):
            log.append([])
            ex.pump()
        jax.block_until_ready(ex.state)
        epoch_s.append(time.perf_counter() - t)
    stats = ex.stop()
    ticks_values = [np.concatenate(parts) if parts else
                    np.zeros(0, np.float32) for parts in log]
    _check(stats["queue_items_dropped"] == 0 and stats["queue_deferred"] == 0
           and stats["truncated_items"] == 0,
           f"items were shed: {stats}")
    _check(stats["windows_partial"] == 0, f"partial windows: {stats}")
    windows = ex.published
    _check(len(windows) == epochs * epoch_ticks,
           f"{len(windows)} windows published for "
           f"{epochs * epoch_ticks} ticks")
    tick0 = windows[0].tick
    worst = _check_windows(pipe.query_layout(), windows, ticks_values, tick0,
                           epoch_ticks)
    return {"capacity": cap, "lower_check_s": lower_s,
            "prewarm_compile_s": prewarm_s,
            "epoch_wall_s": epoch_s,
            "items_per_epoch": [int(sum(v.size for v in ticks_values[
                e * epoch_ticks:(e + 1) * epoch_ticks]))
                for e in range(epochs)],
            "windows": len(windows), "worst": worst,
            "staged_items": stats["staged_items"]}


# ------------------------------------------------------------------- mesh --
def _mesh_batches(specs, cap: int, ticks: int, seed: int):
    """The paper stream as the mesh path's interval batches: node ``d``'s
    buffer (its two sources' items) is the ``d``-th contiguous shard of
    each tick's item axis, so on a 4-device mesh device ``d`` is edge
    node ``d``."""
    from repro.core.types import IntervalBatch, StratumMeta
    from repro.data import stream as S
    import jax.numpy as jnp

    n0 = FANIN[0]
    per = NUM_SOURCES // n0
    srcs = [S.StreamSource(specs, seed=seed * 100 + i)
            for i in range(NUM_SOURCES)]
    x = len(specs)
    value = np.zeros((ticks, n0 * cap), np.float32)
    stratum = np.zeros((ticks, n0 * cap), np.int32)
    valid = np.zeros((ticks, n0 * cap), bool)
    exact = []
    for t in range(ticks):
        tick_vals = []
        for d in range(n0):
            parts = [srcs[d * per + j].tick() for j in range(per)]
            v = np.concatenate([p[0] for p in parts])[:cap]
            s = np.concatenate([p[1] for p in parts])[:cap]
            value[t, d * cap:d * cap + v.size] = v
            stratum[t, d * cap:d * cap + v.size] = s
            valid[t, d * cap:d * cap + v.size] = True
            tick_vals.append(v)
        exact.append(np.concatenate(tick_vals))
    batches = IntervalBatch(
        jnp.asarray(value), jnp.asarray(stratum), jnp.asarray(valid),
        StratumMeta(jnp.ones((ticks, x), jnp.float32),
                    jnp.zeros((ticks, x), jnp.float32)))
    return batches, exact


def mesh_phase(*, seed: int, n_devices: int = 4, rate: int = RATE,
               ticks: int = MESH_TICKS, expect_kernels: bool = True
               ) -> dict:
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.launch.analytics import make_data_mesh

    spec, specs = paper_spec(rate, FRACTION)
    cap = spec.topology.capacity
    batches, exact = _mesh_batches(specs, cap, 2 * ticks, seed)
    halves = [jax.tree.map(lambda a, i=i: a[i * ticks:(i + 1) * ticks],
                           batches) for i in (0, 1)]
    runs = {}
    for n in (n_devices, 1):
        pipe = api.compile(spec, mesh=make_data_mesh(n))
        t = time.perf_counter()
        if expect_kernels:
            text = _lowered_text(pipe._fn, pipe.init(), pipe.default_key,
                                 jnp.float32(pipe.local_budget),
                                 halves[0])
            _check("tpu_custom_call" in text,
                   f"the {n}-device epoch carries no Pallas kernel")
        state = pipe.init()
        answers, bounds, n_sampled, epoch_s = [], [], [], []
        for half in halves:                 # two epochs: state carries
            t0 = time.perf_counter()
            state, wa = pipe.run_epoch(state, pipe.default_key, half)
            jax.block_until_ready(wa.answers)
            epoch_s.append(time.perf_counter() - t0)
            _check(bool(np.asarray(wa.ok).all()), f"{n} devices: empty "
                                                  f"window")
            answers.append(np.asarray(wa.answers))
            bounds.append(np.asarray(wa.bounds))
            n_sampled.append(np.asarray(wa.n_sampled))
        # Each device keeps its own sketch state: every leaf split over
        # the mesh, one distinct shard per device.
        leaves = jax.tree.leaves(state.qstate)
        devices = {len({sh.device for sh in leaf.addressable_shards})
                   for leaf in leaves}
        if n > 1:
            _check(not any(leaf.sharding.is_fully_replicated
                           for leaf in leaves),
                   f"{n} devices: sketch state is replicated, not sharded")
            _check(all(len({sh.index for sh in leaf.addressable_shards})
                       == n for leaf in leaves),
                   f"{n} devices: sketch state shards overlap")
        runs[n] = dict(answers=np.concatenate(answers),
                       bounds=np.concatenate(bounds),
                       n_sampled=np.concatenate(n_sampled),
                       shard_devices=sorted(devices),
                       first_epoch_s=epoch_s[0], second_epoch_s=epoch_s[1],
                       compile_plus_first_s=time.perf_counter() - t
                       - epoch_s[1])
        _log("mesh_run", devices=n, shard_devices=sorted(devices),
             epoch_s=epoch_s)
    _check(runs[n_devices]["shard_devices"] == [n_devices],
           f"sketch state shards sit on {runs[n_devices]['shard_devices']} "
           f"devices, expected {n_devices}")

    off = {name: (o, w) for name, (o, w, _) in pipe.query_layout().items()}
    o, _ = off["requests"]
    counts = {n: r["answers"][:, o] for n, r in runs.items()}
    _check(np.array_equal(counts[n_devices], counts[1]),
           "exact count differs between the mesh and one device")
    _check(np.array_equal(counts[1], np.asarray([v.size for v in exact],
                                                np.float32)),
           "exact count differs from the stream")
    worst = {}
    for n, r in runs.items():
        for name in ("latency_total_ms", "latency_mean_ms"):
            o, _ = off[name]
            want = np.asarray([v.astype(np.float64).sum()
                               / (v.size if "mean" in name else 1.0)
                               for v in exact])
            worst[f"{n}:{name}"] = _check_clt(
                f"{n} devices: {name}", np.abs(r["answers"][:, o] - want),
                r["bounds"][:, o])
        o, w = off["latency_q_ms"]
        seen = np.sort(np.concatenate(exact))
        slack = 2.0 / np.sqrt(float(r["n_sampled"].sum()))
        for q, v, bound in zip((0.5, 0.99), r["answers"][-1, o:o + w],
                               r["bounds"][-1, o:o + w]):
            _check(abs(_rank(seen, float(v)) - q) <= bound + slack,
                   f"{n} devices: quantile {q} outside its rank bound")
    return {"capacity": cap, "ticks": 2 * ticks, "worst": worst,
            "runs": {n: {k: v for k, v in r.items()
                         if k not in ("answers", "bounds", "n_sampled")}
                     for n, r in runs.items()}}


# ------------------------------------------------------------------- main --
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels + the main path on one chip; 4: only "
                         "the mesh phase, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    _log("device", platform=devices[0].platform,
         kind=devices[0].device_kind, count=len(devices),
         compile_cache=enable_compile_cache())
    if args.chips == 4:
        phases = [("mesh", lambda: mesh_phase(seed=args.seed))]
    else:
        from repro.api.spec import resolve

        root_items = resolve(paper_spec(RATE, FRACTION)[0]).capacities[-1]
        phases = [("kernels", lambda: kernel_phase(root_items,
                                                   seed=args.seed)),
                  ("main_path", lambda: main_path_phase(seed=args.seed))]
    for name, run in phases:
        t = time.perf_counter()
        try:
            result = run()
        except Exception:          # every phase's failure ends the run
            traceback.print_exc()
            print(f"chip_smoke: phase {name!r} failed", file=sys.stderr)
            return 1
        _log(name, seconds=time.perf_counter() - t, **result)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
