"""The plain reference and the window checks that decide ``correct``.

The reference is numpy over exactly the items each window took in: the
count and the sum in float64, ranks from the sorted items, and the
exact decayed count of every key. It imports nothing of the program.
The check arithmetic (``max_misses``, the 2-sigma coverage rule, the
rank bound plus a binomial slack) is the on-chip smoke test's, copied
here so that no change to the program moves it.

The quantile slack is two over the square root of the sample behind
the answer, counted as Kish's effective sample size. Each check yields
one number and its limit; a run is correct when
every number is at most its limit. ``PERF.md`` gives the readings each
limit was set from.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import math

import numpy as np

# A calibrated 2-sigma bound misses P(|Z| > 2) of the windows.
NOMINAL_MISS = 0.0455
# The chance that a calibrated bound fails the coverage check by luck.
COVERAGE_ALPHA = 1e-3

# Limits set from readings (PERF.md, "How correct is decided").
LIMITS = {
    "windows_missing": 0.0,
    "count_err": 0.0,
    "sum_worst_x_bound": 3.0,
    "mean_worst_x_bound": 3.0,
    "q_rank_excess_x_slack": 1.0,
    "qw_rank_excess_x_slack": 1.0,
    "hh_err_sigma": 6.0,
    "hh_topk_gap_sigma": 6.0,
}


def max_misses(n: int) -> int:
    """The most of ``n`` windows a calibrated 2-sigma bound may miss:
    the smallest ``k`` with P(Binomial(n, NOMINAL_MISS) > k) <=
    COVERAGE_ALPHA (windows draw independent samples)."""
    tail = 1.0
    log_p, log_q = math.log(NOMINAL_MISS), math.log1p(-NOMINAL_MISS)
    for k in range(n + 1):
        tail -= math.exp(math.lgamma(n + 1) - math.lgamma(k + 1)
                         - math.lgamma(n - k + 1) + k * log_p
                         + (n - k) * log_q)
        if tail <= COVERAGE_ALPHA:
            return k
    return n


@dataclasses.dataclass
class TickTruth:
    """Exact facts of one ring tick: item count, float64 sum, the sorted
    items, the items' keys (``rint``) as ``key_lo + index`` counts, and
    the largest per-stratum share of the tick (for the sampling slack)."""

    n: int
    total: float
    sorted_values: np.ndarray
    key_lo: int
    key_counts: np.ndarray
    stratum_n: np.ndarray


def tick_truth(values: np.ndarray, strata: np.ndarray, num_strata: int
               ) -> TickTruth:
    keys = np.rint(values.astype(np.float64)).astype(np.int64)
    lo = int(keys.min()) if keys.size else 0
    return TickTruth(
        n=int(values.size), total=float(values.astype(np.float64).sum()),
        sorted_values=np.sort(values),
        key_lo=lo, key_counts=np.bincount(keys - lo),
        stratum_n=np.bincount(strata, minlength=num_strata))


def ring_truth(ring, num_strata: int, workers: int = 8) -> list[TickTruth]:
    def one(t):
        pairs = ring.ticks[t]
        return tick_truth(np.concatenate([v for v, _ in pairs]),
                          np.concatenate([s for _, s in pairs]),
                          num_strata)
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        return list(pool.map(one, range(len(ring))))


@dataclasses.dataclass
class Windows:
    """What the timed path published, in tick order: ``tick[i]`` is the
    window's position in the run (0 = first tick of the window), and the
    answers and bounds are the dashboard tenant's flat vectors."""

    tick: np.ndarray          # i64[W]
    answers: np.ndarray       # f32[W, n_out]
    bounds: np.ndarray        # f32[W, n_out]
    n_sampled: np.ndarray     # i64[W]


def check(layout: dict, queries: list[dict], windows: Windows,
          truth: list[TickTruth], due: int) -> dict:
    """Every number compared, each as ``{"value", "limit"}``.

    ``due`` is the number of ticks the timed run offered; run tick ``i``
    took in ring tick ``i % len(truth)``."""
    out = {}
    spec = {q["name"]: q for q in queries}
    ok_ticks = np.zeros(due, bool)
    seen = windows.tick[(windows.tick >= 0) & (windows.tick < due)]
    ok_ticks[seen] = True
    in_order = (np.all(np.diff(windows.tick) > 0)
                and windows.tick.size == seen.size)
    out["windows_missing"] = float(due - ok_ticks.sum()
                                   + (0 if in_order else windows.tick.size))
    # Compare only the windows that came, at their own ticks.
    keep = (windows.tick >= 0) & (windows.tick < due)
    tick = windows.tick[keep]
    a = windows.answers[keep].astype(np.float64)
    b = windows.bounds[keep].astype(np.float64)
    n_sampled = windows.n_sampled[keep].astype(np.float64)
    if tick.size == 0:
        return _with_limits(out)
    n_ring = len(truth)
    ring_of = np.arange(due) % n_ring
    n = np.asarray([truth[r].n for r in ring_of], np.float64)
    tot = np.asarray([truth[r].total for r in ring_of], np.float64)
    for name, (o, w, kind) in layout.items():
        if kind == "count":
            out["count_err"] = float(np.max(np.abs(a[:, o] - n[tick])))
        elif kind in ("sum", "mean"):
            exact = tot[tick] if kind == "sum" else tot[tick] / n[tick]
            ratio = np.abs(a[:, o] - exact) / np.maximum(b[:, o], 1e-300)
            ratio = np.where(b[:, o] > 0.0, ratio, np.inf)
            out[f"{kind}_worst_x_bound"] = float(ratio.max())
            out[f"{kind}_outside_2sigma"] = (float(np.sum(ratio > 1.0)),
                                             float(max_misses(ratio.size)))
        elif kind in ("quantile", "windowed_quantile"):
            tag = "q" if kind == "quantile" else "qw"
            out[f"{tag}_rank_excess_x_slack"] = _check_quantile(
                spec[name], kind, a[:, o:o + w], b[:, o:o + w], tick,
                truth, n_sampled)
        elif kind in ("heavy_hitters", "decayed_heavy_hitters"):
            out.update(_check_hh(spec[name], kind, a[:, o:o + w],
                                 b[:, o:o + w], tick, truth, n_sampled))
    return _with_limits(out)


def _check_quantile(q: dict, kind: str, a, b, tick, truth, n_sampled
                    ) -> float:
    """The worst rank excess over the published rank bound, in units of
    the sampling slack, over every window and quantile. A window's
    answer is ranked among all items since the run began (``quantile``)
    or among its last ``window`` ticks (``windowed_quantile``)."""
    n_ring = len(truth)
    n_win, width = a.shape
    qs = np.asarray(q["qs"], np.float64)
    # le[r, i, j]: items of ring tick r at most window i's j-th answer.
    le = np.stack([np.searchsorted(t.sorted_values, a.reshape(-1),
                                   side="right").reshape(n_win, width)
                   for t in truth]).astype(np.float64)
    n_r = np.asarray([t.n for t in truth], np.float64)
    # Kish's effective sample size: an equal allocation over strata of
    # unequal size weighs items unequally.
    strat = np.stack([t.stratum_n for t in truth]).astype(np.float64)
    deff = (strat.shape[1] * np.sum(strat ** 2, 1)
            / np.maximum(strat.sum(1), 1.0) ** 2)
    eff = n_sampled / deff[tick % n_ring]
    if kind == "quantile":
        # Each ring tick r has entered (t // n_ring) + (r <= t % n_ring)
        # times by run tick t.
        mult = (tick[:, None] // n_ring
                + (np.arange(n_ring)[None, :] <= (tick % n_ring)[:, None]))
        below = np.einsum("ir,rij->ij", mult, le)
        total = mult @ n_r
        cum = np.zeros(int(tick.max()) + 1)
        cum[tick] = eff
        sampled = np.cumsum(cum)[tick]
    else:
        span = int(q["window"])
        js = tick[:, None] - np.arange(span)[None, :]
        live = js >= 0
        rid = np.where(live, js, 0) % n_ring
        rows = np.arange(n_win)[:, None]
        below = np.sum(le[rid, rows, :] * live[:, :, None], axis=1)
        total = np.sum(n_r[rid] * live, axis=1)
        pos = {int(t): i for i, t in enumerate(tick)}
        sampled = np.asarray([sum(eff[pos[int(j)]] for j in js[i]
                                  if int(j) in pos)
                              for i in range(n_win)])
    slack = 2.0 / np.sqrt(np.maximum(sampled, 1.0))
    excess = np.abs(below / total[:, None] - qs[None, :]) - b
    return float(np.max(excess / slack[:, None]))


def _check_hh(q: dict, kind: str, a, b, tick, truth, n_sampled) -> dict:
    """Each reported key's estimate against the key's exact (decayed)
    count ``D``: a count-min estimate of the sampled stream over-counts
    by at most the published bound and under-counts only by sampling, so
    both ``D - est`` and ``est - D - bound`` are held in units of the
    estimate's sampling sd; and no reported key may trail the true k-th
    heaviest by more than the bound plus that slack."""
    k = int(q["k"])
    gamma = float(q.get("decay", 1.0)) if kind.startswith("decayed") else 1.0
    n_ring = len(truth)
    lo = min(t.key_lo for t in truth)
    hi = max(t.key_lo + t.key_counts.size for t in truth)
    counts = np.zeros((n_ring, hi - lo))
    for r, t in enumerate(truth):
        counts[r, t.key_lo - lo:t.key_lo - lo + t.key_counts.size] = \
            t.key_counts
    # A key whose largest count in any tick is below the k-th largest
    # smallest count can never be among the k heaviest: follow only the
    # others and the keys the program reported.
    floor = np.sort(counts.min(0))[-k] if counts.shape[1] >= k else 0.0
    keys = a[:, :k]
    live = keys != float(2 ** 31 - 1)
    reported = np.unique(keys[live & (keys >= lo) & (keys < hi)]
                         .astype(np.int64) - lo)
    cand = np.flatnonzero(counts.max(0) >= max(floor, 1.0))
    track = np.union1d(cand, reported)
    col = {int(c): i for i, c in enumerate(track)}
    in_cand = np.isin(track, cand)
    c_tr = counts[:, track]
    w_of = {int(t): i for i, t in enumerate(tick)}
    d = np.zeros(track.size)
    var = np.zeros(track.size)
    err = gap = -np.inf
    for t in range(int(tick.max()) + 1):
        r = t % n_ring
        i = w_of.get(t)
        # The heaviest stratum's inverse inclusion probability bounds
        # every item's HT weight under an equal allocation.
        strat = truth[r].stratum_n
        s = n_sampled[i] if i is not None else 0.0
        w_max = float(strat.max()) * strat.size / max(s, 1.0)
        d = gamma * d + c_tr[r]
        var = gamma * gamma * var + c_tr[r] * max(w_max - 1.0, 0.0)
        if i is None or not live[i].any():
            continue
        kk = keys[i][live[i]]
        est = a[i, k:][live[i]]
        bound = b[i, k:][live[i]]
        idx = np.asarray([col.get(int(x) - lo, -1) for x in kk])
        true = np.where(idx >= 0, d[idx], 0.0)
        sd = np.sqrt(np.maximum(np.where(idx >= 0, var[idx], 0.0), 1.0))
        err = max(err, float(np.max(np.maximum(true - est,
                                               est - true - bound) / sd)))
        dc = d[in_cand]
        if dc.size >= k:
            j = np.argpartition(dc, dc.size - k)[dc.size - k]
            sd_kth = math.sqrt(max(float(var[in_cand][j]), 1.0))
            g = (dc[j] - true - bound) / np.maximum(sd, sd_kth)
            gap = max(gap, float(g.max()))
    return {"hh_err_sigma": err, "hh_topk_gap_sigma": gap}


def _with_limits(numbers: dict) -> dict:
    out = {}
    for name, v in numbers.items():
        if isinstance(v, tuple):
            out[name] = {"value": v[0], "limit": v[1]}
        else:
            out[name] = {"value": v, "limit": LIMITS[name]}
    return out


def verdict(checks: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def control_answers(layout: dict, windows: Windows, truth: list[TickTruth],
                    due: int) -> np.ndarray:
    """The control: the reference put in the program's place and
    computed in bfloat16, the precision below the configuration's
    float32. Count, sum and mean come from the window's exact items,
    rounded to and summed in bfloat16; the sketch answers (quantiles,
    heavy hitters) are the program's own, rounded to bfloat16. The
    program's bounds stay. ``check`` of these answers has to fail."""
    import ml_dtypes

    bf16 = ml_dtypes.bfloat16
    out = np.array(windows.answers, np.float32, copy=True)
    ring_of = np.arange(due) % len(truth)
    per_ring = {}
    for r in np.unique(ring_of[windows.tick]):
        tr = truth[int(r)]
        s = np.sum(tr.sorted_values.astype(bf16), dtype=bf16)
        per_ring[int(r)] = (bf16(tr.n), s, s / bf16(tr.n))
    for i, t in enumerate(windows.tick):
        n, s, m = per_ring[int(ring_of[t])]
        for name, (o, w, kind) in layout.items():
            if kind == "count":
                out[i, o] = np.float32(n)
            elif kind == "sum":
                out[i, o] = np.float32(s)
            elif kind == "mean":
                out[i, o] = np.float32(m)
            else:
                out[i, o:o + w] = out[i, o:o + w].astype(bf16).astype(
                    np.float32)
    return out
