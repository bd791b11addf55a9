"""The plain reference and the checks that decide ``correct``."""
from __future__ import annotations

import numpy as np
import pytest

from harness import reference as R
from harness import stream

LAYOUT = {"requests": (0, 1, "count"), "total": (1, 1, "sum"),
          "mean": (2, 1, "mean"), "q": (3, 2, "quantile"),
          "qw": (5, 2, "windowed_quantile"),
          "hh": (7, 4, "decayed_heavy_hitters")}
QUERIES = [{"kind": "count", "name": "requests"},
           {"kind": "sum", "name": "total"},
           {"kind": "mean", "name": "mean"},
           {"kind": "quantile", "name": "q", "qs": [0.5, 0.99]},
           {"kind": "windowed_quantile", "name": "qw", "qs": [0.5, 0.99],
            "window": 2},
           {"kind": "decayed_heavy_hitters", "name": "hh", "k": 2,
            "decay": 0.5}]
CONFIG = {"num_sources": 2, "fanin": [1],
          "substreams": [{"dist": "gaussian", "params": [10.0, 3.0],
                          "rate": 3000},
                         {"dist": "gaussian", "params": [1000.0, 50.0],
                          "rate": 3000}]}


def test_max_misses_matches_the_smoke_test_calibration():
    assert R.max_misses(192) == 19
    assert R.max_misses(16) == 4


def _exact_windows(truth, due, sampled=2000):
    """Answers a perfect pipeline would publish: exact count, sum, mean
    and quantiles, exact decayed heavy hitters, zero-width bounds where
    exact, and a small bound elsewhere."""
    n_out = 11
    a = np.zeros((due, n_out), np.float32)
    b = np.zeros((due, n_out), np.float32)
    lo = min(t.key_lo for t in truth)
    hi = max(t.key_lo + t.key_counts.size for t in truth)
    d = np.zeros(hi - lo)
    for t in range(due):
        tr = truth[t % len(truth)]
        c = np.zeros(hi - lo)
        c[tr.key_lo - lo:tr.key_lo - lo + tr.key_counts.size] = tr.key_counts
        d = 0.5 * d + c
        a[t, 0] = tr.n
        a[t, 1], b[t, 1] = tr.total, 1.0 + 1e-3 * abs(tr.total)
        a[t, 2], b[t, 2] = tr.total / tr.n, 1.0
        seen = np.concatenate([truth[j % len(truth)].sorted_values
                               for j in range(t + 1)])
        a[t, 3:5] = np.quantile(seen, [0.5, 0.99], method="inverted_cdf")
        recent = np.concatenate([truth[j % len(truth)].sorted_values
                                 for j in range(max(0, t - 1), t + 1)])
        a[t, 5:7] = np.quantile(recent, [0.5, 0.99], method="inverted_cdf")
        top = np.argsort(d)[-2:][::-1]
        a[t, 7:9] = top + lo
        a[t, 9:11] = d[top]
        b[t, 9:11] = 1.0
    return R.Windows(np.arange(due), a, b, np.full(due, sampled))


@pytest.fixture(scope="module")
def truth():
    ring = stream.make_ring(CONFIG, {}, seed=2 ** 31 + 5, ring_ticks=4)
    return R.ring_truth(ring, 2)


def test_exact_answers_pass_every_check(truth):
    w = _exact_windows(truth, 10)
    checks = R.check(LAYOUT, QUERIES, w, truth, 10)
    assert set(checks) == {
        "windows_missing", "count_err", "sum_worst_x_bound",
        "sum_outside_2sigma", "mean_worst_x_bound", "mean_outside_2sigma",
        "q_rank_excess_x_slack", "qw_rank_excess_x_slack", "hh_err_sigma",
        "hh_topk_gap_sigma"}
    assert R.verdict(checks), checks


@pytest.mark.parametrize("fault,check", [
    ("count_off_by_one", "count_err"),
    ("sum_far_off", "sum_worst_x_bound"),
    ("mean_far_off", "mean_worst_x_bound"),
    ("quantile_shifted", "q_rank_excess_x_slack"),
    ("recent_quantile_shifted", "qw_rank_excess_x_slack"),
    ("heavy_key_estimate_halved", "hh_err_sigma"),
    ("heavy_key_replaced", "hh_topk_gap_sigma"),
    ("window_missing", "windows_missing"),
    ("window_repeated", "windows_missing"),
])
def test_each_altered_answer_fails_its_check(truth, fault, check):
    w = _exact_windows(truth, 10)
    a = w.answers
    if fault == "count_off_by_one":
        a[3, 0] += 1
    elif fault == "sum_far_off":
        a[4, 1] += 5 * w.bounds[4, 1]
    elif fault == "mean_far_off":
        a[4, 2] += 5 * w.bounds[4, 2]
    elif fault == "quantile_shifted":
        a[6, 3] = a[6, 4]
    elif fault == "recent_quantile_shifted":
        a[6, 5] = a[6, 6]
    elif fault == "heavy_key_estimate_halved":
        a[5, 9] *= 0.5
    elif fault == "heavy_key_replaced":
        a[5, 7] = 5000.0
        a[5, 9] = 0.0
    elif fault == "window_missing":
        w = R.Windows(w.tick[1:], a[1:], w.bounds[1:], w.n_sampled[1:])
    elif fault == "window_repeated":
        tick = w.tick.copy()
        tick[5] = 4
        w = R.Windows(tick, a, w.bounds, w.n_sampled)
    checks = R.check(LAYOUT, QUERIES, w, truth, 10)
    assert not R.verdict(checks)
    assert checks[check]["value"] > checks[check]["limit"], checks[check]


def test_coverage_counts_windows_outside_two_sigma(truth):
    w = _exact_windows(truth, 40)
    w.answers[:10, 1] += 1.5 * w.bounds[:10, 1]
    checks = R.check(LAYOUT, QUERIES, w, truth, 40)
    assert checks["sum_outside_2sigma"]["value"] == 10.0
    assert checks["sum_outside_2sigma"]["limit"] == R.max_misses(40)
    assert not R.verdict(checks)


def test_bfloat16_control_fails(truth):
    w = _exact_windows(truth, 10)
    ctl = R.Windows(w.tick, R.control_answers(LAYOUT, w, truth, 10),
                    w.bounds, w.n_sampled)
    checks = R.check(LAYOUT, QUERIES, ctl, truth, 10)
    assert checks["count_err"]["value"] > 0.0
    assert not R.verdict(checks)
