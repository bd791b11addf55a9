"""The benchmark's four-chip cell, ``paper-testbed.mesh4``, end to end on
four simulated devices: ``bench/run.py`` at the harness tests' small cut
(``bench/tests/conftest.py``'s ``small``) drives the real mesh pipeline
through the bulk driver and through the streaming executor (``flat``),
and a run that publishes the previous epoch's answers is not correct.

The runs share one SUBPROCESS: its device count is fixed when JAX
starts, and the test process keeps its default one-device backend.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CELL = "paper-testbed.mesh4"

_RUNS = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{tests!r}, {bench!r}, {src!r}]
    from conftest import small
    import run

    class PreviousEpoch:
        # The pipeline, publishing each epoch the answers of the one
        # before it.
        def __init__(self, pipe):
            self._pipe, self._prev = pipe, None

        def __getattr__(self, name):
            return getattr(self._pipe, name)

        def run_epoch(self, state, key, values, strata, counts,
                      budgets=None):
            new, wa = self._pipe.run_epoch(state, key, values, strata,
                                           counts, budgets)
            prev, self._prev = self._prev, wa
            if prev is not None:
                wa = wa._replace(answers=prev.answers, bounds=prev.bounds,
                                 n_sampled=prev.n_sampled)
            return new, wa

    for name, driver, wrap in (("bulk", "bulk", None),
                               ("flat", "flat", None),
                               ("previous_epoch", "bulk", PreviousEpoch)):
        config, traffic = small({cell!r})
        traffic["driver"] = driver
        rc = run.main(["--workload", {cell!r}, "--seed", str(2 ** 31 + 17),
                       "--seconds", "2", "--trace", "0"],
                      require_tpu=False, config_override=config,
                      traffic_override=traffic, wrap_pipeline=wrap)
        print(json.dumps({{"run": name, "rc": rc}}), flush=True)
""")


@pytest.fixture(scope="module")
def runs():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _RUNS.format(tests=os.path.join(BENCH, "tests"), bench=BENCH,
                        src=os.path.join(ROOT, "src"), cell=CELL)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out = {}
    for i, line in enumerate(lines):
        done = json.loads(line)
        if "run" in done:
            out[done["run"]] = dict(rc=done["rc"],
                                    result=json.loads(lines[i - 1]))
    return out


@pytest.mark.parametrize("driver", ["bulk", "flat"])
def test_the_four_chip_cell_is_correct_on_four_devices(runs, driver):
    r = runs[driver]
    assert r["rc"] == 0
    result = r["result"]
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"]["items_per_s"]["value"] > 0
    device = result["device"]
    assert device["count"] == 4
    assert len(device["memory_peak_bytes_per_chip"]) == 4
    assert device["memory_peak_bytes"] == max(
        device["memory_peak_bytes_per_chip"])


def test_the_previous_epochs_answers_are_not_correct_on_four_devices(runs):
    r = runs["previous_epoch"]
    assert r["rc"] == 0
    assert not r["result"]["correct"], r["result"]["checks"]
