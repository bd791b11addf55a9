"""The tools that set a cell's numbers, at a CPU test's size: the
readings of the program against the control, and the knee sweep."""
from __future__ import annotations

import json

import knee
import readings
from conftest import small


def test_readings_sound_runs_pass_and_the_control_and_fault_fail(capsys):
    config, traffic = small("paper-testbed.bulk")
    rc = readings.main(["--workload", "paper-testbed.bulk", "--seeds",
                        "5,6", "--seconds", "1.5"], require_tpu=False,
                       config_override=config, traffic_override=traffic)
    assert rc == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    seeds = [x for x in lines if "control" in x]
    assert len(seeds) == 2
    assert all(not x["control_correct"] for x in seeds)
    assert all(x["control"]["count_err"] > 0 for x in seeds)
    assert all(not x["previous_epoch_correct"] for x in seeds)
    summary = lines[-1]
    assert summary["lower"]["count_err"] == 0.0
    assert summary["upper"]["count_err"] > 0.0


def test_knee_sweep_reports_the_highest_sustained_rate(capsys):
    config, traffic = small("paper-testbed.paced-10hz")
    rc = knee.main(["--workload", "paper-testbed.paced-10hz", "--rates",
                    "1e4,2e4", "--seconds", "1.5"], require_tpu=False,
                   config_override=config, traffic_override=traffic)
    assert rc == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["knee_items_per_s"] in (1e4, 2e4)
    assert last["paced_items_per_s"] == 0.8 * last["knee_items_per_s"]
