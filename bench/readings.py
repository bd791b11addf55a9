#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds <s>

Runs the cell once per seed in one process, as ``bench/run.py`` does,
and for each run reads every number compared twice: for the program,
for the control, the reference put in the program's place in
bfloat16 (``harness.reference.control_answers``), and for a planted
fault: the program's windows with each epoch's answers swapped for the
previous epoch's, as a program that publishes stale answers would.
Prints one JSON line per seed, then the largest program reading and the
smallest control and fault readings of each number. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import run  # noqa: E402
from harness import reference  # noqa: E402


def main(argv=None, **overrides) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from harness import cells

    _, _, traffic = cells.cell(args.workload)
    traffic = overrides.get("traffic_override") or traffic
    lower, upper, stale_min = {}, {}, {}

    def hook(run, truth, layout, config, checks):
        ctl = reference.Windows(
            run.windows.tick,
            reference.control_answers(layout, run.windows, truth, run.due),
            run.windows.bounds, run.windows.n_sampled)
        control = reference.check(layout, config["queries"], ctl, truth,
                                  run.due)
        stale = reference.check(
            layout, config["queries"],
            previous_epoch(run.windows, int(traffic["epoch_ticks"])),
            truth, run.due)
        line = {"program": {k: v["value"] for k, v in checks.items()},
                "control": {k: v["value"] for k, v in control.items()},
                "control_correct": reference.verdict(control),
                "previous_epoch": {k: v["value"] for k, v in stale.items()},
                "previous_epoch_correct": reference.verdict(stale),
                "windows": int(run.windows.tick.size)}
        for k, v in line["program"].items():
            lower[k] = max(lower.get(k, v), v)
        for k, v in line["control"].items():
            upper[k] = min(upper.get(k, v), v)
        for k, v in line["previous_epoch"].items():
            stale_min[k] = min(stale_min.get(k, v), v)
        print(json.dumps(line), flush=True)

    for seed in args.seeds.split(","):
        rc = run.main(["--workload", args.workload, "--seed", seed,
                       "--seconds", str(args.seconds), "--trace", "0"],
                      hook=hook, **overrides)
        if rc:
            return rc
    print(json.dumps({"lower": lower, "upper": upper,
                      "previous_epoch": stale_min}), flush=True)
    return 0


def previous_epoch(w: reference.Windows, epoch_ticks: int
                   ) -> reference.Windows:
    """``w`` with every window's answers, bounds and sample size taken
    from the window one epoch earlier (the first epoch keeps its own)."""
    pos = {int(t): i for i, t in enumerate(w.tick)}
    idx = [pos.get(int(t) - epoch_ticks, i) for i, t in enumerate(w.tick)]
    return reference.Windows(w.tick, w.answers[idx], w.bounds[idx],
                             w.n_sampled[idx])


if __name__ == "__main__":
    sys.exit(main())
