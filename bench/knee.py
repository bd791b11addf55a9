#!/usr/bin/env python3
"""One flat-out sweep of a paced cell's served path: the highest rate
it sustains.

    python3 bench/knee.py --workload paper-testbed.paced-10hz \
        --rates 4e5,8e5,1.2e6 --seconds 10

For each offered rate (items per second), the cell's stream is made at
that rate and pumped back to back through the streaming executor, with
the cell's own epoch length, as in the ``served-flat`` traffic. A rate
is sustained where the 95th percentile of the pumps, those that flush an
epoch among them, takes no longer than a tick, so that a pacer at that
rate falls behind only by chance: the knee is the highest such rate.
The benchmark's own runs never run this; a paced cell's rate is four
fifths of the knee, written into its traffic file.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import run  # noqa: E402


def main(argv=None, **overrides) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from harness import cells

    _, _, paced = cells.cell(args.workload)
    paced = overrides.pop("traffic_override", None) or paced
    tick_ms = 1e3 * paced["tick_seconds"]
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(paced, driver="flat", items_per_s=rate)
        got = {}

        def hook(run, truth, layout, config, checks):
            got["pump_ms"] = 1e3 * np.asarray(run.pump_s)
            got["items_per_s"] = run.items / (run.t_end - run.t0)
            got["correct"] = all(c["value"] <= c["limit"]
                                 for c in checks.values())

        rc = run.main(["--workload", args.workload, "--seed",
                       str(args.seed), "--seconds", str(args.seconds),
                       "--trace", "0"],
                      traffic_override=traffic, hook=hook, **overrides)
        if rc:
            return rc
        pump = got["pump_ms"]
        p95 = float(np.percentile(pump, 95))
        rows.append({"offered_items_per_s": rate,
                     "pump_ms_mean": float(pump.mean()),
                     "pump_ms_p95": p95,
                     "pump_ms_max": float(pump.max()),
                     "flat_items_per_s": got["items_per_s"],
                     "sustained": p95 <= tick_ms,
                     "correct": got["correct"]})
        print(json.dumps(rows[-1]), flush=True)
    ok = [r["offered_items_per_s"] for r in rows if r["sustained"]]
    print(json.dumps({"knee_items_per_s": max(ok) if ok else None,
                      "paced_items_per_s": 0.8 * max(ok) if ok else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
