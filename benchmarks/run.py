"""Benchmark harness entry point — one module per paper table/figure plus
the framework-integration and roofline tables.

    PYTHONPATH=src python -m benchmarks.run [--only fig6,fig7,...] \
        [--json out.json]

``--json`` writes every module's result rows (plus wall time and status)
to one file, so CI / future PRs can record ``BENCH_*.json`` throughput
trajectories instead of scraping stdout.

Modules:
    fig6   accuracy vs sampling fraction (WHS vs SRS; Gaussian/Poisson)
    fig7   throughput + bandwidth vs fraction (WHS/SRS/native)   [Figs 7+8]
    fig8   query-plane per-query accuracy + error-budget loop    [Fig 8*]
    fig9   latency vs fraction and vs window size                [Figs 9+10]
    fig11  fluctuating arrival rates + heavy skew                [Fig 11a-c]
    fig12  real-world-like datasets (taxi, pollution)            [Fig 12]
    train  approx-training plane (framework integration)
    kernels per-kernel allclose + timing (interpret mode)
    roofline dry-run roofline table (reads cached artifacts)
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

MODULES = ("fig6", "fig7", "fig8", "fig9", "fig11", "fig12", "train",
           "kernels", "roofline")


def main(argv=None) -> int:
    import json
    import pathlib

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(MODULES))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all module result rows to PATH as JSON")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke mode: quick-aware modules (fig6, fig7, "
                         "fig8, fig11, fig12) "
                         "shrink their ticks/sweeps/reps to run in seconds; "
                         "pair with --only to restrict to them (wiring check "
                         "only, numbers are not trajectory-grade)")
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="regression gate: compare this run's rows against "
                         "a previous --json report and exit non-zero on "
                         "any throughput drop beyond --compare-tol")
    ap.add_argument("--compare-tol", type=float, default=0.10,
                    metavar="FRAC",
                    help="allowed fractional throughput drop before "
                         "--compare fails (default 0.10 = 10%%)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the host span tracer's Chrome/Perfetto "
                         "trace.json (one span per module plus the "
                         "drivers' ingest/dispatch spans) to PATH")
    args = ap.parse_args(argv)
    chosen = args.only.split(",") if args.only else list(MODULES)

    from benchmarks import common
    if args.quick:
        common.QUICK = True

    from benchmarks import (fig6_accuracy, fig7_throughput, fig8_accuracy,
                            fig9_latency, fig11_skew, fig12_realworld,
                            kernels_micro, roofline, train_plane)
    impl = {
        "fig6": fig6_accuracy, "fig7": fig7_throughput,
        "fig8": fig8_accuracy, "fig9": fig9_latency,
        "fig11": fig11_skew, "fig12": fig12_realworld, "train": train_plane,
        "kernels": kernels_micro, "roofline": roofline,
    }
    from repro.obs.trace import get_tracer, span

    failures = 0
    report = {"meta": common.run_metadata()}
    for name in chosen:
        print(f"\n{'=' * 70}\n== {name}\n{'=' * 70}")
        t0 = time.time()
        try:
            with span(f"bench:{name}"):
                rows = impl[name].run()
            dt = time.time() - t0
            report[name] = {"ok": True, "seconds": dt, "rows": rows}
            print(f"[{name}] ok in {dt:.1f}s")
        except Exception as e:
            failures += 1
            dt = time.time() - t0
            report[name] = {"ok": False, "seconds": dt, "error": repr(e)}
            traceback.print_exc()
            print(f"[{name}] FAILED after {dt:.1f}s")
    print(f"\nbenchmarks done: {len(chosen) - failures}/{len(chosen)} ok")
    if args.json:
        path = pathlib.Path(args.json)
        path.write_text(json.dumps(report, indent=1, default=str))
        print(f"wrote {path}")
    if args.trace:
        get_tracer().save(args.trace)
        print(f"wrote {args.trace}")
    if args.compare:
        baseline = json.loads(pathlib.Path(args.compare).read_text())
        regressions = common.compare_reports(baseline, report,
                                             tol=args.compare_tol)
        if regressions:
            common.table(f"THROUGHPUT REGRESSIONS vs {args.compare} "
                         f"(tol {args.compare_tol:.0%})", regressions)
            failures += 1
        else:
            print(f"regression gate vs {args.compare}: pass "
                  f"(no throughput drop > {args.compare_tol:.0%})")
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
