"""Observability CLI — artifact validation, calibration, and the
measured-vs-predicted regression sentinel:

    python -m repro.obs --validate-snapshot metrics.json
    python -m repro.obs --calibrate --bench benchmarks/results/BENCH_kernels.json \
        --calibration calibration.json
    python -m repro.obs --validate-calibration calibration.json
    python -m repro.obs --check-regressions --calibration calibration.json \
        --bench benchmarks/results/BENCH_kernels.json --report-out report.md

``--calibrate`` fits the analytic perf-model constants (obs.perfmodel)
from whichever measurement sources are given (``--plan-cache`` autotune
timings, ``--bench`` BENCH_kernels.json; the plan cache at its default
path is used when no source is named) and writes a versioned
calibration.json.

``--check-regressions`` re-reads the same sources and fails (exit 1)
when any measured timing exceeds ``--tolerance`` x the model's
prediction — the CI gate that catches a kernel regression without
golden-number baselines.

Exit 0 when every requested action passes; exit 1 with one problem per
line otherwise.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

from repro.obs import validate_snapshot_file
from repro.obs import perfmodel as pm


def _gather_samples(args) -> tuple[list, list]:
    """(samples, source-descriptions) from the CLI's source flags."""
    samples: list = []
    sources: list = []
    plan_caches = list(args.plan_cache)
    if not plan_caches and not args.bench:
        plan_caches = [None]  # default: the process plan cache
    for p in plan_caches:
        got, untagged = pm.samples_from_plan_cache(p)
        samples += got
        sources.append(f"plan-cache:{p or 'default'}")
        if untagged:
            print(f"note: skipped {untagged} pre-tag timing row(s) in "
                  f"{p or 'default plan cache'} (no interpret tag)",
                  file=sys.stderr)
    for p in args.bench:
        samples += pm.samples_from_bench(p)
        sources.append(f"bench:{p}")
    return samples, sources


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.obs")
    ap.add_argument("--validate-snapshot", action="append", default=[],
                    metavar="PATH", help="metrics snapshot JSON to check")
    ap.add_argument("--validate-calibration", action="append", default=[],
                    metavar="PATH", help="perf-model calibration to check")
    ap.add_argument("--calibrate", action="store_true",
                    help="fit perf-model constants from the measurement "
                         "sources and write --calibration")
    ap.add_argument("--check-regressions", action="store_true",
                    help="compare measured timings against the calibrated "
                         "model; exit 1 on outliers")
    ap.add_argument("--plan-cache", action="append", default=[],
                    metavar="PATH", help="plan cache JSON with autotune "
                                         "timings (measurement source)")
    ap.add_argument("--bench", action="append", default=[], metavar="PATH",
                    help="BENCH_kernels.json (measurement source)")
    ap.add_argument("--calibration", default=None, metavar="PATH",
                    help="calibration.json path (default: "
                         "$REPRO_CALIBRATION or the user cache dir)")
    ap.add_argument("--tolerance", type=float,
                    default=pm.DEFAULT_TOLERANCE,
                    help="regression band: measured > tolerance*predicted "
                         "fails (default %(default)s)")
    ap.add_argument("--report-out", default=None, metavar="PATH",
                    help="write the ranked regression report (markdown)")
    args = ap.parse_args(argv)
    actions = (args.validate_snapshot or args.validate_calibration
               or args.calibrate or args.check_regressions)
    if not actions:
        ap.error("nothing to do")

    problems: list[str] = []
    for p in args.validate_snapshot:
        problems += [f"{p}: {e}" for e in validate_snapshot_file(p)]
    for p in args.validate_calibration:
        problems += [f"{p}: {e}" for e in pm.validate_calibration_file(p)]

    calib_path = args.calibration or pm.default_calibration_path()

    if args.calibrate:
        samples, sources = _gather_samples(args)
        try:
            cal = pm.fit(samples, sources=sources)
        except ValueError as e:
            problems.append(f"calibrate: {e}")
        else:
            # additive collective-time term: fitted from any
            # shard_variants tables the same plan caches carry (absent
            # tables -> the block is simply omitted; version unchanged)
            coll_rows: list = []
            plan_caches = list(args.plan_cache) or [None]
            for p in plan_caches:
                coll_rows += pm.collective_rows_from_plan_cache(p)
            coll = pm.fit_collective(coll_rows, device=cal.device,
                                     interpret=cal.interpret)
            if coll is not None:
                cal.collective = coll
            out = cal.save(calib_path)
            print(f"calibrated {cal.device} interpret={cal.interpret} "
                  f"from {cal.fit['n_samples']} samples "
                  f"(rms rel err {cal.fit['rms_rel_err']:.2f}, "
                  f"max {cal.fit['max_abs_rel_err']:.2f}"
                  + (f"; collective term from {coll['n_samples']} "
                     f"variant rows" if coll else "")
                  + f") -> {out}")

    if args.check_regressions and not problems:
        cal = pm.load_calibration(calib_path)
        if cal is None:
            problems.append(
                f"check-regressions: no calibration matching this "
                f"device/interpret partition at {calib_path} — run "
                f"--calibrate first")
        else:
            samples, _ = _gather_samples(args)
            report = pm.check_regressions(samples, cal,
                                          tolerance=args.tolerance)
            text = pm.render_report(report)
            if args.report_out:
                Path(args.report_out).parent.mkdir(parents=True,
                                                   exist_ok=True)
                Path(args.report_out).write_text(text + "\n")
            print(text)
            if not report["n_samples"]:
                problems.append("check-regressions: no samples in the "
                                "calibration's partition — nothing to "
                                "check")
            elif not report["ok"]:
                problems.append(
                    f"check-regressions: {report['n_outliers']} "
                    f"measurement(s) slower than "
                    f"{args.tolerance:g}x the model prediction")

    if problems:
        print("\n".join(problems), file=sys.stderr)
        return 1
    n = len(args.validate_snapshot) + len(args.validate_calibration)
    if n:
        print(f"ok: {n} artifact(s) schema-valid")
    return 0


if __name__ == "__main__":
    sys.exit(main())
