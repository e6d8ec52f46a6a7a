"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--fast`` trims sizes for CI;
``--smoke`` is the CI drift gate (tiny scales, asserting suites) and
``--csv`` additionally writes the rows to a file so CI can upload them as
a build artifact (the source for BENCH_*.json trajectories).

Exit contract (the smoke gate depends on it): any suite that raises —
including ``SystemExit`` from a ``sys.exit()`` deep in a suite — marks
the run failed and the driver exits 1; an ``--only``/``--smoke``
selection that matches *nothing* exits 2 instead of reporting success
having run nothing; ``--compare`` against a prior BENCH_*.json exits 3
when any shared row regressed by more than 25% (CI treats 3 as
advisory — noise-prone micro rows must not block merges);
``--compare-md`` appends the same deltas as a markdown table, which CI
points at ``$GITHUB_STEP_SUMMARY``.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import traceback

REGRESSION_PCT = 25.0  # --compare gate: slower than prior by more → exit 3

# quality fields compared per-row under --compare (higher = worse for all
# three); regressions past the threshold exit 4 under --quality-gate —
# BLOCKING in CI, unlike the advisory timing exit 3: partition quality is
# deterministic, so any drift is a real algorithm change, not runner noise
QUALITY_METRICS = ("rf", "eb", "vb")
QUALITY_REGRESSION_PCT = 2.0


SMOKE_SUITES = ("theory", "memory", "spmd", "runtime",
                "kernels", "serve")  # tiny CI drift gate
# the quality matrix is NOT in SMOKE_SUITES: its streaming-baseline scans
# are too slow for the smoke gate, so CI gives it a dedicated job


def compare_rows(rows, prior_path: str) -> tuple[list, list, list, list]:
    """Print per-row deltas vs a committed BENCH_*.json.

    Returns ``(deltas, regressions, qdeltas, qregressions)``: every
    comparable-or-new row as ``(name, old_us, new_us, pct)``
    (``old_us``/``pct`` are None for new rows) with the subset that
    slowed by more than :data:`REGRESSION_PCT` percent, plus the same
    for the first-class quality fields — every :data:`QUALITY_METRICS`
    key shared by a row and its prior as
    ``(name, metric, old, new, pct)``, with the subset that *worsened*
    (all three are higher-is-worse) by more than
    :data:`QUALITY_REGRESSION_PCT` percent.  Prior rows may carry their
    metrics as an explicit ``"metrics"`` dict (new format) or packed in
    the ``"derived"`` string (old format) — both parse.
    """
    import json

    from benchmarks.common import parse_metrics

    with open(prior_path) as f:
        prior_rows = json.load(f)
    prior = {r["name"]: float(r["us_per_call"]) for r in prior_rows}
    prior_q = {r["name"]: (r.get("metrics")
                           or parse_metrics(r.get("derived", "")))
               for r in prior_rows}
    deltas, regressions = [], []
    qdeltas, qregressions = [], []
    print(f"\n--- compare vs {prior_path} ---")
    for name, us, derived in rows:
        old = prior.get(name)
        if old is None:
            print(f"{name}: (new) {us:.1f}us")
            deltas.append((name, None, us, None))
            continue
        if old > 0:
            pct = (us - old) / old * 100.0
            flag = "  REGRESSION" if pct > REGRESSION_PCT else ""
            print(f"{name}: {old:.1f}us -> {us:.1f}us ({pct:+.1f}%){flag}")
            deltas.append((name, old, us, pct))
            if pct > REGRESSION_PCT:
                regressions.append((name, old, us, pct))
        mine = parse_metrics(derived)
        theirs = prior_q.get(name) or {}
        for metric in QUALITY_METRICS:
            if metric not in mine or metric not in theirs:
                continue
            o, v = float(theirs[metric]), float(mine[metric])
            if o <= 0:
                continue
            qpct = (v - o) / o * 100.0
            worse = qpct > QUALITY_REGRESSION_PCT
            qdeltas.append((name, metric, o, v, qpct))
            if worse:
                print(f"{name}: {metric} {o:.4f} -> {v:.4f} "
                      f"({qpct:+.2f}%)  QUALITY REGRESSION")
                qregressions.append((name, metric, o, v, qpct))
    return deltas, regressions, qdeltas, qregressions


def write_compare_md(path: str, deltas: list, prior_path: str,
                     qdeltas: list | None = None) -> None:
    """Append the compare deltas as a GitHub-flavored markdown table —
    the ``$GITHUB_STEP_SUMMARY`` payload of the CI bench job (append, not
    truncate: the summary file is shared by every step of the job).
    Quality deltas (rf/eb/vb) get their own table when present."""
    lines = [
        f"### Benchmark deltas vs `{os.path.basename(prior_path)}`",
        "",
        "| row | prior (µs) | now (µs) | delta |",
        "| --- | ---: | ---: | ---: |",
    ]
    for name, old, us, pct in deltas:
        if old is None:
            lines.append(f"| `{name}` | — | {us:.1f} | new |")
        else:
            flag = " ⚠️" if pct > REGRESSION_PCT else ""
            lines.append(
                f"| `{name}` | {old:.1f} | {us:.1f} | {pct:+.1f}%{flag} |"
            )
    if qdeltas:
        lines += [
            "",
            f"### Quality deltas vs `{os.path.basename(prior_path)}` "
            f"(gate: >{QUALITY_REGRESSION_PCT:.0f}% worse blocks)",
            "",
            "| row | metric | prior | now | delta |",
            "| --- | --- | ---: | ---: | ---: |",
        ]
        for name, metric, old, new, pct in qdeltas:
            flag = " ❌" if pct > QUALITY_REGRESSION_PCT else ""
            lines.append(f"| `{name}` | {metric} | {old:.4f} | {new:.4f} "
                         f"| {pct:+.2f}%{flag} |")
    with open(path, "a") as f:
        f.write("\n".join(lines) + "\n\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-scale subset (CI gate: breaks on bench drift)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--csv", default=None,
                    help="also write the result rows to this CSV file "
                         "(written even when suites fail)")
    ap.add_argument("--json", default=None,
                    help="also write the result rows as a JSON list "
                         "(the committed BENCH_*.json format)")
    ap.add_argument("--trace", default=None,
                    help="write a Chrome/Perfetto trace of the run "
                         "(one span per suite) to this .json path")
    ap.add_argument("--compare", default=None,
                    help="prior BENCH_*.json: print per-row deltas; exit 3 "
                         f"when a shared row slowed by >{REGRESSION_PCT:.0f}%%")
    ap.add_argument("--compare-md", default=None,
                    help="append the --compare deltas as a markdown table "
                         "to this file (CI points it at "
                         "$GITHUB_STEP_SUMMARY)")
    ap.add_argument("--quality-gate", action="store_true",
                    help="exit 4 (blocking) when --compare finds any "
                         "rf/eb/vb field worsened by more than "
                         f"{QUALITY_REGRESSION_PCT:.0f}%% — the CI "
                         "quality job's gate, unlike the advisory exit 3")
    args = ap.parse_args()
    if args.smoke:
        args.fast = True
    from repro import compile_cache

    compile_cache.enable()

    from benchmarks import (bench_apps, bench_elapsed, bench_kernels,
                            bench_lambda_sweep, bench_memory, bench_quality,
                            bench_roads, bench_runtime, bench_scaling,
                            bench_sequential, bench_serve, bench_spmd,
                            bench_theory)
    from benchmarks.common import ROWS, header
    from repro.obs import trace as obs

    bench_log = None
    if args.trace:
        bench_log = os.path.join(tempfile.mkdtemp(prefix="bench_trace_"),
                                 obs.log_name(0))
        obs.configure(path=bench_log, process=0,
                      meta={"bench": True, "smoke": bool(args.smoke),
                            "fast": bool(args.fast)})

    suites = {
        "theory": lambda: bench_theory.main(),
        "lambda_sweep": lambda: bench_lambda_sweep.main(
            scale=12 if args.fast else 13),
        "quality": lambda: bench_quality.main(fast=args.fast),
        "memory": lambda: bench_memory.main(smoke=args.smoke,
                                            fast=args.fast),
        "elapsed": lambda: bench_elapsed.main(fast=args.fast),
        "scaling": lambda: bench_scaling.main(fast=args.fast),
        "sequential": lambda: bench_sequential.main(fast=args.fast),
        "spmd": lambda: bench_spmd.main(fast=args.fast),
        "runtime": lambda: bench_runtime.main(fast=args.fast,
                                              smoke=args.smoke),
        "apps": lambda: bench_apps.main(fast=args.fast),
        "roads": lambda: bench_roads.main(fast=args.fast),
        "kernels": lambda: bench_kernels.main(fast=args.fast,
                                              smoke=args.smoke),
        "serve": lambda: bench_serve.main(fast=args.fast,
                                          smoke=args.smoke),
    }
    if args.only is not None and args.only not in suites:
        print(f"unknown suite {args.only!r}; known: {sorted(suites)}",
              file=sys.stderr)
        raise SystemExit(2)
    header()
    failed, ran = [], []
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        if args.smoke and not args.only and name not in SMOKE_SUITES:
            continue
        ran.append(name)
        try:
            with obs.span(name, cat="bench"):
                fn()
        except KeyboardInterrupt:
            raise
        # BaseException, not Exception: a suite calling sys.exit(0) (or a
        # worker helper leaking SystemExit) must count as a failure, not
        # terminate the driver with a success code mid-gate
        except BaseException:  # noqa: BLE001 — report all suites
            failed.append(name)
            traceback.print_exc()
    if args.csv:
        with open(args.csv, "w") as f:
            f.write("name,us_per_call,derived\n")
            for name, us, derived in ROWS:
                f.write(f"{name},{us:.1f},{derived}\n")
    if args.json:
        import json

        from benchmarks.common import parse_metrics

        with open(args.json, "w") as f:
            json.dump([{"name": name, "us_per_call": round(us, 1),
                        "derived": derived,
                        # first-class parsed fields, so baseline readers
                        # (and the quality gate) never re-parse free text
                        "metrics": parse_metrics(derived)}
                       for name, us, derived in ROWS], f, indent=2)
            f.write("\n")
    if args.trace:
        from repro.obs import export

        obs.disable()  # close + flush the bench tracer's JSONL log
        export.write_chrome_trace(args.trace, [bench_log])
        print(f"trace written to {args.trace}", file=sys.stderr)
    regressions, qregressions = [], []
    if args.compare:
        deltas, regressions, qdeltas, qregressions = \
            compare_rows(ROWS, args.compare)
        if args.compare_md:
            write_compare_md(args.compare_md, deltas, args.compare, qdeltas)
    if not ran:
        print("no suites selected — selection bug, not success",
              file=sys.stderr)
        raise SystemExit(2)
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        raise SystemExit(1)
    if args.quality_gate and qregressions:
        print(f"{len(qregressions)} quality field(s) worsened "
              f">{QUALITY_REGRESSION_PCT:.0f}% vs {args.compare} "
              "(BLOCKING)", file=sys.stderr)
        raise SystemExit(4)
    if regressions:
        print(f"{len(regressions)} row(s) regressed >{REGRESSION_PCT:.0f}% "
              f"vs {args.compare} (advisory)", file=sys.stderr)
        raise SystemExit(3)


if __name__ == "__main__":
    main()
