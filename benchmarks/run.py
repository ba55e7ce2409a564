"""Benchmark harness entry point: one function per paper table/figure.

Every sub-benchmark's ``main()`` returns ``(name, us_per_call, derived)``
rows; this driver prints the single CSV stream and optionally dumps the
same rows as JSON (``--json out.json``) so bench trajectories
(``BENCH_*.json``) can be recorded per commit. ``--smoke`` shrinks the
compute-heavy benches to tiny shapes for the CI bench-smoke job;
``--only`` selects a comma-separated subset by module name.

Regression gate: ``--compare BASELINE.json`` checks every fresh row
against the committed baseline by name and exits non-zero when a row got
more than ``--tolerance`` slower (ratio-tolerant: CI runners and dev
hosts differ in absolute speed, so the gate is meant to catch
order-of-magnitude path regressions, not µs jitter — rows faster than
``--min-us`` in the baseline are skipped as noise, and baseline rows
whose benchmark module did not run this invocation are ignored so
``--only``/``--smoke`` subsets stay comparable).
"""
import argparse
import json


def _derived_value(row, key: str):
    """Parse a ``key=<float>`` token out of a row's derived field
    (``None`` when absent or non-numeric)."""
    for token in (row.get("derived") or "").split(";"):
        if token.startswith(key + "="):
            try:
                return float(token[len(key) + 1:])
            except ValueError:
                return None
    return None


def compare_rows(rows, baseline_rows, tolerance: float, min_us: float):
    """Compare fresh rows against a recorded baseline.

    Returns ``(report_lines, regressions, missing)`` where regressions are
    rows slower than ``baseline * (1 + tolerance)`` and missing are
    baseline rows whose module ran but which the fresh run no longer
    produces (a silently dropped benchmark is a coverage regression).

    Latency-SLO gate: rows that carry a ``p99_us=`` derived token in
    BOTH the baseline and the fresh run (the ``serve_bench`` offered-load
    sweep) are additionally bounded at the tail — the fresh p99 must not
    exceed ``baseline_p99 * (1 + tolerance)`` (same noise floor), so a
    serving change that keeps the median but blows up the per-concurrency
    tail still fails the gate.
    """
    fresh = {r["name"]: r for r in rows}
    prefixes_run = {name.split("/")[0] for name in fresh}
    report, regressions, missing = [], [], []
    for brow in baseline_rows:
        name = brow["name"]
        if name.split("/")[0] not in prefixes_run:
            continue                      # that module did not run
        crow = fresh.get(name)
        if crow is None:
            missing.append(name)
            report.append(f"MISSING  {name}")
            continue
        if brow["us_per_call"] < min_us:
            report.append(f"skip     {name} (baseline {brow['us_per_call']:.0f}us "
                          f"< {min_us:.0f}us noise floor)")
            continue
        ratio = crow["us_per_call"] / brow["us_per_call"]
        ok = ratio <= 1.0 + tolerance
        tag = "ok      " if ok else "REGRESSED"
        report.append(f"{tag} {name} {brow['us_per_call']:.0f}us -> "
                      f"{crow['us_per_call']:.0f}us ({ratio:.2f}x)")
        if not ok:
            regressions.append(name)
        b99, c99 = _derived_value(brow, "p99_us"), _derived_value(crow,
                                                                  "p99_us")
        if b99 is not None and c99 is not None and b99 >= min_us:
            ratio99 = c99 / b99
            ok99 = ratio99 <= 1.0 + tolerance
            tag = "ok      " if ok99 else "REGRESSED"
            report.append(f"{tag} {name} [p99 SLO] {b99:.0f}us -> "
                          f"{c99:.0f}us ({ratio99:.2f}x)")
            if not ok99:
                regressions.append(name + ":p99")
    return report, regressions, missing


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", dest="json_path", default=None,
                    help="also write rows as JSON to this path")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for the compute-heavy benches")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names (e.g. "
                         "'search_bench,sdtw_kernel_bench')")
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="fail if any row regressed vs this recorded "
                         "baseline (see module docstring)")
    ap.add_argument("--tolerance", type=float, default=0.5,
                    help="allowed slowdown ratio above 1.0 for --compare "
                         "(0.5 = fail beyond 1.5x the baseline)")
    ap.add_argument("--min-us", type=float, default=200.0,
                    help="baseline rows faster than this are skipped by "
                         "--compare (timer noise)")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (common, endurance, fig09_latency_sweep, fig10_energy_sweep,
                   fig11_12_dataset_sweep, fig13_scaling, profile_bench,
                   roofline_table, sdtw_kernel_bench, search_bench,
                   serve_bench, table6_speedups, tuning_bench)
    mods = [
        ("fig09_latency_sweep", fig09_latency_sweep.main),
        ("fig10_energy_sweep", fig10_energy_sweep.main),
        ("fig11_12_dataset_sweep", fig11_12_dataset_sweep.main),
        ("fig13_scaling", fig13_scaling.main),
        ("table6_speedups", table6_speedups.main),
        ("endurance", endurance.main),
        ("sdtw_kernel_bench",
         lambda: sdtw_kernel_bench.main(smoke=args.smoke)),
        ("search_bench", lambda: search_bench.main(smoke=args.smoke)),
        ("profile_bench", lambda: profile_bench.main(smoke=args.smoke)),
        ("serve_bench", lambda: serve_bench.main(smoke=args.smoke)),
        ("tuning_bench", lambda: tuning_bench.main(smoke=args.smoke)),
        ("roofline_table", roofline_table.main),
    ]
    if args.only:
        wanted = {w.strip() for w in args.only.split(",")}
        unknown = wanted - {name for name, _ in mods}
        if unknown:
            raise SystemExit(f"unknown benchmarks: {sorted(unknown)}")
        mods = [(n, f) for n, f in mods if n in wanted]

    rows = []
    print(common.HEADER)
    for _, fn in mods:
        for row in fn():
            print(common.format_row(row))
            rows.append(row)

    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(common.rows_to_json(rows), f, indent=1)

    if args.compare:
        with open(args.compare) as f:
            baseline = json.load(f)
        report, regressions, missing = compare_rows(
            common.rows_to_json(rows), baseline, args.tolerance, args.min_us)
        print(f"\n--compare {args.compare} (tolerance {args.tolerance:.2f}, "
              f"noise floor {args.min_us:.0f}us)")
        for line in report:
            print("  " + line)
        if regressions or missing:
            raise SystemExit(
                f"bench regression gate failed: {len(regressions)} regressed "
                f"{regressions}, {len(missing)} missing {missing}")
        print("bench regression gate passed")
    return rows


if __name__ == '__main__':
    main()
