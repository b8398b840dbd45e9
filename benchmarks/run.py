# One function per paper table. Print ``name,us_per_call,derived`` CSV.
# ``--json PATH`` additionally writes a machine-readable name -> us_per_call
# map (e.g. BENCH_1.json) so the perf trajectory across PRs is diffable;
# ``--compare BASELINE.json`` exits nonzero on >25% regression of any key
# shared with the baseline (the CI regression guard).
import argparse
import contextlib
import io
import json
import os
import statistics
import sys
import traceback

REGRESSION_THRESHOLD = 0.25

# keys where larger is better (throughput); everything else is
# us/bytes/launch-count style where smaller is better.
_HIGHER_BETTER = ("kpps", "mpps", "pps")


def _parse_rows(text: str) -> dict:
    from benchmarks.common import parse_csv_rows
    return parse_csv_rows(text)


def _is_throughput(key: str) -> bool:
    return any(key.endswith(suf) for suf in _HIGHER_BETTER)


_MIN_NORMALIZE_KEYS = 4


def _speed_factor(results: dict, baseline: dict, shared) -> float:
    """Median uniform slowdown of this machine vs the baseline machine,
    estimated over the non-structural (timing/throughput) shared keys.
    1.0 = same speed; 1.4 = everything uniformly 40% slower.

    With fewer than ``_MIN_NORMALIZE_KEYS`` samples the median IS the keys
    under test (a regression would normalize itself away), so we fall
    back to raw comparison (factor 1.0)."""
    ratios = []
    for key in shared:
        if ".audit." in key or baseline[key] <= 0 or results[key] <= 0:
            continue
        r = results[key] / baseline[key]
        ratios.append(1.0 / r if _is_throughput(key) else r)
    if len(ratios) < _MIN_NORMALIZE_KEYS:
        return 1.0
    return statistics.median(ratios)


def compare_results(results: dict, baseline: dict,
                    threshold: float = REGRESSION_THRESHOLD,
                    normalize: bool = False) -> list[str]:
    """Regressions of ``results`` vs ``baseline`` over their shared keys.

    Throughput-style keys (``*pps``) regress by dropping; cost-style keys
    (us/bytes/counts) regress by growing.  A zero-cost baseline (e.g. the
    structural ``expect=0`` audits) regresses on ANY nonzero value.

    ``normalize=True`` divides out the median machine-speed factor before
    applying the threshold, so a uniformly slower machine (a different CI
    runner class) does not flag every key — only keys that regress
    *relative to the rest of the suite* do.  Structural ``.audit.`` keys
    are never normalized.  The trade-off: a change that slows every path
    by the same factor is invisible under normalization; with fewer than
    ``_MIN_NORMALIZE_KEYS`` shared timing keys normalization disables
    itself and the comparison is raw.
    """
    shared = sorted(set(results) & set(baseline))
    speed = _speed_factor(results, baseline, shared) if normalize else 1.0
    regressions = []
    for key in shared:
        base, new = baseline[key], results[key]
        adj = speed if (normalize and ".audit." not in key) else 1.0
        if _is_throughput(key):
            if base > 0 and new * adj < base * (1 - threshold):
                regressions.append(
                    f"{key}: {new:.4g} < {base:.4g} "
                    f"(-{(1 - new * adj / base) * 100:.0f}% at speed "
                    f"factor {speed:.2f})")
        elif base == 0:
            if new > 0:
                regressions.append(f"{key}: {new:.4g} > 0 (baseline 0)")
        elif new / adj > base * (1 + threshold):
            regressions.append(
                f"{key}: {new:.4g} > {base:.4g} "
                f"(+{(new / adj / base - 1) * 100:.0f}% at speed "
                f"factor {speed:.2f})")
    return regressions


def write_step_summary(path: str, results: dict, baseline: dict,
                       regressions: list[str], *, label: str,
                       normalize: bool) -> None:
    """Append a per-key comparison table (GitHub-flavored markdown) to
    ``path`` — the ``$GITHUB_STEP_SUMMARY`` report CI publishes."""
    shared = sorted(set(results) & set(baseline))
    speed = _speed_factor(results, baseline, shared) if normalize else 1.0
    flagged = {r.split(":", 1)[0] for r in regressions}
    lines = [
        f"### Benchmark comparison vs `{label}`",
        "",
        f"{len(shared)} shared keys, speed factor {speed:.2f}, "
        f"{len(regressions)} regression(s)",
        "",
        "| key | baseline | current | Δ | |",
        "|---|---:|---:|---:|---|",
    ]
    for key in shared:
        base, new = baseline[key], results[key]
        if base > 0:
            delta = (new / base - 1.0) * 100.0
            delta_s = f"{delta:+.0f}%"
        else:
            delta_s = "=" if new == base else f"{new:.4g} vs 0"
        good = _is_throughput(key)
        mark = ("🔴" if key in flagged else
                ("⚪" if ".audit." in key else
                 ("🟢" if (base > 0 and ((new > base) == good or new == base))
                  else "—")))
        lines.append(f"| `{key}` | {base:.4g} | {new:.4g} "
                     f"| {delta_s} | {mark} |")
    lines.append("")
    with open(path, "a") as f:
        f.write("\n".join(lines) + "\n")


def main(argv=None) -> None:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write results as a name -> us_per_call JSON "
                         "map (convention: BENCH_<pr>.json)")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names to run (default: all)")
    ap.add_argument("--compare", metavar="BASELINE", default=None,
                    help="baseline JSON (e.g. BENCH_1.json); exit nonzero on "
                         f">{REGRESSION_THRESHOLD:.0%}".replace("%", "%%")
                         + " regression of any shared key")
    ap.add_argument("--compare-normalize", action="store_true",
                    help="divide out the median machine-speed factor before "
                         "thresholding (for baselines recorded on different "
                         "hardware, e.g. CI runners)")
    ap.add_argument("--summary", metavar="PATH",
                    default=os.environ.get("GITHUB_STEP_SUMMARY"),
                    help="append a markdown per-key comparison table here "
                         "(default: $GITHUB_STEP_SUMMARY when set)")
    args = ap.parse_args(argv)

    from benchmarks import (fig4_runtime, fig5_scaling, fig6_slot_behavior,
                            fig7_fused, fig8_dataplane, fig9_control,
                            fig10_mesh, fig11_workloads, fig12_faults,
                            fig13_obs, fig14_deploy, fig15_swap,
                            roofline, table4_continuity,
                            table5_controlplane)

    benches = [
        ("fig4", fig4_runtime.main),
        ("fig5", fig5_scaling.main),
        ("fig6", fig6_slot_behavior.main),
        ("fig7", fig7_fused.main),
        ("fig8", fig8_dataplane.main),
        ("fig9", fig9_control.main),
        ("fig10", fig10_mesh.main),
        ("fig11", fig11_workloads.main),
        ("fig12", fig12_faults.main),
        ("fig13", fig13_obs.main),
        ("fig14", fig14_deploy.main),
        ("fig15", fig15_swap.main),
        ("table4", table4_continuity.main),
        ("table5", table5_controlplane.main),
        ("roofline", roofline.main),
    ]
    if args.only:
        wanted = set(args.only.split(","))
        unknown = wanted - {n for n, _ in benches}
        if unknown:
            ap.error(f"unknown bench name(s): {sorted(unknown)} "
                     f"(known: {[n for n, _ in benches]})")
        benches = [(n, f) for n, f in benches if n in wanted]

    print("name,us_per_call,derived")
    results: dict = {}
    failures = 0
    for name, fn in benches:
        buf = io.StringIO()
        try:
            with contextlib.redirect_stdout(buf):
                fn()
        except Exception as e:  # keep the suite running
            failures += 1
            buf.write(f"{name}.ERROR,0,{type(e).__name__}: {e}\n")
            traceback.print_exc(file=sys.stderr)
        text = buf.getvalue()
        sys.stdout.write(text)
        sys.stdout.flush()
        results.update(_parse_rows(text))

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {len(results)} entries to {args.json}", file=sys.stderr)
    if args.compare:
        with open(args.compare) as f:
            baseline = json.load(f)
        regressions = compare_results(results, baseline,
                                      normalize=args.compare_normalize)
        shared = len(set(results) & set(baseline))
        print(f"# compared {shared} shared keys vs {args.compare}: "
              f"{len(regressions)} regression(s)", file=sys.stderr)
        for r in regressions:
            print(f"# REGRESSION {r}", file=sys.stderr)
        if args.summary:
            write_step_summary(args.summary, results, baseline, regressions,
                               label=args.compare,
                               normalize=args.compare_normalize)
        if regressions:
            sys.exit(2)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
