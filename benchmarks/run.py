# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV.  ``--full`` uses the paper's 20-minute workload intervals (slow);
# default uses 5-minute intervals (same rates, same dynamics).
import argparse
import sys
import traceback


MODULES = [
    "tab1_latency_breakdown",
    "tab2_ablation",
    "fig7_dynamic_workload",
    "fig8_percentiles",
    "fig9_policy_trace",
    "fig10_topk_sweep",
    "fig11_ondisk_index",
]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-length workload intervals")
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Perfetto/chrome://tracing JSON of the "
                         "benchmarked engines' span timelines")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a JSON snapshot of the central metrics "
                         "registry (counters/gauges/histograms/events)")
    args = ap.parse_args()

    from benchmarks import common
    from benchmarks.common import emit
    if args.trace_out or args.metrics_out:
        from repro.obs import MetricsRegistry, Tracer
        common.set_obs(
            tracer=Tracer() if args.trace_out else None,
            registry=MetricsRegistry() if args.metrics_out else None)
    mods = MODULES if not args.only else args.only.split(",")
    failures = 0
    print("name,us_per_call,derived")
    for name in mods:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            rows = mod.run(full=args.full)
            emit(rows)
        except Exception:
            failures += 1
            print(f"{name},0.0,ERROR", file=sys.stderr)
            traceback.print_exc()
    if args.trace_out:
        n = common.TRACER.export(args.trace_out)
        print(f"# trace: {n} events -> {args.trace_out}", file=sys.stderr)
    if args.metrics_out:
        common.REGISTRY.export(args.metrics_out)
        print(f"# metrics -> {args.metrics_out}", file=sys.stderr)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
