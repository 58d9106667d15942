"""Benchmark orchestrator — one section per paper figure/table plus the
kernel microbench.

    PYTHONPATH=src python -m benchmarks.run [--only fig3,fig6,...]
                                            [--out-dir artifacts/bench]
                                            [--smoke]

``--smoke`` shrinks the sweeps (sections that support it) so CI can run
a fast end-to-end pass and still upload real BENCH_*.json artifacts.

Each section's table is also written as ``BENCH_<section>.json`` (plus a
combined ``BENCH_summary.json``) so the perf trajectory can be tracked
across PRs by diffing machine-readable artifacts instead of log text.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from repro.launch.compile_cache import use_compile_cache


def _emit(out_dir: Path, name: str, payload: dict) -> None:
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"BENCH_{name}.json"
    path.write_text(json.dumps(payload, indent=2, default=str) + "\n")
    print(f"# wrote {path}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help="comma list: fig3,fig6,fig7,prefix,workflow,"
                         "toolcalls,disagg,tenancy,trace,kernels,paged,"
                         "mixed,calibrate")
    ap.add_argument("--out-dir", default="artifacts/bench",
                    help="directory for BENCH_*.json summaries")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sweeps for CI smoke runs")
    args = ap.parse_args()
    print(f"# compile cache: {use_compile_cache()}", flush=True)
    want = None if args.only == "all" else set(args.only.split(","))
    out_dir = Path(args.out_dir)

    summary: dict[str, dict] = {}
    names = [n for n in ("fig3", "fig6", "fig7", "prefix", "workflow",
                         "toolcalls", "disagg", "tenancy", "trace",
                         "kernels", "paged", "mixed", "calibrate")
             if want is None or n in want]
    for name in names:
        t0 = time.time()
        print(f"\n######## {name} ########", flush=True)
        report = None
        if name == "fig3":
            from benchmarks import bench_fig3
            report = bench_fig3.main(smoke=args.smoke)
        elif name == "fig6":
            from benchmarks import bench_fig6
            report = bench_fig6.main()
        elif name == "fig7":
            from benchmarks import bench_fig7
            report = bench_fig7.main()
        elif name == "prefix":
            from benchmarks import bench_prefix
            report = bench_prefix.main(smoke=args.smoke)
        elif name == "workflow":
            from benchmarks import bench_workflow
            report = bench_workflow.main(smoke=args.smoke)
        elif name == "toolcalls":
            from benchmarks import bench_toolcalls
            report = bench_toolcalls.main(smoke=args.smoke)
        elif name == "disagg":
            from benchmarks import bench_disagg
            report = bench_disagg.main(smoke=args.smoke)
        elif name == "tenancy":
            from benchmarks import bench_tenancy
            report = bench_tenancy.main(smoke=args.smoke)
        elif name == "trace":
            from benchmarks import bench_trace
            report = bench_trace.main(smoke=args.smoke,
                                      out_dir=str(out_dir))
        elif name == "kernels":
            from benchmarks import bench_kernels
            report = bench_kernels.main()
        elif name == "paged":
            from benchmarks import bench_paged_engine
            report = bench_paged_engine.main(smoke=args.smoke)
        elif name == "mixed":
            from benchmarks import bench_mixed
            report = bench_mixed.main(smoke=args.smoke)
        elif name == "calibrate":
            from benchmarks import calibrate
            report = calibrate.main(smoke=args.smoke,
                                    out_dir=str(out_dir))
        elapsed = time.time() - t0
        if report is not None:
            print(report.render())
            payload = {**report.to_dict(), "elapsed_s": round(elapsed, 2)}
            summary[name] = payload
            _emit(out_dir, name, payload)
        print(f"# section {name} took {elapsed:.1f}s", flush=True)
    if summary:
        _emit(out_dir, "summary", summary)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
