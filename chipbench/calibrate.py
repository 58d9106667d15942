#!/usr/bin/env python3
"""Readings that a limit of ``correct`` is set from; not a benchmark run.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s> [--rehearse]

For each seed, in one process: one run of the cell as ``run.py`` makes
it (same set-up, same window at the cell's own load), then the sampled
requests through the reference twice, once as the benchmark reads them
(the program's widest logit gap) and once computed in fp8, the control
(the widest gap of the tokens the fp8 logits put first), each judged by
the benchmark's own comparison (``correct``, ``control_correct``).  One
JSON line per seed.  PERF.md records the readings and the limit set
from them.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "chipbench"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
    from chipbench import harness, spec
    import jax
    if not args.rehearse and jax.default_backend() != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    cell = spec.cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        out, compared = harness.run(cell, seed, args.seconds, False,
                                    args.rehearse, time.perf_counter(),
                                    control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "control_correct": out["control_correct"],
                          "reading": out["reading"],
                          "metrics": out.get("metrics")}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
