#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

From the root of a checkout.  The cell is an entry of ``BENCHMARK.json``'s
``workloads``.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, from a profiler trace of the
window's last seconds and the harness's own records.  The last line of
standard output is one JSON object; the numbers compared to decide
``correct`` are also the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.  ``--rehearse`` runs the configuration's tiny
rehearsal sizes on the CPU instead: its line says so, names the CPU and
carries counts, never a device metric.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the script's own directory would shadow the standard library by module
# name; the checkout root (for ``chipbench``) and ``src`` go first instead
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "chipbench"]

NO_CHIP = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; reports no device metric")
    args = ap.parse_args(argv)

    if args.rehearse:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
    from chipbench import harness, spec
    cell = spec.cell(args.workload)
    import jax
    backend = jax.default_backend()
    if not args.rehearse and (backend != "tpu"
                              or len(jax.devices()) < cell.chips):
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(jax.devices())} {backend!r} device(s)",
              file=sys.stderr)
        return NO_CHIP
    out, compared = harness.run(cell, args.seed, args.seconds,
                                bool(args.trace), args.rehearse, T_PROCESS)
    out["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name}: {c['value']} limit {c['limit']} "
              f"({c['must_be']})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
