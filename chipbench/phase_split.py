#!/usr/bin/env python3
"""One traced run of a cell with the engine's step phases recorded.

    python3 chipbench/phase_split.py --workload <cell> --seed <n> \\
        --seconds <s> [--tracer 0|1]

From the root of a checkout, on a TPU.  The run is ``run.py``'s
``--trace 1`` run of the cell, with an enabled ``Tracer`` attached to
the engine at the start of the window (``--tracer 0``: none, the
benchmark's traced run as it is).  Its last line is ``run.py``'s result
with a ``phases`` object added: the device's idle time by engine phase
(``phases.reduce``), the readers ``PHASE_METRICS`` on the window's
spans, how much of the steps' time their phases cover, and how far the
tracer's spans, carried through its anchor, lie from their copies in the
profile.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "chipbench"]

PHASE_METRICS = ("engine_host_idle_share", "sync_idle_share",
                 "decode_host_ms", "mixed_host_ms", "queue_wait_span_ms_p90",
                 "prefill_span_ms_p90")


def instrument(with_tracer: bool) -> dict:
    """Wrap the harness so that its traced run attaches a tracer to the
    engine as the window starts and reduces the profile by engine phase
    too.  Returns what the run leaves behind: the tracer, the Run."""
    from chipbench import harness, phases, xplane
    from repro.core.trace import Tracer
    seen: dict = {}
    drive, reduce = harness.drive, xplane.reduce

    def traced_drive(eng, *args, **kw):
        if with_tracer:
            tr = Tracer(eng.now, name=f"{eng.name}.tracer")
            tr.set_scope(None, 1.0)
            seen["tracer"] = eng.attach_tracer(tr)
        return drive(eng, *args, **kw)

    def phase_reduce(pd, kernels=(), labels=xplane.harness_span):
        red = reduce(pd, kernels, labels)
        red.update(phases.reduce(pd, labels))
        tr = seen.get("tracer")
        if tr is not None:
            seen["offsets_ns"] = phases.clock_offsets(
                pd, tr.all_spans(), tr.origin_unix_s)
        return red

    @dataclass
    class Run(harness.Run):
        def __post_init__(self):
            seen["run"] = self

    harness.drive, xplane.reduce, harness.Run = (traced_drive, phase_reduce,
                                                 Run)
    return seen


def summary(seen: dict) -> dict:
    from chipbench import phases, spec
    run, tr = seen["run"], seen.get("tracer")
    spans = tr.all_spans() if tr is not None else None
    run.spans = spans
    trace = run.trace or {}
    out = {"idle_by_phase": trace.get("idle_by_phase"),
           "phase_gaps": trace.get("phase_gaps"),
           "metrics": {m: spec.reader("layer_metrics", m).read(run)
                       for m in PHASE_METRICS}}
    if spans:
        steps = [s for s in spans if s.name == phases.ROOT]
        mean = {}
        for s in spans:
            if s.cat == "step":
                mean.setdefault(s.name, []).append(s.dur)
        out.update(
            steps=len(steps),
            traced_steps=sum(bool(s.attrs.get("traced")) for s in steps),
            coverage=phases.step_coverage(spans),
            phase_mean_ms={k: 1e3 * sum(v) / len(steps)
                           for k, v in mean.items()},
            origin_unix_s=tr.origin_unix_s)
        offs = [abs(o) for o in seen.get("offsets_ns", [])]
        if offs:
            out["clock_offset_ns"] = {
                "median": statistics.median(offs), "max": max(offs),
                "n": len(offs)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tracer", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    import jax
    from chipbench import harness, spec
    if jax.default_backend() != "tpu":
        print("phase_split: needs a TPU", file=sys.stderr)
        return 3
    seen = instrument(bool(args.tracer))
    out, compared = harness.run(spec.cell(args.workload), args.seed,
                                args.seconds, True, False, T_PROCESS)
    out["compared"] = compared
    out["phases"] = summary(seen)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
