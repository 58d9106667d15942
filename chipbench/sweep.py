#!/usr/bin/env python3
"""Knee sweep of an open-loop cell; not a benchmark run.

    python3 chipbench/sweep.py --workload <cell> --rates 1,2,3 \\
        --seconds <s> [--seed n] [--orders 0,1] [--rehearse]

In one process: weights once, then for each order of the mix's sizes
(``order_seed``; the mix's own by default) and each rate a fresh engine
(the compiled steps load from the cache), the set-up prefill, and one
window of the cell's mix at that rate.  One JSON line per window:
requests due and finished, the backlog (due, not finished) at the
window's middle and end, the turns drawn in the window but still held
by their session at its end (closed-loop sessions only), and the cell's
end-to-end metrics.  The knee is the highest rate whose backlog does not
grow; PERF.md records the sweep and the rate the cell's mix fixes.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--orders", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
    import gc

    import jax
    from chipbench import generator, harness, spec, weights
    from repro.launch.compile_cache import use_compile_cache
    if not args.rehearse and jax.default_backend() != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    use_compile_cache()
    cell = spec.cell(args.workload)
    model, engine = spec.sizes(cell.config, args.rehearse)
    m_cfg = spec.model_config(cell.config, args.rehearse)
    mix = cell.mix
    if args.rehearse:
        mix = generator.scaled(mix, engine["max_context"]
                               / cell.config["engine"]["max_context"])
    params = weights.to_program(weights.make(model, args.seed), m_cfg)
    orders = ([int(o) for o in args.orders.split(",")] if args.orders
              else [mix["order_seed"]])
    for order, rate in [(o, float(r)) for o in orders
                        for r in args.rates.split(",")]:
        sc = spec.scheduler_config(cell.config, args.rehearse)
        eng, cache = harness._engine(m_cfg, sc, params, args.seed)
        harness.warm_up(eng, cache, model["vocab"], sc, args.seed)
        m = dict(mix, order_seed=order,
                 arrival=dict(mix["arrival"], rate=rate))
        traffic = generator.generate(m, model["vocab"],
                                     engine["max_context"], args.seconds,
                                     args.seed)
        harness.prefill_setup(eng, traffic.setup)
        t0 = time.perf_counter()
        tracks, steps, window_s, late, hits, misses = harness.drive(
            eng, cache, traffic, args.seconds, None)
        run = harness.Run(cell, model, engine, args.seconds, window_s,
                          0.0, tracks, steps, hits, misses, late)

        def backlog(t):
            due = sum(1 for x in tracks if x.due <= t)
            done = sum(1 for x in tracks if x.finished_at is not None
                       and x.finished_at <= t)
            return due - done

        out = {"order_seed": order, "rate": rate, "due": len(tracks),
               "finished": sum(1 for x in tracks
                               if x.finished_at is not None),
               "backlog_mid": backlog(window_s / 2),
               "backlog_end": backlog(window_s),
               "held_end": len(traffic.requests) - len(tracks),
               "late_max_ms": 1e3 * max(late) if late else 0.0,
               "wall_s": time.perf_counter() - t0}
        for mtr in cell.end_to_end:
            if mtr["name"] != "setup_s":
                out[mtr["name"]] = spec.reader("end_to_end",
                                               mtr["name"]).read(run)
        out["queue_wait_ms_p90"] = spec.reader(
            "layer_metrics", "queue_wait_ms_p90").read(run)
        print(json.dumps(out), flush=True)
        eng.cache = None
        del eng, cache
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
