#!/usr/bin/env python3
"""Record the small profiler trace that ``test_xplane.py`` reads.

    python3 chipbench/tests/record_trace.py [out_dir]     # on a TPU

Runs the harness's window for about a second on one chip at
qwen2-vl-2b's rehearsal sizes (4 layers), traced from its first step,
and writes the trace (gzipped) with the facts the test checks it
against: how many steps of each kind ran inside the traced span and how
many layers each runs the paged decode kernel in.
"""
import gzip
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "chipbench"]
DATA = Path(__file__).resolve().parent / "data"


def main(argv=None) -> int:
    out_dir = Path((argv or sys.argv[1:] or [str(DATA)])[0])
    import jax
    from chipbench import generator, harness, spec, weights, xplane
    if jax.default_backend() != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    harness.TRACE_SECONDS = 10.0          # trace the whole short window
    cell = spec.rehearsal_cell("qwen2-vl-2b", "short_chat")
    model, engine = spec.sizes(cell.config, True)
    m_cfg = spec.model_config(cell.config, True)
    sc = spec.scheduler_config(cell.config, True)
    mix = generator.scaled(cell.mix, engine["max_context"]
                           / cell.config["engine"]["max_context"])
    mix["arrival"] = dict(mix["arrival"], rate=20.0)
    traffic = generator.generate(mix, model["vocab"], engine["max_context"],
                                 1.0, 7)
    params = weights.to_program(weights.make(model, 7), m_cfg)
    eng, cache = harness._engine(m_cfg, sc, params, 7)
    harness.warm_up(eng, cache, model["vocab"], sc, 7)
    out = Path(tempfile.mkdtemp(prefix="record_trace_"))
    tracks, steps, *_ = harness.drive(eng, cache, traffic, 1.0, str(out))
    path = xplane.find(out)
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "window.xplane.pb.gz").write_bytes(
        gzip.compress(path.read_bytes()))
    facts = {"steps": [s.kind for s in steps if s.traced],
             "n_layers": model["n_layers"],
             "decode_rows": [len(s.decode_ctx) for s in steps if s.traced]}
    (out_dir / "window.json").write_text(json.dumps(facts, indent=1) + "\n")
    print(json.dumps(xplane.reduce(
        xplane.load(out_dir / "window.xplane.pb.gz"),
        kernels=(harness.KERNEL,))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
