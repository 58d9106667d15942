#!/usr/bin/env python3
"""Record the small profiler trace with engine step phases that
``test_phases.py`` reads.

    python3 chipbench/tests/record_phase_trace.py [out_dir]     # on a TPU

``record_trace.py``'s run (about a second at qwen2-vl-2b's rehearsal
sizes, traced from its first step), with an enabled ``Tracer`` attached
to the engine as the window starts.  Besides that script's two files it
writes ``spans.json``: the tracer's spans and its anchor.
"""
import dataclasses
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE)]
import record_trace  # noqa: E402  (sets the import path to the checkout)

DATA = HERE / "data" / "phases"


def main(argv=None) -> int:
    out_dir = Path((argv or sys.argv[1:] or [str(DATA)])[0])
    from chipbench import phase_split
    seen = phase_split.instrument(True)
    rc = record_trace.main([str(out_dir)])
    tr = seen.get("tracer")
    if rc or tr is None:
        return rc or 1
    spans = [dataclasses.asdict(s) for s in tr.all_spans()]
    (out_dir / "spans.json").write_text(json.dumps(
        {"origin_unix_s": tr.origin_unix_s, "spans": spans}, indent=0) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
