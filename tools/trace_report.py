#!/usr/bin/env python
"""Trace report — walk an exported ``TRACE_*.json`` (Chrome-trace JSON,
written by ``Tracer.export``) and print, per task, the workflow's
critical path with the dominant latency segment at every hop, and for a
live engine's step spans (category ``step``) the mean time of each
phase.  Step spans never enter the request tiling or the critical path.

Works from the exported file alone (stdlib only, no repro import): the
span tree is rebuilt from the ``args.span_id``/``args.parent_id`` the
exporter embeds in every complete event.

    PYTHONPATH=src python tools/trace_report.py artifacts/bench/TRACE_fig1.json
    python tools/trace_report.py --validate TRACE_*.json   # schema check

``--validate`` exits non-zero when a file is not loadable Chrome-trace
JSON (the CI schema gate).
"""
from __future__ import annotations

import argparse
import json
import sys
from dataclasses import dataclass, field
from typing import Optional

_EPS = 1e-9


@dataclass
class SpanView:
    """One span rebuilt from an exported complete ('X') event."""

    span_id: int
    name: str
    cat: str
    trace_id: str
    t0: float                      # seconds (events carry microseconds)
    t1: float
    parent_id: Optional[int] = None
    args: dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


# ---------------------------------------------------------------------------
# loading + schema validation
# ---------------------------------------------------------------------------

def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


_PHASES = {"X", "B", "E", "i", "I", "M", "s", "f", "t", "C", "b", "e", "n"}


def validate(doc) -> list[str]:
    """Chrome-trace JSON shape errors ([] = valid).  Accepts the two
    legal top-level forms (object with ``traceEvents``, or a bare event
    array) and checks the fields every consumer (chrome://tracing,
    Perfetto) requires per event."""
    errors: list[str] = []
    if isinstance(doc, dict):
        events = doc.get("traceEvents")
        if not isinstance(events, list):
            return ["top-level object has no 'traceEvents' array"]
    elif isinstance(doc, list):
        events = doc
    else:
        return ["document is neither an object nor an event array"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errors.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in _PHASES:
            errors.append(f"event {i}: bad phase {ph!r}")
        if not isinstance(ev.get("ts"), (int, float)):
            errors.append(f"event {i}: missing numeric 'ts'")
        if "pid" not in ev:
            errors.append(f"event {i}: missing 'pid'")
        if ph == "X" and not isinstance(ev.get("dur"), (int, float)):
            errors.append(f"event {i}: complete event missing 'dur'")
        if ph in ("s", "f") and "id" not in ev:
            errors.append(f"event {i}: flow event missing 'id'")
        if len(errors) >= 20:
            errors.append("... (truncated)")
            break
    return errors


def spans_from(doc) -> list[SpanView]:
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    out = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        a = ev.get("args") or {}
        if "span_id" not in a:
            continue
        t0 = ev["ts"] / 1e6
        out.append(SpanView(int(a["span_id"]), ev.get("name", ""),
                            ev.get("cat", ""), str(a.get("trace_id", "")),
                            t0, t0 + ev.get("dur", 0) / 1e6,
                            a.get("parent_id"), a))
    out.sort(key=lambda s: (s.t0, s.span_id))
    return out


def flow_links(doc) -> int:
    """Count of causal action→span links (flow-start events)."""
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    return sum(1 for ev in events if ev.get("ph") == "s")


# ---------------------------------------------------------------------------
# critical path
# ---------------------------------------------------------------------------

def _children(spans: list[SpanView]) -> dict[Optional[int], list[SpanView]]:
    idx: dict[Optional[int], list[SpanView]] = {}
    for s in spans:
        idx.setdefault(s.parent_id, []).append(s)
    return idx


def critical_path(spans: list[SpanView],
                  trace_id: str) -> list[SpanView]:
    """The chain of stage/request spans that determined the task's end:
    start from the hop finishing last, repeatedly step to the
    predecessor hop that finished latest no later than the current
    hop's start (the edge it actually waited on), prepend until the
    chain bottoms out at the task's first hop."""
    hops = [s for s in spans
            if s.trace_id == trace_id and s.cat in ("stage", "request")]
    if not hops:
        return []
    # workflow traces path over stages; flat fig1 traces over requests
    if any(s.cat == "stage" for s in hops):
        hops = [s for s in hops if s.cat == "stage"]
    path = [max(hops, key=lambda s: s.t1)]
    while True:
        cur = path[0]
        preds = [s for s in hops
                 if s is not cur and s.t1 <= cur.t0 + _EPS
                 and s not in path]
        if not preds:
            return path
        path.insert(0, max(preds, key=lambda s: s.t1))


def _descendant_segments(span: SpanView,
                         children: dict) -> dict[str, float]:
    """Summed cat=='segment' durations under a path hop (requests under
    a stage contribute theirs)."""
    segs: dict[str, float] = {}
    stack = [span]
    while stack:
        node = stack.pop()
        for c in children.get(node.span_id, ()):
            if c.cat == "segment":
                segs[c.name] = segs.get(c.name, 0.0) + c.dur
            else:
                stack.append(c)
    return segs


def dominant_segment(span: SpanView,
                     children: dict) -> tuple[str, float, float]:
    """(name, seconds, fraction-of-hop) of the hop's largest segment;
    ('-', 0, 0) when the hop recorded none."""
    segs = _descendant_segments(span, children)
    if not segs:
        return ("-", 0.0, 0.0)
    name = max(segs, key=lambda k: segs[k])
    return (name, segs[name], segs[name] / max(span.dur, _EPS))


def decomposition_check(spans: list[SpanView]) -> list[tuple]:
    """Per closed request span: (req span, segment sum, request dur).
    The acceptance criterion is |sum - dur| within 1% of dur."""
    children = _children(spans)
    out = []
    for s in spans:
        if s.cat != "request" or s.args.get("open"):
            continue
        total = sum(c.dur for c in children.get(s.span_id, ())
                    if c.cat == "segment")
        # pre-engine throttle spans are parented under the root too
        out.append((s, total, s.dur))
    return out


def step_phases(spans: list[SpanView]) -> dict[str, dict[str, tuple]]:
    """Per engine, per step-phase name: (count, mean seconds) of its
    ``step`` spans (``engine.step`` and the phases that tile it)."""
    acc: dict[str, dict[str, list]] = {}
    for s in spans:
        if s.cat != "step":
            continue
        eng = str(s.args.get("engine", s.trace_id))
        n_t = acc.setdefault(eng, {}).setdefault(s.name, [0, 0.0])
        n_t[0] += 1
        n_t[1] += s.dur
    return {e: {k: (n, t / n) for k, (n, t) in ph.items()}
            for e, ph in acc.items()}


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

def report(doc, limit: int = 8) -> str:
    spans = spans_from(doc)
    children = _children(spans)
    tasks = [s for s in spans if s.cat == "task"]
    if not tasks:
        # flat traces (pool benches) have parentless request roots
        tasks = [s for s in spans
                 if s.cat == "request" and s.parent_id is None]
    lines = [f"{len(spans)} spans, {len(tasks)} traced tasks, "
             f"{flow_links(doc)} causal action links"]
    for task in sorted(tasks, key=lambda s: -s.dur)[:limit]:
        lines.append("")
        lines.append(f"{task.name}  [{task.t0:.3f}s → {task.t1:.3f}s]  "
                     f"e2e {task.dur * 1e3:.1f} ms")
        path = critical_path(spans, task.trace_id)
        if path and path[0] is not task:
            lines.append("  critical path:")
            for hop in path:
                seg, sec, frac = dominant_segment(hop, children)
                mark = (f"dominant: {seg} {sec * 1e3:.1f} ms "
                        f"({frac:.0%})" if seg != "-" else "no segments")
                lines.append(f"    {hop.name:<28s} "
                             f"[{hop.t0:.3f}, {hop.t1:.3f}]  "
                             f"{hop.dur * 1e3:7.1f} ms   {mark}")
        acts = task.args.get("actions") or []
        for a in acts:
            lines.append(f"    ! control: {a}")
    for eng, phases in sorted(step_phases(spans).items()):
        lines.append("")
        lines.append(f"{eng}: {phases.get('engine.step', (0,))[0]} steps; "
                     "mean ms " + ", ".join(
                         f"{k} {t * 1e3:.3f}" for k, (_, t) in
                         sorted(phases.items())))
    checks = decomposition_check(spans)
    if checks:
        worst = max(abs(tot - dur) / max(dur, _EPS)
                    for _, tot, dur in checks)
        lines.append("")
        lines.append(f"{len(checks)} closed requests; worst "
                     f"segment-sum/e2e mismatch {worst:.2%}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="+", help="TRACE_*.json files")
    ap.add_argument("--validate", action="store_true",
                    help="schema-check only (CI gate); non-zero on error")
    ap.add_argument("--limit", type=int, default=8,
                    help="max tasks to print per trace")
    args = ap.parse_args(argv)
    bad = 0
    for path in args.paths:
        try:
            doc = load(path)
        except (OSError, json.JSONDecodeError) as e:
            print(f"{path}: UNREADABLE: {e}")
            bad += 1
            continue
        errors = validate(doc)
        if errors:
            print(f"{path}: INVALID")
            for e in errors:
                print(f"  {e}")
            bad += 1
            continue
        if args.validate:
            n = len(doc["traceEvents"] if isinstance(doc, dict) else doc)
            print(f"{path}: ok ({n} events)")
            continue
        print(f"== {path}")
        print(report(doc, limit=args.limit))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
