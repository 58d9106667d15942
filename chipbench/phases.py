"""Device idle time inside ``Engine.step()``, split by the engine's phases.

With an enabled ``repro.core.trace.Tracer`` attached, every
``Engine.step()`` records an ``engine.step`` span whose children tile it
(``PHASES``), each also a host event of the profile.  ``reduce`` labels
each idle gap of the device by the phase covering most of it, beside the
harness's own label (``step.decode/engine.plan``); a gap under no phase
keeps the label ``xplane.reduce`` gives it.  ``clock_offsets`` compares
the tracer's spans, through its anchor, with their copies in the
profile.  Nothing here changes what ``xplane.reduce`` returns.
"""
from __future__ import annotations

from collections import defaultdict

from chipbench import xplane

PHASES = ("engine.plan", "engine.inputs", "engine.dispatch", "engine.sync",
          "engine.apply")
# the host's own work, during which the device waits on the engine
HOST_PHASES = ("engine.plan", "engine.inputs", "engine.dispatch",
               "engine.apply")
ROOT = "engine.step"


def _idle(plane, w0: int, w1: int) -> list[tuple[int, int]]:
    """Stretches of [w0, w1) with no operation on ``plane``, counted as
    ``xplane.reduce`` counts them."""
    ivs = []
    for line in plane.lines:
        if line.name != xplane.OPS_LINE:
            continue
        for e in line.events:
            s, t = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
            if t > s and xplane._base(e.name).lower() not in xplane.CONTAINERS:
                ivs.append((s, t))
    gaps, prev = [], w0
    for s, e in xplane._union(ivs) + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return gaps


def reduce(pd, labels=xplane.harness_span) -> dict:
    """``idle_by_phase``: device idle seconds under each of ``PHASES``
    (mean over device planes); ``phase_gaps``: the top 10 labels by idle
    time.  A gap that lies mostly inside a harness ``step#<i>`` goes to
    the phase covering most of it, labelled ``<harness label>/<phase>``;
    every other gap keeps its harness label."""
    spans = xplane._host_spans(pd)
    win = [(s, e) for n, s, e in spans if n == xplane.WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {xplane.WINDOW_SPAN!r} span in the trace")
    w0, w1 = win[0]
    inside = [(n, s, e) for n, s, e in spans if s < w1 and e > w0]
    harness = [(n, s, e) for n, s, e in inside
               if n != xplane.WINDOW_SPAN and labels(n) is not None]
    engine = [(n, s, e) for n, s, e in inside if n in PHASES]
    planes = xplane._device_planes(pd)
    by_phase, by_label = defaultdict(float), defaultdict(float)
    for p in planes:
        gaps = _idle(p, w0, w1)
        for (s, e), h, ph in zip(gaps, xplane._labels(harness, gaps),
                                 xplane._labels(engine, gaps)):
            share = (e - s) / len(planes) * 1e-9
            # only a gap that lies mostly inside a step is the step's
            in_step = h.startswith("step#")
            h = labels(h) or h
            if in_step and ph in PHASES:
                by_phase[ph] += share
                h = f"{h}/{ph}"
            by_label[h] += share
    return {"idle_by_phase": {k: by_phase[k] for k in PHASES},
            "phase_gaps": [[k, v] for k, v in sorted(
                by_label.items(), key=lambda kv: -kv[1])[:10]]}


def profile_start_ns(pd) -> int | None:
    """The profile's ``profile_start_time``: CLOCK_REALTIME nanoseconds
    at which its host events' clock reads 0."""
    env = pd.find_plane_with_name("Task Environment")
    stats = dict(env.stats) if env is not None else {}
    return stats.get("profile_start_time")


def clock_offsets(pd, spans, origin_unix_s: float) -> list[float]:
    """For each ``engine.step`` host event in the profile, its start less
    the start of the tracer's nearest ``engine.step`` span carried
    through the tracer's anchor, in nanoseconds."""
    start = profile_start_ns(pd)
    if start is None or origin_unix_s is None:
        return []
    ours = sorted((s.t0 + origin_unix_s) * 1e9 - start for s in spans
                  if s.name == ROOT)
    if not ours:
        return []
    out = []
    for n, s, _ in xplane._host_spans(pd):
        if n == ROOT:
            out.append(s - min(ours, key=lambda t: abs(t - s)))
    return out


def step_coverage(spans) -> float | None:
    """The share of the ``engine.step`` spans' summed time that their
    child phases cover."""
    roots = {s.span_id: s for s in spans if s.name == ROOT and s.closed}
    kids = sum(s.dur for s in spans if s.parent_id in roots and s.closed)
    total = sum(s.dur for s in roots.values())
    return kids / total if total > 0 else None


def host_ms(spans, kind: str) -> float | None:
    """Mean over the ``engine.step`` spans of ``kind`` of the step less
    its ``engine.sync`` children, in milliseconds: the host's part."""
    roots = {s.span_id: s for s in spans if s.name == ROOT and s.closed
             and s.attrs.get("kind") == kind}
    if not roots:
        return None
    sync = sum(s.dur for s in spans
               if s.parent_id in roots and s.name == "engine.sync")
    return 1e3 * (sum(s.dur for s in roots.values()) - sync) / len(roots)


def segment_ms(spans, name: str) -> list[float]:
    """Durations of the request segments ``name`` in milliseconds; one
    still open counts to the latest time any span reached."""
    if not spans:
        return []
    end = max(s.t1 if s.closed else s.t0 for s in spans)
    return [1e3 * ((s.t1 if s.closed else end) - s.t0) for s in spans
            if s.cat == "segment" and s.name == name]
