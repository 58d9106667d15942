"""Reduce a profiler trace (``.xplane.pb``) to busy and idle time,
kernel time and the host's spans over the idle gaps.

The harness wraps the traced part of its window in one host span,
``WINDOW_SPAN``, and every call it makes into the program in a span of
its own (``submit``, ``step#<i>``, ``wait``).  Device planes are the
``/device:TPU:<n>`` planes; their ``XLA Ops`` line holds one event per
operation run, named by its HLO instruction; control-flow operations
(a layer scan's ``while``) are left out, since their bodies' operations
have events of their own.  Busy time is the union of the operation
events inside the window, averaged over the chips used; an idle gap is a
stretch of the window with no operation on the device, labelled by the
host span that overlaps it most.  An operation is reported under its
executable (the ``XLA Modules`` event it lies in), its instruction and
its result type, as ``jit__decode_paged/fusion.385 f32[2,6,1024]``: one
instruction of a layer scan's body counts once for all its layers.
"""
from __future__ import annotations

import bisect
import gzip
import re
from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "bench.trace_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(path: Path):
    from jax.profiler import ProfileData
    path = Path(path)
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    return ProfileData.from_file(str(path))


def find(directory: Path) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def _device_planes(pd):
    return [p for p in pd.planes if re.fullmatch(r"/device:TPU:\d+", p.name)]


def _host_spans(pd):
    """(name, start_ns, end_ns) of every host-thread event."""
    out = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _base(name: str) -> str:
    """An operation's instruction without its instance number: the TPU
    trace names an op event by its HLO text
    (``%fusion.12 = bf16[...] fusion(...)``), whose base is ``fusion``."""
    name = re.match(r"%?([^\s=]*)", name).group(1)
    return re.sub(r"(\.\d+|\.clone)+$", "", name)


def _op(name: str) -> str:
    """Instruction and result type, without the layout:
    ``fusion.12 bf16[16,1,1536]``."""
    m = re.match(r"%?(\S+) = \(?([a-z0-9]+\[[0-9,]*\])?", name)
    if not m:
        return name[:80]
    return f"{m.group(1)} {m.group(2) or ''}".strip()


def _module(name: str) -> str:
    """``jit__decode_paged(1091...)`` -> ``jit__decode_paged``."""
    return name.split("(")[0]


# control flow: its event spans the operations of its body, which have
# events of their own
CONTAINERS = {"while", "conditional", "call"}


def harness_span(name: str) -> str | None:
    """The harness's own host spans; every other host event (JAX's
    internal ones) is left out of gap labelling."""
    if name in ("submit", "wait", "bookkeeping") or name.startswith("step#"):
        return name
    return None


def reduce(pd, kernels: tuple[str, ...] = (), labels=harness_span) -> dict:
    """``window_s``, ``busy_s`` (mean over device planes), ``kernel_s``
    (summed device time of each named kernel's events, over all
    planes), ``kernel_calls``, ``device_ops`` (top 10 operations by
    time, as the module docstring names them) and ``idle_gaps`` (top 10 host labels by idle time).  ``labels``
    maps a host span name to the label its gaps get, or to None for a
    span that labels nothing."""
    spans = _host_spans(pd)
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = win[0]
    inner = [(labels(n), s, e) for n, s, e in spans
             if n != WINDOW_SPAN and s < w1 and e > w0
             and labels(n) is not None]
    planes = _device_planes(pd)
    if not planes:
        raise ValueError("no /device:TPU:<n> plane in the trace")
    busy, kernel_s, kernel_calls = [], defaultdict(float), defaultdict(int)
    by_op = defaultdict(float)
    gaps_by_label = defaultdict(float)
    for p in planes:
        ivs = []
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       _module(e.name))
                      for line in p.lines if line.name == MODULES_LINE
                      for e in line.events)
        starts = [m[0] for m in mods]
        for line in p.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                s, t = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
                if t <= s:
                    continue
                base = _base(e.name)
                if base.lower() in CONTAINERS:
                    continue
                ivs.append((s, t))
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] else "?"
                by_op[f"{mod}/{_op(e.name)}"] += (t - s) / len(planes)
                if base in kernels:
                    kernel_s[base] += (t - s)
                    kernel_calls[base] += 1
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged))
        gaps, prev = [], w0
        for s, e in merged + [[w1, w1]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        for label, g in zip(_labels(inner, gaps), gaps):
            gaps_by_label[label] += (g[1] - g[0]) / len(planes)
    ns = 1e-9
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "kernel_s": {k: kernel_s[k] * ns for k in kernels},
        "kernel_calls": {k: kernel_calls[k] for k in kernels},
        "device_ops": [[k, v * ns] for k, v in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v * ns] for k, v in
                      sorted(gaps_by_label.items(), key=lambda kv: -kv[1])[:10]],
    }


def _labels(spans, gaps) -> list[str]:
    """For each gap (in time order), the labelled host span covering
    most of it.  The harness's spans run one after another on one
    thread, so one sweep over both lists in time order does."""
    spans = sorted(spans, key=lambda x: x[2])
    out, lo = [], 0
    for s, e in gaps:
        while lo < len(spans) and spans[lo][2] <= s:
            lo += 1
        best, best_key = "no host span", None
        for j in range(lo, len(spans)):
            name, a, b = spans[j]
            if a >= e:
                break
            cover = min(b, e) - max(a, s)
            if best_key is None or cover > best_key:
                best, best_key = name, cover
        out.append(best)
    return out
