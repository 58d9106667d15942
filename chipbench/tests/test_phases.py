"""The engine-phase split of the device's idle time: on a hand-made
trace whose answers are known, on a small trace recorded on a TPU v5e
(``record_phase_trace.py``), and through the harness at rehearsal sizes
on the CPU."""
import json
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import harness, phase_split, phases, spec, xplane
from chipbench.tests.test_xplane import _space

DATA = Path(__file__).resolve().parent / "data" / "phases"


def _labels(kinds):
    return lambda n: kinds.get(xplane.harness_span(n), xplane.harness_span(n))


def test_idle_split_by_engine_phase():
    # step#0 [0, 500) holds engine.step [10, 490) and its phases; step#1
    # [580, 980) ends in engine.sync [900, 970); the harness then waits
    # [980, 1400); the device runs [200, 350) and [600, 700)
    host = [(xplane.WINDOW_SPAN, 0, 1400), ("step#0", 0, 500),
            ("engine.step", 10, 480), ("engine.plan", 10, 40),
            ("engine.inputs", 50, 100), ("engine.dispatch", 150, 40),
            ("engine.sync", 190, 200), ("engine.apply", 390, 90),
            ("bookkeeping", 500, 80), ("step#1", 580, 400),
            ("engine.step", 590, 380), ("engine.sync", 900, 70),
            ("wait", 980, 420)]
    pd = _space(device_ops=[("%fusion.1 = f32[2] fusion(x)", 200, 150),
                            ("%fusion.2 = f32[2] fusion(y)", 600, 100)],
                host_spans=host,
                modules=[("jit__decode_paged(1)", 200, 150),
                         ("jit__decode_paged(1)", 600, 100)])
    kinds = {"step#0": "step.decode", "step#1": "step.mixed"}
    red = xplane.reduce(pd, labels=_labels(kinds))
    split = phases.reduce(pd, labels=_labels(kinds))
    # idle [0, 200): inputs [50, 150) covers most of it; [350, 600):
    # apply [390, 480) more than sync [190, 390); [700, 1400) lies mostly
    # in the wait, so it stays the wait's though engine.sync touches it
    assert split["idle_by_phase"] == pytest.approx({
        "engine.plan": 0.0, "engine.inputs": 200e-9,
        "engine.dispatch": 0.0, "engine.sync": 0.0,
        "engine.apply": 250e-9})
    assert dict(split["phase_gaps"]) == pytest.approx({
        "step.decode/engine.inputs": 200e-9,
        "step.decode/engine.apply": 250e-9, "wait": 700e-9})
    # labels outside every phase are the reduction's own, and the split
    # adds up to the idle time
    assert dict(red["idle_gaps"]) == pytest.approx({
        "step.decode": 450e-9, "wait": 700e-9})
    assert sum(v for _, v in split["phase_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"])
    run = SimpleNamespace(trace={**red, **split})
    host_share = spec.reader("layer_metrics",
                             "engine_host_idle_share").read(run)
    sync_share = spec.reader("layer_metrics", "sync_idle_share").read(run)
    assert host_share == pytest.approx(100 * 450 / 1400)
    assert sync_share == pytest.approx(0.0)


def test_readers_find_nothing_without_the_spans():
    """What a run of a program without step phases gives every reader."""
    run = SimpleNamespace(trace={"window_s": 1.0, "busy_s": 0.9},
                          steps=[], tracks=[])
    for name in phase_split.PHASE_METRICS:
        assert spec.reader("layer_metrics", name).read(run) is None


def _recorded():
    from repro.core.trace import Span
    doc = json.loads((DATA / "spans.json").read_text())
    spans = [Span(**s) for s in doc["spans"]]
    facts = json.loads((DATA / "window.json").read_text())
    return (xplane.load(DATA / "window.xplane.pb.gz"), spans,
            doc["origin_unix_s"], facts)


def test_recorded_tpu_trace_with_engine_phases():
    """qwen2-vl-2b's rehearsal steps on one v5e chip with an enabled
    tracer: every harness step holds one engine.step, its phases cover
    it, the idle time inside the steps splits by phase, and the tracer's
    anchor puts its spans within 100 us of their copies in the profile."""
    pd, spans, origin, facts = _recorded()
    kinds = {f"step#{i}": f"step.{k}" for i, k in enumerate(facts["steps"])}
    red = xplane.reduce(pd, labels=_labels(kinds))
    split = phases.reduce(pd, labels=_labels(kinds))
    roots = [s for s in spans if s.name == phases.ROOT]
    assert [s.attrs["kind"] for s in roots] == facts["steps"]
    assert not any(s.attrs["traced"] for s in roots)
    assert phases.step_coverage(spans) >= 0.95
    in_steps = sum(v for k, v in red["idle_gaps"] if k.startswith("step."))
    by_phase = sum(split["idle_by_phase"].values())
    assert 0.9 * in_steps <= by_phase <= red["window_s"] - red["busy_s"]
    offsets = [abs(o) for o in phases.clock_offsets(pd, spans, origin)]
    assert len(offsets) == len(roots)
    assert sorted(offsets)[len(offsets) // 2] < 100e3
    run = SimpleNamespace(trace={**red, **split}, spans=spans)
    got = {m: spec.reader("layer_metrics", m).read(run)
           for m in phase_split.PHASE_METRICS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    share = got["engine_host_idle_share"] + got["sync_idle_share"]
    assert share <= 100 * (1 - red["busy_s"] / red["window_s"]) + 1e-9


def test_harness_run_with_a_tracer_at_rehearsal_sizes(monkeypatch):
    for name in ("drive", "Run"):
        monkeypatch.setattr(harness, name, getattr(harness, name))
    monkeypatch.setattr(xplane, "reduce", xplane.reduce)
    seen = phase_split.instrument(True)
    cell = spec.rehearsal_cell("h2o-danube-3-4b", "prefill_heavy")
    out, _ = harness.run(cell, 5, 4.0, False, True, time.perf_counter())
    got = phase_split.summary(seen)
    assert got["steps"] == sum(out["counts"]["steps"].values())
    assert got["traced_steps"] == 0
    assert got["metrics"]["mixed_host_ms"] > 0
    assert got["metrics"]["prefill_span_ms_p90"] > 0
    assert got["metrics"]["engine_host_idle_share"] is None   # no profile
