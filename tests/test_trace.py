"""Tracing plane: sampling policy, segment tiling, the flight recorder,
Chrome-trace export + causal action links, and the bounded audit log."""
import importlib.util
import json
import sys
from pathlib import Path

import pytest

from repro.agents import AgenticPipeline, PipelineConfig, TaskSpec
from repro.configs import get_config
from repro.core import (Controller, IntentError, MetricBus, Registry,
                        compile_intent)
from repro.core.metrics import CentralPoller, Collector, StateStore
from repro.core.trace import (SEGMENTS, FlightRecorder, Tracer,
                              request_decomposition)
from repro.core.types import Request, RequestState
from repro.serving.disagg import DisaggPool
from repro.serving.engine_sim import SimEngine
from repro.serving.kv_transfer import KVTransferManager, SessionDirectory
from repro.serving.scheduler import SchedulerConfig
from repro.sim.clock import EventLoop
from repro.sim.costmodel import CostModel

from tests.test_controller import FakeKnobbed

_ROOT = Path(__file__).resolve().parent.parent


def _report_tool():
    path = _ROOT / "tools" / "trace_report.py"
    spec = importlib.util.spec_from_file_location("trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _traced_fig1(n_tasks=3, intent=None, watch=None):
    pipe = AgenticPipeline(PipelineConfig(n_testers=2))
    if intent:
        pipe.controller.install(compile_intent(intent))
    if watch:
        pipe.recorder.watch(watch)
    pipe.tracer.set_scope(None, 1.0)
    for i in range(n_tasks):
        pipe.submit(TaskSpec(session=f"s{i}", n_functions=4))
    pipe.run(until=120.0)
    assert len(pipe.done) == n_tasks
    return pipe


# ---------------------------------------------------------------------------
# Sampling policy
# ---------------------------------------------------------------------------

def test_decide_uncached_while_disabled_enables_mid_run():
    tr = Tracer(lambda: 0.0)
    assert tr.decide("t1") is False          # off by default, zero cost
    tr.set_scope(None, 1.0)                  # ... flipped at runtime
    assert tr.enabled is True
    assert tr.decide("t1") is True           # earlier False was NOT cached


def test_sampling_is_deterministic_and_partitions():
    a = Tracer(lambda: 0.0)
    b = Tracer(lambda: 0.0)
    a.set_scope(None, 0.4)
    b.set_scope(None, 0.4)
    ids = [f"task-{i}" for i in range(200)]
    da = [a.decide(t) for t in ids]
    assert da == [b.decide(t) for t in ids]  # replay traces the same tasks
    assert 0 < sum(da) < len(ids)            # rate actually partitions


def test_scope_precedence_stage_over_tenant_over_global():
    tr = Tracer(lambda: 0.0)
    tr.set_scope(None, 0.0)                  # global off
    tr.set_scope("tenant:gold", 1.0)         # scoped rate implies enabled
    assert tr.enabled is True
    assert tr.decide("t1", tenant="gold") is True
    assert tr.decide("t2", tenant="bronze") is False
    tr.set_scope("stage:editor", 1.0)        # stage is most specific
    assert tr.decide("t2", tenant="bronze", stage="editor") is True
    assert tr.decided("t1") is True          # cached-decision-only lookup
    assert tr.decided("never-seen") is False


def test_span_store_is_bounded():
    tr = Tracer(lambda: 0.0, cap=8)
    for i in range(20):
        tr.record(f"s{i}", "t", float(i), float(i) + 1.0)
    assert len(tr.spans) <= 8
    assert tr.spans_total == 20
    assert tr.spans_dropped > 0


# ---------------------------------------------------------------------------
# Intent verb
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("program,fragment", [
    ("rule r: when mean(x) > 1 => trace 1.5", "outside [0, 1]"),
    ("rule r: when mean(x) > 1 => trace maybe", "on|off|FLOAT"),
    ("rule r: when mean(x) > 1 => trace cluster gold on",
     "selector must be tenant|stage"),
])
def test_trace_verb_parse_errors(program, fragment):
    with pytest.raises(IntentError) as ei:
        compile_intent(program)
    assert fragment in str(ei.value)


def test_trace_verb_scopes_tracer_and_audits():
    loop = EventLoop()
    bus = MetricBus()
    reg = Registry()
    tr = Tracer(loop.now)
    reg.register(tr)
    reg.register(FakeKnobbed())
    store = StateStore()
    poller = CentralPoller(store)
    col = Collector(bus=bus)
    poller.attach(col)
    c = Controller(loop, reg, poller, interval=0.05, bus=bus)
    c.install(compile_intent("""
rule a on eng.queue_len > 10: => trace tenant gold 0.5
rule b on eng.queue_len > 20: => trace stage editor on
"""))
    col.gauge("eng.queue_len", 15, 0.01)
    loop.run_until(0.02)
    assert tr.scopes == {"tenant:gold": 0.5}
    assert tr.enabled is True
    col.gauge("eng.queue_len", 25, 0.05)
    loop.run_until(0.1)
    assert tr.scopes["stage:editor"] == 1.0
    kinds = [a.kind for a in c.action_log("trace")]
    assert len(kinds) == 2                   # both verbs audited


# ---------------------------------------------------------------------------
# Segment tiling (the acceptance bound)
# ---------------------------------------------------------------------------

def test_fig1_segments_tile_request_latency_within_1pct():
    pipe = _traced_fig1()
    decomp = request_decomposition(pipe.tracer.all_spans())
    assert decomp, "no closed request spans"
    for span, segs, dur in decomp:
        assert set(segs) <= set(SEGMENTS)
        total = sum(segs.values())
        assert abs(total - dur) <= 0.01 * max(dur, 1e-9), (
            f"{span.name}: segments {total:.6f}s != e2e {dur:.6f}s")
    # the decomposition is also published as request.<segment> gauges
    names = {s.name for s, _, _ in decomp}
    assert names                              # every traced request closed


def test_segment_gauges_reach_metric_plane():
    pipe = AgenticPipeline(PipelineConfig(n_testers=2))
    hits = []
    pipe.bus.subscribe("request.decode", above=0.0, edge=False,
                       fn=lambda n, v, t: hits.append((v, t)))
    pipe.tracer.set_scope(None, 1.0)
    for i in range(3):
        pipe.submit(TaskSpec(session=f"s{i}", n_functions=4))
    pipe.run(until=120.0)
    assert hits, "closed decode segments never reached the bus"
    assert all(v > 0 for v, _ in hits)
    # ... and land in the collector rings the poller scrapes
    assert pipe.collector._rings["request.queue_wait"].last() is not None


# ---------------------------------------------------------------------------
# Export: schema, causal links, critical path
# ---------------------------------------------------------------------------

def test_export_is_valid_chrome_trace_with_causal_links(tmp_path):
    intent = """
rule widen on developer.queue_len > 1:
    => set developer.max_num_seqs 48; note widened under burst
"""
    pipe = _traced_fig1(intent=intent, watch="tester-*.queue_len")
    out = tmp_path / "TRACE_fig1.json"
    doc = pipe.tracer.export(out, recorder=pipe.recorder)
    rpt = _report_tool()
    assert rpt.validate(rpt.load(out)) == []
    assert doc["otherData"]["links"] >= 1, "no action causally linked"
    evs = doc["traceEvents"]
    starts = [e for e in evs if e["ph"] == "s"]
    ends = [e for e in evs if e["ph"] == "f"]
    assert len(starts) == len(ends) == doc["otherData"]["links"]
    assert any(e["ph"] == "i" for e in evs)       # instant control events
    # the linked span carries the action text for the report tool
    linked = [e for e in evs
              if e["ph"] == "X" and (e["args"].get("actions"))]
    assert linked
    # recorder windows captured the watched series
    assert pipe.recorder.window("tester-0.queue_len")


def test_workflow_critical_path_reproduced_from_export_alone(tmp_path):
    from repro.agents import WorkflowConfig, deep_review
    from repro.agents.workloads import GraphBurst
    wf = AgenticPipeline.build(deep_review(depth=2),
                               WorkflowConfig(router_policy="least_loaded"))
    wf.tracer.set_scope(None, 1.0)
    GraphBurst(wf, n_tasks=2).start()
    wf.run(until=240.0)
    assert wf.done
    out = tmp_path / "TRACE_workflow.json"
    wf.tracer.export(out, recorder=wf.recorder)
    rpt = _report_tool()
    doc = rpt.load(out)
    assert rpt.validate(doc) == []
    spans = rpt.spans_from(doc)
    path = rpt.critical_path(spans, wf.done[0].task_id)
    assert len(path) >= 2, "critical path did not chain stages"
    assert all(s.cat == "stage" for s in path)
    assert path[0].name.startswith("stage:author")
    # dominant segment attribution works from the file alone
    seg, sec, frac = rpt.dominant_segment(path[-1], rpt._children(spans))
    assert seg in SEGMENTS and sec > 0


def test_trace_artifacts_are_valid_chrome_trace():
    """CI schema gate: every TRACE_*.json the benchmark smoke emitted
    must load as valid Chrome-trace JSON (skips when none exist)."""
    arts = sorted((_ROOT / "artifacts" / "bench").glob("TRACE_*.json"))
    if not arts:
        pytest.skip("no trace artifacts (run benchmarks.run --only trace)")
    rpt = _report_tool()
    for p in arts:
        doc = json.loads(p.read_text())
        assert rpt.validate(doc) == [], f"{p.name} failed schema check"
        assert rpt.spans_from(doc), f"{p.name} exported no spans"


# ---------------------------------------------------------------------------
# Flight recorder + bounded audit log
# ---------------------------------------------------------------------------

def test_controller_audit_log_is_bounded_ring():
    loop = EventLoop()
    reg = Registry()
    store = StateStore()
    poller = CentralPoller(store)
    col = Collector()
    c = Controller(loop, reg, poller, interval=0.05, collector=col,
                   actions_cap=8)
    rec = FlightRecorder(loop.now, action_cap=6)
    c.attach_recorder(rec)
    for i in range(20):
        c._log("note", f"t{i}", f"detail {i}")
    assert len(c.actions) <= 8
    assert c.actions_total == 20
    assert c.actions[-1].target == "t19"          # newest survives
    assert len(rec.actions) <= 6                  # recorder has its own bound
    assert rec.actions_total == 20
    # filtering API intact on the bounded list
    assert all(a.kind == "note" for a in c.action_log("note"))
    assert c.action_log("set") == []
    # retained-size gauge published for the dashboard
    assert col._rings["controller.actions_retained"].last() == len(c.actions)


def test_flight_recorder_windows_and_snapshot():
    bus = MetricBus()
    rec = FlightRecorder(lambda: 5.0, bus=bus, window_cap=4)
    rec.watch("eng-*.queue_len")
    for t in range(10):
        bus.publish("eng-0.queue_len", float(t), float(t))
        bus.publish("eng-1.queue_len", 100.0 + t, float(t))
    bus.publish("other.latency", 1.0, 9.0)        # unwatched
    assert len(rec.window("eng-0.queue_len")) == 4          # bounded ring
    assert [v for _, v in rec.window("eng-1.queue_len", since=8.0)] \
        == [108.0, 109.0]
    assert rec.window("other.latency") == []
    snap = rec.snapshot(since=8.0)
    assert snap["t"] == 5.0
    assert set(snap["metrics"]) == {"eng-0.queue_len", "eng-1.queue_len"}
    assert all(t >= 8.0 for series in snap["metrics"].values()
               for t, _ in series)


def test_recorder_actions_between_filters_by_time_and_kind():
    rec = FlightRecorder(lambda: 0.0)

    class A:
        def __init__(self, t, kind):
            self.t, self.kind, self.target, self.detail = t, kind, "x", ""
    for t, k in [(0.0, "set"), (1.0, "note"), (2.0, "set"), (3.0, "scale")]:
        rec.record_action(A(t, k))
    assert [a.t for a in rec.actions_between(0.5, 2.5)] == [1.0, 2.0]
    assert [a.t for a in rec.actions_between(kind="set")] == [0.0, 2.0]


# ---------------------------------------------------------------------------
# Disagg: handoff_wait segment + kv chunk spans
# ---------------------------------------------------------------------------

def test_disagg_handoff_traced_with_kv_chunk_spans():
    loop = EventLoop()
    col = Collector("t")
    cm = CostModel(get_config("agent-7b"), chips=4)
    engines = [
        SimEngine(loop, cm,
                  SchedulerConfig(max_slots=8, num_pages=2048,
                                  max_context=4096, role=r),
                  name=f"e{i}", collector=col)
        for i, r in enumerate(("prefill", "decode"))]
    kvx = KVTransferManager(loop, SessionDirectory(),
                            bytes_fn=cm.kv_transfer_bytes, collector=col)
    tr = Tracer(loop.now)
    tr.set_scope(None, 1.0)
    pool = DisaggPool(loop, engines, kvx, collector=col, tracer=tr)
    r = Request(prompt_len=2048, max_new_tokens=16)
    pool.submit(r)
    loop.run_until(60.0)
    assert r.state == RequestState.FINISHED
    spans = tr.all_spans()
    segs = {s.name for s in spans if s.cat == "segment"}
    assert "handoff_wait" in segs             # release→resume gap captured
    assert "prefill" in segs and "decode" in segs
    kv = [s for s in spans if s.cat == "kv"]
    assert kv, "no kv chunk spans for a chunk-streamed handoff"
    assert any(s.name == "kv_chunk_tail" for s in kv)
    assert all(s.attrs["src"] == "e0" and s.attrs["dst"] == "e1"
               for s in kv)
    # the request's segments still tile its latency across BOTH engines
    for span, sgs, dur in request_decomposition(spans):
        assert abs(sum(sgs.values()) - dur) <= 0.01 * max(dur, 1e-9)


# ---------------------------------------------------------------------------
# Live engine: step-phase spans on the profiler's clock
# ---------------------------------------------------------------------------

PHASES = ("engine.plan", "engine.inputs", "engine.dispatch", "engine.sync",
          "engine.apply")


def _live_engine(layout="paged", mixed=True, chunk=8):
    import jax
    from repro import models
    from repro.serving.engine import Engine
    cfg = get_config("tiny-agent").replace(dtype="float32")
    sched = SchedulerConfig(max_slots=3, num_pages=64, max_context=128,
                            page_size=16, max_batch_tokens=64,
                            prefill_chunk=chunk, mixed=mixed)
    eng = Engine(cfg, models.init(cfg, jax.random.key(0)), sched,
                 name=f"live-{layout}-{int(mixed)}", cache_layout=layout)
    return cfg, eng


def _live_tracer(eng, enabled=True):
    tr = Tracer(eng.now, name="live.tracer")
    tr.set_scope(None, 1.0 if enabled else 0.0)
    eng.attach_tracer(tr)
    return tr


def _serve(eng, lengths, seed=0, max_new=4):
    import numpy as np
    rng = np.random.default_rng(seed)
    reqs = [Request(prompt_len=n, max_new_tokens=max_new,
                    prompt_tokens=rng.integers(0, 200, n).astype(np.int32))
            for n in lengths]
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    assert all(r.state == RequestState.FINISHED for r in reqs)
    return reqs


def _steps(spans):
    """(engine.step span, its children) in time order."""
    kids: dict = {}
    for s in spans:
        if s.parent_id is not None and s.cat == "step":
            kids.setdefault(s.parent_id, []).append(s)
    return [(s, kids.get(s.span_id, [])) for s in spans
            if s.name == "engine.step"]


@pytest.mark.parametrize("layout,mixed", [
    ("paged", True),          # mixed steps, then decode steps
    ("paged", False),         # serialized chunked paged prefill
    ("ring", False),          # one-shot ring prefill
])
def test_live_engine_step_phases_tile_each_step(layout, mixed):
    _, eng = _live_engine(layout, mixed)
    tr = _live_tracer(eng)
    plans = []
    plan_step = eng.scheduler.plan_step

    def recorded():
        plans.append(plan_step())
        return plans[-1]
    eng.scheduler.plan_step = recorded
    lengths = (35, 21, 40)
    _serve(eng, lengths)
    first_wave = len(plans)
    _serve(eng, lengths, seed=1)          # same shapes: nothing retraces
    steps = _steps(tr.all_spans())
    assert len(steps) == len(plans) == eng.steps
    # the phases tile the steps: what lies between them is the spans'
    # own entry and exit (tens of microseconds a step)
    assert sum(k.dur for _, kids in steps for k in kids) >= \
        0.9 * sum(root.dur for root, _ in steps)
    kinds_seen = set()
    for i, ((root, kids), plan) in enumerate(zip(steps, plans)):
        assert {k.name for k in kids} <= set(PHASES)
        assert kids[0].name == "engine.plan"
        assert kids[-1].name == "engine.apply"
        assert all(root.t0 <= k.t0 and k.t1 <= root.t1 for k in kids)
        assert root.attrs["kind"] == plan.kind.value
        assert root.attrs["rows"] == len(plan.decodes)
        assert root.attrs["chunk"] == sum(w.chunk for w in plan.prefills)
        if i >= first_wave:
            assert root.attrs["traced"] is False
        elif mixed:
            # each executable traces on its first call only
            assert root.attrs["traced"] is (plan.kind not in kinds_seen)
            kinds_seen.add(plan.kind)
    assert any(r.attrs["traced"] for r, _ in steps)
    assert {r.attrs["kind"] for r, _ in steps} >= (
        {"mixed", "decode"} if mixed else {"prefill", "decode"})
    if mixed:
        assert eng.mixed_step_traces == 1
        assert eng.traces["_decode_paged"] == 1


def test_phases_cost_nothing_without_an_enabled_tracer(monkeypatch):
    import jax.profiler
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **k):
            built.append(a)
            super().__init__(*a, **k)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    _, eng = _live_engine()
    assert eng.tracer is None
    _serve(eng, (35, 21))
    tr = _live_tracer(eng, enabled=False)
    _serve(eng, (35, 21))
    assert built == [] and tr.spans == [] and tr.spans_total == 0
    assert tr.phase("engine.step") is tr.phase("engine.plan")  # shared
    tr.set_scope(None, 1.0)
    _serve(eng, (35,))
    assert len(built) == sum(s.cat == "step" for s in tr.spans) > 0


def test_live_engine_phases_in_profiler_host_plane(tmp_path):
    """Under jax.profiler.trace the engine.* events sit in the host plane,
    nested in time inside their engine.step, and the tracer's anchor
    puts its spans where the profiler stamped them."""
    import statistics

    import jax
    from jax.profiler import ProfileData
    _, eng = _live_engine()
    _serve(eng, (35, 21))                 # compile outside the trace
    tr = _live_tracer(eng)
    with jax.profiler.trace(str(tmp_path)):
        _serve(eng, (35, 21), seed=2)
    pd = ProfileData.from_file(str(sorted(tmp_path.rglob("*.xplane.pb"))[-1]))
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for p in pd.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events
              if e.name.startswith("engine.")]
    roots = sorted(e for e in events if e[0] == "engine.step")
    spans = _steps(tr.all_spans())
    assert len(roots) == len(spans) > 0
    for name, s, e in events:
        if name != "engine.step":
            assert any(a <= s and e <= b for _, a, b in roots), name
    for (_, a, b), (root, kids) in zip(roots, spans):
        inside = sorted(n for n, s, e in events
                        if n != "engine.step" and a <= s and e <= b)
        assert inside == sorted(k.name for k in kids)
    env = pd.find_plane_with_name("Task Environment")
    start = dict(env.stats)["profile_start_time"]
    gaps = [abs(s + start - (root.t0 + tr.origin_unix_s) * 1e9)
            for (_, s, _), (root, _) in zip(roots, spans)]
    assert statistics.median(gaps) < 2e6    # ns; a CPU bound, not the chip's
    doc = tr.export()
    assert doc["otherData"]["clock"] == f"{eng.name}.now"
    assert doc["otherData"]["origin_unix_s"] == tr.origin_unix_s


def test_live_engine_request_segments_still_tile_latency():
    _, eng = _live_engine()
    tr = _live_tracer(eng)
    reqs = _serve(eng, (35, 21, 40, 9))
    decomp = request_decomposition(tr.all_spans())
    assert len(decomp) == len(reqs)
    for span, segs, dur in decomp:
        assert set(segs) <= set(SEGMENTS)
        assert abs(sum(segs.values()) - dur) <= 0.01 * max(dur, 1e-9)


def test_trace_report_leaves_step_spans_out(tmp_path):
    _, eng = _live_engine()
    tr = _live_tracer(eng)
    reqs = _serve(eng, (35, 21, 40))
    out = tmp_path / "TRACE_live.json"
    tr.export(out)
    rpt = _report_tool()
    doc = rpt.load(out)
    assert rpt.validate(doc) == []
    spans = rpt.spans_from(doc)
    assert any(s.cat == "step" for s in spans)
    checks = rpt.decomposition_check(spans)
    assert len(checks) == len(reqs)
    for _, total, dur in checks:
        assert abs(total - dur) <= 0.01 * max(dur, 1e-9)
    for r in reqs:
        path = rpt.critical_path(spans, r.req_id)
        assert path and all(s.cat == "request" for s in path)
    phases = rpt.step_phases(spans)[eng.name]
    assert phases["engine.step"][0] == eng.steps
    assert set(phases) == {"engine.step", *PHASES}
    assert f"{eng.steps} steps" in rpt.report(doc)
