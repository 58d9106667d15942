"""Serving layer: sim engine semantics, real JAX engine generation,
KV extract/inject parity, router, KV transfer timing."""
import jax
import numpy as np
import pytest

from repro import models
from repro.configs import get_config
from repro.core.metrics import Collector
from repro.core.types import Message, Request, RequestState
from repro.serving.engine import Engine
from repro.serving.engine_sim import SimEngine
from repro.serving.kv_transfer import KVTransferManager, SessionDirectory
from repro.serving.router import Router
from repro.serving.scheduler import SchedulerConfig
from repro.sim.clock import EventLoop
from repro.sim.costmodel import CostModel


# ---------------------------------------------------------------------------
# Sim engine
# ---------------------------------------------------------------------------

def _sim(loop=None, **sched_kw):
    loop = loop or EventLoop()
    cm = CostModel(get_config("agent-7b"), chips=4)
    cfg = SchedulerConfig(max_slots=4, num_pages=256, **sched_kw)
    return loop, SimEngine(loop, cm, cfg, collector=Collector())


def test_sim_engine_completes_requests():
    loop, eng = _sim()
    reqs = [Request(prompt_len=64, max_new_tokens=8) for _ in range(6)]
    for r in reqs:
        eng.submit(r)
    loop.run_until(120.0)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    assert all(r.generated == 8 for r in reqs)
    assert eng.tokens_generated == 48
    # latency metrics recorded
    assert len(eng.finished) == 6
    assert all(r.first_token_time is not None for r in reqs)


def test_sim_engine_continuous_batching_faster_than_serial():
    loop1, eng1 = _sim()
    batch = [Request(prompt_len=32, max_new_tokens=16) for _ in range(4)]
    for r in batch:
        eng1.submit(r)
    loop1.run_until(1e5)
    t_batched = max(r.finish_time for r in batch)

    loop2, eng2 = _sim()
    t = 0.0
    serial = []
    for i in range(4):
        r = Request(prompt_len=32, max_new_tokens=16)
        serial.append(r)

    def submit_next(i=0):
        if i < 4:
            eng2.on_finish = lambda *_: submit_next(i + 1)
            eng2.submit(serial[i])
    submit_next()
    loop2.run_until(1e5)
    t_serial = max(r.finish_time for r in serial)
    assert t_batched < 0.5 * t_serial      # slot batching amortizes weights


def test_sim_engine_pause_resume():
    loop, eng = _sim()
    r = Request(prompt_len=16, max_new_tokens=4)
    eng.set_param("paused", True)
    eng.submit(r)
    loop.run_until(10.0)
    assert r.state != RequestState.FINISHED
    eng.set_param("paused", False)
    loop.run_until(50.0)
    assert r.state == RequestState.FINISHED


def test_sim_engine_knob_shim():
    loop, eng = _sim()
    eng.set_param("max_num_seqs", 2)
    assert eng.scheduler.cfg.max_slots == 2
    eng.reset_param("max_num_seqs")
    assert eng.scheduler.cfg.max_slots == 4
    with pytest.raises(KeyError):
        eng.set_param("no_such_knob", 1)
    card = eng.card()
    assert card.kind == "llm" and "kv_transfer" in card.capabilities


# ---------------------------------------------------------------------------
# Real JAX engine
# ---------------------------------------------------------------------------

def _real_engine():
    cfg = get_config("tiny-agent")
    params = models.init(cfg, jax.random.key(0))
    sched = SchedulerConfig(max_slots=2, num_pages=64, max_context=128)
    return cfg, Engine(cfg, params, sched, name="real0")


def test_real_engine_generates():
    cfg, eng = _real_engine()
    prompts = [np.arange(5, 13) % cfg.vocab, np.arange(3, 10) % cfg.vocab]
    reqs = [Request(prompt_len=len(p), max_new_tokens=6,
                    prompt_tokens=np.asarray(p, np.int32)) for p in prompts]
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    for r in reqs:
        assert r.state == RequestState.FINISHED
        assert len(r.output_tokens) == 6
        assert all(0 <= t < cfg.vocab for t in r.output_tokens)


def test_real_engine_greedy_deterministic():
    cfg, eng1 = _real_engine()
    _, eng2 = _real_engine()
    p = np.arange(7, 23) % cfg.vocab
    outs = []
    for eng in (eng1, eng2):
        r = Request(prompt_len=len(p), max_new_tokens=8,
                    prompt_tokens=np.asarray(p, np.int32))
        eng.submit(r)
        eng.run_until_idle()
        outs.append(r.output_tokens)
    assert outs[0] == outs[1]


def test_real_engine_gauges_only_with_a_collector(monkeypatch):
    """With no collector the step skips its gauges (the windowed ITL
    sort, the waiting-queue scan) but keeps the step-time and token
    counters; with one, the gauges are published."""
    cfg, eng = _real_engine()

    def unread(self):
        raise AssertionError("gauge computed with no collector")
    monkeypatch.setattr(Engine, "itl_p95", property(unread))
    monkeypatch.setattr(type(eng.scheduler), "prefill_queue_tokens",
                        property(unread))
    p = np.asarray(np.arange(5, 13) % cfg.vocab, np.int32)
    eng.submit(Request(prompt_len=len(p), max_new_tokens=4,
                       prompt_tokens=p))
    eng.run_until_idle()
    assert eng.tokens_generated == 4 and eng.step_time_total > 0
    assert eng.mean_step_time > 0
    monkeypatch.undo()
    eng.collector = col = Collector()
    eng.submit(Request(prompt_len=len(p), max_new_tokens=4,
                       prompt_tokens=p))
    eng.run_until_idle()
    assert col._rings[f"{eng.name}.itl_p95"].last() is not None
    assert col._rings[f"{eng.name}.prefill_queue_tokens"].last() is not None


def test_real_engine_kv_extract_inject_parity():
    """Migrating a sequence between engines preserves greedy decoding."""
    cfg, eng1 = _real_engine()
    _, eng2 = _real_engine()
    p = np.arange(1, 17) % cfg.vocab

    # run to completion on engine 1 (reference)
    ref = Request(prompt_len=len(p), max_new_tokens=10,
                  prompt_tokens=np.asarray(p, np.int32))
    eng1.submit(ref)
    eng1.run_until_idle()

    # same prompt on a fresh engine; migrate MID-FLIGHT after 4 tokens
    # (the slot must still be live — finishing releases it)
    _, engA = _real_engine()
    r = Request(prompt_len=len(p), max_new_tokens=10,
                prompt_tokens=np.asarray(p, np.int32))
    engA.submit(r)
    while r.generated < 4:
        engA.step()
    state = engA.extract_state(r)
    first4 = list(r.output_tokens)
    engA.scheduler.preempt_one()          # drop it from the source
    assert r.output_tokens == []           # emission record fully reset
    r.generated = 4                        # resume point rides the state
    r.prefilled = r.prompt_len
    ok = eng2.scheduler.admit_direct(r)
    assert ok
    eng2.inject_state(r, state)
    eng2.run_until_idle()
    assert first4 + r.output_tokens == ref.output_tokens


# ---------------------------------------------------------------------------
# Router + KV transfer
# ---------------------------------------------------------------------------

class _Sink:
    def __init__(self, name):
        self.name = name
        self.msgs = []

    def deliver(self, m):
        self.msgs.append(m)

    def load(self):
        return float(len(self.msgs))


def test_router_static_affinity_and_rules():
    loop = EventLoop()
    r = Router(loop, policy="static")
    a, b = _Sink("i0"), _Sink("i1")
    r.add_instance(a)
    r.add_instance(b)
    m1 = Message(src="s", dst="r", payload={"session": "x"}, task_id="t1")
    m2 = Message(src="s", dst="r", payload={"session": "x"}, task_id="t2")
    r.deliver(m1)
    r.deliver(m2)
    # same session -> same instance
    assert (len(a.msgs), len(b.msgs)) in ((2, 0), (0, 2))
    # an installed rule overrides
    from repro.core.rules import RequestRule
    r.rules.install(RequestRule(session="x", route_to="i1"))
    m3 = Message(src="s", dst="r", payload={"session": "x"}, task_id="t3")
    r.deliver(m3)
    assert b.msgs and b.msgs[-1] is m3


def test_router_least_loaded():
    loop = EventLoop()
    r = Router(loop, policy="least_loaded")
    a, b = _Sink("i0"), _Sink("i1")
    a.msgs = [None] * 5                       # pre-loaded
    r.add_instance(a)
    r.add_instance(b)
    m = Message(src="s", dst="r", payload={"session": "y"}, task_id="t")
    r.deliver(m)
    assert b.msgs == [m]


def test_router_remove_instance_redispatches_held_and_drops_pins():
    """Removing an instance must (a) drop stale fallback session pins
    targeting it and (b) re-dispatch held/blocked messages, even when no
    new deliver bumps the rule-table version afterwards."""
    from repro.core.rules import RequestRule
    loop = EventLoop()
    r = Router(loop, policy="static")
    a, b = _Sink("i0"), _Sink("i1")
    r.add_instance(a)
    r.add_instance(b)
    # pin a session to each instance via the fallback hash
    sessions = [f"s{i}" for i in range(8)]
    for s in sessions:
        r.deliver(Message(src="x", dst="r", payload={"session": s},
                          task_id=s))
    pinned_to_a = [s for s, i in r._session_pin.items() if i == "i0"]
    assert pinned_to_a
    # hold a message behind a block rule
    r.rules.install(RequestRule(session=pinned_to_a[0], block=True))
    held = Message(src="x", dst="r", payload={"session": pinned_to_a[0]},
                   task_id="held")
    r.deliver(held)
    assert held in r._held
    # unblock (version bump happens, but no new deliver arrives) ...
    r.rules.remove_request_rules(lambda rule: rule.block)
    # ... then the pinned instance dies
    n_b = len(b.msgs)
    r.remove_instance("i0")
    assert all(i != "i0" for i in r._session_pin.values())
    assert held not in r._held
    assert b.msgs[-1] is held and len(b.msgs) == n_b + 1
    # re-delivery of an old i0 session lands on the survivor
    r.deliver(Message(src="x", dst="r", payload={"session": pinned_to_a[0]},
                      task_id="again"))
    assert b.msgs[-1].task_id == "again"


def test_router_held_message_survives_remove_last_then_add():
    """A message held while the fleet is momentarily empty must be
    re-dispatched when a replacement instance registers, and the
    ``held_count`` gauge must make the whole window observable (the
    failover-drill satellite)."""
    from repro.core.rules import RequestRule
    loop = EventLoop()
    col = Collector()
    r = Router(loop, policy="static", collector=col)
    a = _Sink("i0")
    r.add_instance(a)
    r.rules.install(RequestRule(session="s", block=True))
    held = Message(src="x", dst="r", payload={"session": "s"},
                   task_id="held")
    r.deliver(held)
    assert held in r._held
    assert r.held_count == 1
    assert col.last("router.held_count") == 1
    r.rules.remove_request_rules(lambda rule: rule.block)
    r.remove_instance("i0")              # fleet empty: nothing to pump to
    assert held in r._held
    assert col.last("router.held_count") == 1
    b = _Sink("i1")
    r.add_instance(b)                    # replacement arrives
    assert b.msgs == [held] and not r._held
    assert col.last("router.held_count") == 0


def test_router_empty_fleet_holds_instead_of_crashing():
    """Delivering into a momentarily-empty fleet (remove-last before the
    replacement registers) holds the message rather than raising, so an
    elastic-group failover never drops traffic."""
    loop = EventLoop()
    col = Collector()
    r = Router(loop, policy="least_loaded", collector=col)
    a = _Sink("i0")
    r.add_instance(a)
    r.remove_instance("i0")
    m = Message(src="x", dst="r", payload={"session": "s"}, task_id="t")
    r.deliver(m)                         # no instances: held, not raised
    assert r.held_count == 1
    assert col.last("router.held_count") == 1
    b = _Sink("i1")
    r.add_instance(b)
    assert b.msgs == [m] and r.held_count == 0


def test_kv_transfer_timing_and_residency():
    loop = EventLoop()
    d = SessionDirectory()
    kvx = KVTransferManager(loop, d, bytes_fn=lambda ctx: ctx * 1000,
                            bandwidth=1e6, latency=0.0)
    d.ensure("s0", "i0")
    d.grow("s0", 500)                          # 500k bytes -> 0.5 s
    t = kvx.transfer("s0", "i0", "i1")
    assert abs(t - 0.5) < 1e-6
    assert not d.resident("s0", "i1", now=0.0)
    assert abs(kvx.wait_time("s0", "i1") - 0.5) < 1e-6
    loop.run_until(1.0)
    assert d.resident("s0", "i1", now=1.0)
    assert d.get("s0").instance == "i1"
    assert kvx.wait_time("s0", "i1") == 0.0


def test_kv_transfers_serialize_on_link():
    loop = EventLoop()
    d = SessionDirectory()
    kvx = KVTransferManager(loop, d, bytes_fn=lambda ctx: 1_000_000,
                            bandwidth=1e6, latency=0.0)
    for s in ("a", "b"):
        d.ensure(s, "i0")
        d.grow(s, 1)
    t1 = kvx.transfer("a", "i0", "i1")
    t2 = kvx.transfer("b", "i0", "i1")
    assert abs(t1 - 1.0) < 1e-6 and abs(t2 - 2.0) < 1e-6   # FIFO pipe
