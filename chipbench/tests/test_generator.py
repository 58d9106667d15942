"""The traffic generator: seeded, and every seed the same work."""
import json

import numpy as np
import pytest

from chipbench import generator, spec


def mix(name):
    return spec.load_json(spec.HERE / "traffic" / f"{name}.json")


def test_same_seed_same_inputs():
    a = generator.generate(mix("short_chat"), 1000, 16384, 20, 5)
    b = generator.generate(mix("short_chat"), 1000, 16384, 20, 5)
    assert [r.due for r in a.requests] == [r.due for r in b.requests]
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.requests, b.requests))


@pytest.mark.parametrize("name", ["decode_heavy", "prefill_heavy",
                                  "short_chat", "agent_loop"])
def test_every_seed_serves_the_same_sizes(name):
    """Every seed serves the same sizes at the same times, with other
    token ids; each block of requests holds the block's quantiles."""
    runs = [generator.generate(mix(name), 1000, 16384, 51, s)
            for s in (1, 2, 2**31 + 77)]
    for size in (lambda r: len(r.prompt), lambda r: r.max_new,
                 lambda r: r.due):
        got = [[size(r) for r in t.requests] for t in runs]
        assert got[0] == got[1] == got[2]
    assert [r.prompt.tolist() for r in runs[0].requests[:3]] != \
        [r.prompt.tolist() for r in runs[1].requests[:3]]
    m = mix(name)
    if "sessions" not in m:
        block = generator.BLOCK
        first = sorted(r.max_new for r in runs[0].requests[:block])
        q = (np.arange(block) + 0.5) / block
        lo, hi = m["output"]["lo"], m["output"]["hi"]
        want = lo * (hi / lo) ** q if m["output"]["dist"] == "loguniform" \
            else lo + q * (hi - lo)
        if len(runs[0].requests) >= block:
            assert first == sorted(np.clip(np.rint(want), lo, hi).astype(int)
                                   .tolist())


def test_open_loop_offers_the_rate():
    m = mix("short_chat")
    rate = m["arrival"]["rate"]
    runs = [generator.generate(m, 1000, 16384, 50, s) for s in range(5)]
    assert {len(t.requests) for t in runs} == {round(rate * 50)}
    for t in runs:
        due = [r.due for r in t.requests]
        assert due[0] == 0.0 and due == sorted(due) and due[-1] < 50


def test_agent_sessions():
    """Session turns carry only their tool result; the closed loop holds
    a session's next turn until its last answer came back, then makes
    its prompt from the conversation as served."""
    m = mix("agent_loop")
    t = generator.generate(m, 1000, 16384, 51, 3)
    shared = t.setup[0]
    assert len(shared) == m["shared_prefix"]
    assert all(m["prompt"]["lo"] <= len(r.prompt) <= m["prompt"]["hi"]
               and 0 <= r.session < m["sessions"]["count"]
               for r in t.requests)
    loop = t.sessions
    first = next(r for r in t.requests if r.session == 0)
    again = next(r for r in t.requests if r.session == 0 and r is not first)
    hist = loop.conv[0]
    assert loop.ready(0) and loop.due(first) == first.due
    p1 = loop.prompt(first)
    assert np.array_equal(p1, np.concatenate([shared, hist, first.prompt]))
    assert not loop.ready(0)
    served = list(range(first.max_new))
    loop.answered(0, p1, served, first.due + 100.0)
    assert loop.ready(0) and loop.due(again) == first.due + 100.0
    p2 = loop.prompt(again)
    assert np.array_equal(p2[:len(p1)], p1)
    assert p2[len(p1):len(p1) + len(served)].tolist() == served
    assert np.array_equal(p2[len(p1) + len(served):], again.prompt)


def test_agent_session_restarts_past_max_context():
    m = mix("agent_loop")
    t = generator.generate(m, 1000, 16384, 51, 3)
    loop, r = t.sessions, t.requests[0]
    loop.conv[r.session] = np.zeros(16384, np.int32)
    p = loop.prompt(r)
    assert np.array_equal(p, np.concatenate([t.setup[0], r.prompt]))


def test_mixes_are_json_files():
    for name in ("agent_loop", "short_chat", "decode_heavy",
                 "prefill_heavy"):
        assert json.loads((spec.HERE / "traffic" / f"{name}.json")
                          .read_text())["arrival"]["process"]
