"""The harness drives agent sessions as a closed loop: at the rehearsal
sizes on the CPU, no turn of a session is due before its previous turn
finished, and each later turn's prompt holds the earlier prompt and the
tokens that were actually served for it."""
import numpy as np

from chipbench import generator, harness, spec, weights


def test_turns_wait_for_their_session():
    cell = spec.rehearsal_cell("qwen2-vl-2b", "agent_loop")
    model, engine = spec.sizes(cell.config, True)
    m_cfg = spec.model_config(cell.config, True)
    sc = spec.scheduler_config(cell.config, True)
    mix = generator.scaled(cell.mix, engine["max_context"]
                           / cell.config["engine"]["max_context"])
    mix["arrival"] = dict(mix["arrival"], rate=4.0)
    traffic = generator.generate(mix, model["vocab"],
                                 engine["max_context"], 6.0, 5)
    params = weights.to_program(weights.make(model, 5), m_cfg)
    eng, cache = harness._engine(m_cfg, sc, params, 5)
    harness.prefill_setup(eng, traffic.setup)
    tracks, *_ = harness.drive(eng, cache, traffic, 6.0, None)
    by = {}
    for tr in tracks:
        by.setdefault(tr.session, []).append(tr)
    pairs = [(a, b) for trs in by.values() for a, b in zip(trs, trs[1:])]
    grown = 0
    for a, b in pairs:
        assert a.finished_at is not None and b.due >= a.finished_at
        pa = np.concatenate([a.req.prompt_tokens, a.req.output_tokens])
        pb = b.req.prompt_tokens
        if len(pb) > len(pa):          # not restarted past max_context
            assert np.array_equal(pb[:len(pa)], pa)
            grown += 1
    assert grown, "no session's conversation grew by a served answer"
    assert len(tracks) < len(traffic.requests)   # some turns were held
