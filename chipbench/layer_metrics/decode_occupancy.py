"""Scheduler: sequences advanced per decode-bearing step, as a share of
the engine's slots, counted from the tokens each step appended."""


def read(run):
    rows = [len(s.decode_ctx) for s in run.steps if s.decode_ctx]
    if not rows:
        return None
    return 100.0 * sum(rows) / (len(rows) * run.engine["max_slots"])
