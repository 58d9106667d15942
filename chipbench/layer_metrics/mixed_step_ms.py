"""Engine: mean host-clock time of the Engine.step() calls that ran a
mixed step (a prefill chunk with the live decode rows)."""


def read(run):
    d = [s.t1 - s.t0 for s in run.steps if s.kind == "mixed"]
    return 1e3 * sum(d) / len(d) if d else None
