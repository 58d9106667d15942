"""Engine: mean host-clock time of the Engine.step() calls that ran a
decode step (total over count)."""


def read(run):
    d = [s.t1 - s.t0 for s in run.steps if s.kind == "decode"]
    return 1e3 * sum(d) / len(d) if d else None
