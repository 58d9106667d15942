"""Engine: mean over the window's decode steps of ``engine.step`` less
its ``engine.sync`` child (Tracer spans): the host's part of a step."""
from chipbench import phases


def read(run):
    spans = getattr(run, "spans", None)
    return phases.host_ms(spans, "decode") if spans else None
