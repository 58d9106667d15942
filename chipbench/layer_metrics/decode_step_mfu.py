"""Model step: model FLOPs of the decode steps (dense matmuls and
attention over the live contexts) over their host-clock time at the
chip's bf16 peak."""
from chipbench import flops


def read(run):
    steps = [s for s in run.steps if s.kind == "decode"]
    t = sum(s.t1 - s.t0 for s in steps)
    if not steps or t <= 0:
        return None
    work = sum(flops.step_flops(run.model, s.decode_ctx) for s in steps)
    return 100.0 * work / (t * run.peak["flops"])
