"""Model step: model FLOPs of the mixed steps (the chunk's valid tokens
and the live decode rows, never the chunk's padding) over their
host-clock time at the chip's bf16 peak."""
from chipbench import flops


def read(run):
    steps = [s for s in run.steps if s.kind == "mixed"]
    t = sum(s.t1 - s.t0 for s in steps)
    if not steps or t <= 0:
        return None
    work = sum(flops.step_flops(run.model, s.decode_ctx, s.prefill)
               for s in steps)
    return 100.0 * work / (t * run.peak["flops"])
