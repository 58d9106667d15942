"""Kernel: the least time the paged decode kernel's required work (live
K/V pages, q and out; attention FLOPs over the live contexts) takes at
the chip's peaks, over the summed device time of the kernel's events
in the trace.  One call per layer per decode-bearing traced step."""
from chipbench import flops

KERNEL = "paged_decode_attention"


def read(run):
    if run.trace is None or not run.trace["kernel_s"].get(KERNEL):
        return None
    least = 0.0
    for s in run.steps:
        if s.traced and s.kind in ("decode", "mixed"):
            f, b = flops.paged_decode_cost(run.model, s.decode_ctx,
                                           run.engine["page_size"])
            least += run.model["n_layers"] * flops.least_seconds(
                f, b, run.peak)
    return 100.0 * least / run.trace["kernel_s"][KERNEL]
