"""Engine: 90th percentile of the program's ``prefill`` segments
(admission to the first token on the host; Tracer spans)."""
import numpy as np

from chipbench import phases


def read(run):
    ms = phases.segment_ms(getattr(run, "spans", None), "prefill")
    return float(np.percentile(ms, 90)) if ms else None
