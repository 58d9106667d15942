"""Scheduler: 90th percentile of the program's ``queue_wait`` segments
(``Engine.submit`` to the scheduler's admission; Tracer spans)."""
import numpy as np

from chipbench import phases


def read(run):
    ms = phases.segment_ms(getattr(run, "spans", None), "queue_wait")
    return float(np.percentile(ms, 90)) if ms else None
