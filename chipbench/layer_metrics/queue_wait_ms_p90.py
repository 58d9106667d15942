"""Scheduler: 90th percentile of due time to the first step after which
the request holds a slot (end minus due for one still waiting)."""
import numpy as np


def read(run):
    waits = [(t.admitted if t.admitted is not None else run.window_s) - t.due
             for t in run.tracks]
    return float(np.percentile(waits, 90)) * 1e3 if waits else None
