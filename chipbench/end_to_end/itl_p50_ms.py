"""Median gap between successive output tokens, over every request in
the window."""
import numpy as np


def read(run):
    gaps = np.concatenate([np.diff(t.token_times) for t in run.tracks]
                          + [np.zeros(0)])
    return float(np.percentile(gaps, 50)) * 1e3 if len(gaps) else None
