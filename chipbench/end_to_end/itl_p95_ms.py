"""95th percentile of the gaps between successive output tokens, over
every request in the window: the mixed steps' stalls land here."""
import numpy as np


def read(run):
    gaps = np.concatenate([np.diff(t.token_times) for t in run.tracks]
                          + [np.zeros(0)])
    return float(np.percentile(gaps, 95)) * 1e3 if len(gaps) else None
