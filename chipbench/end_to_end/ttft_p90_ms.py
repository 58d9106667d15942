"""90th percentile of time to first token over every request due in
the window, timed from its due time; a request with no token by the
window's end counts with end minus due."""
import numpy as np


def read(run):
    ttft = [(t.token_times[0] if t.token_times else run.window_s) - t.due
            for t in run.tracks]
    return float(np.percentile(ttft, 90)) * 1e3 if ttft else None
