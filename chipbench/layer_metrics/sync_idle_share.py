"""Device: share of the traced window in which the device sat idle while
the host waited in ``engine.sync`` for the sampled tokens: the device's
own bubbles, inside or between executables, and the token copy."""


def read(run):
    idle = (run.trace or {}).get("idle_by_phase")
    if not idle or run.trace["window_s"] <= 0:
        return None
    return 100.0 * idle["engine.sync"] / run.trace["window_s"]
