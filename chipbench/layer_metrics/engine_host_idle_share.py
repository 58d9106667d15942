"""Engine: share of the traced window in which the device sat idle while
the host was in the engine's own phases of ``Engine.step()``
(``engine.plan``, ``.inputs``, ``.dispatch``, ``.apply``; Tracer spans
in the profile, ``chipbench/phases.py``)."""
from chipbench import phases


def read(run):
    idle = (run.trace or {}).get("idle_by_phase")
    if not idle or run.trace["window_s"] <= 0:
        return None
    return 100.0 * sum(idle[p] for p in phases.HOST_PHASES) \
        / run.trace["window_s"]
