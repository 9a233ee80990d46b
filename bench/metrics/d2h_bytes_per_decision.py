"""Bytes the program's device sweeps read back from device to host per
decision: the ``sweep.d2h_bytes`` counter of the window's decisions, read
from the program's per-decision summaries (``repro.obs.trace.recent()``).
A program whose relocate+swap sweeps fetch every candidate's score (no
``ScheduleState.best_relocate_swap``) has no such counter and reports
nothing; sweeps scored on the host read back none."""

COUNTER = "sweep.d2h_bytes"


def read(run: dict):
    try:
        from repro.core.schedule_state import ScheduleState
        from repro.obs.trace import recent
    except ImportError:
        return None
    if not hasattr(ScheduleState, "best_relocate_swap"):
        return None
    held = [s for s in recent() if s["name"] == "refine"]
    k = min(run["decisions"], len(held))
    if k == 0:
        return None
    held = held[-k:]
    return sum(s["counters"].get(COUNTER, 0.0) for s in held) / k
