"""Bytes the program's device sweeps sent from host to device per
decision: the ``sweep.h2d_bytes`` counter of the window's decisions, read
from the program's per-decision summaries (``repro.obs.trace.recent()``;
none in a program without them). Sweeps scored on the host send none."""

COUNTER = "sweep.h2d_bytes"


def read(run: dict):
    try:
        from repro.obs.trace import recent
    except ImportError:
        return None
    held = [s for s in recent() if s["name"] == "refine"]
    k = min(run["decisions"], len(held))
    if k == 0:
        return None
    held = held[-k:]
    return sum(s["counters"].get(COUNTER, 0.0) for s in held) / k
