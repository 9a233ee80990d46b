"""Candidate rows the program scored per decision: the ``refine.rows``
counter of the window's decisions, read from the program's per-decision
summaries (``repro.obs.trace.recent()``; none in a program without them)."""

COUNTER = "refine.rows"


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
