"""Share of the window's candidate rows that device sweeps scored as edits
of one base row: the ``sweep.edit_rows`` counter over the ``refine.rows``
counter of the window's decisions, in percent, read from the program's
per-decision summaries (``repro.obs.trace.recent()``). A program without
the edit path (no ``ScheduleState.score_relocate_swap``) reports nothing; one
with it and no edit sweep in the window reads 0."""

COUNTER = "sweep.edit_rows"
BASE = "refine.rows"


def read(run: dict):
    try:
        from repro.core.schedule_state import ScheduleState
        from repro.obs.trace import recent
    except ImportError:
        return None
    if not hasattr(ScheduleState, "score_relocate_swap"):
        return None
    held = [s for s in recent() if s["name"] == "refine"]
    k = min(run["decisions"], len(held))
    if k == 0:
        return None
    held = held[-k:]
    rows = sum(s["counters"].get(BASE, 0.0) for s in held)
    if rows == 0:
        return None
    return 100.0 * sum(s["counters"].get(COUNTER, 0.0) for s in held) / rows
