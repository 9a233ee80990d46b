"""Bytes of per-cluster tables the program's device sweeps sent from host
to device per decision: the ``sweep.table_h2d_bytes`` counter (``e_cm``,
``met_cm``, ``capacity`` and the resource tables of each device sweep, a
part of ``sweep.h2d_bytes``) of the window's decisions, read from the
program's per-decision summaries (``repro.obs.trace.recent()``). A program
without the counter, or whose window ran no device sweep, reports
nothing."""

COUNTER = "sweep.table_h2d_bytes"


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
    if not any(COUNTER in s["counters"] for s in held):
        return None
    return sum(s["counters"].get(COUNTER, 0.0) for s in held) / k
