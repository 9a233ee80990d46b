"""Host seconds per decision spent pricing cut traffic: the self time of
the program's ``net.host`` spans (``cost_model.network_unit_load``, the
NumPy cut-traffic term), read from its per-decision summaries
(``repro.obs.trace.recent()``). A program without the span reports
nothing."""

SPAN = "net.host"


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
    if not any(SPAN in s["self_s"] for s in held):
        return None
    return sum(s["self_s"].get(SPAN, 0.0) for s in held) / k
