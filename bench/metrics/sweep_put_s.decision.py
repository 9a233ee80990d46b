"""Seconds per decision in the program's ``sweep.put`` spans: the
``jax.device_put`` of each device sweep's operands, read from its
per-decision summaries (``repro.obs.trace.recent()``; none in a program
without them)."""

SPAN = "sweep.put"


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
    return sum(s["self_s"].get(SPAN, 0.0) for s in held) / k
