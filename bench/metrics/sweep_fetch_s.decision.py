"""Seconds per decision in the program's ``sweep.fetch`` spans: reading
each device sweep's rates and throughput back to the host, which waits
there for the sweep to finish; read from its per-decision summaries
(``repro.obs.trace.recent()``; none in a program without them)."""

SPAN = "sweep.fetch"


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
