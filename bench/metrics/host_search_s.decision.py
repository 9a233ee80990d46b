"""Host seconds per decision spent searching, outside every scoring sweep:
the self time of the program's ``refine``, ``refine.round`` and
``refine.build`` spans (move enumeration, candidate rows, winner
picking), read from its per-decision summaries
(``repro.obs.trace.recent()``; none in a program without them)."""

SPANS = ("refine", "refine.round", "refine.build")


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
    return sum(s["self_s"].get(n, 0.0) for s in held for n in SPANS) / k
