"""Share of the window's candidate rows whose cut-traffic term and memory
mask the device computed: the ``sweep.net_rows`` counter over the
``refine.rows`` counter of the window's decisions, in percent, read from
the program's per-decision summaries (``repro.obs.trace.recent()``). A
program that prices cut traffic only on the host (no
``sim_jax.device_resources``) reports nothing; one with the device term
and no such sweep in the window reads 0."""

COUNTER = "sweep.net_rows"
BASE = "refine.rows"


def read(run: dict):
    try:
        from repro.core import sim_jax
        from repro.obs.trace import recent
    except ImportError:
        return None
    if not hasattr(sim_jax, "device_resources"):
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
