"""Relocate+swap device sweeps per refine round that scored the menu: the
``sweep.rs_sweeps`` counter over the ``refine.rs_menus`` counter (one per
relocate+swap menu with a candidate, which refine scores once a round) of
the window's decisions, read from the program's per-decision summaries
(``repro.obs.trace.recent()``). A program without the menu counter
reports nothing; one whose relocate+swap sweeps run on the host reads 0."""

COUNTER = "sweep.rs_sweeps"
BASE = "refine.rs_menus"


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
    menus = sum(s["counters"].get(BASE, 0.0) for s in held)
    if menus == 0:
        return None
    return sum(s["counters"].get(COUNTER, 0.0) for s in held) / menus
