"""Scoring sweeps dispatched per decision: entries of the program's
dispatch log (``repro.obs``) over the window's decisions."""


def read(run: dict):
    return sum(len(t["sweeps"]) for t in run["tallies"]) / run["decisions"]
