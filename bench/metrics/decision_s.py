"""Mean wall time of one decision as the caller sees it: the window's
wall time over the decisions completed in it (closed loop, one decision
in flight, each ending with its answer on the host)."""


def read(run: dict):
    return run["window_s"] / run["decisions"]
