"""Share of the traced window in which no operation ran on the device,
in %: 1 - busy / window, from the profiler trace (harness/devtrace.py)."""


def read(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
