"""Device busy time, idle gaps and top operations from a profiler trace.

``capture`` wraps the measured window in ``jax.profiler`` tracing (host
Python tracing off) and a host span named ``WINDOW``; ``load_events``
flattens the written ``.xplane.pb`` into plain lists; ``reduce`` turns
those lists into the numbers a run reports. ``reduce`` works on the plain
lists alone, so a small recorded trace checks it (tests/).

Busy time is the union of the intervals in which an operation ran on a
device, clipped to the window and averaged over the chips used; the idle
share is 1 - busy / window. Each idle gap is named by the innermost host
span on the window's thread that covers its midpoint: what the host was
doing while the device waited.
"""

from __future__ import annotations

import contextlib
import glob
import re
import shutil
from pathlib import Path

WINDOW = "bench.window"
TOP = 10
# Planes of chips ("/device:TPU:0"), not the profiler's own planes
# ("/device:CUSTOM:Megascale Trace").
CHIP = re.compile(r"/device:[A-Z]+:\d+$")


@contextlib.contextmanager
def capture(out_dir: Path):
    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(out_dir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def load_events(out_dir: Path) -> dict:
    """{"device": {plane: [[op, start_ns, dur_ns], ...]},
    "host": [[line, name, start_ns, dur_ns], ...]} of the newest trace."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(str(out_dir / "**" / "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {out_dir}")
    data = ProfileData.from_file(paths[-1])
    device: dict = {}
    host: list = []
    for plane in data.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            device[plane.name] = [
                [ev.name, ev.start_ns, ev.duration_ns] for ln in ops for ev in ln.events
            ]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                host += [[ln.name, ev.name, ev.start_ns, ev.duration_ns] for ev in ln.events]
    return {"device": device, "host": host}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce(events: dict, chips: int) -> dict | None:
    """busy_s, window_s, device_ops and idle_gaps of one traced window;
    None when the trace holds no window or no device operation in it."""
    spans = [h for h in events["host"] if h[1] == WINDOW]
    planes = sorted(p for p in events["device"] if CHIP.match(p))[:chips]
    if not spans or not planes:
        return None
    line, _, w0, wdur = spans[0]
    w1 = w0 + wdur
    per_op: dict[str, float] = {}
    busy = []
    unions = []
    for plane in planes:
        clipped = []
        for name, start, dur in events["device"][plane]:
            a, b = max(start, w0), min(start + dur, w1)
            if b > a:
                clipped.append((a, b))
                op = name.split(" = ")[0].lstrip("%")
                per_op[op] = per_op.get(op, 0.0) + (b - a) * 1e-9 / len(planes)
        u = _union(clipped)
        unions.append(u)
        busy.append(sum(b - a for a, b in u) * 1e-9)
    if sum(busy) <= 0:
        return None
    gaps = []
    edge = w0
    for a, b in unions[0] + [(w1, w1)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    host = [h for h in events["host"] if h[0] == line and h[1] != WINDOW]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = (a + b) / 2
        covering = [h for h in host if h[2] <= mid <= h[2] + h[3]]
        name = min(covering, key=lambda h: h[3])[1] if covering else WINDOW
        named.append([name, (b - a) * 1e-9])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": wdur * 1e-9,
        "device_ops": [[k, v] for k, v in ops],
        "idle_gaps": named,
    }
