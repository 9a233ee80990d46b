"""Run one cell of the benchmark once, on the chip it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, kind of request and metrics are
found by name (harness/cells.py). Set-up builds the program's inputs from
the configuration, has the kind draw the request cycle from the traffic
file and ``--seed`` (``kinds/<kind>.py``: ``requests``) and serves every
request of the cycle once, so every program the window runs is compiled or
loaded before it. The window then serves the cycle again and again, one
request in flight (a closed loop), and starts no new cycle once
``--seconds`` have passed. With ``--trace 1`` the window runs under the
profiler and the run reports the per-layer metrics; otherwise the
end-to-end ones. After the window every answer is compared with the plain
reference (reference/), and each number compared is printed beside its
limit, on standard error and last in the result line.

The last line of standard output is the result, as one JSON object. With
no TPU, or fewer chips than the cell asks for, the run prints no result
and exits with 2. JAX's compile cache is kept in ``.jax_cache`` at the
root of the checkout, also where ``JAX_COMPILATION_CACHE_DIR`` names
another directory: a cache outside the checkout could be shared with
another checkout's runs.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH / "harness"), str(BENCH / "reference"), str(ROOT / "src")]

import cells  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_out" / "trace"


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def accelerator(chips: int):
    """The devices the cell runs on, or None (with a message) when JAX
    finds no TPU or fewer chips than the cell asks for."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(
            f"bench: needs {chips} TPU chip(s), JAX found {len(devices)} "
            f"{devices[0].platform} device(s)",
            file=sys.stderr,
        )
        return None
    return devices


def serve(workload, requests, seconds: float, traced: bool, counter, chips: int):
    """Warm-up, then the measured window; the run's record."""
    import devtrace

    for request in requests:
        workload.decide(request)
    setup_s = time.perf_counter() - T_START
    fresh, hits = counter.snapshot()
    print(f"setup {setup_s!r} s, {fresh} programs compiled, {hits} loaded", file=sys.stderr)
    c0 = fresh + hits
    window = devtrace.capture(TRACE_DIR) if traced else contextlib.nullcontext()
    decisions = []
    with window:
        w0 = time.perf_counter()
        while True:
            for request in requests:
                t0 = time.perf_counter()
                answer, tally = workload.decide(request)
                decisions.append((request, answer, tally))
                print(
                    f"decision {request['id']} {time.perf_counter() - t0!r} s",
                    file=sys.stderr,
                )
            if time.perf_counter() - w0 >= seconds:
                break
        window_s = time.perf_counter() - w0
    record = {
        "setup_s": setup_s,
        "window_s": window_s,
        "decisions": len(decisions),
        "tallies": [t for _, _, t in decisions],
        "window_compiles": sum(counter.snapshot()) - c0,
        "trace": None,
    }
    if traced:
        record["trace"] = devtrace.reduce(devtrace.load_events(TRACE_DIR), chips)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return record, decisions


def judge(workload, decisions):
    """Worst reading of each number over every answer of the window, and
    how many answers broke a limit. Answers repeat with their request, so
    each distinct answer is compared once."""
    limits = workload.limits
    seen: dict = {}
    worst: dict = {}
    failed = 0
    for request, answer, _ in decisions:
        key = (request["id"], json.dumps(answer))
        if key not in seen:
            seen[key] = workload.check(request, answer)
        numbers = seen[key]
        failed += any(numbers[k] > limits[k] for k in limits)
        for k, v in numbers.items():
            worst[k] = max(worst.get(k, v), v)
    checks = {k: {"value": worst[k], "limit": limits[k]} for k in limits}
    return checks, failed


def main(argv) -> int:
    args = parse(argv)
    cell = cells.find_cell(cells.load_spec(), args.workload)
    devices = accelerator(cell.chips)
    if devices is None:
        return 2
    from compiles import CompileCounter

    counter = CompileCounter()
    kind = cells.load_module("kinds", cell.traffic["kind"])
    workload = kind.Workload(cell.config, cell.traffic)
    requests = kind.requests(cell.config, cell.traffic, args.seed)
    record, decisions = serve(
        workload, requests, args.seconds, bool(args.trace), counter, cell.chips
    )
    used = devices[: cell.chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in used)
    checks, failed = judge(workload, decisions)
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())

    metrics = {}
    for m in cell.per_layer if args.trace else cell.end_to_end:
        value = cells.load_module("metrics", m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    result = {
        "correct": correct,
        "attempted": record["decisions"],
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if record["trace"] is not None:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        result["breakdown"] = {
            "device_ops": record["trace"]["device_ops"],
            "idle_gaps": record["trace"]["idle_gaps"],
        }
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
