"""The readings a cell's limits are set from, on the chip: the program's
(the lower readings) and its control's (the upper readings), the control
being the plain reference put in the program's place in the precision
below the configuration's (float32 for float64).

    python bench/control.py --workload <cell> --pool-seeds <n> [<n> ...] [--program]

For each pool seed the cell's traffic draws a pool of requests from that
seed in place of its own ``pool_seed``, so every pool seed brings new
requests. Each is answered by the control (``Workload.control``), or by
the program with ``--program``, and judged by the float64 reference
exactly as a run judges the program. Prints one JSON line per pool seed
with the worst reading of each number, then one with the largest (the
program) or smallest (the control) of those. The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH / "harness"), str(BENCH / "reference"), str(BENCH.parent / "src")]

import cells  # noqa: E402


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--pool-seeds", type=int, nargs="+", required=True)
    p.add_argument("--program", action="store_true")
    args = p.parse_args(argv)
    cell = cells.find_cell(cells.load_spec(), args.workload)
    kind = cells.load_module("kinds", cell.traffic["kind"])
    wl = kind.Workload(cell.config, cell.traffic)
    if args.program:
        from repro.compile_cache import setup_compile_cache

        setup_compile_cache()
    per_pool = []
    for pool_seed in args.pool_seeds:
        traffic = dict(cell.traffic, pool_seed=pool_seed)
        worst: dict = {}
        for request in kind.requests(cell.config, traffic, 0):
            t0 = time.perf_counter()
            answer = wl.decide(request)[0] if args.program else wl.control(request, np.float32)
            numbers = wl.check(request, answer)
            print(f"pool {pool_seed} request {request['id']} {time.perf_counter() - t0:.1f} s "
                  f"{json.dumps(numbers)}", file=sys.stderr, flush=True)
            for k, v in numbers.items():
                worst[k] = max(worst.get(k, v), v)
        per_pool.append(worst)
        print(json.dumps({"pool_seed": pool_seed, "worst": worst}), flush=True)
    pick = max if args.program else min
    summary = {k: pick(w[k] for w in per_pool) for k in per_pool[0]}
    print(json.dumps({"program" if args.program else "control": summary, "limits": wl.limits}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
