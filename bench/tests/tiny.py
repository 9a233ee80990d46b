"""A cell's own files cut to a size a CPU test run holds, and a helper
that runs ``bench/run.py``'s ``main`` on the CPU (the look for a chip is
skipped) and returns the result line and the standard error."""

from __future__ import annotations

import contextlib
import io
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH / "reference"), str(BENCH / "harness"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import cells  # noqa: E402

COUNTS = [2, 3, 4]


def tiny_cell(name: str) -> cells.Cell:
    """The cell of BENCHMARK.json on a 2/3/4 cluster: the deployed placement
    is the program's Alg. 1+2 there and requests slow 2 machines."""
    from repro.core import schedule
    from system import program_cluster, program_topology

    cell = cells.find_cell(cells.load_spec(), name)
    config = json.loads(json.dumps(cell.config))
    config["cluster"]["counts"] = COUNTS
    if "deployed" in config:
        etg = schedule(
            program_topology(config["topology"]),
            program_cluster(config["cluster"]),
            r0=1.0,
            rate_epsilon=1.0,
        ).etg
        config["deployed"] = [a.tolist() for a in etg.assignment]
    traffic = dict(cell.traffic)
    if "slow_machines" in traffic:
        traffic["slow_machines"] = 2
    return cells.Cell(cell.name, cell.chips, config, traffic, cell.end_to_end, cell.per_layer)


def run_main(monkeypatch, name: str, trace: int = 0, seed: int = 2**31 + 11):
    """(result line as a dict, standard error) of one CPU run of the tiny
    cell ``name``."""
    import jax

    import run

    cell = tiny_cell(name)
    monkeypatch.setattr(cells, "find_cell", lambda spec, n, root=cells.ROOT: cell)
    monkeypatch.setattr(run, "accelerator", lambda chips: jax.devices())
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main([
            "--workload", name, "--seed", str(seed), "--seconds", "0.3",
            "--trace", str(trace),
        ])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()
