"""§3 — scheduler wall-time vs the exhaustive optimal search, plus the
scheduler-engine perf baseline (``BENCH_sched.json``).

The paper reports the optimal scheduler checking 27 405 possibilities in
~18 hours on a 4-socket Xeon server. Our batched closed-form evaluator
(beyond-paper: multiset placement collapse + type-symmetry pruning +
vectorized max-stable-rate scoring) covers a *larger* design space in
seconds on one CPU; the proposed heuristic is another 2-3 orders faster.

``BENCH_sched.json`` records the perf trajectory future PRs regress
against: large-scenario (20/70/90 machines, 478 tasks) ``schedule()`` wall
time for the reference vs incremental engines (with an identity check on
the resulting schedule), and ``simulate_batch`` placements/sec for the
NumPy and JAX backends.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks.common import emit
from repro.core import (
    linear_topology,
    optimal_schedule,
    paper_cluster,
    schedule,
    simulate_batch,
)
from repro.core.refine import refine

LARGE = (20, 70, 90)
SIM_BATCH = 2048


def bench_engines(skip_reference: bool = False) -> dict:
    """Large-scenario schedule() wall time: reference vs incremental."""
    cluster = paper_cluster(LARGE)
    topo = linear_topology()

    t0 = time.perf_counter()
    inc = schedule(topo, cluster, r0=1.0, rate_epsilon=1.0, engine="incremental")
    t_inc = time.perf_counter() - t0

    out = {
        "scenario": "large_linear_20_70_90",
        "tasks": int(inc.etg.total_tasks),
        "iterations": inc.iterations,
        "rate": inc.rate,
        "incremental_s": round(t_inc, 4),
    }
    if not skip_reference:
        t0 = time.perf_counter()
        ref = schedule(topo, cluster, r0=1.0, rate_epsilon=1.0, engine="reference")
        t_ref = time.perf_counter() - t0
        out["reference_s"] = round(t_ref, 4)
        out["speedup"] = round(t_ref / max(t_inc, 1e-9), 1)
        out["identical_schedule"] = bool(
            ref.rate == inc.rate
            and np.array_equal(ref.etg.n_instances, inc.etg.n_instances)
            and np.array_equal(ref.etg.task_machine(), inc.etg.task_machine())
        )
    return out


def bench_sim_backends() -> dict:
    """simulate_batch placements/sec, NumPy vs JAX, medium scenario."""
    cluster = paper_cluster((10, 10, 10))
    etg = schedule(linear_topology(), cluster, r0=1.0, rate_epsilon=1.0).etg
    rng = np.random.default_rng(0)
    tm = rng.integers(0, cluster.n_machines, size=(SIM_BATCH, etg.total_tasks))
    r0 = 60.0

    t0 = time.perf_counter()
    simulate_batch(etg, cluster, tm, r0, backend="numpy")
    t_np = time.perf_counter() - t0
    out = {
        "batch": SIM_BATCH,
        "tasks": int(etg.total_tasks),
        "numpy_placements_per_s": round(SIM_BATCH / t_np, 1),
    }
    simulate_batch(etg, cluster, tm, r0, backend="jax")  # compile
    t0 = time.perf_counter()
    simulate_batch(etg, cluster, tm, r0, backend="jax")
    t_jax = time.perf_counter() - t0
    out["jax_placements_per_s"] = round(SIM_BATCH / t_jax, 1)
    out["jax_speedup"] = round(t_np / t_jax, 1)
    return out


def main(json_path: str | None = None, skip_reference: bool = False) -> None:
    cluster = paper_cluster((1, 1, 1))
    topo = linear_topology()

    t0 = time.perf_counter()
    sched = schedule(topo, cluster, r0=1.0, rate_epsilon=0.05)
    refine(sched.etg, cluster)
    t_heur = time.perf_counter() - t0

    t0 = time.perf_counter()
    opt = optimal_schedule(topo, cluster, max_total_tasks=10)
    t_opt = time.perf_counter() - t0

    emit(
        "sec3_scheduler_walltime",
        t_heur * 1e6,
        f"heuristic={t_heur*1e3:.1f}ms;optimal={t_opt:.2f}s;"
        f"candidates={opt.candidates_evaluated};"
        f"paper_optimal=18h@27405cands;"
        f"speedup_vs_paper={(18*3600)/max(t_opt,1e-9):,.0f}x",
    )

    engines = bench_engines(skip_reference=skip_reference)
    emit(
        "sched_engine_large",
        engines["incremental_s"] * 1e6,
        ";".join(f"{k}={v}" for k, v in engines.items() if k != "incremental_s"),
    )
    sim = bench_sim_backends()
    emit(
        "sim_batch_backends",
        0.0,
        ";".join(f"{k}={v}" for k, v in sim.items()),
    )

    if json_path:
        with open(json_path, "w") as f:
            json.dump({"schedule": engines, "simulate_batch": sim}, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--json", default=None, help="write BENCH_sched.json here")
    parser.add_argument(
        "--skip-reference",
        action="store_true",
        help="skip the ~12-25 s reference-engine timing (noisy CI runners)",
    )
    args = parser.parse_args()
    main(json_path=args.json, skip_reference=args.skip_reference)
