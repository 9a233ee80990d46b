"""Multi-tenant scheduling benchmark (``BENCH_multitenant.json``).

Three question groups, each with recorded acceptance gates (ISSUE 7):

* **scale** — ~100 tenants on one shared heterogeneous cluster must
  schedule in seconds, with the shared-load invariant intact and no
  tenant below its guaranteed floor (``fair_slice_floors`` — the
  warm-start baseline, re-verified here against an independent
  recomputation). Two cluster variants: roomy machines (most fair
  slices host their tenant — the no-regression gate is non-vacuous)
  and paper-capacity machines (thin slices exercise the MET-deferral
  path).
* **batching** — scoring candidate rows of many tenants through one
  tenant-batched per-row-capacity call vs the explicit per-tenant
  residual loop: reported speedup plus max |diff| (parity is the test
  suite's job; the bench records it anyway).
* **runtime** — a small fleet executes its traces on the shared capacity
  grid; per-tenant satisfaction and arbiter admissions are recorded.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks.common import emit
from repro.core import (
    ScheduleState,
    diamond_topology,
    jain_index,
    linear_topology,
    paper_cluster,
    rolling_count_topology,
    star_topology,
)
from repro.multitenant import (
    MultiTenantRuntime,
    MultiTenantState,
    Tenant,
    TenantSet,
    TenantBatchScorer,
    compile_tenant_traces,
    fair_slice_floors,
    schedule_tenants,
)
from repro.runtime_stream import TraceSpec

SEED = 0
TOPOS = [linear_topology, diamond_topology, star_topology, rolling_count_topology]

# Large-fleet knobs: a light warm refine and one structural attempt per
# tenant keep 100 tenants in single-digit seconds; the guarantees
# (invariant, fair-slice no-regression) do not depend on these budgets.
FLEET_KW = dict(warm_refine_rounds=2, structure_attempts=1, refine_moves=1)


def _fleet(n_tenants: int, rng: np.random.Generator) -> list[Tenant]:
    tenants = []
    for i in range(n_tenants):
        tenants.append(
            Tenant(
                name=f"t{i:03d}",
                utg=TOPOS[i % len(TOPOS)](),
                target_rate=float(rng.uniform(20, 200)),
                priority=float(rng.choice([1.0, 1.0, 2.0, 4.0])),
            )
        )
    return tenants


def _no_regression(tenants, cluster, ms) -> tuple[bool, int]:
    """Re-verify the warm-start guarantee against independently recomputed
    floors (``fair_slice_floors`` with the same refine budget the run
    used): every tenant's solo rate on its fair slice of the MET-reduced
    working capacity, 0 for deferred tenants — theirs holds trivially, so
    only floors > 0 count as non-vacuous. Returns (all_ok, n_nonvacuous)."""
    floors = fair_slice_floors(
        tenants, cluster, warm_refine_rounds=FLEET_KW["warm_refine_rounds"]
    )
    rates = np.array([ms.allocation(t.name).rate for t in tenants])
    ok = bool(np.all(rates >= floors * (1.0 - 1e-6)))
    return ok, int(np.sum(floors > 0.0))


def scale_row(n_tenants: int, counts, cap_scale: float, label: str) -> dict:
    rng = np.random.default_rng(SEED)
    tenants = _fleet(n_tenants, rng)
    cluster = paper_cluster(counts)
    cluster = cluster.with_capacity(cluster.capacity * cap_scale)

    t0 = time.perf_counter()
    ms = schedule_tenants(tenants, cluster, validate=False, **FLEET_KW)
    wall = time.perf_counter() - t0

    states = [
        ScheduleState.from_etg(a.etg, cluster, skew=t.skew)
        for a, t in zip(ms.allocations, tenants)
    ]
    mt = MultiTenantState(TenantSet(tenants), cluster, states, rates=ms.rates)
    feasible = mt.feasible(slack=1e-9)
    no_reg, nonvacuous = _no_regression(tenants, cluster, ms)
    levels = ms.levels
    return {
        "label": label,
        "n_tenants": n_tenants,
        "n_machines": cluster.n_machines,
        "capacity_per_machine": float(cluster.capacity[0]),
        "wall_s": round(wall, 3),
        "rounds": ms.rounds,
        "candidates_evaluated": ms.candidates_evaluated,
        "total_rate": round(float(ms.rates.sum()), 3),
        "min_level": float(levels.min()),
        "median_level": float(np.median(levels)),
        "jain_index_levels": round(jain_index(levels), 4),
        "feasible": bool(feasible),
        "no_regression_vs_fair_slice": bool(no_reg),
        "nonvacuous_baselines": nonvacuous,
        "under_60s": bool(wall < 60.0),
    }


def batching_case(n_tenants: int = 20):
    """(mt, sweeps) of the batching comparison: every tenant's single-task
    relocation rows on a shared 12-machine cluster at 90% of its rate."""
    rng = np.random.default_rng(SEED)
    tenants = _fleet(n_tenants, rng)
    cluster = paper_cluster((4, 4, 4))
    ms = schedule_tenants(tenants, cluster, **FLEET_KW)
    states = [
        ScheduleState.from_etg(a.etg, cluster) for a in ms.allocations
    ]
    mt = MultiTenantState(
        TenantSet(tenants), cluster, states, rates=ms.rates * 0.9
    )
    m = cluster.n_machines
    sweeps = []
    for t, st in enumerate(mt.states):
        base = st.task_machine()
        rows = []
        for col in range(base.shape[0]):
            for dest in range(m):
                if dest == base[col]:
                    continue
                row = base.copy()
                row[col] = dest
                rows.append(row)
        sweeps.append((t, np.stack(rows)))
    return mt, sweeps


def batching_row(n_tenants: int = 20) -> dict:
    """Tenant-batched met-fold scoring vs the per-tenant residual loop."""
    mt, sweeps = batching_case(n_tenants)
    n_rows = sum(r.shape[0] for _, r in sweeps)

    scorer = TenantBatchScorer(mt, backend="auto")
    t0 = time.perf_counter()
    batched = scorer.score(sweeps)
    t_batched = time.perf_counter() - t0

    t0 = time.perf_counter()
    looped = [scorer.reference_scores(t, rows) for t, rows in sweeps]
    t_loop = time.perf_counter() - t0

    diff = max(
        float(np.max(np.abs(b[0] - l[0]))) if b[0].size else 0.0
        for b, l in zip(batched, looped)
    )
    return {
        "n_tenants": n_tenants,
        "candidate_rows": n_rows,
        "batched_s": round(t_batched, 4),
        "per_tenant_loop_s": round(t_loop, 4),
        "speedup": round(t_loop / max(t_batched, 1e-9), 2),
        "max_abs_rate_diff": diff,
        "parity_1e9": bool(diff <= 1e-9),
    }


def runtime_row() -> dict:
    tenants = TenantSet(
        [
            Tenant(name="alice", utg=linear_topology(), target_rate=8.0),
            Tenant(name="bob", utg=diamond_topology(), target_rate=8.0, priority=2.0),
            Tenant(name="carol", utg=star_topology(), target_rate=6.0),
        ]
    )
    cluster = paper_cluster((2, 2, 2))
    ms = schedule_tenants(list(tenants), cluster)
    specs = [
        TraceSpec(name=t.name, n_windows=96, base_rate=0.8 * ms.rates[i])
        for i, t in enumerate(tenants)
    ]
    mtrace = compile_tenant_traces(tenants, specs, cluster, seed=SEED)
    rt = MultiTenantRuntime(ms, tenants, cluster, mtrace)
    t0 = time.perf_counter()
    res = rt.run(online=True, moves_per_period=4)
    wall = time.perf_counter() - t0
    admitted = [int(ok) for *_rest, ok in res.arbiter_log]
    return {
        "n_tenants": len(tenants),
        "n_windows": mtrace.n_windows,
        "wall_s": round(wall, 3),
        "allocated_rates": [round(float(r), 3) for r in ms.rates],
        "satisfaction": [round(float(s), 3) for s in res.satisfaction],
        "arbiter_requests": len(res.arbiter_log),
        "arbiter_admitted": int(sum(admitted)),
        "all_served": bool(np.all(res.satisfaction > 0.0)),
    }


def main(json_path: str | None = None) -> None:
    rows = {
        "scale": [
            scale_row(100, (20, 30, 40), cap_scale=4.0, label="roomy_90x400"),
            scale_row(100, (20, 30, 40), cap_scale=1.0, label="paper_90x100"),
        ],
        "batching": batching_row(),
        "runtime": runtime_row(),
    }
    for row in rows["scale"]:
        emit(
            f"multitenant_scale_{row['label']}",
            row["wall_s"] * 1e6,
            f"tenants={row['n_tenants']};rounds={row['rounds']};"
            f"feasible={row['feasible']};no_regression={row['no_regression_vs_fair_slice']};"
            f"jain={row['jain_index_levels']};under_60s={row['under_60s']}",
        )
    b = rows["batching"]
    emit(
        "multitenant_batching",
        b["batched_s"] * 1e6,
        f"rows={b['candidate_rows']};speedup={b['speedup']};parity={b['parity_1e9']}",
    )
    r = rows["runtime"]
    emit(
        "multitenant_runtime",
        r["wall_s"] * 1e6,
        f"tenants={r['n_tenants']};satisfaction={r['satisfaction']};"
        f"all_served={r['all_served']}",
    )
    if json_path:
        with open(json_path, "w") as f:
            json.dump(rows, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--json", default=None, help="write BENCH_multitenant.json here")
    args = parser.parse_args()
    main(json_path=args.json)
