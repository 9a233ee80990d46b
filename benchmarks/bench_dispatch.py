"""Calibrate the closed-form scorer's NumPy/JAX dispatch crossovers.

``max_stable_rate_batch`` / ``ScheduleState.score_task_machine_batch`` can
run the eq. 5 closed form either through NumPy's sequential ``np.add.at``
accumulation (the bit-exact reference) or through the scatter-free jitted
JAX kernel (one-hot contraction, ~1e-15 relative agreement). The JAX path
pays a fixed dispatch cost per call and does B*T*m work versus NumPy's
B*T, so ``backend="auto"`` needs per-regime crossovers: element floors
(below which NumPy wins) plus a machine-count gate (above which the dense
contraction loses on CPU).

This benchmark times both backends over (scenario × regime × batch size):
scenarios span paper-realistic clusters (3 / 6 / 15 machines) plus the
wide-cluster ``stress`` shape (180 machines, the paper's 20/70/90 large
scenario), and each scenario is swept through all three kernel regimes —
``shared`` ((T,) task maps), ``per_row`` ((B, T) maps, lockstep growth
sweeps) and ``skew`` (realized fields-grouping rates on a keyed topology).
Everything lands in ``BENCH_dispatch.json``.

Recorded calibration (2-core CPU-only container): the scatter-free kernel
beats NumPy 1.5-6x on every realistic scenario once the sweep clears the
per-regime element floors (``simulator._CLOSED_FORM_AUTO_THRESHOLDS``), so
``"auto"`` picks JAX there; on the 180-machine stress shape the m-fold
contraction overhead flips the verdict at every size, which is exactly
what ``simulator._AUTO_MAX_MACHINES`` encodes. Re-run on new hardware and
override via ``REPRO_CLOSED_FORM_JAX_THRESHOLD`` (all regimes) or
``REPRO_CLOSED_FORM_JAX_THRESHOLD_{SHARED,PER_ROW,SKEW}`` if the picture
differs.

``--check`` replays ``resolve_closed_form_backend`` over a recorded grid
and fails if "auto" ever selects a backend slower than the recorded NumPy
time — the CI smoke gate for dispatch regressions.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from benchmarks.common import emit
from repro.core import (
    keyed_rolling_count_topology,
    paper_cluster,
    schedule,
    wide_fanout_topology,
)
from repro.core.schedule_state import ScheduleState
from repro.core.simulator import (
    _AUTO_MAX_MACHINES,
    _AUTO_MAX_WORK,
    _CLOSED_FORM_AUTO_THRESHOLDS,
    resolve_closed_form_backend,
)

# Batch sizes swept per (scenario, regime) — rows per scored sweep.
BATCH_SIZES = (1, 8, 64, 256, 1024, 4096, 16384)
# (cluster counts, label, max batch). The three realistic scenarios track
# paper-scale clusters where the scatter-free path should win; ``stress``
# keeps the 20/70/90 wide cluster as an honest diagnostic of where the
# dense contraction loses (capped batch: the losing kernel is slow).
SCENARIOS = (
    ((1, 1, 1), "small", 16384),
    ((2, 2, 2), "medium", 16384),
    ((4, 5, 6), "large", 16384),
    ((20, 70, 90), "stress", 4096),
)
REGIMES = ("shared", "per_row", "skew")


def _skew_state(cluster) -> ScheduleState:
    """A ScheduleState carrying a realized fields-grouping skew model
    (keyed topology, key realization drawn at trace compile time)."""
    from repro.runtime_stream import StreamExecutor, TraceSpec

    utg = keyed_rolling_count_topology(n_keys=16, zipf_s=1.5)
    etg = schedule(utg, cluster, r0=1.0, rate_epsilon=1.0).etg
    probe = StreamExecutor(
        etg, cluster, TraceSpec(name="probe", n_windows=2, base_rate=1.0), seed=5
    )
    return ScheduleState.from_etg(etg, cluster, skew=probe.skew_model_at(0))


def _time_backend(state: ScheduleState, tm: np.ndarray, backend: str,
                  n_instances: np.ndarray | None = None,
                  iters: int = 5) -> float:
    """Median wall time (s) of one scored sweep (post-warmup, so the JAX
    number is steady-state dispatch, not compilation)."""
    for _ in range(2):
        state.score_task_machine_batch(tm, n_instances=n_instances,
                                       backend=backend)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        state.score_task_machine_batch(tm, n_instances=n_instances,
                                       backend=backend)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench_dispatch() -> dict:
    rng = np.random.default_rng(0)
    grid = []
    crossovers = []
    auto_picks_jax = False
    for counts, label, max_batch in SCENARIOS:
        cluster = paper_cluster(counts)
        m = cluster.n_machines
        sched = schedule(wide_fanout_topology(6), cluster,
                         r0=1.0, rate_epsilon=1.0)
        plain = ScheduleState.from_etg(sched.etg, cluster)
        skewed = _skew_state(cluster)
        for regime in REGIMES:
            state = skewed if regime == "skew" else plain
            T = int(state.n_instances.sum())
            n = state.utg.n_components
            rows = []
            for B in BATCH_SIZES:
                if B > max_batch:
                    continue
                tm = rng.integers(0, m, size=(B, T))
                n_inst = (
                    np.tile(state.n_instances, (B, 1))
                    if regime == "per_row"
                    else None
                )
                t_np = _time_backend(state, tm, "numpy", n_inst)
                elements = B * T
                auto = resolve_closed_form_backend(
                    "auto", elements, regime=regime, n_machines=m
                )
                auto_picks_jax = auto_picks_jax or auto == "jax"
                row = {
                    "scenario": label,
                    "regime": regime,
                    "machines": m,
                    "tasks": T,
                    "components": n,
                    "batch": B,
                    "elements": elements,
                    "numpy_us": round(t_np * 1e6, 1),
                    "auto_backend": auto,
                }
                t_jax = _time_backend(state, tm, "jax", n_inst)
                row["jax_us"] = round(t_jax * 1e6, 1)
                row["jax_speedup"] = round(t_np / max(t_jax, 1e-12), 2)
                rows.append(row)
            grid.extend(rows)
            # Crossover = smallest sweep from which JAX wins by a real
            # margin (10%+) at that size and every larger one — a single
            # noisy win on a microsecond-scale batch is not a crossover.
            for i, row in enumerate(rows):
                if all(r["jax_speedup"] >= 1.1 for r in rows[i:]):
                    crossovers.append(
                        {
                            "scenario": label,
                            "regime": regime,
                            "machines": m,
                            "tasks": T,
                            "crossover_elements": row["elements"],
                        }
                    )
                    break
    return {
        "grid": grid,
        "crossovers": crossovers,
        "auto_thresholds": dict(_CLOSED_FORM_AUTO_THRESHOLDS),
        "auto_max_machines": _AUTO_MAX_MACHINES,
        "auto_max_work": _AUTO_MAX_WORK,
        "auto_picks_jax": auto_picks_jax,
    }


def check(json_path: str) -> int:
    """Smoke gate: replay auto dispatch over a recorded grid; any pick that
    the recording shows losing to NumPy is a failure. Run without
    REPRO_CLOSED_FORM_JAX_THRESHOLD* overrides."""
    with open(json_path) as f:
        recorded = json.load(f)
    failures = []
    picked_jax = 0
    for row in recorded["grid"]:
        auto = resolve_closed_form_backend(
            "auto", row["elements"], regime=row["regime"],
            n_machines=row["machines"],
        )
        if auto == "jax":
            picked_jax += 1
            if row["jax_us"] > row["numpy_us"]:
                failures.append(
                    f"auto picked jax but recorded jax_us={row['jax_us']} > "
                    f"numpy_us={row['numpy_us']} at {row['scenario']}/"
                    f"{row['regime']} B={row['batch']} ({row['elements']} el)"
                )
    if picked_jax == 0:
        failures.append("auto never picked jax anywhere on the recorded grid")
    for msg in failures:
        print(f"DISPATCH-CHECK FAIL: {msg}")
    if not failures:
        print(
            f"dispatch check ok: {picked_jax} grid points route to jax, "
            "none slower than numpy"
        )
    return 1 if failures else 0


def main(json_path: str | None = None) -> None:
    out = bench_dispatch()
    for c in out["crossovers"]:
        emit(
            f"dispatch_crossover_{c['scenario']}_{c['regime']}",
            float(c["crossover_elements"]),
            f"tasks={c['tasks']};machines={c['machines']}",
        )
    if not out["crossovers"]:
        emit(
            "dispatch_crossover",
            0.0,
            f"auto_picks_jax={out['auto_picks_jax']};"
            "numpy_wins_all_measured_sizes",
        )
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--json", default=None,
                        help="write BENCH_dispatch.json here")
    parser.add_argument("--check", default=None, metavar="JSON",
                        help="validate auto dispatch against a recorded grid")
    args = parser.parse_args()
    if args.check:
        sys.exit(check(args.check))
    main(json_path=args.json)
