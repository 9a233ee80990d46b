"""Smoke run of the scheduler's main path on one TPU chip.

    python chip_smoke.py [PHASE ...]

Phases (default: all, in this order), each run twice in this process,
cold then warm, and compared with its NumPy reference:

  offline   schedule + refine(backend="auto") on the paper's large cluster
            (20/70/90 machines, 478 tasks on the linear topology) vs
            refine(backend="numpy"), from the Alg. 1+2 start and from a
            start with 4 instances moved off it, where refine climbs (its
            first 6 moves): throughput to rel 1e-9, move lists compared;
            plus three of refine's sweep kinds from the perturbed start
            (16384 single-task relocations, one growth step per component,
            every DROP) scored on the device vs NumPy: rel 1e-12, identical
            pick per segment.
  tenants   schedule_tenants at the paper_90x100 row of
            benchmarks/bench_multitenant.py (100 tenants, 90 machines):
            feasible and no tenant below its fair slice; plus the
            tenant-batched scorer vs the per-tenant loop (rel 1e-12,
            identical argmax).
  runtime   evaluate_policies_batch(backend="jax") vs backend="numpy" on
            the evaluator parity scenario of benchmarks/bench_runtime.py
            at 240 windows: rel 1e-9.
  simulate  simulate_batch(backend="jax") at B=2048 on paper_cluster
            ((10, 10, 10)) vs backend="numpy": rel 1e-9.

Every phase must send at least one sweep to the device, counted from the
``repro.obs`` dispatch log. Deviations are scale-relative:
max|x - ref| / max|ref|. One line per phase reports the wall time of each
run, its device sweeps, its XLA compiles and persistent-cache hits, and
the deviation. The last line of stdout is the JSON result, printed only
when every phase passed. Without a TPU the script exits non-zero before
any phase. The compile cache is set up first (repro.compile_cache).
"""

from __future__ import annotations

import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

SEED = 0
# Deployment sizes (machines per type, candidate batch); see the docstring.
LARGE_CLUSTER = (20, 70, 90)
TENANT_CLUSTER, N_TENANTS = (20, 30, 40), 100
SIM_CLUSTER, SIM_B = (10, 10, 10), 2048
# Refine climb: instances moved off the Alg. 1+2 start, and the moves
# compared (the full climb from there takes about 60).
CLIMB_PERTURB, CLIMB_ROUNDS = 4, 6
# Single-task relocations scored in the offline phase's relocate sweep.
RELOCATE_B = 16384


def _compile_counter():
    """The benchmark's ``CompileCounter`` (bench/harness/compiles.py),
    loaded from its file: programs compiled and persistent-cache hits."""
    import importlib.util

    path = ROOT / "bench" / "harness" / "compiles.py"
    spec = importlib.util.spec_from_file_location("compiles", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.CompileCounter()


def rel_dev(got, ref) -> float:
    """Scale-relative deviation max|got - ref| / max|ref| (0 for empties)."""
    got = np.asarray(got, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    if ref.size == 0:
        return 0.0
    scale = float(np.max(np.abs(ref)))
    diff = float(np.max(np.abs(got - ref)))
    return diff / scale if scale > 0.0 else diff


# --------------------------------------------------------------- phases
#
# Each phase is (prepare, run, check): ``prepare`` builds inputs and the
# reference once, ``run`` is the device path timed cold and warm, and
# ``check`` returns (deviation, tolerance, ok, extra fields).


def _relocations(base: np.ndarray, n_machines: int, batch: int) -> np.ndarray:
    """(batch, T) single-task relocations of the placement row ``base``, as
    one refine sweep scores them: row i moves task i % T to another
    machine."""
    i = np.arange(batch)
    task = i % base.shape[0]
    tm = np.tile(base, (batch, 1))
    tm[i, task] = (base[task] + 1 + i // base.shape[0]) % n_machines
    return tm


def _perturbed(etg, n_machines: int, k: int):
    """Copy of ``etg`` with ``k`` seeded instances moved to random machines:
    a start from which refine has to climb."""
    rng = np.random.default_rng(SEED)
    out = etg.copy()
    offsets = out.component_offsets()
    for t in rng.choice(out.total_tasks, size=k, replace=False):
        c = int(np.searchsorted(offsets, t, side="right") - 1)
        out.assignment[c][t - offsets[c]] = rng.integers(n_machines)
    return out


def _refine_sweeps(state, n_machines: int) -> dict:
    """name -> (rows, per-row counts or None, segment sizes) for three of
    refine's sweep kinds on ``state``: single-task relocations (shared
    counts), one greedy growth step of every component (per-row counts, one
    m-row segment per component) and every DROP candidate (per-row counts,
    one segment per component with >= 2 instances). Refine picks the best
    row of each segment."""
    base = state.task_machine()
    offsets = state.component_offsets()
    n_inst = state.n_instances
    m = n_machines
    grow_rows, grow_counts, drop_rows, drop_counts, drop_sizes = [], [], [], [], []
    for c in range(n_inst.shape[0]):
        grow_rows.append(
            np.insert(np.tile(base, (m, 1)), offsets[c + 1], np.arange(m), axis=1)
        )
        grown = n_inst.copy()
        grown[c] += 1
        grow_counts.append(np.tile(grown, (m, 1)))
        nk = int(n_inst[c])
        if nk >= 2:
            drop_rows.append(
                np.stack([np.delete(base, offsets[c] + k) for k in range(nk)])
            )
            dropped = n_inst.copy()
            dropped[c] -= 1
            drop_counts.append(np.tile(dropped, (nk, 1)))
            drop_sizes.append(nk)
    return {
        "relocate": (_relocations(base, m, RELOCATE_B), None, [RELOCATE_B]),
        "grow": (
            np.concatenate(grow_rows), np.concatenate(grow_counts),
            [m] * n_inst.shape[0],
        ),
        "drop": (
            np.concatenate(drop_rows), np.concatenate(drop_counts), drop_sizes,
        ),
    }


def _segment_picks_identical(got, want, sizes) -> bool:
    """Same best row in every segment, as refine picks per chain."""
    bounds = np.cumsum([0] + list(sizes))
    return all(
        int(np.argmax(got[a:b])) == int(np.argmax(want[a:b]))
        for a, b in zip(bounds[:-1], bounds[1:])
    )


def _offline():
    from repro.core import linear_topology, paper_cluster, refine, schedule
    from repro.core.schedule_state import ScheduleState

    cluster = paper_cluster(LARGE_CLUSTER)
    topo = linear_topology()

    def starts():
        start = schedule(topo, cluster, r0=1.0, rate_epsilon=1.0).etg
        return start, _perturbed(start, cluster.n_machines, CLIMB_PERTURB)

    def sweep_scores(start, backend):
        state = ScheduleState.from_etg(start, cluster)
        sweeps = _refine_sweeps(state, cluster.n_machines)
        return {
            name: state.score_task_machine_batch(tm, counts, backend=backend)[1]
            for name, (tm, counts, _) in sweeps.items()
        }, {name: sizes for name, (_, _, sizes) in sweeps.items()}

    def prepare():
        start, climb = starts()
        scores, sizes = sweep_scores(climb, "numpy")
        return (
            refine(start, cluster, backend="numpy"),
            refine(climb, cluster, max_rounds=CLIMB_ROUNDS, backend="numpy"),
            scores,
            sizes,
        )

    def run(ref):
        start, climb = starts()
        # Three of refine's sweep kinds, scored alone from the perturbed
        # start, so device scores are compared row by row.
        return (
            refine(start, cluster, backend="auto"),
            refine(climb, cluster, max_rounds=CLIMB_ROUNDS, backend="auto"),
            sweep_scores(climb, "jax")[0],
        )

    def check(out, ref):
        (got, got_climb, scores), (want, want_climb, want_scores, sizes) = out, ref
        dev = max(
            abs(g.throughput - w.throughput) / abs(w.throughput)
            for g, w in ((got, want), (got_climb, want_climb))
        )
        ok = dev <= 1e-9
        extra = {
            "tasks": int(got.etg.total_tasks),
            "moves": len(got.moves),
            "moves_identical": got.moves == want.moves,
            "climb_moves": len(got_climb.moves),
            "climb_ref_moves": len(want_climb.moves),
            "climb_moves_identical": got_climb.moves == want_climb.moves,
            "sweep_tol": 1e-12,
        }
        for name, got_s in scores.items():
            sweep_dev = rel_dev(got_s, want_scores[name])
            picks = _segment_picks_identical(got_s, want_scores[name], sizes[name])
            ok = ok and sweep_dev <= 1e-12 and picks
            extra[f"{name}_rows"] = got_s.shape[0]
            extra[f"{name}_dev"] = sweep_dev
            extra[f"{name}_picks_identical"] = picks
        return dev, 1e-9, ok, extra

    return prepare, run, check


def _tenants():
    from benchmarks.bench_multitenant import batching_case, scale_row
    from repro.multitenant import TenantBatchScorer

    def prepare():
        mt, sweeps = batching_case()
        loop = [
            TenantBatchScorer(mt, backend="numpy").reference_scores(t, rows)
            for t, rows in sweeps
        ]
        return mt, sweeps, loop

    def run(ref):
        mt, sweeps, _ = ref
        row = scale_row(
            N_TENANTS, TENANT_CLUSTER, cap_scale=1.0, label="paper_90x100"
        )
        batched = TenantBatchScorer(mt, backend="jax").score(sweeps)
        return row, batched

    def check(out, ref):
        row, batched = out
        _, _, loop = ref
        dev = max(
            max(rel_dev(b[0], lp[0]), rel_dev(b[1], lp[1]))
            for b, lp in zip(batched, loop)
        )
        same_argmax = all(
            int(np.argmax(b[1])) == int(np.argmax(lp[1]))
            for b, lp in zip(batched, loop)
            if lp[1].size
        )
        ok = (
            dev <= 1e-12 and same_argmax and row["feasible"]
            and row["no_regression_vs_fair_slice"]
        )
        return dev, 1e-12, ok, {
            "tenants": row["n_tenants"],
            "machines": row["n_machines"],
            "feasible": row["feasible"],
            "no_regression": row["no_regression_vs_fair_slice"],
            "batch_argmax_identical": same_argmax,
        }

    return prepare, run, check


def _runtime():
    from benchmarks.bench_runtime import parity_case
    from repro.core import linear_topology, paper_cluster
    from repro.runtime_stream import evaluate_policies_batch

    cluster = paper_cluster((1, 1, 1))
    fields = (
        "throughput", "admitted", "dropped", "queue_total", "throttle",
        "machine_util_mean", "sustained",
    )

    def prepare():
        etg, traces, policies = parity_case(linear_topology(), cluster)
        ref = evaluate_policies_batch(
            etg, cluster, traces, policies, backend="numpy"
        )
        return etg, traces, policies, ref

    def run(ref):
        etg, traces, policies, _ = ref
        return evaluate_policies_batch(
            etg, cluster, traces, policies, backend="jax"
        )

    def check(out, ref):
        _, traces, policies, want = ref
        dev = max(rel_dev(getattr(out, f), getattr(want, f)) for f in fields)
        return dev, 1e-9, dev <= 1e-9, {
            "traces": len(traces),
            "placements": policies.shape[0],
            "windows": traces[0].n_windows,
        }

    return prepare, run, check


def _simulate():
    from repro.core import linear_topology, paper_cluster, schedule, simulate_batch

    cluster = paper_cluster(SIM_CLUSTER)
    fields = ("ir", "pr", "tcu", "machine_util", "throughput")

    def prepare():
        etg = schedule(linear_topology(), cluster, r0=1.0, rate_epsilon=1.0).etg
        rng = np.random.default_rng(SEED)
        tm = rng.integers(0, cluster.n_machines, size=(SIM_B, etg.total_tasks))
        return etg, tm, simulate_batch(etg, cluster, tm, 60.0, backend="numpy")

    def run(ref):
        etg, tm, _ = ref
        return simulate_batch(etg, cluster, tm, 60.0, backend="jax")

    def check(out, ref):
        _, tm, want = ref
        dev = max(rel_dev(getattr(out, f), getattr(want, f)) for f in fields)
        return dev, 1e-9, dev <= 1e-9, {"batch": tm.shape[0], "tasks": tm.shape[1]}

    return prepare, run, check


PHASES = {
    "offline": _offline,
    "tenants": _tenants,
    "runtime": _runtime,
    "simulate": _simulate,
}


def run_phase(name: str, counter) -> bool:
    from repro.obs import TraceRecorder

    prepare, run, check = PHASES[name]()
    t0 = time.perf_counter()
    ref = prepare()
    ref_s = time.perf_counter() - t0
    fields = [f"phase={name}", f"ref_s={ref_s}"]
    ok = True
    for label in ("cold", "warm"):
        rec = TraceRecorder(name=f"{name}-{label}")
        c0, h0 = counter.snapshot()
        t0 = time.perf_counter()
        with rec.activate():
            out = run(ref)
        wall = time.perf_counter() - t0
        c1, h1 = counter.snapshot()
        sweeps = sum(d.backend == "jax" for d in rec.dispatch_log)
        dev, tol, passed, extra = check(out, ref)
        ok = ok and passed and sweeps > 0
        fields += [
            f"{label}_s={wall}",
            f"{label}_device_sweeps={sweeps}/{len(rec.dispatch_log)}",
            f"{label}_compiles={c1 - c0}",
            f"{label}_cache_hits={h1 - h0}",
            f"{label}_dev={dev}",
        ]
    fields.append(f"tol={tol}")
    fields += [f"{k}={v}" for k, v in extra.items()]
    fields.append(f"ok={ok}")
    print(" ".join(fields), flush=True)
    return ok


def main(argv: list[str]) -> int:
    from repro.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, found {devices[0].platform}",
            file=sys.stderr,
        )
        return 2
    names = argv or list(PHASES)
    unknown = [n for n in names if n not in PHASES]
    if unknown:
        print(f"chip_smoke: unknown phase(s) {unknown}", file=sys.stderr)
        return 2
    print(
        f"device={devices[0].device_kind} count={len(devices)} "
        f"cache_dir={cache_dir}",
        flush=True,
    )
    counter = _compile_counter()
    ok = True
    for name in names:
        try:
            ok = run_phase(name, counter) and ok
        except Exception:
            # Phase boundary: report the failure and go on to the next.
            traceback.print_exc()
            print(f"phase={name} ok=False", flush=True)
            ok = False
    if not ok:
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
