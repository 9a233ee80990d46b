"""Rate-based cluster simulator — the paper's §6.3 simulator, vectorized.

Given an ETG, a cluster and an offered topology input rate, compute the
*measured* steady state: per-task processing rates under machine saturation
and back-pressure, per-machine utilization, and overall throughput. This is
the ground truth that (a) the prediction model (eq. 5) is scored against
(Fig. 6), and (b) all three schedulers are compared on (Figs. 3/8/9/10).

Saturation model
----------------
A machine w hosting tasks with offered variable load ``sum_i e_i * IR_i``
and fixed overhead ``sum_i MET_i`` saturates when total demand exceeds its
capacity. Under overload the machine applies proportional fair throttling:
every hosted task processes at ``s_w * IR_i`` with

    s_w = clip((capacity_w - sum MET) / sum(e_i * IR_i), 0, 1).

Throttled output back-pressures downstream components (their input rate is
the *processed* upstream rate), which is the domino effect of §5.2. Because
saturation on one machine changes rates feeding other machines, the steady
state is a fixed point; demand scale factors decrease monotonically along
iterations, so a short damped fixed-point loop converges (we iterate to
convergence with a hard cap).

The batched variant evaluates B candidate placements that share one
instance-count vector in a single vectorized sweep — this is what makes the
exhaustive optimal scheduler tractable (the paper reports 18 hours for
27 405 placements; see benchmarks/bench_sched_speed.py).
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.core.graph import ExecutionGraph
from repro.core.profiles import Cluster
from repro.obs.trace import record_dispatch

__all__ = ["SimResult", "simulate", "simulate_batch", "measured_tcu"]

_MAX_ITERS = 200
_TOL = 1e-10


@dataclasses.dataclass(frozen=True)
class SimResult:
    """Steady state of the simulated cluster.

    Attributes:
      ir: (T,) offered per-task input rate (post-back-pressure).
      pr: (T,) processing rate actually achieved per task.
      tcu: (T,) occupied CPU per task at the steady state.
      machine_util: (m,) per-machine utilization (capped at capacity only by
        the throttling model itself).
      throughput: overall topology throughput = sum of task processing rates
        (the paper's throughput definition, eq. 2).
    """

    ir: np.ndarray
    pr: np.ndarray
    tcu: np.ndarray
    machine_util: np.ndarray
    throughput: float


def _flat_arrays(etg: ExecutionGraph, cluster: Cluster):
    comp = etg.task_component()
    machine = etg.task_machine()
    ttypes = etg.utg.component_types[comp]
    mtypes = cluster.machine_types[machine]
    e = cluster.profile.e[ttypes, mtypes]
    met = cluster.profile.met[ttypes, mtypes]
    return comp, machine, e, met


def simulate(etg: ExecutionGraph, cluster: Cluster, r0: float) -> SimResult:
    """Single-placement steady state (thin wrapper over the batched core)."""
    machine = etg.task_machine()[None, :]
    return simulate_batch(etg, cluster, machine, r0).row(0)


@dataclasses.dataclass(frozen=True)
class BatchSimResult:
    ir: np.ndarray            # (B, T)
    pr: np.ndarray            # (B, T)
    tcu: np.ndarray           # (B, T)
    machine_util: np.ndarray  # (B, m)
    throughput: np.ndarray    # (B,)

    def row(self, i: int) -> SimResult:
        """Single candidate row as a ``SimResult``."""
        return SimResult(
            ir=self.ir[i],
            pr=self.pr[i],
            tcu=self.tcu[i],
            machine_util=self.machine_util[i],
            throughput=float(self.throughput[i]),
        )


# Per-regime element floors (B*T per sweep) below which "auto" never
# considers JAX for the closed-form scorers, calibrated by
# benchmarks/bench_dispatch.py (see BENCH_dispatch.json). Since the scorer
# went scatter-free (``sim_jax._msr_kernel``'s one-hot contraction — XLA's
# serial CPU scatter-add never won), the JAX path beats NumPy 2-6x on CPU
# for paper-realistic machine counts once the sweep amortizes dispatch.
# The floors sit above the largest sweep the golden refine/optimal suites
# issue (measured by instrumenting this resolver under the full tier-1 +
# slow runs: 98,304 shared / 8,800 per-row / 960 skew elements), so
# reference results stay bit-identical by construction; the bench's
# realistic scenarios (B*T >= ~230k at B=16384) clear them. The contraction does B*T*m work versus NumPy's B*T, so wide
# clusters flip the verdict — ``_AUTO_MAX_MACHINES`` gates those back to
# NumPy on CPU (accelerators keep parallel reductions, no gate). Skew rows
# run the same kernel (skew only changes the unit-rate values), sharing the
# measured per-row crossover. Recalibrate with bench_dispatch.py when the
# host changes; override via REPRO_CLOSED_FORM_JAX_THRESHOLD (all regimes)
# or REPRO_CLOSED_FORM_JAX_THRESHOLD_{SHARED,PER_ROW,SKEW}.
_CLOSED_FORM_AUTO_THRESHOLDS = {
    "shared": 131_072,
    "per_row": 65_536,
    "skew": 65_536,
}

# CPU-only machine-count gate for "auto": the dense contraction's B*T*m
# cost loses to NumPy's serial B*T scatter on wide clusters (measured 180
# machines: 0.03-0.4x across nine formulations). Bench large scenario (15
# machines) still wins, the stress scenario (180) documents the loss.
_AUTO_MAX_MACHINES = 32

# CPU-only work ceiling for "auto", in B*T*m products: past it the one-hot
# intermediates fall out of cache and the contraction collapses even on
# mid-width clusters (measured on the 15-machine scenario: 1.2-1.3x NumPy
# at 3.3M products, 0.35x at 13.3M). Between the floors and this ceiling
# the contraction wins at every measured grid point.
_AUTO_MAX_WORK = 6_000_000


@functools.cache
def _jax_accelerator_available() -> bool:
    """True iff JAX's default backend is not the CPU."""
    import jax

    return jax.default_backend() != "cpu"


def _closed_form_auto_threshold(regime: str = "shared") -> tuple[float, bool]:
    """Current "auto" crossover in elements for one scoring regime.

    Returns ``(threshold, overridden)``. ``REPRO_CLOSED_FORM_JAX_THRESHOLD``
    overrides every regime; ``REPRO_CLOSED_FORM_JAX_THRESHOLD_<REGIME>``
    (SHARED / PER_ROW / SKEW) wins over both for its regime. An env
    override also bypasses the machine-count gate (set one after
    recalibrating bench_dispatch.py on new hardware, or to force the JAX
    path in tests); otherwise the calibrated per-regime floor applies.
    """
    import os

    if regime not in _CLOSED_FORM_AUTO_THRESHOLDS:
        raise ValueError(f"unknown scoring regime {regime!r}")
    env = os.environ.get(f"REPRO_CLOSED_FORM_JAX_THRESHOLD_{regime.upper()}")
    if env is None:
        env = os.environ.get("REPRO_CLOSED_FORM_JAX_THRESHOLD")
    if env is not None:
        return float(env), True
    return float(_CLOSED_FORM_AUTO_THRESHOLDS[regime]), False


def resolve_closed_form_backend(
    backend: str,
    elements: int | None = None,
    regime: str = "shared",
    n_machines: int | None = None,
    site: str | None = None,
) -> str:
    """Validate + resolve a closed-form scoring backend request.

    Shared by ``cost_model.max_stable_rate_batch`` and
    ``ScheduleState.score_task_machine_batch`` so the backend-string
    contract and the ``"auto"`` dispatch heuristic live in one place
    (``simulate_batch`` keeps its own policy: its fixed-point loop has a
    different cost profile).

    Args:
      backend: ``"numpy"``, ``"jax"``, or ``"auto"`` (JAX iff the sweep
        clears the regime's calibrated element crossover and the cluster
        passes the machine-count gate — see ``_closed_form_auto_threshold``).
      elements: batch size in B*T elements; required for ``"auto"`` to ever
        pick JAX (``None`` resolves to NumPy — the safe reference).
      regime: which crossover table applies — ``"shared"`` ((T,) maps),
        ``"per_row"`` ((B, T) maps), or ``"skew"`` (realized fields-grouping
        rates; per-row shapes, separate calibration row in the bench).
      n_machines: cluster width for the CPU contraction gates (the dense
        one-hot does B*T*m work, so wide clusters and out-of-cache sweeps
        stay NumPy). ``None`` skips the gates; internal scoring call sites
        always pass it.
      site: caller label recorded in the observability dispatch log
        (``repro.obs``); no effect on resolution.
    """
    requested = backend
    if backend not in ("numpy", "jax", "auto"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend == "auto":
        threshold, overridden = _closed_form_auto_threshold(regime)
        if elements is None:
            backend = "numpy"
        else:
            gate_ok = (
                overridden
                or n_machines is None
                or _jax_accelerator_available()
                or (
                    n_machines <= _AUTO_MAX_MACHINES
                    and elements * n_machines <= _AUTO_MAX_WORK
                )
            )
            backend = "jax" if gate_ok and elements >= threshold else "numpy"
    # Auditability of the auto-dispatch gates: when a TraceRecorder is
    # active, every resolution lands in its dispatch log (no-op otherwise).
    record_dispatch(requested, backend, regime, elements, n_machines, site)
    return backend


# Batches at least this large amortize JAX dispatch/compile overhead on the
# fixed-point sweep; below it the NumPy path wins.
_JAX_AUTO_THRESHOLD = 32_768  # B * T elements


def simulate_batch(
    etg: ExecutionGraph,
    cluster: Cluster,
    task_machine: np.ndarray,
    r0,
    backend: str = "auto",
) -> BatchSimResult:
    """Evaluate B placements (same instance counts) in one vectorized sweep.

    Args:
      etg: supplies the UTG and instance counts (its own assignment ignored).
      task_machine: (B, T) machine index per task per candidate.
      r0: offered topology input rate at each spout — a scalar applied to
        every candidate, or a (B,) vector with one rate per candidate row
        (lets e.g. benchmarks score proposed-vs-default placements at their
        own stable rates in a single sweep).
      backend: ``"numpy"`` (reference), ``"jax"`` (jitted
        ``lax.while_loop`` fixed point, float64 — agrees with NumPy to
        1e-9), or ``"auto"`` (JAX for batches of at least
        ``_JAX_AUTO_THRESHOLD`` elements, NumPy below). The resolution lands
        in the active ``repro.obs`` recorder's dispatch log.
    """
    requested = backend
    if backend not in ("auto", "numpy", "jax"):
        raise ValueError(f"unknown backend {backend!r}")
    tm = np.asarray(task_machine)
    if backend == "auto":
        backend = "jax" if tm.size >= _JAX_AUTO_THRESHOLD else "numpy"
    record_dispatch(
        requested, backend, "simulate", tm.size, cluster.n_machines,
        "simulate_batch",
    )
    if backend == "jax":
        from repro.core.sim_jax import simulate_batch_jax

        return simulate_batch_jax(etg, cluster, task_machine, r0)

    utg = etg.utg
    comp = etg.task_component()                       # (T,)
    n_inst = etg.n_instances
    task_machine = np.asarray(task_machine, dtype=np.int64)
    if task_machine.ndim != 2 or task_machine.shape[1] != comp.shape[0]:
        raise ValueError("task_machine must be (B, T)")
    B, T = task_machine.shape
    m = cluster.n_machines
    r0 = np.asarray(r0, dtype=np.float64)
    if r0.ndim not in (0, 1) or (r0.ndim == 1 and r0.shape != (B,)):
        raise ValueError("r0 must be a scalar or a (B,) vector")
    if B == 0:
        # Empty batch: the fixed point's convergence reduction is undefined
        # over zero rows; return correctly-shaped empties instead.
        empty = np.zeros((0, T), dtype=np.float64)
        return BatchSimResult(
            ir=empty,
            pr=empty.copy(),
            tcu=empty.copy(),
            machine_util=np.zeros((0, m), dtype=np.float64),
            throughput=np.zeros(0, dtype=np.float64),
        )

    ttypes = utg.component_types[comp]                # (T,)
    mtypes = cluster.machine_types[task_machine]      # (B, T)
    e = cluster.profile.e[ttypes[None, :], mtypes]    # (B, T)
    met = cluster.profile.met[ttypes[None, :], mtypes]

    # Fixed MET load per machine (rate independent).
    rows = np.repeat(np.arange(B), T)
    cols = task_machine.reshape(-1)
    met_load = np.zeros((B, m), dtype=np.float64)
    np.add.at(met_load, (rows, cols), met.reshape(-1))

    topo = utg.topo_order()
    sources = set(utg.sources)
    parents = [utg.parents(i) for i in range(utg.n_components)]
    alpha = utg.alpha

    # Machine demand scale factors, refined to a fixed point.
    s = np.ones((B, m), dtype=np.float64)
    cir = np.zeros((B, utg.n_components), dtype=np.float64)
    pr_comp = np.zeros_like(cir)  # processed (post-throttle) rate per component

    # Mean throttle factor applied to a component's instances, given the
    # candidate's machine scale factors: instances split rate evenly, so the
    # component's processed rate is CIR/N * sum_k s[machine of instance k].
    inst_of_comp = [np.flatnonzero(comp == i) for i in range(utg.n_components)]

    ir_task = np.zeros((B, T), dtype=np.float64)
    for _ in range(_MAX_ITERS):
        # Propagate rates in topo order under current throttle factors.
        for i in topo:
            if i in sources:
                cir[:, i] = r0
            else:
                cir[:, i] = 0.0
                for p in parents[i]:
                    cir[:, i] += alpha[p] * pr_comp[:, p]
            idx = inst_of_comp[i]
            per_inst = cir[:, i : i + 1] / float(n_inst[i])     # (B, 1)
            ir_task[:, idx] = per_inst
            s_inst = np.take_along_axis(s, task_machine[:, idx], axis=1)
            pr_comp[:, i] = per_inst[:, 0] * s_inst.sum(axis=1)

        # Recompute machine scale factors from offered variable load.
        var = e * ir_task                                         # (B, T)
        var_load = np.zeros((B, m), dtype=np.float64)
        np.add.at(var_load, (rows, cols), var.reshape(-1))
        head = np.maximum(cluster.capacity[None, :] - met_load, 0.0)
        with np.errstate(divide="ignore", invalid="ignore"):
            s_new = np.where(var_load > head, head / np.maximum(var_load, 1e-300), 1.0)
        if np.max(np.abs(s_new - s)) < _TOL:
            s = s_new
            break
        s = s_new

    pr_task = ir_task * np.take_along_axis(s, task_machine, axis=1)
    tcu = e * pr_task + met
    util = np.zeros((B, m), dtype=np.float64)
    np.add.at(util, (rows, cols), tcu.reshape(-1))
    return BatchSimResult(
        ir=ir_task,
        pr=pr_task,
        tcu=tcu,
        machine_util=util,
        throughput=pr_task.sum(axis=1),
    )


def measured_tcu(
    etg: ExecutionGraph,
    cluster: Cluster,
    r0: float,
    seed: int = 0,
    noise_scale: float = 0.035,
) -> np.ndarray:
    """'Measured' per-task CPU utilization with the paper's noise profile.

    §6.2: measurement variance is low when the CPU is lightly or heavily
    loaded and highest at moderate load. We model the measurement error as
    zero-mean Gaussian with std ``noise_scale * 100 * 4u(1-u)`` where u is
    the machine's utilization fraction — a parabola peaking at u=0.5 —
    truncated so the max |error| stays below the paper's observed 8 points.
    """
    sim = simulate(etg, cluster, r0)
    machine = etg.task_machine()
    u = np.clip(sim.machine_util[machine] / cluster.capacity[machine], 0.0, 1.0)
    std = noise_scale * 100.0 * 4.0 * u * (1.0 - u)
    rng = np.random.default_rng(seed)
    noise = np.clip(rng.normal(0.0, 1.0, size=std.shape) * std, -7.9, 7.9)
    return np.clip(sim.tcu + noise, 0.0, None)
