"""Dispatch + host-side gather for the scheduling-score Pallas kernel.

``closed_form_rates_sched`` is drop-in compatible with
``core.sim_jax.closed_form_rates_jax``: same (task_machine, comp, unit_ir,
e_cm, met_cm, capacity) surface covering all three scoring regimes —
shared (T,) maps, per-row (B, T) maps, and skew rows (which only differ in
the ``unit_ir`` values). The component->machine profile gather and the
throughput reduction happen on the host; the kernel sees pre-gathered
(B, T) tiles.
"""

from __future__ import annotations

import numpy as np

from repro.kernels.sched_scoring.ref import sched_scoring_ref
from repro.obs.trace import record_dispatch

__all__ = ["closed_form_rates_sched"]


def closed_form_rates_sched(
    task_machine: np.ndarray,
    comp: np.ndarray,
    unit_ir: np.ndarray,
    e_cm: np.ndarray,
    met_cm: np.ndarray,
    capacity: np.ndarray,
    *,
    impl: str,
    net_var: np.ndarray | None = None,
    mem: np.ndarray | None = None,
    mem_capacity: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """(rates, throughputs) over B candidate rows.

    Args:
      task_machine: (B, T) machine index per task.
      comp / unit_ir: (T,) shared or (B, T) per-row task maps.
      e_cm / met_cm: (n_components, n_machines) profile slices.
      impl: ``"pallas"`` (compiled, TPU only), ``"interpret"`` (Pallas
        interpreter — CPU-testable), or ``"ref"`` (NumPy oracle). The
        kernel runs in JAX's default float dtype: float32 unless the caller
        enabled x64. The TPU compiler refuses 64-bit operands in a Pallas
        kernel, so compiled calls come from outside ``jax.enable_x64``.
      net_var / mem / mem_capacity: resource-vector extras with the
        ``cost_model.closed_form_rates`` semantics — (B, m) cut-traffic
        variable load, (T,)/(B, T) per-task memory demand, (m,) memory
        capacity. All ``None`` (the default) runs the scalar-CPU kernel
        unchanged; any present extra routes to the resource variant with
        zeros / +inf filling the absent type.
    """
    task_machine = np.asarray(task_machine, dtype=np.int64)
    per_row = comp.ndim == 2
    cmap = comp if per_row else comp[None, :]
    e = e_cm[cmap, task_machine]                       # (B, T)
    met = met_cm[cmap, task_machine]
    ev = e * (unit_ir if per_row else unit_ir[None, :])
    B, T = task_machine.shape
    if impl not in ("pallas", "interpret", "ref"):
        raise ValueError(f"unknown impl {impl!r}")
    record_dispatch(
        impl, impl, "sched_scoring", B * T, capacity.shape[0],
        "closed_form_rates_sched",
    )
    if B == 0:
        return np.zeros(0), np.zeros(0)
    has_resources = (
        net_var is not None or mem is not None or mem_capacity is not None
    )
    if impl in ("pallas", "interpret"):
        from repro.kernels.sched_scoring.kernel import (
            sched_scoring_pallas,
            sched_scoring_pallas_resources,
        )

        if has_resources:
            m = capacity.shape[0]
            net_b = (
                net_var
                if net_var is not None
                else np.zeros((B, m), dtype=np.float64)
            )
            mem_bt = (
                np.broadcast_to(
                    mem if mem.ndim == 2 else mem[None, :], (B, T)
                ).astype(np.float64, copy=False)
                if mem is not None
                else np.zeros((B, T), dtype=np.float64)
            )
            mem_cap = (
                mem_capacity
                if mem_capacity is not None
                else np.full(m, np.inf, dtype=np.float64)
            )
            rates = np.asarray(
                sched_scoring_pallas_resources(
                    task_machine, ev, met, mem_bt, capacity,
                    net_b, mem_cap,
                    interpret=impl == "interpret",
                )
            )
        else:
            rates = np.asarray(
                sched_scoring_pallas(
                    task_machine, ev, met, capacity,
                    interpret=impl == "interpret",
                )
            )
    else:
        mem_bt = None
        if mem is not None:
            mem_bt = np.broadcast_to(
                mem if mem.ndim == 2 else mem[None, :], (B, T)
            )
        rates = sched_scoring_ref(
            task_machine, ev, met, capacity,
            net_var=net_var, mem=mem_bt, mem_capacity=mem_capacity,
        )
    thpt = rates * (unit_ir.sum(axis=1) if per_row else unit_ir.sum())
    return rates, thpt
