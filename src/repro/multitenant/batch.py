"""Tenant-batched closed-form scoring: tenants become rows.

The single-tenant engines score B candidate placements of *one* topology
per kernel call. Multi-tenant search wants to score candidates belonging
to *different* tenants — each against its own residual capacity — in one
``(B, T, m)`` closed-form evaluation, reusing the per-row task-map support
(``cost_model.per_row_task_maps`` / the ``per_row`` ``_msr_kernel``
variant) that already lets rows differ structurally.

Two ingredients make different tenants batch into one call:

* a **met fold** — tenant s's committed load is linear in its allocated
  rate R_s, so each of its tasks contributes the fixed quantity
  ``met_cm[c, w] + e_cm[c, w] * unit_ir_task * R_s`` to its machine
  (skew-aware: the per-task unit IR comes from
  ``SkewModel.per_task_unit_ir`` when the tenant has a key-share model).
  Folding those per-task loads onto their incumbent machines (one
  canonical-order ``bincount``) prices the whole fleet as one fixed
  (m,) frozen-load vector F, and tenant t's residual capacity is
  ``cluster.capacity - (F - F_t_own)``.

* **per-row capacity** — ``closed_form_rates`` and the jitted
  ``_msr_kernel`` accept a (B, m) capacity matrix, so each candidate row
  scores against *its* tenant's residual. Rows stay compact: width is
  max tenant task count (co-tenants live in the capacity row, not in
  frozen columns), padded with a zero profile row for shorter tenants.

The closed form then returns exactly tenant t's residual R* and
throughput per row. Rows dispatch through the same ``backend="auto"``
crossover policy as the single-tenant path.

Floats differ from the explicit residual-capacity subtraction only in
summation association (~1e-15 relative); ``tests/test_multitenant_golden``
pins parity at 1e-12 with identical argmax.
"""

from __future__ import annotations

import numpy as np

from repro.core import cost_model
from repro.core.schedule_state import ScheduleState

from repro.multitenant.state import MultiTenantState

__all__ = ["TenantBatchScorer"]


class TenantBatchScorer:
    """Score count-preserving candidate rows for many tenants in one call.

    Snapshots the multi-tenant state's committed rates at construction
    (the met fold bakes them into the frozen-load vector) — rebuild the
    scorer after rates or placements change. Candidate rows must keep
    each tenant's instance counts (RELOCATE/SWAP-style sweeps); growth
    moves go through the per-tenant refine path on residual clusters.
    """

    def __init__(self, mt: MultiTenantState, backend: str = "auto"):
        self.mt = mt
        self.backend = backend
        self.candidates_evaluated = 0

        states = mt.states
        self._has_skew = any(st.skew is not None for st in states)

        # Blocks concatenate in canonical (name) order — NOT submission
        # order — so the frozen-load bincount sums every tenant's tasks in
        # one canonical sequence and scores are bit-identical under
        # submission-order permutations. Per-tenant spans map a tenant
        # *index* to its rows/columns.
        order = mt.tenant_set.canonical_order()
        self._comp_span: dict[int, tuple[int, int]] = {}
        self._task_span: dict[int, tuple[int, int]] = {}
        n_all = 0
        t_all = 0
        for t in order:
            st = states[t]
            self._comp_span[t] = (n_all, n_all + st.utg.n_components)
            self._task_span[t] = (t_all, t_all + int(st.n_instances.sum()))
            n_all += st.utg.n_components
            t_all += int(st.n_instances.sum())
        self.n_tasks = t_all
        self.t_max = max(hi - lo for lo, hi in self._task_span.values())

        m = mt.cluster.n_machines
        e_act = np.concatenate([states[t].e_cm for t in order], axis=0)
        met_act = np.concatenate([states[t].met_cm for t in order], axis=0)
        # One zero profile row pads short tenants' columns: a padding task
        # parks on machine 0 with e = met = unit = 0 and contributes
        # nothing to either accumulator.
        self.pad_comp = n_all
        self.e_table = np.concatenate([e_act, np.zeros((1, m))], axis=0)
        self.met_table = np.concatenate([met_act, np.zeros((1, m))], axis=0)

        # Concatenated incumbent row, per-task active maps, and the met
        # fold: each task's committed load on its incumbent machine.
        self._has_network = mt.cluster.has_network
        self._has_memory = mt.cluster.has_memory
        base_row = np.concatenate([states[t].task_machine() for t in order])
        active_comp = np.empty(t_all, dtype=np.int64)
        active_unit = np.empty(t_all, dtype=np.float64)
        task_load = np.empty(t_all, dtype=np.float64)
        # Local (tenant-topology) task maps for score-time network pricing,
        # and the per-task memory column: memory demand is rate-independent,
        # so it needs no fold — pad columns carry 0 and contribute nothing.
        self._local_comp: dict[int, np.ndarray] = {}
        self._active_mem = (
            np.empty(t_all, dtype=np.float64) if self._has_memory else None
        )
        net_own = np.zeros((len(states), m), dtype=np.float64)
        for t in order:
            st = states[t]
            lo, hi = self._task_span[t]
            comp_t = np.repeat(np.arange(st.utg.n_components), st.n_instances)
            if st.skew is not None:
                unit_t = st.skew.per_task_unit_ir(st.n_instances)
            else:
                unit_t = (st.cir_unit / st.n_instances)[comp_t]
            active_comp[lo:hi] = self._comp_span[t][0] + comp_t
            active_unit[lo:hi] = unit_t
            self._local_comp[t] = comp_t
            if self._has_memory:
                self._active_mem[lo:hi] = st.mem_c[comp_t]
            if self._has_network:
                # Tenant t's committed cut-traffic CPU load at its rate —
                # part of the met fold (also linear in R_t, machine-indexed
                # rather than task-indexed, so it adds after the bincount).
                net_own[t] = float(mt.rates[t]) * st.net_load
            rate_t = float(mt.rates[t])
            w = base_row[lo:hi]
            task_load[lo:hi] = (
                st.met_cm[comp_t, w] + st.e_cm[comp_t, w] * unit_t * rate_t
            )

        self.base_row = base_row
        self.active_comp = active_comp
        self.active_unit = active_unit
        # Fleet frozen load F (canonical-order bincount, plus each tenant's
        # committed network load), then per-tenant residual capacity:
        # cluster capacity minus everyone *else*.
        frozen = np.bincount(base_row, weights=task_load, minlength=m)
        if self._has_network:
            for t in order:
                frozen = frozen + net_own[t]
        self._resid_cap = np.empty((len(states), m), dtype=np.float64)
        for t in order:
            lo, hi = self._task_span[t]
            own = np.bincount(
                base_row[lo:hi], weights=task_load[lo:hi], minlength=m
            )
            if self._has_network:
                own = own + net_own[t]
            self._resid_cap[t] = mt.cluster.capacity - (frozen - own)
        # Residual memory capacity per tenant: neighbours' rate-independent
        # working sets come straight off each machine's memory budget.
        self._resid_mem: np.ndarray | None = None
        if self._has_memory:
            frozen_mem = np.zeros(m, dtype=np.float64)
            for t in order:
                frozen_mem = frozen_mem + states[t].mem_load
            self._resid_mem = np.empty((len(states), m), dtype=np.float64)
            for t in order:
                self._resid_mem[t] = mt.cluster.mem_capacity - (
                    frozen_mem - states[t].mem_load
                )

    # ----------------------------------------------------------- scoring

    def score(
        self, sweeps: "list[tuple[int, np.ndarray]]"
    ) -> "list[tuple[np.ndarray, np.ndarray]]":
        """Score candidate sweeps for several tenants in one kernel call.

        Args:
          sweeps: list of ``(tenant_index, rows)`` where ``rows`` is a
            (B_t, T_t) array of candidate placements for that tenant's
            column block (T_t = tenant's task count). B_t = 0 sweeps are
            allowed and return empty scores.

        Returns:
          One ``(rates, throughputs)`` pair per sweep, in order — each
          tenant's residual closed-form scores for its rows.
        """
        sizes = []
        for t, rows in sweeps:
            rows = np.asarray(rows, dtype=np.int64)
            lo, hi = self._task_span[t]
            if rows.ndim != 2 or rows.shape[1] != hi - lo:
                raise ValueError(
                    f"tenant {t} sweep must be (B, {hi - lo}), got {rows.shape}"
                )
            sizes.append(rows.shape[0])
        b_total = int(sum(sizes))
        if b_total == 0:
            empty = np.zeros(0, dtype=np.float64)
            return [(empty.copy(), empty.copy()) for _ in sweeps]

        cluster = self.mt.cluster
        m = cluster.n_machines
        tm = np.zeros((b_total, self.t_max), dtype=np.int64)
        comp = np.full((b_total, self.t_max), self.pad_comp, dtype=np.int64)
        unit = np.zeros((b_total, self.t_max), dtype=np.float64)
        cap = np.empty((b_total, m), dtype=np.float64)
        # Resource-vector columns: each tenant's candidate rows price their
        # *own* topology's cut traffic (cross-tenant traffic does not exist
        # — tenants are separate topologies) against the shared distance
        # matrix, and their memory against the tenant's residual memory.
        mem = (
            np.zeros((b_total, self.t_max), dtype=np.float64)
            if self._has_memory
            else None
        )
        memcap = (
            np.empty((b_total, m), dtype=np.float64)
            if self._has_memory
            else None
        )
        row0 = 0
        for (t, rows), b_t in zip(sweeps, sizes):
            if b_t == 0:
                continue
            lo, hi = self._task_span[t]
            w = hi - lo
            sl = slice(row0, row0 + b_t)
            rows_arr = np.asarray(rows, dtype=np.int64)
            tm[sl, :w] = rows_arr
            comp[sl, :w] = self.active_comp[lo:hi]
            unit[sl, :w] = self.active_unit[lo:hi]
            cap[sl] = self._resid_cap[t]
            if self._has_memory:
                mem[sl, :w] = self._active_mem[lo:hi]
                memcap[sl] = self._resid_mem[t]
            row0 += b_t

        rates, thpt = self._dispatch(
            sweeps, sizes, tm, comp, unit, cap, mem=mem, mem_capacity=memcap
        )
        self.candidates_evaluated += b_total
        out: list[tuple[np.ndarray, np.ndarray]] = []
        row0 = 0
        for b_t in sizes:
            out.append((rates[row0 : row0 + b_t], thpt[row0 : row0 + b_t]))
            row0 += b_t
        return out

    def residual_rates(self) -> np.ndarray:
        """(N,) residual closed-form R* of every tenant's incumbent row —
        all tenants scored as rows of one batched call."""
        sweeps = []
        for t in range(len(self.mt.states)):
            lo, hi = self._task_span[t]
            sweeps.append((t, self.base_row[lo:hi][None, :]))
        scored = self.score(sweeps)
        return np.array([float(r[0]) for r, _ in scored], dtype=np.float64)

    def _dispatch(
        self,
        sweeps: "list[tuple[int, np.ndarray]]",
        sizes: list[int],
        tm: np.ndarray,
        comp: np.ndarray,
        unit: np.ndarray,
        capacity: np.ndarray,
        mem: np.ndarray | None = None,
        mem_capacity: np.ndarray | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Score the stacked rows of ``sweeps`` (``sizes`` rows each). A
        device sweep prices each row's cut traffic on the device from its
        tenant's topology tables; a NumPy sweep prices it per tenant block
        on the host."""
        from repro.core.simulator import resolve_closed_form_backend

        resolved = resolve_closed_form_backend(
            self.backend,
            tm.size,
            regime="skew" if self._has_skew else "per_row",
            n_machines=capacity.shape[-1],
            site="tenant_batch",
        )
        cluster = self.mt.cluster
        if resolved == "jax":
            from repro.core.sim_jax import closed_form_rates_jax

            resources = None
            if cluster.has_resources:
                resources = self._device_resources(sweeps, sizes, mem_capacity)
            return closed_form_rates_jax(
                tm, comp, unit, self.e_table, self.met_table, capacity, resources
            )
        net_var = None
        if self._has_network:
            net_var = np.empty((tm.shape[0], cluster.n_machines), dtype=np.float64)
            row0 = 0
            for (t, rows), b_t in zip(sweeps, sizes):
                if b_t == 0:
                    continue
                st = self.mt.states[t]
                lo, hi = self._task_span[t]
                net_var[row0 : row0 + b_t] = cost_model.network_unit_load(
                    np.asarray(rows, dtype=np.int64),
                    self._local_comp[t],
                    self.active_unit[lo:hi],
                    st.utg.alpha,
                    st.cir_unit,
                    st.utg.edges,
                    cluster.distance,
                    cluster.net_penalty,
                )
                row0 += b_t
        e = self.e_table[comp, tm]
        met = self.met_table[comp, tm]
        return cost_model.closed_form_rates(
            tm, e, met, unit, capacity,
            net_var=net_var, mem=mem, mem_capacity=mem_capacity,
        )

    def _device_resources(self, sweeps, sizes, mem_capacity) -> list:
        """``sim_jax.device_resources``' tail for stacked tenant rows: memory
        per global component (the padding row's 0) against the rows'
        residual memory, and per-row tables of each row's own topology, its
        components counted from the row's least global component."""
        from repro.core.sim_jax import edge_counts, network_tables

        states = self.mt.states
        mem_c = np.zeros(self.e_table.shape[0], dtype=np.float64)
        if self._has_memory:
            for t, st in enumerate(states):
                lo, hi = self._comp_span[t]
                mem_c[lo:hi] = st.mem_c
        else:
            mem_capacity = np.full(self.mt.cluster.n_machines, np.inf)
        n = max(st.utg.n_components for st in states)
        tables = np.zeros((len(states), n, n + 2), dtype=np.float64)
        for t, st in enumerate(states):
            k = st.utg.n_components
            tables[t, :k, :k] = edge_counts(k, st.utg.edges)
            tables[t, :k, n] = st.utg.alpha
            tables[t, :k, n + 1] = st.cir_unit
        rows = tables[np.repeat([t for t, _ in sweeps], sizes)]
        network = network_tables(
            self.mt.cluster, rows[:, :, :n], rows[:, :, n], rows[:, :, n + 1]
        )
        return [mem_c, mem_capacity, *network]

    # ------------------------------------------------- reference (tests)

    def reference_scores(
        self, tenant: int, rows: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """Per-tenant NumPy reference: explicit residual-capacity scoring.

        Builds a fresh single-tenant state on ``residual_cluster(tenant)``
        and scores ``rows`` through the stock NumPy path — the loop the
        parity tests compare the batched scoring against.
        """
        mt = self.mt
        st = mt.states[tenant]
        solo = ScheduleState.from_etg(
            st.to_etg(), mt.residual_cluster(tenant), skew=st.skew
        )
        return solo.score_task_machine_batch(
            np.asarray(rows, dtype=np.int64), backend="numpy"
        )
