"""JAX backend for the batched back-pressure simulator (§6.3).

Mirrors the NumPy fixed point in ``simulator.py`` — same damped iteration,
same topo-order propagation, same termination rule — but jitted and driven
by ``jax.lax.while_loop`` so thousands of candidate placements score in one
compiled sweep. The topology structure (component order, parent lists,
alphas) is baked in as static arguments while instance counts are dynamic
inputs, so each (topology, batch-shape) combination compiles once and is
re-used across rate sweeps, placement batches and instance-count vectors
of equal task total.

Rate propagation uses the sparse structure of the UTG directly: components'
tasks are contiguous in the flattened task order (paper eq. 3), so the
per-component gather/scatter reduces to static slices, and the parent sum
``CIR_b = sum alpha_a * PR_a`` unrolls over the (few) DAG edges. Everything
runs in float64 (inside ``jax.enable_x64(True)``; emulated on TPU) so the
backends agree to 1e-9; the NumPy path remains the reference.
"""

from __future__ import annotations

import functools

import numpy as np

from repro.core.graph import ExecutionGraph
from repro.core.profiles import Cluster
from repro.obs import trace

__all__ = [
    "simulate_batch_jax",
    "max_stable_rate_batch_jax",
    "closed_form_rates_jax",
    "relocate_swap_scores_jax",
    "relocate_swap_winners_jax",
    "count_edit_scores_jax",
    "device_resources",
    "network_tables",
]

_MAX_ITERS = 200
_TOL = 1e-10

# Machines the edit kernel ranks from the base row: a move touches two, so
# the third best is always untouched.
_EDIT_SPARE = 3


@functools.lru_cache(maxsize=None)
def _compiled_kernel(static: tuple):
    """Build + cache the jitted fixed-point kernel for one topology structure.

    ``static`` is a hashable description: (topo order, sources, parent
    tuples, alphas, component count). Instance counts and task maps are
    dynamic kernel inputs — see ``_static_descriptor``.
    """
    import jax
    import jax.numpy as jnp

    topo, sources, parents, alpha, n_comp = static
    src = frozenset(sources)

    @jax.jit
    def simulate_fixed_point(task_machine, comp, n_inst, e_cm, met_cm, capacity, r0):
        """Fixed point over machine scale factors s (B, m).

        ``r0`` is a (B,) per-candidate offered-rate vector (a scalar sweep
        broadcasts before the call), so one compiled sweep can score
        placements at heterogeneous rates.

        The task dimension is collapsed before the loop: all instances of a
        component on a machine are interchangeable, so the state inside the
        fixed point is the sparse count tensor ``counts`` (B, n, m) and the
        loop body is two einsum contractions plus the O(n) topo recurrence —
        no per-task gathers/scatters until the final readout.
        """
        with jax.named_scope("simulate_fixed_point"):
            return _fixed_point(task_machine, comp, n_inst, e_cm, met_cm, capacity, r0)

    def _fixed_point(task_machine, comp, n_inst, e_cm, met_cm, capacity, r0):
        B, T = task_machine.shape
        m = capacity.shape[0]
        rows = jnp.arange(B)[:, None]
        one = jnp.ones((), dtype=e_cm.dtype)
        counts = (
            jnp.zeros((B, n_comp, m), dtype=e_cm.dtype)
            .at[rows, comp[None, :], task_machine]
            .add(one)
        )
        ew = counts * e_cm[None, :, :]          # (B, n, m) variable-load weights
        met_load = jnp.einsum("bnm,nm->bm", counts, met_cm)
        head = jnp.maximum(capacity[None, :] - met_load, 0.0)

        def step(s):
            pr = [None] * n_comp
            per = [None] * n_comp
            for i in topo:
                if i in src:
                    cir_i = r0.astype(s.dtype)
                else:
                    cir_i = jnp.zeros((B,), dtype=s.dtype)
                    for p in parents[i]:
                        cir_i = cir_i + alpha[p] * pr[p]
                per[i] = cir_i / n_inst[i]
                s_sum = jnp.einsum("bm,bm->b", counts[:, i, :], s)
                pr[i] = per[i] * s_sum
            per_inst = jnp.stack(per, axis=1)    # (B, n)
            var_load = jnp.einsum("bn,bnm->bm", per_inst, ew)
            s_new = jnp.where(
                var_load > head, head / jnp.maximum(var_load, 1e-300), 1.0
            )
            return per_inst, s_new

        def body(carry):
            s, _, _, it = carry
            per_inst, s_new = step(s)
            delta = jnp.max(jnp.abs(s_new - s))
            return s_new, per_inst, delta, it + 1

        def cond(carry):
            _, _, delta, it = carry
            return (delta >= _TOL) & (it < _MAX_ITERS)

        s0 = jnp.ones((B, m), dtype=e_cm.dtype)
        carry = body((s0, jnp.zeros((B, n_comp), dtype=e_cm.dtype), jnp.inf, 0))
        s, per_inst, _, _ = jax.lax.while_loop(cond, body, carry)

        # Per-task readout, once. Matches the NumPy loop's exit state:
        # ``per_inst`` comes from the last propagation (previous s); ``s``
        # is the final converged factor.
        ir = per_inst[:, comp]                   # (B, T)
        e = e_cm[comp[None, :], task_machine]    # (B, T)
        met = met_cm[comp[None, :], task_machine]
        pr = ir * jnp.take_along_axis(s, task_machine, axis=1)
        tcu = e * pr + met
        util = jnp.zeros((B, m), dtype=e.dtype).at[rows, task_machine].add(tcu)
        return ir, pr, tcu, util, pr.sum(axis=1)

    return simulate_fixed_point


def _static_descriptor(etg: ExecutionGraph) -> tuple:
    """Hashable topology structure. Instance counts are *dynamic* kernel
    inputs, so every count vector of a topology with the same task total
    shares one compiled kernel (sweeps over thousands of count vectors
    retrace only when the task count T changes)."""
    utg = etg.utg
    return (
        tuple(utg.topo_order()),
        tuple(utg.sources),
        tuple(tuple(utg.parents(i)) for i in range(utg.n_components)),
        tuple(float(a) for a in utg.alpha),
        utg.n_components,
    )


def simulate_batch_jax(
    etg: ExecutionGraph,
    cluster: Cluster,
    task_machine: np.ndarray,
    r0,
):
    """JAX implementation of ``simulator.simulate_batch`` (same contract).

    ``r0`` may be a scalar or a (B,) per-candidate rate vector.
    """
    import jax

    # Imported here to avoid a cycle (simulator dispatches to this module).
    from repro.core.simulator import BatchSimResult

    utg = etg.utg
    comp = etg.task_component()
    task_machine = np.asarray(task_machine, dtype=np.int64)
    if task_machine.ndim != 2 or task_machine.shape[1] != comp.shape[0]:
        raise ValueError("task_machine must be (B, T)")
    r0 = np.asarray(r0, dtype=np.float64)
    if r0.ndim not in (0, 1) or (
        r0.ndim == 1 and r0.shape != (task_machine.shape[0],)
    ):
        raise ValueError("r0 must be a scalar or a (B,) vector")
    r0_b = np.broadcast_to(r0, (task_machine.shape[0],)).copy()
    if task_machine.shape[0] == 0:
        # Empty batch: the while-loop reductions are undefined over B=0, so
        # short-circuit with correctly-shaped empties (matches NumPy path).
        T, m = task_machine.shape[1], cluster.n_machines
        empty = np.zeros((0, T), dtype=np.float64)
        return BatchSimResult(
            ir=empty,
            pr=empty.copy(),
            tcu=empty.copy(),
            machine_util=np.zeros((0, m), dtype=np.float64),
            throughput=np.zeros(0, dtype=np.float64),
        )

    ttypes = utg.component_types
    e_cm = cluster.profile.e[ttypes][:, cluster.machine_types]      # (n, m)
    met_cm = cluster.profile.met[ttypes][:, cluster.machine_types]  # (n, m)

    kernel = _compiled_kernel(_static_descriptor(etg))
    n_inst = np.asarray(etg.n_instances, dtype=np.float64)
    with jax.enable_x64(True):
        ir, pr, tcu, util, thpt = kernel(
            task_machine, comp, n_inst, e_cm, met_cm, cluster.capacity, r0_b
        )
    return BatchSimResult(
        ir=np.asarray(ir),
        pr=np.asarray(pr),
        tcu=np.asarray(tcu),
        machine_util=np.asarray(util),
        throughput=np.asarray(thpt),
    )


# ----------------------------------------------------- closed-form scoring


@functools.lru_cache(maxsize=None)
def _msr_kernel(
    per_row: bool = False,
    with_resources: bool = False,
    edits: bool = False,
    count_edits: bool = False,
):
    """Jitted closed-form max-stable-rate scorer (paper eq. 5 linearity).

    Mirrors ``cost_model.max_stable_rate_batch``'s NumPy math: per-machine
    utilization is ``met_w + R * var_w``, so the binding machine gives
    ``R* = min_w (cap_w - met_w) / var_w``.

    The per-machine accumulation is **scatter-free**: instead of XLA's
    scatter-add (serial scalar updates on CPU — 0.2-0.4x NumPy's
    ``np.add.at`` at every measured size, see BENCH_dispatch.json), the
    one-hot membership tensor is laid out (B, m, T) and both accumulators
    reduce over the innermost task axis, which XLA fuses into a vectorized
    compare-select-sum. The contraction does B*T*m element ops versus the
    scatter's B*T, so it wins only while the machine count stays small —
    exactly the regime ``simulator.resolve_closed_form_backend`` dispatches
    to it (the auto machine-count gate; NumPy keeps wide clusters).
    Summation association differs from NumPy's sequential ``np.add.at``, so
    agreement is ~1e-15 relative, not bit-exact — the NumPy backend stays
    the reference.

    Two cached variants: ``per_row=False`` takes shared (T,) ``comp`` /
    ``unit_ir`` maps (every row one instance-count vector — no point
    shipping B identical copies to the device); ``per_row=True`` takes
    (B, T) maps so rows may carry different count vectors (lockstep growth
    batches) or per-row skew-realized unit rates. ``capacity`` may be (m,)
    shared or (B, m) per-row (the multi-tenant batch scorer prices each
    row against its tenant's residual capacity); the rank difference is a
    trace-time constant, so both shapes share one cached variant.

    ``with_resources=True`` selects the resource-vector variant. Its extra
    operands are the tail ``device_resources`` builds: ``mem`` memory
    demand per component (indexed by ``comp``) and ``mem_capacity``
    per-machine memory ceiling driving the hard feasibility mask, then the
    network tables — the (m, m)
    ``distance``, the (n, n) edge-count ``adjacency`` (``adjacency[a, b]``
    edges a -> b), ``alpha``, ``cir_unit`` and the scalar ``net_penalty``
    (per-row (B, n, n) / (B, n) tables where rows carry different
    topologies, as tenants do). The kernel builds each component's send
    and receive mass per machine with the same one-hot contraction
    (``_net_masses``) and prices the cut traffic with the distance matrix
    on the device — ``cost_model.network_unit_load``'s term, added to the
    variable coefficient. Absent resource types are zeros / +inf / no
    components. Kept as separate cached kernels so scalar-CPU scoring never
    re-traces and executes byte-for-byte the legacy contraction.

    ``edits=True`` selects ``msr_edits``, the edit path: refine's
    RELOCATE and SWAP candidates of one base row, which each change the
    totals of two machines only. It takes the (T,) base row, the (A,)
    tasks that move and the shared maps, computes the base's ``var_w`` /
    ``met_w`` once with the same contraction, ranks the base's machines
    once, and returns the throughput of an (A, m) relocate grid (task a to
    machine w) and an (A, T) swap grid (tasks a and b trading machines):
    each cell patches the two touched machines' sums and runs ``_finish``
    on them and on the best machine the move leaves alone. That is O(1)
    per candidate against the row kernel's O(T·m), with nothing per
    candidate shipped: on a TPU a gather indexed per candidate costs about
    its table's size per index, so the candidates are dense grids rather
    than per-row lookups. The same program also selects each grid's winner
    on the device: non-candidate cells (w already a's machine; b not after
    a, of a's component or on a's machine) read -inf, and the first maximum
    in row-major order, the host menu's, gives four float64s (each family's
    index and throughput). A caller fetches the grids
    (``relocate_swap_scores_jax``) or the winners
    (``relocate_swap_winners_jax``), never both; both run one compiled
    program. Refine fetches the winners of every RELOCATE+SWAP sweep that
    resolves to JAX (``ScheduleState.best_relocate_swap``). The winners
    follow both edit kernels' grids, with or without resources.

    ``edits=True, with_resources=True`` selects ``msr_edits_resources``,
    the edit path of a cluster with network or memory resources. There a
    move changes the cut traffic of every machine that hosts a
    neighbouring component, so each candidate is scored on all m machines:
    an (A, m, m) relocate grid and an (A, T, m) swap grid of machine
    totals, each patched from the base's. Moving task t of component c
    from u to v moves machine w's cut traffic by ``(D[w, v] - D[w, u]) *
    K_t[w] + ([w == v] - [w == u]) * L_t[w]``, where K_t is the base's
    neighbour mass of c weighted by t's rates and L_t its distance
    product (``_net_masses``); a swap adds both tasks' terms and, for
    adjacent components, the flow between the two tasks, which the two
    terms each count as colocated. Memory changes on the two touched
    machines only.

    ``count_edits=True`` selects ``msr_count_edits``, the count-edit
    path: refine's growth steps (ADD / GROW / PAIRGROW) and DROP
    candidates, which change one component's instance count by one. It
    takes k (k, T) base rows, their (k, n) instance counts, the (k, n)
    per-component unit rates of their candidates and the (k,) component
    each row's candidates grow or shrink. It computes each row's totals
    rescaled to the candidates' counts with the one-hot contraction, laid
    out (k·m, T) (a fresh sum at the new rates, not a correction of the old
    one), then patches
    the one machine a candidate touches, as ``msr_edits`` does: with the
    static ``drop=False`` a (k, m) grid, [i, v] one more task of the
    component on machine v; with ``drop=True`` a (k, T) grid, [i, p] row i
    without task p. ``count_edits=True, with_resources=True`` selects
    ``msr_count_edits_resources``: every candidate is scored on all m
    machines, a (k, m, m) or (k, T, m) grid, with the "+v" or "-u" half of
    ``msr_edits_resources``' cut-traffic term at the rescaled rates. Both
    return the rate and the throughput of each cell.

    Each variant is named by what it computes — ``msr_shared``,
    ``msr_per_row``, ``msr_resources_shared``, ``msr_resources_per_row``,
    ``msr_edits``, ``msr_edits_resources``, ``msr_count_edits``,
    ``msr_count_edits_resources`` — as its jitted function (the module
    ``jit_<name>`` in a profiler trace) and as a ``jax.named_scope`` around
    its body.
    """
    import jax
    import jax.numpy as jnp

    def _accumulate(task_machine, comp, unit_ir, e_cm, met_cm, capacity):
        m = capacity.shape[-1]
        cmap = comp if per_row else comp[None, :]
        e = e_cm[cmap, task_machine]                 # (B, T)
        met = met_cm[cmap, task_machine]
        ev = e * (unit_ir if per_row else unit_ir[None, :])
        # One-hot contraction, (B, m, T) layout: membership of task t on
        # machine w, reduced over the innermost t axis. No scatter anywhere.
        onehot = (
            task_machine[:, None, :]
            == jnp.arange(m, dtype=task_machine.dtype)[None, :, None]
        )
        var_w = jnp.sum(jnp.where(onehot, ev[:, None, :], 0.0), axis=-1)
        met_w = jnp.sum(jnp.where(onehot, met[:, None, :], 0.0), axis=-1)
        return onehot, var_w, met_w

    def _limits(head, var):
        """Each machine's rate limit: head room over the variable load."""
        return jnp.where(var > 0.0, head / jnp.maximum(var, 1e-300), jnp.inf)

    def _finish(var_w, met_w, capacity, unit_ir, infeasible_extra=None):
        cap_b = capacity if capacity.ndim == 2 else capacity[None, :]
        head = cap_b - met_w
        infeasible = jnp.any(head < 0.0, axis=1)
        if infeasible_extra is not None:
            infeasible = infeasible | infeasible_extra
        limits = _limits(head, var_w)
        rates = jnp.clip(jnp.min(limits, axis=1), 0.0, None)
        rates = jnp.where(infeasible, 0.0, rates)
        thpt = rates * (unit_ir.sum(axis=1) if per_row else unit_ir.sum())
        return rates, thpt

    def _net_masses(onehot, comp, unit_ir, distance, adjacency, alpha, cir_unit):
        """``_net_terms`` of rows given by their (B, m, T) one-hot: each
        component's unit-rate mass per machine, by the same contraction.
        Components count from the row's least ``comp``, so rows of
        different topologies index their own (B, n, n) / (B, n) tables."""
        cmap = comp if comp.ndim == 2 else comp[None, :]
        u = unit_ir if unit_ir.ndim == 2 else unit_ir[None, :]
        local = cmap - jnp.min(cmap, axis=-1, keepdims=True)
        n = adjacency.shape[-1]
        mass = jnp.stack(
            [
                jnp.sum(
                    jnp.where(onehot & (local == c)[:, None, :], u[:, None, :], 0.0),
                    axis=-1,
                )
                for c in range(n)
            ],
            axis=1,
        )
        return _net_terms(mass, distance, adjacency, alpha, cir_unit)

    def _net_terms(mass, distance, adjacency, alpha, cir_unit):
        """Cut-traffic terms at unit rate of (B, n, m) masses, each (B, n,
        m): what the instances of component c on machine w send (``send``)
        and what share of c's input they receive (``recv``), the neighbour
        masses ``r_out`` (c's children's ``recv``) and ``s_in`` (c's
        parents' ``send``), and their distance products ``(D x)[w] =
        sum_v D[w, v] x[v]``."""
        adj = adjacency if adjacency.ndim == 3 else adjacency[None]
        al = (alpha if alpha.ndim == 2 else alpha[None])[:, :, None]
        cir = (cir_unit if cir_unit.ndim == 2 else cir_unit[None])[:, :, None]
        send = al * mass
        recv = jnp.where(cir > 0.0, mass / jnp.where(cir > 0.0, cir, 1.0), 0.0)
        r_out = jnp.sum(adj[:, :, :, None] * recv[:, None, :, :], axis=2)
        s_in = jnp.sum(adj[:, :, :, None] * send[:, :, None, :], axis=1)

        def dist(x):
            # Laid out (B·n, m, m): over the 4-D (B, n, m, m) broadcast the
            # TPU compiler takes about ten times longer.
            b, n, m = x.shape
            rows = x.reshape(b * n, 1, m) * distance[None]
            return jnp.sum(rows, axis=-1).reshape(b, n, m)

        return send, recv, r_out, s_in, dist(r_out), dist(s_in)

    def _net_load(onehot, comp, unit_ir, distance, adjacency, alpha, cir_unit, penalty):
        """(B, m) cut-traffic CPU load at unit rate, ``network_unit_load``'s
        term: each machine pays for its flows to every other machine."""
        if adjacency.shape[-1] == 0:
            return jnp.zeros(onehot.shape[:2], dtype=unit_ir.dtype)
        send, recv, _, _, d_r_out, d_s_in = _net_masses(
            onehot, comp, unit_ir, distance, adjacency, alpha, cir_unit
        )
        return penalty * jnp.sum(send * d_r_out + recv * d_s_in, axis=1)

    if edits or count_edits:
        if per_row or (edits and count_edits):
            raise ValueError("an edit kernel takes its own maps")
        name = ("msr_count_edits" if count_edits else "msr_edits") + (
            "_resources" if with_resources else ""
        )
    else:
        name = ("msr_resources_" if with_resources else "msr_") + (
            "per_row" if per_row else "shared"
        )

    def kernel(task_machine, comp, unit_ir, e_cm, met_cm, capacity):
        with jax.named_scope(name):
            _, var_w, met_w = _accumulate(
                task_machine, comp, unit_ir, e_cm, met_cm, capacity
            )
            return _finish(var_w, met_w, capacity, unit_ir)

    def _patched_rates(
        var_w, met_w, mem_w, net_w, capacity, mem_capacity, dvar, dmet, dmem, dnet
    ):
        """Rates of a grid of candidates given every machine's change to the
        base's totals (last axis): each total is the base's plus its change,
        so a machine the candidate leaves alone keeps its sums exactly."""
        head = capacity - (met_w + dmet)
        den = (var_w + dvar) + (net_w + dnet)
        infeasible = jnp.any(head < 0.0, axis=-1) | jnp.any(
            mem_w + dmem > mem_capacity, axis=-1
        )
        rates = jnp.clip(jnp.min(_limits(head, den), axis=-1), 0.0, None)
        return jnp.where(infeasible, 0.0, rates)

    def kernel_resources(
        task_machine, comp, unit_ir, e_cm, met_cm, capacity,
        mem, mem_capacity, distance, adjacency, alpha, cir_unit, net_penalty,
    ):
        with jax.named_scope(name):
            onehot, var_w, met_w = _accumulate(
                task_machine, comp, unit_ir, e_cm, met_cm, capacity
            )
            var_w = var_w + _net_load(
                onehot, comp, unit_ir, distance, adjacency, alpha, cir_unit,
                net_penalty,
            )
            mem_bt = mem[comp if per_row else comp[None, :]]
            mem_w = jnp.sum(jnp.where(onehot, mem_bt[:, None, :], 0.0), axis=-1)
            mem_cap_b = (
                mem_capacity if mem_capacity.ndim == 2 else mem_capacity[None, :]
            )
            over_mem = jnp.any(mem_w > mem_cap_b, axis=1)
            return _finish(
                var_w, met_w, capacity, unit_ir, infeasible_extra=over_mem
            )

    def _winners(relocate, swap, base, rows, comp):
        """Each grid's first maximum over its candidates, in row-major
        order (the host menu's): ``[relocate index, relocate throughput,
        swap index, swap throughput]``, the indices flat in their grids.
        Relocations leave a's machine; swaps pair a with a later task b of
        another component on another machine. A grid with no candidate
        reads index 0 and -inf."""
        s_a, c_a = base[rows][:, None], comp[rows][:, None]
        moves = jnp.arange(relocate.shape[1], dtype=base.dtype)[None, :] != s_a
        b = jnp.arange(base.shape[0], dtype=base.dtype)[None, :]
        pairs = (b > rows[:, None]) & (comp[None, :] != c_a) & (base[None, :] != s_a)
        out = []
        for grid, cells in ((relocate, moves), (swap, pairs)):
            flat = jnp.where(cells, grid, -jnp.inf).reshape(-1)
            i = jnp.argmax(flat)
            out += [i.astype(grid.dtype), flat[i]]
        return jnp.stack(out)

    def kernel_edits(base, rows, comp, unit_ir, e_cm, met_cm, capacity):
        with jax.named_scope(name):
            _, var_w, met_w = _accumulate(
                base[None, :], comp, unit_ir, e_cm, met_cm, capacity
            )
            m = capacity.shape[0]
            # Neutral machines (no load, unbounded capacity) pad the base, so
            # that three distinct machines rank first whatever m is.
            pad = jnp.zeros(_EDIT_SPARE, dtype=var_w.dtype)
            var_m = jnp.concatenate([var_w[0], pad])
            met_m = jnp.concatenate([met_w[0], pad])
            cap_m = jnp.concatenate([capacity, pad + jnp.inf])
            # Rank the base's machines once: infeasible first, then by
            # limit, ties by index. A move touches two machines, so the
            # least limit among the others is that of the first of the three
            # best that it leaves alone.
            head = cap_m - met_m
            key = jnp.where(head < 0.0, -jnp.inf, _limits(head, var_m))
            idx = jnp.arange(key.shape[0], dtype=base.dtype)
            before = (key[:, None] < key[None, :]) | (
                (key[:, None] == key[None, :]) & (idx[:, None] < idx[None, :])
            )
            rank = jnp.sum(before, axis=0, dtype=base.dtype)
            best = [jnp.argmax(rank == k) for k in range(_EDIT_SPARE)]

            def score(src, var_src, met_src, cap_src, dst, var_dst, met_dst, cap_dst):
                """Throughput of a grid of candidates touching two machines,
                ``src`` and ``dst``, with their new totals given."""
                rest_var, rest_met, rest_cap = (
                    t[best[-1]] for t in (var_m, met_m, cap_m)
                )
                for k in reversed(best[:-1]):
                    free = (src != k) & (dst != k)
                    rest_var = jnp.where(free, var_m[k], rest_var)
                    rest_met = jnp.where(free, met_m[k], rest_met)
                    rest_cap = jnp.where(free, cap_m[k], rest_cap)
                shape = jnp.broadcast_shapes(src.shape, dst.shape)
                cols = [
                    jnp.stack([jnp.broadcast_to(x, shape) for x in xs], axis=-1)
                    .reshape(-1, 3)
                    for xs in (
                        (var_src, var_dst, rest_var),
                        (met_src, met_dst, rest_met),
                        (cap_src, cap_dst, rest_cap),
                    )
                ]
                return _finish(*cols, unit_ir)[1].reshape(shape)

            # ev / mt: what task t adds to machine w's var_w / met_w there.
            ev = e_cm[comp] * unit_ir[:, None]                 # (T, m)
            mt = met_cm[comp]
            ev_home = jnp.take_along_axis(ev, base[:, None], axis=1)[:, 0]
            mt_home = jnp.take_along_axis(mt, base[:, None], axis=1)[:, 0]
            # Rows of the block: task a leaves its machine s_a.
            s_a = base[rows][:, None]                          # (A, 1)
            var_sa = var_m[s_a] + -ev_home[rows][:, None]
            met_sa = met_m[s_a] + -mt_home[rows][:, None]
            cap_sa = cap_m[s_a]
            # RELOCATE a -> w, every machine w (w == s_a is no candidate).
            w = jnp.arange(m, dtype=base.dtype)[None, :]       # (1, m)
            relocate = score(
                s_a, var_sa, met_sa, cap_sa,
                w, var_w + ev[rows], met_w + mt[rows], capacity[None, :],
            )
            # SWAP a <-> b, every task b: a joins s_b, b leaves s_b for s_a.
            # Each machine's change is taken first, so that two tasks of
            # equal load trading places leave its sum exactly as it was.
            s_b = base[None, :]                                # (1, T)
            swap = score(
                s_a,
                var_m[s_a] + (ev.T[s_a[:, 0]] - ev_home[rows][:, None]),
                met_m[s_a] + (mt.T[s_a[:, 0]] - mt_home[rows][:, None]),
                cap_sa,
                s_b,
                var_m[s_b] + (ev[rows].T[base].T - ev_home[None, :]),
                met_m[s_b] + (mt[rows].T[base].T - mt_home[None, :]),
                cap_m[s_b],
            )
            return relocate, swap, _winners(relocate, swap, base, rows, comp)

    def kernel_edits_resources(
        base, rows, comp, unit_ir, e_cm, met_cm, capacity,
        mem, mem_capacity, distance, adjacency, alpha, cir_unit, net_penalty,
    ):
        with jax.named_scope(name):
            onehot, var_w, met_w = _accumulate(
                base[None, :], comp, unit_ir, e_cm, met_cm, capacity
            )
            var_w, met_w = var_w[0], met_w[0]
            mem = mem[comp]                                                  # (T,)
            mem_w = jnp.sum(jnp.where(onehot[0], mem[None, :], 0.0), axis=-1)
            m = capacity.shape[0]
            zero = jnp.zeros((), dtype=unit_ir.dtype)
            if adjacency.shape[-1]:
                send, recv, r_out, s_in, d_r_out, d_s_in = (
                    x[0]
                    for x in _net_masses(
                        onehot, comp, unit_ir, distance, adjacency, alpha, cir_unit
                    )
                )
                net_w = net_penalty * jnp.sum(send * d_r_out + recv * d_s_in, axis=0)
                # Task t's send rate and receive share, and what its move
                # changes: K (distance-weighted) and L (on its two machines).
                o = alpha[comp] * unit_ir
                cir_t = cir_unit[comp]
                r = jnp.where(cir_t > 0.0, unit_ir / jnp.where(cir_t > 0.0, cir_t, 1.0), 0.0)
                K = r[:, None] * s_in[comp] + o[:, None] * r_out[comp]      # (T, m)
                L = o[:, None] * d_r_out[comp] + r[:, None] * d_s_in[comp]
                Dt = distance.T                                              # [v, w] = D[w, v]
            else:
                net_w = jnp.zeros(m, dtype=unit_ir.dtype)

            def score(*changes):
                totals = (var_w, met_w, mem_w, net_w, capacity, mem_capacity)
                return _patched_rates(*totals, *changes) * unit_ir.sum()

            ev = e_cm[comp] * unit_ir[:, None]                               # (T, m)
            mt = met_cm[comp]
            ev_home = jnp.take_along_axis(ev, base[:, None], axis=1)[:, 0]
            mt_home = jnp.take_along_axis(mt, base[:, None], axis=1)[:, 0]
            machines = jnp.arange(m, dtype=base.dtype)
            x = base[rows]                                                   # (A,)
            on_x = (machines[None, :] == x[:, None])[:, None, :]             # (A, 1, m)
            # RELOCATE a -> v, every machine v: the (A, v, w) grid.
            on_v = jnp.eye(m, dtype=bool)[None, :, :]
            flip = on_v.astype(ev.dtype) - on_x.astype(ev.dtype)
            dnet = zero
            if adjacency.shape[-1]:
                dnet = net_penalty * (
                    (Dt[None, :, :] - Dt[x][:, None, :]) * K[rows][:, None, :]
                    + flip * L[rows][:, None, :]
                )
            relocate = score(
                jnp.where(on_v, ev[rows][:, :, None], 0.0)
                - jnp.where(on_x, ev_home[rows][:, None, None], 0.0),
                jnp.where(on_v, mt[rows][:, :, None], 0.0)
                - jnp.where(on_x, mt_home[rows][:, None, None], 0.0),
                flip * mem[rows][:, None, None],
                dnet,
            )
            # SWAP a <-> b, every task b: the (A, b, w) grid; a joins y = s_b,
            # b joins x = s_a.
            on_y = (machines[None, :] == base[:, None])[None, :, :]          # (1, T, m)
            flip = on_y.astype(ev.dtype) - on_x.astype(ev.dtype)
            dnet = zero
            if adjacency.shape[-1]:
                ca = comp[rows]
                # The flow between a and b, when their components are
                # adjacent: each task's own term counts it as colocated.
                link = 2.0 * (
                    o[rows][:, None] * r[None, :] * adjacency[ca][:, comp]
                    + o[None, :] * r[rows][:, None] * adjacency.T[ca][:, comp]
                )
                cross = link[:, :, None] * (
                    jnp.where(on_x, distance[x][:, base][:, :, None], 0.0)
                    + jnp.where(on_y, Dt[x][:, base][:, :, None], 0.0)
                )
                dnet = net_penalty * (
                    (Dt[base][None, :, :] - Dt[x][:, None, :])
                    * (K[rows][:, None, :] - K[None, :, :])
                    + flip * (L[rows][:, None, :] - L[None, :, :])
                    + cross
                )
            mem_ab = (mem[rows][:, None] - mem[None, :])[:, :, None]
            swap = score(
                jnp.where(on_x, (ev.T[x] - ev_home[rows][:, None])[:, :, None], 0.0)
                + jnp.where(on_y, (ev[rows][:, base] - ev_home[None, :])[:, :, None], 0.0),
                jnp.where(on_x, (mt.T[x] - mt_home[rows][:, None])[:, :, None], 0.0)
                + jnp.where(on_y, (mt[rows][:, base] - mt_home[None, :])[:, :, None], 0.0),
                jnp.where(on_y, mem_ab, 0.0) - jnp.where(on_x, mem_ab, 0.0),
                dnet,
            )
            return relocate, swap, _winners(relocate, swap, base, rows, comp)

    def _machine_sums(base, m):
        """Per-machine sums over each of k rows' tasks: a function from
        (k, T) per-task values to (k, m) sums, by the one-hot contraction
        laid out (k·m, T). On a short leading axis (k is at most n² chains)
        the (k, m, T) layout takes the TPU compiler several times longer."""
        k = base.shape[0]
        onehot = jnp.repeat(base, m, axis=0) == jnp.tile(
            jnp.arange(m, dtype=base.dtype), k
        )[:, None]

        def sums(x):
            rows = jnp.repeat(x, m, axis=0)
            return jnp.sum(jnp.where(onehot, rows, 0.0), axis=-1).reshape(k, m)

        return sums

    def _count_rows(base, counts, unit, grown):
        """Per-task (k, T) component and unit-rate maps of each base row
        under its candidates' counts, and the (k,) unit rate ``u`` of the
        component whose count they change."""
        ends = jnp.cumsum(counts, axis=1)
        tasks = jnp.arange(base.shape[1], dtype=counts.dtype)
        comp = jnp.sum(
            tasks[None, :, None] >= ends[:, None, :], axis=-1, dtype=base.dtype
        )
        unit_ir = jnp.take_along_axis(unit, comp, axis=1)
        u = jnp.take_along_axis(unit, grown[:, None], axis=1)[:, 0]
        return comp, unit_ir, u

    def kernel_count_edits(base, counts, unit, grown, e_cm, met_cm, capacity, drop):
        with jax.named_scope(name):
            comp, unit_ir, u = _count_rows(base, counts, unit, grown)
            sums = _machine_sums(base, capacity.shape[0])
            var_w = sums(e_cm[comp, base] * unit_ir)                         # (k, m)
            met_w = sums(met_cm[comp, base])
            head = capacity - met_w
            limits = _limits(head, var_w)
            over = head < 0.0
            # What one task of the changed component adds to each machine.
            ev = e_cm[grown] * u[:, None]                                    # (k, m)
            mt = met_cm[grown]
            if drop:
                # Task p leaves its machine x: the (k, T) grid.
                x = base
                var_x = jnp.take_along_axis(var_w, x, axis=1) - jnp.take_along_axis(ev, x, axis=1)
                met_x = jnp.take_along_axis(met_w, x, axis=1) - jnp.take_along_axis(mt, x, axis=1)
                total = unit_ir.sum(axis=1) - u
            else:
                # One more task on machine x, every x: the (k, m) grid.
                x = jnp.broadcast_to(jnp.arange(capacity.shape[0], dtype=base.dtype), var_w.shape)
                var_x, met_x = var_w + ev, met_w + mt
                total = unit_ir.sum(axis=1) + u
            # The least limit among the machines a candidate leaves alone:
            # the row's least, unless x alone holds it, then the next.
            least = jnp.min(limits, axis=1, keepdims=True)
            alone = jnp.sum(limits == least, axis=1, keepdims=True) == 1
            runner_up = jnp.min(
                jnp.where(limits == least, jnp.inf, limits), axis=1, keepdims=True
            )
            lim_x = jnp.take_along_axis(limits, x, axis=1)
            rest = jnp.where((lim_x == least) & alone, runner_up, least)
            n_over = jnp.sum(over, axis=1, keepdims=True, dtype=base.dtype)
            rest_over = n_over - jnp.take_along_axis(over, x, axis=1).astype(base.dtype) > 0
            head_x = capacity[x] - met_x
            rates = jnp.clip(jnp.minimum(_limits(head_x, var_x), rest), 0.0, None)
            rates = jnp.where(rest_over | (head_x < 0.0), 0.0, rates)
            return rates, rates * total[:, None]

    def kernel_count_edits_resources(
        base, counts, unit, grown, e_cm, met_cm, capacity,
        mem, mem_capacity, distance, adjacency, alpha, cir_unit, net_penalty, drop,
    ):
        with jax.named_scope(name):
            comp, unit_ir, u = _count_rows(base, counts, unit, grown)
            k, m = base.shape[0], capacity.shape[0]
            sums = _machine_sums(base, m)
            var_w = sums(e_cm[comp, base] * unit_ir)                         # (k, m)
            met_w = sums(met_cm[comp, base])
            mem_w = sums(mem[comp])
            dnet = jnp.zeros((), dtype=unit_ir.dtype)
            net_w = jnp.zeros((k, m), dtype=unit_ir.dtype)
            if adjacency.shape[-1]:
                mass = jnp.stack(
                    [
                        sums(jnp.where(comp == c, unit_ir, 0.0))
                        for c in range(adjacency.shape[-1])
                    ],
                    axis=1,
                )
                send, recv, r_out, s_in, d_r_out, d_s_in = _net_terms(
                    mass, distance, adjacency, alpha, cir_unit
                )
                net_w = net_penalty * jnp.sum(send * d_r_out + recv * d_s_in, axis=1)
                # A task's send rate and receive share at the rescaled rate,
                # and what it adds: K (distance-weighted), L (on its machine).
                rows = jnp.arange(k)
                o = (alpha[grown] * u)[:, None]
                cir_c = cir_unit[grown]
                r = jnp.where(cir_c > 0.0, u / jnp.where(cir_c > 0.0, cir_c, 1.0), 0.0)[:, None]
                K = r * s_in[rows, grown] + o * r_out[rows, grown]          # (k, m)
                L = o * d_r_out[rows, grown] + r * d_s_in[rows, grown]
                Dt = distance.T                                              # [v, w] = D[w, v]
            ev = e_cm[grown] * u[:, None]                                    # (k, m)
            mt = met_cm[grown]
            mem_c = mem[grown][:, None, None]
            if drop:
                # Task p leaves its machine x = base[i, p]: the (k, T, w) grid.
                on = base[:, :, None] == jnp.arange(m, dtype=base.dtype)     # (k, T, m)
                dvar = -jnp.where(on, jnp.take_along_axis(ev, base, axis=1)[..., None], 0.0)
                dmet = -jnp.where(on, jnp.take_along_axis(mt, base, axis=1)[..., None], 0.0)
                dmem = -jnp.where(on, mem_c, 0.0)
                if adjacency.shape[-1]:
                    dnet = -(net_penalty * (Dt[base] * K[:, None, :] + on * L[:, None, :]))
                total = unit_ir.sum(axis=1) - u
            else:
                # One more task on machine v, every v: the (k, v, w) grid.
                on = jnp.eye(m, dtype=bool)[None]                           # (1, m, m)
                dvar = jnp.where(on, ev[:, :, None], 0.0)
                dmet = jnp.where(on, mt[:, :, None], 0.0)
                dmem = jnp.where(on, mem_c, 0.0)
                if adjacency.shape[-1]:
                    dnet = net_penalty * (Dt[None] * K[:, None, :] + on * L[:, None, :])
                total = unit_ir.sum(axis=1) + u
            totals = (x[:, None, :] for x in (var_w, met_w, mem_w, net_w))
            rates = _patched_rates(
                *totals, capacity, mem_capacity, dvar, dmet, dmem, dnet
            )
            return rates, rates * total[:, None]

    if count_edits:
        fn = kernel_count_edits_resources if with_resources else kernel_count_edits
        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn, static_argnames="drop")
    if edits:
        fn = kernel_edits_resources if with_resources else kernel_edits
    else:
        fn = kernel_resources if with_resources else kernel
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def closed_form_rates_jax(
    task_machine: np.ndarray,
    comp: np.ndarray,
    unit_ir: np.ndarray,
    e_cm: np.ndarray,
    met_cm: np.ndarray,
    capacity: np.ndarray,
    resources: list | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """JAX twin of ``cost_model.closed_form_rates`` (scatter-free).

    ``comp`` / ``unit_ir`` may be (T,) shared maps or (B, T) per-row maps;
    each shape routes to its own cached kernel variant. ``capacity`` may be
    (m,) shared or (B, m) per-row. The sweep runs the XLA contraction in
    float64 on every backend (emulated on TPU).

    ``resources`` is the operand tail ``device_resources`` builds for a
    cluster with network or memory resources (``None`` without them, which
    runs the exact legacy kernels). It has the ``cost_model.closed_form_rates``
    semantics: the cut-traffic load, computed on the device from the rows
    and the distance matrix, is added to the variable coefficient, and
    memory is a hard feasibility mask. Nothing per row is computed on the
    host.

    On the active recorder the sweep is three spans — ``sweep.put`` (the
    operands' ``jax.device_put``), ``sweep.run`` (the jitted call) and
    ``sweep.fetch`` (the ``np.asarray`` of rates and throughput) — and the
    operands' ``nbytes`` add to the ``sweep.h2d_bytes`` counter (those of
    the tables, from ``e_cm`` on, also to ``sweep.table_h2d_bytes``).

    Refine's RELOCATE+SWAP rows are all edits of one base row; where they
    would resolve to this function they go to ``relocate_swap_scores_jax``
    instead, which ships the base row alone and scores the candidates as
    edits (``msr_edits``, ``msr_edits_resources``).
    """
    operands = [task_machine, comp, unit_ir, e_cm, met_cm, capacity]
    if resources is not None:
        operands += resources
    kernel = _msr_kernel(
        per_row=comp.ndim == 2, with_resources=resources is not None
    )
    return _device_sweep(kernel, operands, 3)


def edge_counts(n_components: int, edges) -> np.ndarray:
    """(n, n) float matrix of edge multiplicities: ``[a, b]`` counts the
    topology's edges a -> b (``network_unit_load`` prices each once)."""
    adjacency = np.zeros((n_components, n_components), dtype=np.float64)
    for a, b in edges:
        adjacency[a, b] += 1.0
    return adjacency


def device_resources(
    cluster: Cluster,
    component_types: np.ndarray,
    edges,
    alpha: np.ndarray,
    cir_unit: np.ndarray,
) -> list | None:
    """The resource operands of a device sweep of one topology on
    ``cluster``, or ``None`` on a cluster without network or memory
    resources: ``[mem, mem_capacity, distance, adjacency, alpha, cir_unit,
    net_penalty]``, with ``mem`` the (n,) memory demand per component.
    Without a memory model it is zeros against an unbounded capacity, and
    without a distance matrix the network tables hold no component, so the
    kernel prices no cut traffic."""
    if not cluster.has_resources:
        return None
    if cluster.has_memory:
        mem = cluster.profile.mem[component_types]
        mem_capacity = cluster.mem_capacity
    else:
        mem = np.zeros(len(component_types), dtype=np.float64)
        mem_capacity = np.full(cluster.n_machines, np.inf)
    tables = network_tables(
        cluster,
        edge_counts(len(cir_unit), edges),
        np.asarray(alpha, dtype=np.float64),
        np.asarray(cir_unit, dtype=np.float64),
    )
    return [mem, mem_capacity, *tables]


def network_tables(cluster: Cluster, adjacency, alpha, cir_unit) -> list:
    """The network tail of a resource sweep: ``[distance, adjacency, alpha,
    cir_unit, net_penalty]`` (shared (n, n) / (n,) tables, or per-row
    (B, n, n) / (B, n) ones), or tables of no component when ``cluster``
    has no distance matrix."""
    if not cluster.has_network:
        empty = np.zeros(0, dtype=np.float64)
        return [np.zeros((0, 0)), np.zeros((0, 0)), empty, empty, np.float64(0.0)]
    return [cluster.distance, adjacency, alpha, cir_unit, np.float64(cluster.net_penalty)]


def _device_sweep(
    kernel, operands: list, tables: int, keep: slice = slice(None)
) -> tuple[np.ndarray, ...]:
    """Run a jitted scorer on host operands in float64: ``sweep.put`` (the
    operands' ``jax.device_put``, whose ``nbytes`` add to the
    ``sweep.h2d_bytes`` counter, and those of ``operands[tables:]``, the
    per-cluster tables ``e_cm``, ``met_cm``, ``capacity`` and
    ``device_resources``' tail, also to ``sweep.table_h2d_bytes``),
    ``sweep.run`` (the call, which only enqueues) and ``sweep.fetch`` (the
    ``np.asarray`` of the results that ``keep`` selects, where the host
    waits for the device; their ``nbytes`` add to the ``sweep.d2h_bytes``
    counter). Results left out are never read back."""
    import jax

    with jax.enable_x64(True):
        with trace.span("sweep.put", "sweep"):
            on_device = jax.device_put(operands)
        trace.count("sweep.h2d_bytes", sum(int(x.nbytes) for x in operands))
        trace.count(
            "sweep.table_h2d_bytes", sum(int(x.nbytes) for x in operands[tables:])
        )
        with trace.span("sweep.run", "sweep"):
            out = kernel(*on_device)
    with trace.span("sweep.fetch", "sweep"):
        fetched = tuple(np.asarray(x) for x in out[keep])
    trace.count("sweep.d2h_bytes", sum(int(x.nbytes) for x in fetched))
    return fetched


def relocate_swap_scores_jax(
    base: np.ndarray,
    rows: np.ndarray,
    comp: np.ndarray,
    unit_ir: np.ndarray,
    e_cm: np.ndarray,
    met_cm: np.ndarray,
    capacity: np.ndarray,
    resources: list | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Closed-form throughput of the RELOCATE and SWAP edits of one base
    row, on ``msr_edits`` (``msr_edits_resources`` with ``resources``).

    ``base`` is the (T,) task->machine row and ``rows`` the (A,) tasks a
    that move. Returns the (A, m) grid whose [a, w] entry scores ``base``
    with task a on machine w, and the (A, T) grid whose [a, b] entry scores
    it with tasks a and b trading machines. Entries that are no candidate
    (w already a's machine, a and b on one machine) hold no meaning.
    ``comp`` / ``unit_ir`` are the (T,) shared maps and ``capacity`` is
    (m,); ``resources`` is ``device_resources``' tail, or ``None`` on a
    cluster without resources. Each entry is what
    ``closed_form_rates_jax`` gives the materialised row, up to the
    rounding of the patched machine sums.

    Only the base row and the tables ship (indices as int32), and only the
    two grids come back (A·(m + T) float64s), not the kernel's winners.
    Spans and the ``sweep.h2d_bytes`` / ``sweep.d2h_bytes`` counters are
    those of ``closed_form_rates_jax``.
    """
    return _relocate_swap_sweep(
        base, rows, comp, unit_ir, e_cm, met_cm, capacity, resources, slice(2)
    )


def relocate_swap_winners_jax(
    base: np.ndarray,
    rows: np.ndarray,
    comp: np.ndarray,
    unit_ir: np.ndarray,
    e_cm: np.ndarray,
    met_cm: np.ndarray,
    capacity: np.ndarray,
    resources: list | None = None,
) -> np.ndarray:
    """The winners of ``relocate_swap_scores_jax``'s grids, selected on the
    device by the same program: ``[relocate index, relocate throughput,
    swap index, swap throughput]``, each index flat in its grid ((A, m) and
    (A, T), row-major) and each the first strict maximum over the grid's
    candidates (w not a's machine; b after a, of another component, on
    another machine), which is ``np.argmax`` over the candidates in the
    host menu's order. A grid with no candidate reads index 0 and -inf.

    Only these four float64s come back (32 bytes, against the grids'
    8·A·(m + T)); operands, spans and counters are
    ``relocate_swap_scores_jax``'s.
    """
    return _relocate_swap_sweep(
        base, rows, comp, unit_ir, e_cm, met_cm, capacity, resources, slice(2, 3)
    )[0]


def _relocate_swap_sweep(
    base, rows, comp, unit_ir, e_cm, met_cm, capacity, resources, keep: slice
) -> tuple[np.ndarray, ...]:
    """One ``msr_edits`` (``msr_edits_resources``) sweep, fetching the
    outputs ``keep`` selects of (relocate grid, swap grid, winners)."""
    operands = [
        np.asarray(base, dtype=np.int32),
        np.asarray(rows, dtype=np.int32),
        np.asarray(comp, dtype=np.int32),
        unit_ir,
        e_cm,
        met_cm,
        capacity,
    ]
    if resources is not None:
        operands += resources
    kernel = _msr_kernel(edits=True, with_resources=resources is not None)
    return _device_sweep(kernel, operands, 4, keep)


def count_edit_scores_jax(
    base: np.ndarray,
    counts: np.ndarray,
    unit: np.ndarray,
    grown: np.ndarray,
    e_cm: np.ndarray,
    met_cm: np.ndarray,
    capacity: np.ndarray,
    resources: list | None = None,
    drop: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Closed-form (rate, throughput) of the count edits of k base rows, on
    ``msr_count_edits`` (``msr_count_edits_resources`` with ``resources``).

    Row i of ``base`` (k, T) holds ``counts[i]`` (n,) instances per
    component, in task order. Its candidates change the count of component
    ``grown[i]`` by one; ``unit`` (k, n) holds their per-component unit
    input rates (``cir_unit`` over the changed counts), which every task of
    the row takes. With ``drop=False`` returns two (k, m) grids whose [i, v]
    entries score row i with one more task of ``grown[i]`` on machine v;
    with ``drop=True`` two (k, T) grids whose [i, p] entries score row i
    without task p (meaningful where p is a task of ``grown[i]``). Each
    entry is what ``closed_form_rates_jax`` gives the materialised row, up
    to the rounding of the patched machine sums. ``resources`` is
    ``device_resources``' tail, or ``None`` on a cluster without resources.

    Only the base rows, counts and tables ship (indices as int32), and only
    the grids come back. Spans and the ``sweep.h2d_bytes`` counter are those
    of ``closed_form_rates_jax``.
    """
    operands = [
        np.asarray(base, dtype=np.int32),
        np.asarray(counts, dtype=np.int32),
        unit,
        np.asarray(grown, dtype=np.int32),
        e_cm,
        met_cm,
        capacity,
    ]
    if resources is not None:
        operands += resources
    kernel = _msr_kernel(count_edits=True, with_resources=resources is not None)
    return _device_sweep(functools.partial(kernel, drop=drop), operands, 4)


def max_stable_rate_batch_jax(
    etg: ExecutionGraph,
    cluster: Cluster,
    task_machine: np.ndarray,
    n_instances: np.ndarray | None = None,
    skew=None,
) -> tuple[np.ndarray, np.ndarray]:
    """JAX backend for ``cost_model.max_stable_rate_batch`` (same contract,
    including the optional (B, n) per-row ``n_instances`` matrix and the
    optional ``skew`` model — skew rows score through the same jitted
    kernel, fed the skew-realized unit rates instead of the even split)."""
    from repro.core import cost_model

    utg = etg.utg
    task_machine = np.asarray(task_machine, dtype=np.int64)
    if task_machine.ndim != 2:
        raise ValueError("task_machine must be (B, T)")
    if skew is not None and skew.utg is not utg:
        raise ValueError("skew model was built for a different topology")
    if n_instances is not None:
        n_inst_bn = np.asarray(n_instances, dtype=np.int64)
        cir_unit = skew.cir_unit if skew is not None else (
            cost_model.component_rates(utg, 1.0)
        )
        comp, unit_ir = cost_model.per_row_task_maps(
            cir_unit, n_inst_bn, task_machine.shape[1]
        )
        if skew is not None:
            unit_ir = skew.per_row_unit_ir(n_inst_bn)
    else:
        comp = etg.task_component()
        if task_machine.shape[1] != comp.shape[0]:
            raise ValueError("task_machine must be (B, T)")
        unit_ir = (
            skew.per_task_unit_ir(etg.n_instances)
            if skew is not None
            else cost_model.instance_rates(etg, 1.0)
        )
    ttypes = utg.component_types
    e_cm = cluster.profile.e[ttypes][:, cluster.machine_types]
    met_cm = cluster.profile.met[ttypes][:, cluster.machine_types]
    cir_unit = skew.cir_unit if skew is not None else (
        cost_model.component_rates(utg, 1.0)
    )
    resources = device_resources(cluster, ttypes, utg.edges, utg.alpha, cir_unit)
    return closed_form_rates_jax(
        task_machine, comp, unit_ir, e_cm, met_cm, cluster.capacity, resources
    )
