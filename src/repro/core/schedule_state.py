"""Incremental scheduling engine: flat ScheduleState + closed-form stepping.

The reference implementation of Algorithm 2 (``maximize_throughput`` in
``maximize_throughput.py``) re-derives everything from the ``ExecutionGraph``
on every iteration: ``predict`` walks all T tasks, ``with_new_instance``
copies the whole graph, and ``_grow_component`` runs a full greedy placement
attempt for *every* candidate target count — on the paper's large scenario
(20/70/90 machines, 478 tasks) that is ~600k O(m) numpy calls and ~25 s of
wall clock for 46 algorithm iterations.

This module rebuilds the hot path around three observations (see
docs/architecture.md for the full derivation):

1. **Flat structure-of-arrays state.** Instances of one component on one
   machine are indistinguishable, so the whole schedule collapses to an
   (n_components, n_machines) count matrix plus per-component instance
   totals. Adding an instance is an O(m) delta (the eq. 6 re-split touches
   only the grown component's row); rollback to the last stable schedule is
   a cheap snapshot/restore instead of a deep graph copy.

2. **Closed-form rate stepping.** eq. 5/6 are linear in the topology input
   rate R, so per-machine utilization is ``met_load + R * var_load`` with
   rate-independent coefficients and the binding machine's maximum stable
   rate has the closed form ``R* = min_w (cap_w - met_w) / var_w``. The
   raise loop jumps through its geometric schedule comparing against R*
   (O(1) per step after one O(m) reduction per structural change) instead
   of re-predicting all T tasks per step. Iterations within a relative
   float-uncertainty band of R* are decided by *exact rational
   arithmetic* on the cached linear coefficients (``fractions.Fraction``
   over the per-machine ``met_load``/``var_load`` floats), so the
   feasibility boundary is a hard number — no heuristic re-check band
   (the golden equivalence suite remains the gate that boundary
   decisions agree with the reference's per-task summation in practice).
   Trace semantics (one trace entry per Algorithm-2 iteration) are
   preserved.

3. **Closed-form growth feasibility.** Inside ``_grow_component`` the new
   chunk TCU is a fixed per-machine value, so greedy placement of k new
   instances succeeds iff ``sum_w max(0, floor(avail_w / tcu_w) - counts_w)
   >= k`` — no per-instance simulation needed to *reject* a target count.
   The scan over candidate targets becomes one vectorized (n_targets, m)
   computation; the exact reference greedy (same lexsort tie-breaking)
   runs only for the first target the closed form admits, preserving the
   reference placement order exactly.

The engine is selected via ``schedule(..., engine="incremental")`` (the
default); ``engine="reference"`` runs the original path. Golden tests in
``tests/test_sched_equivalence.py`` assert both produce identical final
``(rate, n_instances, assignment)`` across topologies and cluster sizes.
"""

from __future__ import annotations

import dataclasses
from fractions import Fraction

import numpy as np

from repro.core import cost_model
from repro.core.graph import ExecutionGraph, UserGraph
from repro.core.profiles import Cluster
from repro.obs import trace

__all__ = ["ScheduleState", "maximize_throughput_incremental"]

# Relative half-width of the float pre-filter around the closed-form R*.
# Rates outside the band are decided by the float comparison alone (the
# float R* is within a few ulps of the exact rational value, far inside
# 1e-9 relative); rates inside the band are decided exactly, in rational
# arithmetic over the cached linear coefficients (`feasible_linear_exact`).
_RSTAR_GUARD = 1e-9


# Grid cells of one RELOCATE+SWAP sweep on the device
# (``ScheduleState.score_relocate_swap``): a sweep of A moving tasks scores
# an (A, m) relocate grid and an (A, T) swap grid. At 2**22 the paper's
# large scenario (478 tasks, 180 machines) is one sweep per round.
_EDIT_SWEEP_CELLS = 1 << 22

# The same budget on a cluster with network or memory resources, counted in
# (candidate x machine) cells: every candidate is scored on all m machines,
# an (A, m, m) relocate grid and an (A, T, m) swap grid. At 2**26 the
# paper's large scenario is still one sweep per round (56.6M cells); the
# rack-aware deployment of the same cluster (1,434 tasks) is 7 sweeps of 230
# moving tasks (416.6M cells).
_NET_EDIT_SWEEP_CELLS = 1 << 26


def _edited_rows(base: np.ndarray, edits: np.ndarray) -> np.ndarray:
    """(B, T) rows of ``base`` with ``row[pos_a] = val_a`` then ``row[pos_b]
    = val_b`` for ``(pos_a, val_a, pos_b, val_b) = edits[:, b]``."""
    tm = np.tile(base, (edits.shape[1], 1))
    rows = np.arange(edits.shape[1])
    tm[rows, edits[0]] = edits[1]
    tm[rows, edits[2]] = edits[3]
    return tm


@dataclasses.dataclass(frozen=True, eq=False)
class _CountEdits:
    """Candidate rows given as count edits of k base rows, which
    ``ScheduleState.score_task_machine_batch`` scores without building
    them where it can: row i of ``rows`` (k, T) holds ``counts[i]`` (n,)
    instances per component, and its candidates change the count of
    ``comps[i]`` by one. A growth step appends one more instance at the end
    of the component's block, on each of ``n_machines`` machines (k·m rows
    of T + 1 tasks, chain by chain); a ``drop`` removes each of its
    instances in turn (rows of T - 1 tasks). ``shared``: a single chain,
    whose m rows resolve as one shared count vector."""

    rows: np.ndarray
    counts: np.ndarray
    comps: np.ndarray
    n_machines: int
    drop: bool = False
    shared: bool = False

    def __post_init__(self):
        for name in ("rows", "counts", "comps"):
            object.__setattr__(self, name, np.asarray(getattr(self, name), dtype=np.int64))

    @property
    def new_counts(self) -> np.ndarray:
        """(k, n) instance counts of each row's candidates."""
        new = self.counts.copy()
        new[np.arange(self.comps.size), self.comps] += -1 if self.drop else 1
        return new

    @property
    def shape(self) -> tuple[int, int]:
        """(B, T') of the candidate rows."""
        k, n_tasks = self.rows.shape
        if self.drop:
            return int(self.counts[np.arange(k), self.comps].sum()), n_tasks - 1
        return k * self.n_machines, n_tasks + 1

    def materialise(self) -> tuple[np.ndarray, np.ndarray]:
        """The (B, T') candidate rows, in order, and their counts: (B, n),
        or the one (n,) vector where ``shared``."""
        k, n_tasks = self.rows.shape
        m, new = self.n_machines, self.new_counts
        ends = np.cumsum(self.counts, axis=1)[np.arange(k), self.comps]
        if self.drop:
            sizes = self.counts[np.arange(k), self.comps]
            task = np.concatenate([np.arange(e - s, e) for e, s in zip(ends, sizes)])
            row = np.repeat(np.arange(k), sizes)
            cols = np.arange(n_tasks - 1)
            tm = self.rows[row[:, None], cols[None, :] + (cols[None, :] >= task[:, None])]
            return tm, np.repeat(new, sizes, axis=0)
        # Insert one column at the end of each row's grown block: source
        # column j-1 right of it, j left of it; the insert column itself is
        # overwritten with the machine index.
        cols = np.arange(n_tasks + 1)
        src = np.clip(cols[None, :] - (cols[None, :] > ends[:, None]), 0, max(n_tasks - 1, 0))
        tm = np.repeat(np.take_along_axis(self.rows, src, axis=1), m, axis=0)
        tm[np.arange(k * m), np.repeat(ends, m)] = np.tile(np.arange(m), k)
        return tm, new[0] if self.shared else np.repeat(new, m, axis=0)


class ScheduleState:
    """Flat, incrementally-updatable schedule state (structure of arrays).

    Instead of per-instance objects, the state stores:

    * ``n_instances``   (n,)   — instance count per component;
    * ``comp_counts``   (n, m) — instances of component c on machine w;
    * ``assignment``    list of per-component machine-index lists, in the
      order instances were added (preserves ``with_new_instance`` append
      semantics so the final ETG is byte-identical to the reference path);
    * cached profile slices ``e_cm``/``met_cm`` (n, m) for the concrete
      cluster, and the unit-rate component input rates ``cir_unit`` (n,).

    Per-machine accumulators ``met_load`` and ``var_load`` (d util / d R)
    are derived from the count matrix in O(n·m) and cached; structural
    mutations invalidate the cache. All mutation is O(m) per added
    instance.
    """

    __slots__ = (
        "utg",
        "cluster",
        "n_instances",
        "assignment",
        "comp_counts",
        "e_cm",
        "met_cm",
        "cir_unit",
        "mem_c",
        "skew",
        "rows_scored",
        "_met_load",
        "_var_load",
        "_mem_load",
        "_net_load",
    )

    def __init__(
        self,
        utg: UserGraph,
        cluster: Cluster,
        etg: ExecutionGraph,
        skew: "cost_model.SkewModel | None" = None,
    ):
        self.utg = utg
        self.cluster = cluster
        self.n_instances = etg.n_instances.copy()
        self.assignment = [list(map(int, a)) for a in etg.assignment]
        n, m = utg.n_components, cluster.n_machines
        ttypes = utg.component_types
        self.e_cm = cluster.profile.e[ttypes][:, cluster.machine_types]
        self.met_cm = cluster.profile.met[ttypes][:, cluster.machine_types]
        self.cir_unit = cost_model.component_rates(utg, 1.0)
        self.mem_c = cluster.profile.mem[ttypes] if cluster.has_memory else None
        if skew is not None and skew.utg is not utg:
            raise ValueError("skew model was built for a different topology")
        self.skew = skew
        self.comp_counts = np.zeros((n, m), dtype=np.int64)
        for c, machines in enumerate(self.assignment):
            for w in machines:
                self.comp_counts[c, w] += 1
        self._met_load: np.ndarray | None = None
        self._var_load: np.ndarray | None = None
        self._mem_load: np.ndarray | None = None
        self._net_load: np.ndarray | None = None
        # Candidate rows scored by ``score_task_machine_batch`` so far.
        self.rows_scored = 0

    @classmethod
    def from_etg(
        cls,
        etg: ExecutionGraph,
        cluster: Cluster,
        skew: "cost_model.SkewModel | None" = None,
    ) -> "ScheduleState":
        return cls(etg.utg, cluster, etg, skew=skew)

    # ------------------------------------------------------------- loads

    @property
    def met_load(self) -> np.ndarray:
        """(m,) fixed (rate-independent) MET load per machine."""
        if self._met_load is None:
            self._met_load = (self.met_cm * self.comp_counts).sum(axis=0)
        return self._met_load

    def _skew_variable_load(self, cir: np.ndarray) -> np.ndarray:
        """(m,) variable load for a per-component input-rate vector:
        keyed components accumulated per instance at their realized key
        shares, shuffle components as the even path's per-(component,
        machine) term in component order, so a model without keyed
        components reproduces the even-split floats bit for bit. The single
        skew accumulation both ``var_load`` and ``utilization`` use."""
        var = np.zeros(self.cluster.n_machines, dtype=np.float64)
        for c in range(self.utg.n_components):
            nk = int(self.n_instances[c])
            frac = self.skew.instance_fractions(c, nk)
            if frac is None:
                var += self.e_cm[c] * self.comp_counts[c] * (cir[c] / nk)
                continue
            w = np.asarray(self.assignment[c], dtype=np.int64)
            np.add.at(var, w, self.e_cm[c, w] * (cir[c] * frac))
        return var

    @property
    def var_load(self) -> np.ndarray:
        """(m,) d utilization / d rate per machine at the current structure."""
        if self._var_load is None:
            if self.skew is None:
                per_unit = self.cir_unit / self.n_instances
                self._var_load = (
                    self.e_cm * self.comp_counts * per_unit[:, None]
                ).sum(axis=0)
            else:
                # Keyed components: instances are no longer interchangeable
                # (each handles its own key share), so accumulate per
                # instance instead of per (component, machine) count.
                self._var_load = self._skew_variable_load(self.cir_unit)
        return self._var_load

    @property
    def mem_load(self) -> np.ndarray:
        """(m,) resident memory per machine (rate-independent hard resource).

        Accumulated per task via ``np.add.at`` so the floats match the batch
        scorer's memory-mask accumulation exactly. Zeros on clusters without
        a memory model.
        """
        if self._mem_load is None:
            load = np.zeros(self.cluster.n_machines, dtype=np.float64)
            if self.mem_c is not None:
                comp = np.repeat(
                    np.arange(self.utg.n_components), self.n_instances
                )
                np.add.at(load, self.task_machine(), self.mem_c[comp])
            self._mem_load = load
        return self._mem_load

    @property
    def net_load(self) -> np.ndarray:
        """(m,) d network-load / d rate per machine — the cut-traffic term.

        ``cost_model.network_unit_load`` on the current placement (the same
        operands the batch scorer uses, so incremental and batched scores
        agree). Recomputed lazily after structural mutations, like the
        other load caches. Zeros on distance-free clusters.
        """
        if self._net_load is None:
            if not self.cluster.has_network:
                self._net_load = np.zeros(
                    self.cluster.n_machines, dtype=np.float64
                )
            else:
                comp = np.repeat(
                    np.arange(self.utg.n_components), self.n_instances
                )
                if self.skew is None:
                    unit_ir = (self.cir_unit / self.n_instances)[comp]
                else:
                    unit_ir = self.skew.per_task_unit_ir(self.n_instances)
                self._net_load = cost_model.network_unit_load(
                    self.task_machine()[None, :],
                    comp,
                    unit_ir,
                    self.utg.alpha,
                    self.cir_unit,
                    self.utg.edges,
                    self.cluster.distance,
                    self.cluster.net_penalty,
                )[0]
        return self._net_load

    def utilization(self, rate: float) -> np.ndarray:
        """(m,) predicted machine utilization at topology input rate ``rate``.

        Uses the same eq. 6 propagation as the reference (``component_rates``
        at the actual rate, not ``cir_unit * rate``) so per-chunk TCUs match
        the reference floats exactly; the per-machine summation is collapsed
        from per-task to per-component, which can differ from the
        reference's ``np.add.at`` accumulation in the last ulp. With a skew
        model, keyed components accumulate per instance at their realized
        key shares (the skew-aware utilization bound).
        """
        cir = cost_model.component_rates(self.utg, rate)
        if self.skew is not None:
            util = self.met_load + self._skew_variable_load(cir)
        else:
            per_inst = cir / self.n_instances
            util = self.met_load + (
                self.e_cm * self.comp_counts * per_inst[:, None]
            ).sum(axis=0)
        if self.cluster.has_network:
            util = util + rate * self.net_load
        return util

    def feasible(self, rate: float) -> bool:
        """Reference feasibility: every machine's MAC >= 0 at ``rate``."""
        return bool(np.all(self.cluster.capacity - self.utilization(rate) >= 0.0))

    def max_stable_rate(self) -> float:
        """Closed-form R* = min_w (cap_w - met_w) / (var_w + net_w).

        Paper eq. 5 linearity; the cut-traffic term is linear in R too, so
        folding ``net_load`` into the variable coefficient keeps the closed
        form exact. Memory is rate-independent, so an over-memory machine
        makes the placement infeasible at any rate (R* = 0).
        """
        head = self.cluster.capacity - self.met_load
        if np.any(head < 0.0):
            return 0.0
        if self.cluster.has_memory and np.any(
            self.mem_load > self.cluster.mem_capacity
        ):
            return 0.0
        var = self.var_load
        if self.cluster.has_network:
            var = var + self.net_load
        with np.errstate(divide="ignore"):
            limits = np.where(var > 0.0, head / np.maximum(var, 1e-300), np.inf)
        return float(max(np.min(limits), 0.0))

    def max_stable_rate_exact(self) -> "Fraction | None":
        """Exact rational R* of the linear load model (``None`` = unbounded).

        Treats the cached float coefficients as exact rationals, so
        ``rate`` is stable iff ``Fraction(rate) <= max_stable_rate_exact()``
        — the feasibility boundary is a hard number, with no float-rounding
        band around it. A negative result means the rate-independent load
        alone (MET, or the hard memory constraint) already exceeds some
        machine's capacity. The cut-traffic coefficient enters the rational
        arithmetic exactly (``Fraction(var) + Fraction(net)``).
        """
        if self.cluster.has_memory and np.any(
            self.mem_load > self.cluster.mem_capacity
        ):
            return Fraction(-1)
        best: Fraction | None = None
        for cap_w, met_w, var_w, net_w in zip(
            self.cluster.capacity.tolist(),
            self.met_load.tolist(),
            self.var_load.tolist(),
            self._net_list(),
        ):
            head = Fraction(cap_w) - Fraction(met_w)
            var = Fraction(var_w) + Fraction(net_w)
            if var > 0:
                lim = head / var
            elif head < 0:
                return Fraction(-1)
            else:
                continue
            if best is None or lim < best:
                best = lim
        return best

    def _net_list(self) -> list[float]:
        """Per-machine cut-traffic coefficients for the exact paths (all
        zeros on distance-free clusters, without touching the cache)."""
        if not self.cluster.has_network:
            return [0.0] * self.cluster.n_machines
        return self.net_load.tolist()

    def feasible_linear_exact(self, rate: float) -> bool:
        """Exact feasibility of the linear model at ``rate``.

        Evaluates ``met_load_w + rate * var_load_w <= cap_w`` per machine in
        rational arithmetic over the cached float coefficients — the
        arbiter for rates inside the float pre-filter band around R*.
        """
        return self.first_over_machine_exact(rate) is None

    def first_over_machine_exact(self, rate: float) -> "int | None":
        """First machine (reference index order) over capacity at ``rate``
        under the exact linear model, or ``None`` if every machine fits.
        A machine over its memory capacity is over at any rate."""
        r = Fraction(rate)
        mem_over = (
            self.mem_load > self.cluster.mem_capacity
            if self.cluster.has_memory
            else None
        )
        for w, (cap_w, met_w, var_w, net_w) in enumerate(
            zip(
                self.cluster.capacity.tolist(),
                self.met_load.tolist(),
                self.var_load.tolist(),
                self._net_list(),
            )
        ):
            if mem_over is not None and mem_over[w]:
                return w
            util = Fraction(met_w) + r * (Fraction(var_w) + Fraction(net_w))
            if util > Fraction(cap_w):
                return w
        return None

    # --------------------------------------------------------- mutation

    def add_instance(self, component: int, machine: int) -> None:
        """O(m) delta update: append one instance of ``component`` on ``machine``."""
        self.comp_counts[component, machine] += 1
        self.n_instances[component] += 1
        self.assignment[component].append(int(machine))
        self._met_load = None
        self._var_load = None
        self._mem_load = None
        self._net_load = None

    def relocate_instance(self, component: int, k: int, machine: int) -> None:
        """O(1) delta: move instance (component, k) to ``machine``.

        Instance counts are unchanged, so the per-instance split (eq. 6) is
        untouched — only two entries of the count matrix move.
        """
        src = self.assignment[component][k]
        self.comp_counts[component, src] -= 1
        self.comp_counts[component, machine] += 1
        self.assignment[component][k] = int(machine)
        self._met_load = None
        self._var_load = None
        self._mem_load = None
        self._net_load = None

    def swap_instances(self, ca: int, ka: int, cb: int, kb: int) -> None:
        """O(1) delta: exchange the machines of instances (ca, ka) and (cb, kb)."""
        wa = self.assignment[ca][ka]
        wb = self.assignment[cb][kb]
        self.relocate_instance(ca, ka, wb)
        self.relocate_instance(cb, kb, wa)

    def drop_instance(self, component: int, k: int) -> None:
        """O(m) delta: remove instance (component, k); the component's stream
        re-splits over the remaining instances (eq. 6)."""
        if int(self.n_instances[component]) < 2:
            raise ValueError("every component needs >= 1 instance (paper constraint)")
        w = self.assignment[component].pop(k)
        self.comp_counts[component, w] -= 1
        self.n_instances[component] -= 1
        self._met_load = None
        self._var_load = None
        self._mem_load = None
        self._net_load = None

    def evacuate_machines(self, dead: np.ndarray, rate: float) -> int:
        """Relocate every instance hosted on a ``dead``-masked machine.

        A hill climb scoring closed-form throughput cannot escape the
        0-throughput plateau when *several* instances sit on a dead (or
        draining) machine — no single move restores feasibility — so such
        machines are drained greedily first: each stranded instance moves
        to the feasible non-dead machine with the least chunk TCU (ties
        toward most remaining head, ``_greedy_place``'s rule), and
        ``refine`` polishes from there. Returns the number of relocations.
        The same primitive serves machine *failure* (capacity already 0)
        and planned *drain* (capacity-notice scale-in: pass the mask of
        machines dead in the lookahead capacity).
        """
        from repro.core.maximize_throughput import _least_tcu_machine

        dead = np.asarray(dead, dtype=bool)
        if not dead.any():
            return 0
        cir = cost_model.component_rates(self.utg, rate)
        per_inst = cir / self.n_instances
        util = self.utilization(rate)
        mem = self.mem_load.copy() if self.cluster.has_memory else None
        moves = 0
        for c in range(self.utg.n_components):
            tcu_w = self.e_cm[c] * per_inst[c] + self.met_cm[c]
            for k, w in enumerate(self.assignment[c]):
                if not dead[w]:
                    continue
                # Dead machines get -inf head so the shared rule never
                # picks them; when nothing fits, least-overloaded alive.
                head = np.where(dead, -np.inf, self.cluster.capacity - util - tcu_w)
                if mem is not None:
                    # Machines the instance's memory would not fit on are
                    # masked out of the fit rule; the nothing-fits fallback
                    # stays least-overloaded-alive (memory-blind — refine
                    # cannot polish from a stranded instance).
                    fit_head = np.where(
                        mem + self.mem_c[c] > self.cluster.mem_capacity,
                        -np.inf,
                        head,
                    )
                else:
                    fit_head = head
                target = _least_tcu_machine(tcu_w, fit_head)
                if target is None:
                    target = int(np.argmax(head))
                self.relocate_instance(c, k, target)
                util[w] -= tcu_w[w]
                util[target] += tcu_w[target]
                if mem is not None:
                    mem[w] -= self.mem_c[c]
                    mem[target] += self.mem_c[c]
                moves += 1
        return moves

    # ------------------------------------------------------ batch export

    def task_machine(self) -> np.ndarray:
        """(T,) flattened machine per task (paper eq. 3 order), for use as the
        base row when building candidate batches for ``max_stable_rate_batch``."""
        flat: list[int] = []
        for machines in self.assignment:
            flat.extend(machines)
        return np.asarray(flat, dtype=np.int64)

    def component_offsets(self) -> np.ndarray:
        """(n+1,) start offset of each component's block in the flattened
        task order; ``offsets[c] + k`` is the column of instance (c, k)."""
        return np.concatenate(
            [np.zeros(1, dtype=np.int64), np.cumsum(self.n_instances)]
        )

    def template_etg(self, n_instances: np.ndarray | None = None) -> ExecutionGraph:
        """Shape-only ETG for batched scoring (assignment is a placeholder).

        ``max_stable_rate_batch`` reads only the UTG and instance counts from
        its template — candidate placements come in as (B, T) rows — so the
        export is O(n), no deep copy of the real assignment.
        """
        if n_instances is None:
            n_instances = self.n_instances
        n_instances = np.asarray(n_instances, dtype=np.int64)
        return ExecutionGraph(
            utg=self.utg,
            n_instances=n_instances.copy(),
            assignment=[np.zeros(int(k), dtype=np.int64) for k in n_instances],
        )

    def score_task_machine_batch(
        self,
        task_machine: np.ndarray,
        n_instances: np.ndarray | None = None,
        backend: str = "numpy",
    ) -> tuple[np.ndarray, np.ndarray]:
        """Closed-form (rate, throughput) of B exported candidate placements.

        Bit-identical to ``cost_model.max_stable_rate_batch`` on a template
        with the same instance counts — both call the one shared
        ``closed_form_rates`` core with identical per-task gathers — but
        skips per-call ``ExecutionGraph`` construction and the Python eq. 6
        walk by reusing the cached ``e_cm``/``met_cm``/``cir_unit`` slices.
        This is the scoring entry point behind the refine/optimal batch
        engines.

        Args:
          task_machine: (B, T') candidate rows, T' = sum of each row's
            instance counts (every row must share one task total).
          n_instances: per-component counts for the candidates — a shared
            (n,) vector (defaults to the current state's counts; pass a
            modified vector for ADD/DROP/GROW-style candidates), or a
            (B, n) matrix giving every row its *own* counts (lockstep
            growth chains batching different components' next steps into
            one sweep). Per-row scores are bit-identical to scoring each
            row against its own shared-count template.
          backend: ``"numpy"`` (reference floats), ``"jax"`` (jitted
            float64 scatter-free closed form, ~1e-15 relative agreement),
            or ``"auto"`` (JAX above the regime's calibrated element-count crossover,
            machine-count gated on CPU — skew rows dispatch under the
            ``"skew"`` regime; the jitted kernel is skew-agnostic).

        ``task_machine`` may also be growth steps or drops given as count
        edits of base rows (``score_grow_steps``, ``score_drops``; ``n_instances``
        then unused), which resolve as the rows they stand for and, on a
        device sweep without a skew model, score without being built.

        Each call is one ``refine.sweep`` span on the active recorder and
        adds its B rows to ``rows_scored`` and the ``refine.rows`` counter.
        """
        with trace.span("refine.sweep", "refine"):
            out = self._score_batch(task_machine, n_instances, backend)
        rows = int(out[1].shape[0])
        self.rows_scored += rows
        trace.count("refine.rows", rows)
        return out

    def score_relocate_swap(
        self, base: np.ndarray, backend: str, row_chunk: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """Closed-form throughput of every RELOCATE and SWAP candidate of
        the (T,) ``base`` row under the state's instance counts.

        Returns ``(edits, throughput)``: candidate b is ``base`` with
        ``row[pos_a] = val_a`` then ``row[pos_b] = val_b`` for ``(pos_a,
        val_a, pos_b, val_b) = edits[:, b]`` — every task to every other
        machine (a relocate writes one column twice), then every two tasks
        of different components on different machines trading machines,
        each family in task order (refine's menu and its tie order).

        The menu is scored in sweeps of ``_EDIT_SWEEP_CELLS // (m + T)``
        moving tasks (one sweep on the paper's clusters), each resolved like
        a ``score_task_machine_batch`` call of the rows it stands for (same
        elements, regime and machine count). A device sweep ships only the
        base row to ``sim_jax``'s ``msr_edits`` kernel, fetches the sweep's
        relocate and swap grids (8·A·(m + T) bytes for A moving tasks) and
        bumps the ``sweep.edit_rows`` counter by its candidates; a NumPy
        sweep builds the rows ``row_chunk`` at a time and scores them with
        the reference core, so its scores are bit-identical to
        ``score_task_machine_batch``'s. On a cluster with network or memory
        resources a move changes the cut traffic of machines it does not
        touch, so a device sweep scores every candidate on every machine
        (``msr_edits_resources``, with the cut traffic and the memory mask
        computed on the device), in sweeps of ``_NET_EDIT_SWEEP_CELLS // ((m
        + T) * m)`` moving tasks, and also bumps the ``sweep.net_rows``
        counter. Refine needs only the menu's winner and asks
        ``best_relocate_swap``, which fetches no grid.

        Each sweep is one ``refine.sweep`` span and adds its candidates to
        ``rows_scored`` and the ``refine.rows`` counter; each device sweep
        also bumps ``sweep.rs_sweeps``. A menu with a candidate bumps
        ``refine.rs_menus`` once.
        """
        m = self.cluster.n_machines
        comp = np.repeat(np.arange(self.utg.n_components), self.n_instances)
        moves = np.arange(m)[None, :] != base[:, None]              # (T, m)
        pairs = np.triu(
            (comp[:, None] != comp[None, :]) & (base[:, None] != base[None, :]),
            k=1,
        )                                                            # (T, T)
        reloc_pos, reloc_w = np.nonzero(moves)
        swap_a, swap_b = np.nonzero(pairs)
        edits = np.stack([
            np.concatenate([reloc_pos, swap_a]),
            np.concatenate([reloc_w, base[swap_b]]),
            np.concatenate([reloc_pos, swap_b]),
            np.concatenate([reloc_w, base[swap_a]]),
        ])
        thpt = np.empty(edits.shape[1], dtype=np.float64)
        sweeps = self._relocate_swap_sweeps(base)
        if sweeps:
            trace.count("refine.rs_menus", 1)
        # Candidates of tasks [a0, a1) are one slice of each family.
        reloc_at, swap_at = 0, reloc_pos.size
        for a0, a1, n_reloc, n_swap in sweeps:
            parts = [
                slice(reloc_at, reloc_at + n_reloc),
                slice(swap_at, swap_at + n_swap),
            ]
            reloc_at, swap_at = parts[0].stop, parts[1].stop
            with trace.span("refine.sweep", "refine"):
                self._score_moves(
                    base, edits, parts, (a0, a1), moves, pairs, thpt,
                    backend, row_chunk,
                )
            rows = n_reloc + n_swap
            self.rows_scored += rows
            trace.count("refine.rows", rows)
        return edits, thpt

    def best_relocate_swap(
        self, base: np.ndarray, backend: str, row_chunk: int
    ) -> tuple[tuple[int, int, int, int], float] | None:
        """The winner of ``score_relocate_swap``'s menu of the (T,) ``base``
        row: ``(edit, throughput)``, with ``edit = (pos_a, val_a, pos_b,
        val_b)`` as there, the first strict maximum in the menu's order
        (``np.argmax`` over its scores); ``None`` for an empty menu.

        Where every sweep of the menu resolves to JAX, each runs
        ``score_relocate_swap``'s device program and fetches only its
        relocate and swap winners (``sim_jax.relocate_swap_winners_jax``,
        four float64s), and the host keeps the first maximum of all sweeps'
        relocate winners, then all their swap winners. No candidate is
        built on the host, and the candidates are counted, not listed.
        Otherwise it takes ``np.argmax`` over ``score_relocate_swap``'s
        scores. Either way the sweeps, their resolutions (site
        ``score_relocate_swap``), spans and counters are
        ``score_relocate_swap``'s: ``sweep.rs_sweeps`` counts each device
        sweep, ``refine.rs_menus`` the menu.
        """
        n_tasks, m = int(base.shape[0]), self.cluster.n_machines
        sweeps = self._relocate_swap_sweeps(base)
        with trace.unrecorded():
            on_device = all(
                self._resolve_rows(
                    backend, n_reloc + n_swap, n_tasks, "shared", "score_relocate_swap"
                ) == "jax"
                for _, _, n_reloc, n_swap in sweeps
            )
        if not on_device:
            edits, thpt = self.score_relocate_swap(base, backend, row_chunk)
            i = int(np.argmax(thpt))
            return tuple(int(x) for x in edits[:, i]), float(thpt[i])
        if sweeps:
            trace.count("refine.rs_menus", 1)
        relocates, swaps = [], []
        for a0, a1, n_reloc, n_swap in sweeps:
            rows = n_reloc + n_swap
            with trace.span("refine.sweep", "refine"):
                reloc_i, reloc_s, swap_i, swap_s = self._move_winners(
                    base, (a0, a1), rows, backend
                )
            if n_reloc:
                a, w = divmod(int(reloc_i), m)
                relocates.append(((a0 + a, w, a0 + a, w), reloc_s))
            if n_swap:
                a, b = divmod(int(swap_i), n_tasks)
                a += a0
                swaps.append(((a, int(base[b]), b, int(base[a])), swap_s))
            self.rows_scored += rows
            trace.count("refine.rows", rows)
        menu = relocates + swaps
        if not menu:
            return None
        i = int(np.argmax([score for _, score in menu]))
        return menu[i][0], float(menu[i][1])

    def _relocate_swap_sweeps(
        self, base: np.ndarray
    ) -> list[tuple[int, int, int, int]]:
        """``(a0, a1, relocations, swaps)`` of each sweep of the RELOCATE
        and SWAP menu of the (T,) ``base`` row that has a candidate: its
        moving tasks [a0, a1) and their candidates of each family, counted
        without being built. Each task has m - 1 relocations; task a's swaps
        are the later tasks, less those of its component and those on its
        machine, plus those of both (counted twice)."""
        n_tasks, m = int(base.shape[0]), self.cluster.n_machines
        comp = np.repeat(np.arange(self.utg.n_components), self.n_instances)

        def later(key):
            """Per task a: the tasks b > a with ``key[b] == key[a]``."""
            order = np.argsort(key, kind="stable")
            ends = np.searchsorted(key[order], key[order], side="right")
            out = np.empty(n_tasks, dtype=np.int64)
            out[order] = ends - np.arange(n_tasks) - 1
            return out

        swaps = (
            (n_tasks - 1 - np.arange(n_tasks))
            - later(comp) - later(base) + later(comp * m + base)
        )
        if self.cluster.has_resources:
            block = _NET_EDIT_SWEEP_CELLS // ((m + n_tasks) * m)
        else:
            block = _EDIT_SWEEP_CELLS // (m + n_tasks)
        block = max(1, block)
        sweeps = []
        for a0 in range(0, n_tasks, block):
            a1 = min(a0 + block, n_tasks)
            n_reloc, n_swap = (a1 - a0) * (m - 1), int(swaps[a0:a1].sum())
            if n_reloc + n_swap:
                sweeps.append((a0, a1, n_reloc, n_swap))
        return sweeps

    def score_grow_steps(
        self,
        rows: np.ndarray,
        counts: np.ndarray,
        comps: np.ndarray,
        backend: str,
    ) -> np.ndarray:
        """Closed-form throughput of the next greedy step of k growth
        chains, on every machine.

        Row i of ``rows`` (k, T) holds ``counts[i]`` (n,) instances per
        component; candidate [i, v] appends one more instance of
        ``comps[i]`` at the end of its block, on machine v, and every
        instance of that component takes the new even split. Returns the
        (k, m) grid. A single chain — a (T,) row, (n,) counts and one
        component — returns (m,) and resolves as m rows with shared counts.

        One ``score_task_machine_batch`` call of the k·m rows, given as
        count edits of the base rows: a device sweep without a skew model
        ships only the base rows and the tables (``msr_count_edits``).
        """
        single = np.ndim(rows) == 1
        m = self.cluster.n_machines
        edits = _CountEdits(
            np.atleast_2d(rows), np.atleast_2d(counts), np.atleast_1d(comps), m,
            shared=single,
        )
        thpt = self.score_task_machine_batch(edits, backend=backend)[1].reshape(-1, m)
        return thpt[0] if single else thpt

    def score_drops(
        self, base: np.ndarray, counts: np.ndarray, backend: str
    ) -> np.ndarray:
        """Closed-form throughput of the (T,) ``base`` row under ``counts``
        without task p, for every task p of a component with at least two
        instances (the others, which are no candidate, hold NaN); the
        component's remaining instances take the new even split.

        One ``score_task_machine_batch`` call of the candidate rows with
        per-row counts, given as count edits of the base row (once per
        droppable component), as ``score_grow_steps`` does; none when no
        component can drop an instance.
        """
        counts = np.asarray(counts, dtype=np.int64)
        thpt = np.full(np.shape(base)[0], np.nan)
        comps = np.flatnonzero(counts >= 2)
        if comps.size:
            k = comps.size
            edits = _CountEdits(
                np.tile(base, (k, 1)), np.tile(counts, (k, 1)), comps,
                self.cluster.n_machines, drop=True,
            )
            offsets = np.concatenate([[0], np.cumsum(counts)])
            tasks = np.concatenate([np.arange(offsets[c], offsets[c + 1]) for c in comps])
            thpt[tasks] = self.score_task_machine_batch(edits, backend=backend)[1]
        return thpt

    def _score_count_edits(
        self, edits: "_CountEdits", backend: str
    ) -> tuple[np.ndarray, np.ndarray]:
        """Closed form of the rows ``edits`` stands for, resolved as those
        rows. A device sweep without a skew model scores them as count edits
        on ``sim_jax``'s ``msr_count_edits`` (``msr_count_edits_resources``
        on a cluster with network or memory resources, which also bumps
        ``sweep.net_rows``) and bumps ``sweep.edit_rows`` by its candidates.
        Otherwise — a NumPy sweep, or a skew model, whose per-instance key
        shares do not rescale evenly — the rows are built and scored as
        such, NumPy scores bit-identical to materialised rows'."""
        n_rows, n_tasks = edits.shape
        regime = "shared" if edits.shared else "per_row"
        resolved = self._resolve_rows(backend, n_rows, n_tasks, regime)
        if resolved != "jax" or self.skew is not None:
            with trace.span("refine.build", "refine"):
                tm, n_inst = edits.materialise()
            comp, unit_ir, _ = self._task_maps(n_inst, *tm.shape)
            return self._score_rows(tm, comp, unit_ir, resolved)
        from repro.core.sim_jax import count_edit_scores_jax

        resources = self._device_resources()
        trace.count("sweep.edit_rows", n_rows)
        if resources is not None:
            trace.count("sweep.net_rows", n_rows)
        rates, thpt = count_edit_scores_jax(
            edits.rows, edits.counts, self.cir_unit[None, :] / edits.new_counts,
            edits.comps, self.e_cm, self.met_cm, self.cluster.capacity,
            resources, drop=edits.drop,
        )
        if not edits.drop:
            return rates.reshape(-1), thpt.reshape(-1)
        # Row i's candidates: the tasks of its component, in task order.
        k = edits.rows.shape[0]
        ends = np.cumsum(edits.counts, axis=1)[np.arange(k), edits.comps]
        sizes = edits.counts[np.arange(k), edits.comps]
        row = np.repeat(np.arange(k), sizes)
        task = np.concatenate([np.arange(e - s, e) for e, s in zip(ends, sizes)])
        return rates[row, task], thpt[row, task]

    def _resolve_rows(
        self,
        backend: str,
        rows: int,
        n_tasks: int,
        regime: str,
        site: str = "score_task_machine_batch",
    ) -> str:
        """The backend of a sweep of ``rows`` candidate rows of ``n_tasks``
        tasks, under the skew regime where the state has a skew model."""
        from repro.core.simulator import resolve_closed_form_backend

        return resolve_closed_form_backend(
            backend,
            rows * n_tasks,
            regime="skew" if self.skew is not None else regime,
            n_machines=self.cluster.n_machines,
            site=site,
        )

    def _score_moves(
        self,
        base: np.ndarray,
        edits: np.ndarray,
        parts: list[slice],
        tasks: tuple[int, int],
        moves: np.ndarray,
        pairs: np.ndarray,
        thpt: np.ndarray,
        backend: str,
        row_chunk: int,
    ) -> None:
        """One sweep of ``score_relocate_swap``: the candidates of moving
        tasks ``tasks``, which fill ``thpt[parts]``."""
        rows = sum(p.stop - p.start for p in parts)
        resolved = self._resolve_rows(
            backend, rows, base.size, "shared", "score_relocate_swap"
        )
        if resolved == "jax":
            from repro.core.sim_jax import relocate_swap_scores_jax

            relocate, swap = self._edit_sweep(
                relocate_swap_scores_jax, base, tasks, rows
            )
            a = slice(*tasks)
            thpt[parts[0]] = relocate[moves[a]]
            thpt[parts[1]] = swap[pairs[a]]
            return
        comp, unit_ir, _ = self._task_maps(self.n_instances, rows, base.size)
        for part in parts:
            for start in range(part.start, part.stop, row_chunk):
                chunk = slice(start, min(start + row_chunk, part.stop))
                with trace.span("refine.build", "refine"):
                    tm = _edited_rows(base, edits[:, chunk])
                thpt[chunk] = self._score_rows(tm, comp, unit_ir, "numpy")[1]

    def _move_winners(
        self, base: np.ndarray, tasks: tuple[int, int], rows: int, backend: str
    ) -> np.ndarray:
        """One device sweep of ``best_relocate_swap``: the winners of moving
        tasks ``tasks`` (``rows`` candidates), from ``sim_jax``'s
        ``relocate_swap_winners_jax``. It records the sweep's resolution,
        which ``best_relocate_swap`` found to be JAX, as ``_score_moves``
        does."""
        from repro.core.sim_jax import relocate_swap_winners_jax

        self._resolve_rows(backend, rows, base.size, "shared", "score_relocate_swap")
        return self._edit_sweep(relocate_swap_winners_jax, base, tasks, rows)

    def _edit_sweep(self, score, base: np.ndarray, tasks: tuple[int, int], rows: int):
        """``score`` (``sim_jax.relocate_swap_scores_jax`` or
        ``relocate_swap_winners_jax``) of moving tasks ``tasks`` of the base
        row, with the state's maps and tables; bumps ``sweep.rs_sweeps`` by
        one and ``sweep.edit_rows`` (and, on a cluster with resources,
        ``sweep.net_rows``) by the sweep's ``rows`` candidates."""
        comp, unit_ir, _ = self._task_maps(self.n_instances, rows, base.size)
        resources = self._device_resources()
        trace.count("sweep.rs_sweeps", 1)
        trace.count("sweep.edit_rows", rows)
        if resources is not None:
            trace.count("sweep.net_rows", rows)
        return score(
            base, np.arange(*tasks), comp, unit_ir,
            self.e_cm, self.met_cm, self.cluster.capacity, resources,
        )

    def _score_batch(
        self,
        task_machine: "np.ndarray | _CountEdits",
        n_instances: np.ndarray | None,
        backend: str,
    ) -> tuple[np.ndarray, np.ndarray]:
        if isinstance(task_machine, _CountEdits):
            return self._score_count_edits(task_machine, backend)
        n_inst = self.n_instances if n_instances is None else np.asarray(
            n_instances, dtype=np.int64
        )
        task_machine = np.asarray(task_machine, dtype=np.int64)
        if task_machine.ndim != 2:
            raise ValueError("task_machine must be (B, sum(n_instances))")
        comp, unit_ir, regime = self._task_maps(n_inst, *task_machine.shape)
        resolved = self._resolve_rows(backend, *task_machine.shape, regime)
        return self._score_rows(task_machine, comp, unit_ir, resolved)

    def _task_maps(
        self, n_inst: np.ndarray, rows: int, n_tasks: int
    ) -> tuple[np.ndarray, np.ndarray, str]:
        """Per-task (component, unit IR) maps of B candidate rows and the
        dispatch regime they score under: (T,) maps for shared counts,
        (B, T) for per-row counts. Keyed components' unit IR comes from the
        realized per-instance fractions under a skew model; either backend
        feeds the maps to the same closed-form core."""
        n = self.utg.n_components
        if n_inst.ndim == 2:
            if n_inst.shape != (rows, n):
                raise ValueError("per-row n_instances must be (B, n)")
            comp, unit_ir = cost_model.per_row_task_maps(
                self.cir_unit, n_inst, n_tasks
            )                                             # each (B, T)
            if self.skew is not None:
                unit_ir = self.skew.per_row_unit_ir(n_inst)
        else:
            comp = np.repeat(np.arange(n), n_inst)
            if n_tasks != comp.shape[0]:
                raise ValueError("task_machine must be (B, sum(n_instances))")
            if self.skew is not None:
                unit_ir = self.skew.per_task_unit_ir(n_inst)
            else:
                # Per-component division then gather: per-element operands
                # match instance_rates()' per-task division exactly, so
                # floats agree.
                unit_ir = (self.cir_unit / n_inst)[comp]
        if self.skew is not None:
            regime = "skew"
        else:
            regime = "per_row" if n_inst.ndim == 2 else "shared"
        return comp, unit_ir, regime

    def _score_rows(
        self,
        task_machine: np.ndarray,
        comp: np.ndarray,
        unit_ir: np.ndarray,
        backend: str,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Closed form of materialised (B, T) rows on a resolved backend.
        A device sweep on a cluster with resources computes the cut traffic
        and the memory mask on the device and bumps ``sweep.net_rows``; a
        NumPy sweep prices them on the host (``net.host``)."""
        if backend == "jax":
            from repro.core.sim_jax import closed_form_rates_jax

            resources = self._device_resources()
            if resources is not None:
                trace.count("sweep.net_rows", task_machine.shape[0])
            return closed_form_rates_jax(
                task_machine,
                comp,
                unit_ir,
                self.e_cm,
                self.met_cm,
                self.cluster.capacity,
                resources,
            )
        net_var, mem, mem_cap = self._resource_operands(
            task_machine, comp, unit_ir
        )
        gather_comp = comp if comp.ndim == 2 else comp[None, :]
        e = self.e_cm[gather_comp, task_machine]          # (B, T)
        met = self.met_cm[gather_comp, task_machine]
        return cost_model.closed_form_rates(
            task_machine, e, met, unit_ir, self.cluster.capacity,
            net_var=net_var, mem=mem, mem_capacity=mem_cap,
        )

    def _device_resources(self) -> list | None:
        """``sim_jax.device_resources`` of the state's topology and cluster."""
        from repro.core.sim_jax import device_resources

        return device_resources(
            self.cluster, self.utg.component_types, self.utg.edges,
            self.utg.alpha, self.cir_unit,
        )

    def _resource_operands(
        self,
        task_machine: np.ndarray,
        comp: np.ndarray,
        unit_ir: np.ndarray,
    ) -> tuple[np.ndarray | None, np.ndarray | None, np.ndarray | None]:
        """Resource-vector extras for a candidate batch — all ``None`` on
        scalar-CPU clusters so default scoring stays byte-identical."""
        if not self.cluster.has_resources:
            return None, None, None
        return cost_model.resource_operands(
            self.cluster,
            task_machine,
            comp,
            unit_ir,
            self.utg.alpha,
            self.cir_unit,
            self.utg.edges,
            self.utg.component_types,
        )

    def snapshot(self) -> tuple:
        return (
            self.n_instances.copy(),
            self.comp_counts.copy(),
            [list(a) for a in self.assignment],
        )

    def restore(self, snap: tuple) -> None:
        self.n_instances = snap[0].copy()
        self.comp_counts = snap[1].copy()
        self.assignment = [list(a) for a in snap[2]]
        self._met_load = None
        self._var_load = None
        self._mem_load = None
        self._net_load = None

    def to_etg(self) -> ExecutionGraph:
        return ExecutionGraph(
            utg=self.utg,
            n_instances=self.n_instances.copy(),
            assignment=[np.asarray(a, dtype=np.int64) for a in self.assignment],
        )


def _grow_component_fast(
    state: ScheduleState,
    component: int,
    rate: float,
    max_extra: int | None = None,
) -> int:
    """Incremental equivalent of the reference ``_grow_component``.

    Scans candidate target counts with the closed-form per-machine capacity
    bound (one vectorized (n_targets, m) pass), then runs the exact greedy
    (``_greedy_place``, the same code path as the reference engine) for
    admitted targets only. Mutates ``state`` in place on success.

    Returns the number of instances added (0 if no target packs).
    """
    from repro.core.maximize_throughput import _greedy_place

    cluster = state.cluster
    cap = cluster.capacity
    m = cluster.n_machines
    n0 = int(state.n_instances[component])
    cir_vec = cost_model.component_rates(state.utg, rate)
    cir = cir_vec[component]
    e_row = state.e_cm[component]
    met_row = state.met_cm[component]
    existing_counts = state.comp_counts[component]

    # Machine load from everything except this component (its variable part
    # re-splits with the new count; reference subtracts the same quantity).
    per_inst = cir_vec / state.n_instances
    util = state.met_load + (
        state.e_cm * state.comp_counts * per_inst[:, None]
    ).sum(axis=0)
    if cluster.has_network:
        # Current cut-traffic load enters the head as a fixed charge (the
        # grown component's own re-split is approximated as unchanged —
        # the main loop re-scores the true generalized R* after growth).
        util = util + rate * state.net_load
    own_tcu = e_row * (cir / n0) + met_row
    base_load = util - existing_counts * own_tcu

    # Hard memory constraint: at most floor(room / mem_c) new instances per
    # machine (no float slack — memory infeasibility cannot be admitted;
    # under-counting an exact fit by one is merely conservative).
    mem_new = None
    if cluster.has_memory and float(state.mem_c[component]) > 0.0:
        mem_room = np.maximum(cluster.mem_capacity - state.mem_load, 0.0)
        mem_new = np.floor(mem_room / float(state.mem_c[component]))

    max_target = n0 + (max_extra if max_extra is not None else max(2 * n0, 2 * m, 16))
    targets = np.arange(n0 + 1, max_target + 1)
    if targets.size == 0:
        return 0

    # Closed-form packing bound: with a fixed per-machine chunk TCU, greedy
    # placement order cannot change how many chunks fit, so target t packs
    # iff sum_w max(0, floor(avail_w / tcu_w(t)) - counts_w) >= t - n0.
    # The +1e-9 slack absorbs the reference's repeated-addition rounding;
    # admitted targets are confirmed by the exact greedy below.
    tcu_t = e_row[None, :] * (cir / targets)[:, None] + met_row[None, :]
    avail = cap - base_load
    with np.errstate(divide="ignore", invalid="ignore"):
        fit = np.floor(avail[None, :] / tcu_t + 1e-9)
    fit = np.where(np.isfinite(fit), fit, 0.0)
    # A zero-cost chunk (e == met == 0 for this type pair) fits without
    # bound on any machine that is not already over capacity.
    unlimited = (tcu_t <= 0.0) & (avail[None, :] >= 0.0)
    fit = np.where(unlimited, float(max_target), fit)
    n_new_w = np.clip(fit - existing_counts[None, :], 0.0, None)
    if mem_new is not None:
        n_new_w = np.minimum(n_new_w, mem_new[None, :])
    n_new = n_new_w.sum(axis=1)
    admitted = targets[n_new >= (targets - n0)]

    for target in admitted:
        target = int(target)
        per_ir = cir / target
        tcu = e_row * per_ir + met_row
        placed = _greedy_place(
            cap, base_load, existing_counts, tcu, target - n0, max_new=mem_new
        )
        if placed is None:
            continue
        for w in placed:
            state.add_instance(component, w)
        return len(placed)
    return 0


def _hottest_component(state: ScheduleState, machine: int, rate: float) -> int:
    """Component owning the hottest task on ``machine`` (reference semantics).

    All instances of a component on one machine share one TCU, and tasks are
    ordered component-major, so the reference ``argmax`` over per-task TCUs
    reduces to a first-max argmax over per-component TCUs.
    """
    cir = cost_model.component_rates(state.utg, rate)
    per_inst = cir / state.n_instances
    tcu_c = state.e_cm[:, machine] * per_inst + state.met_cm[:, machine]
    present = state.comp_counts[:, machine] > 0
    return int(np.argmax(np.where(present, tcu_c, -np.inf)))


def maximize_throughput_incremental(
    etg: ExecutionGraph,
    cluster: Cluster,
    r0: float,
    rate_epsilon: float = 1.0,
    max_iters: int = 100_000,
):
    """Algorithm 2 with the incremental engine; reference control flow."""
    # Imported here, not at module level: maximize_throughput imports this
    # module lazily, and keeping both imports function-local makes the
    # non-cycle obvious regardless of which module loads first.
    from repro.core.maximize_throughput import Schedule

    state = ScheduleState.from_etg(etg, cluster)
    scale = 1.0
    current_rate = float(r0)
    final_snap = state.snapshot()
    final_rate = 0.0
    trace: list[tuple[int, str, float]] = []
    # Closed-form R* for the current structure; None = needs recompute.
    rstar: float | None = None

    it = 0
    while it < max_iters:
        it += 1
        if rstar is None:
            rstar = state.max_stable_rate()
        # Closed-form feasibility: far from R* the float comparison alone
        # decides (float R* is within ulps of the exact rational value);
        # inside the pre-filter band, exact rational arithmetic over the
        # linear coefficients is the arbiter — no heuristic re-check.
        if current_rate <= rstar * (1.0 - _RSTAR_GUARD):
            feasible = True
        elif current_rate >= rstar * (1.0 + _RSTAR_GUARD):
            feasible = False
        else:
            feasible = state.feasible_linear_exact(current_rate)
        if feasible:
            final_snap = state.snapshot()
            final_rate = current_rate
            increment = current_rate / scale
            if increment < rate_epsilon:
                trace.append((it, "terminate", current_rate))
                break
            current_rate += increment
            trace.append((it, "raise_rate", current_rate))
            continue
        # Over-utilization: hottest task on the first over-utilized machine
        # (reference index order) under the same linear model; the exact
        # rational scan runs only when float rounding hides the machine.
        var = state.var_load
        if cluster.has_network:
            var = var + state.net_load
        head = cluster.capacity - (state.met_load + current_rate * var)
        over_idx = np.flatnonzero(head < 0.0)
        if over_idx.size:
            over_w = int(over_idx[0])
        else:
            exact_w = state.first_over_machine_exact(current_rate)
            over_w = int(np.argmin(head)) if exact_w is None else exact_w
        component = _hottest_component(state, over_w, current_rate)
        added = _grow_component_fast(state, component, current_rate)
        if added:
            rstar = None
            trace.append((it, f"new_instance:c{component}x{added}", current_rate))
            continue
        # No candidate machine (reference lines 11-16).
        if current_rate > scale and final_rate > 0.0:
            scale *= 2.0
            state.restore(final_snap)
            rstar = None
            current_rate = final_rate + final_rate / scale
            trace.append((it, "backoff", current_rate))
            continue
        trace.append((it, "terminate", final_rate))
        break

    state.restore(final_snap)
    final_etg = state.to_etg()
    pred_final = cost_model.predict(final_etg, cluster, final_rate)
    return Schedule(
        etg=final_etg,
        rate=final_rate,
        predicted_throughput=pred_final.throughput,
        iterations=it,
        trace=trace,
    )
