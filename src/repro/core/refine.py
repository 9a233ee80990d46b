"""Local-search refinement of a schedule (beyond-paper enhancement).

The paper's Algorithm 2 only ever *adds* instances; it can never rebalance
earlier placement decisions, so on profiles where task "chunks" pack
awkwardly it terminates at a local optimum measurably below the exhaustive
optimum. This pass closes that gap with a hill climb over these move types,
each scored by the closed-form maximum stable throughput (paper eq. 5/6 are
linear in the topology input rate, so no simulation is needed):

* RELOCATE — move one instance to a different machine;
* SWAP     — exchange the machines of two instances of different components;
* ADD      — grow one component by one instance on some machine;
* GROW     — grow one component by k instances at once, placed greedily;
* PAIRGROW — grow two components together (crosses eq. 6 re-split valleys);
* DROP     — remove an instance of a component with >= 2 instances (undoes
             over-provisioning that only burns MET overhead).

The climb applies the single best improving move until no move improves
throughput by more than ``tol`` (first-improvement would also work; best-
improvement keeps the trace short and deterministic).

Engines
-------
``engine="state"`` (default) runs the climb on the incremental
``ScheduleState`` engine: moves are O(m) count-matrix deltas (no
``ExecutionGraph`` copies), and each round's candidate set is scored
through vectorized sweeps — candidate placements are edits of the exported
task->machine row (materialised as (B, T) matrices where NumPy scores them;
scored as edits of their base rows on the device), greedy growth chains
across all components/pairs advance in depth-lockstep sweeps (4 per
round), and every NumPy-scored candidate's score is bit-identical to the
reference path's scalar ``max_stable_rate``, so the two engines provably
choose the same moves. The default ``backend="auto"`` preserves that
contract below the per-regime dispatch crossovers (shared / per-row /
skew element floors plus a CPU machine-count gate, calibrated by
benchmarks/bench_dispatch.py) — which cover every golden/equivalence-suite
sweep by construction — and above them trades bit-exactness for the
scatter-free jitted JAX scorer (~1e-15 agreement: exact ties between
moves may break differently from ``engine="reference"``, with
equal-quality results; pass ``backend="numpy"`` to keep strict
replayability on hosts where sweeps cross). ``engine="reference"`` keeps the
original copy-and-score implementation as the semantic reference for the
golden equivalence tests (``tests/test_sched_equivalence.py``).

This module is *not* part of the faithful reproduction; benchmarks report
"proposed" (faithful Alg. 1+2) and "proposed+refine" separately. See
docs/architecture.md for the engine design and docs/api.md for usage.
"""

from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

from repro.core import cost_model
from repro.core.cost_model import max_stable_rate
from repro.core.graph import ExecutionGraph
from repro.core.profiles import Cluster
from repro.core.schedule_state import ScheduleState
from repro.obs import trace

__all__ = ["RefineResult", "refine"]

# Candidate rows built at a time when a RELOCATE+SWAP sweep scores on NumPy;
# bounds the (chunk, T) batch memory on large clusters without changing
# results (rows are independent). Device sweeps score the edits without
# building rows, with or without resources, so this does not chunk them
# (``ScheduleState.best_relocate_swap``). Network-aware clusters tighten
# this further (see ``_effective_chunk``): the host cut-traffic term expands
# every row into (n_components, m) scatter tensors plus distance matvecs,
# so the naive cap would materialize the full edge×machine product on wide
# topologies (regression-tested at m=90).
_SCORE_CHUNK = 16_384


def _effective_chunk(cluster: Cluster, n_components: int) -> int:
    """Rows per NumPy RELOCATE+SWAP chunk: ``_SCORE_CHUNK``, tightened on
    network-aware clusters so one chunk's host cut-traffic accumulation
    stays within the ``cost_model._NET_CHUNK_ELEMS`` (chunk · n · m)
    element budget instead of relying on its inner chunking to re-split an
    oversized batch."""
    if not cluster.has_network:
        return _SCORE_CHUNK
    per_row = max(1, n_components * cluster.n_machines)
    return min(_SCORE_CHUNK, max(256, cost_model._NET_CHUNK_ELEMS // per_row))

# Total steps (prefix included) a depth-adaptive growth chain may reach —
# a runaway backstop far above any profitable chain, shared by the lockstep
# and sequential explorers so their stopping decisions are identical.
_ADAPTIVE_GROW_CAP = 64


@dataclasses.dataclass(frozen=True)
class RefineResult:
    """``candidates``: candidate placements the climb scored, each counted
    once per round (the reference engine re-scores greedy growth prefixes
    and the candidate a chain ends on; those repeats are not counted), so
    both engines report the same number for the same climb."""

    etg: ExecutionGraph
    rate: float
    throughput: float
    moves: list[str]
    candidates: int = 0


def _score(etg: ExecutionGraph, cluster: Cluster) -> float:
    return max_stable_rate(etg, cluster)[1]


def refine(
    etg: ExecutionGraph,
    cluster: Cluster,
    max_rounds: int = 200,
    tol: float = 1e-9,
    allow_add: bool = True,
    engine: str = "state",
    backend: str = "auto",
    lockstep: bool = True,
    adaptive_growth: bool = False,
    skew: "cost_model.SkewModel | None" = None,
    recorder=None,
) -> RefineResult:
    """Hill-climb refinement of ``etg``'s placement (and instance counts).

    Args:
      etg: schedule to refine (not mutated).
      cluster: the heterogeneous cluster.
      max_rounds: maximum number of applied moves.
      tol: minimum throughput improvement for a move to be applied.
      allow_add: when False, only count-preserving moves (RELOCATE/SWAP)
        are considered.
      engine: ``"state"`` (incremental ScheduleState deltas + batched
        scoring, default) or ``"reference"`` (original per-candidate
        copy-and-score path). Both produce identical results.
      backend: scoring backend for the state engine's batched closed-form
        evaluator — ``"auto"`` (default: the bit-exact NumPy reference
        below the calibrated dispatch crossover, the jitted JAX kernel for
        large sweeps such as big-cluster RELOCATE+SWAP chunks; see
        benchmarks/bench_dispatch.py), ``"numpy"`` (always the reference
        floats), or ``"jax"`` (always the jitted float64 kernel, ~1e-15
        relative agreement). Ignored by the reference engine.
      lockstep: explore greedy growth chains in depth-lockstep sweeps (4
        per round regardless of component count, default) instead of one
        m-row sweep per chain step. Identical results either way; the
        sequential path is the benchmark baseline.
      adaptive_growth: keep extending growth chains past the reference
        menu's depth 4 while their closed-form score strictly improves
        (one extra sweep per depth), offering GROW k>4 and PAIRGROW
        (a, b>2) candidates the fixed menu cannot see. Off by default —
        the reference engine has no adaptive menu, so the golden
        equivalence contract covers the default; lockstep and sequential
        explorers produce identical adaptive results (tested). State
        engine only.
      skew: optional ``cost_model.SkewModel`` — every candidate (and the
        incumbent) scores with the skew-aware per-instance utilization
        bound instead of the eq. 6 even split, so growth offers on a
        component whose instances are skew-saturated cannot report
        even-split gains. State engine only; forces NumPy scoring.
      recorder: optional ``repro.obs.TraceRecorder``. When enabled, the
        climb runs under a ``refine`` span with one ``refine.round`` span
        per applied move (state engine), and the recorder is *activated*
        for the duration so every closed-form backend resolution during
        scoring lands in its dispatch log, each candidate batch's
        construction and scoring land in ``refine.build`` /
        ``refine.sweep`` spans (``sweep.put`` / ``sweep.run`` /
        ``sweep.fetch`` on the device) and the ``refine.rows`` /
        ``sweep.h2d_bytes`` counters. ``None`` (or a ``NullRecorder``)
        adds no work to the climb.
    """
    rec = recorder if recorder is not None and recorder.enabled else None
    if engine == "state":
        if rec is None:
            return _refine_state(
                etg, cluster, max_rounds, tol, allow_add, backend, lockstep,
                adaptive_growth, skew,
            )
        with rec.activate(), rec.span(
            "refine", cat="refine", engine=engine, backend=backend
        ) as sp:
            result = _refine_state(
                etg, cluster, max_rounds, tol, allow_add, backend, lockstep,
                adaptive_growth, skew, recorder=rec,
            )
            sp["args"]["applied_moves"] = len(result.moves)
            sp["args"]["throughput"] = float(result.throughput)
        return result
    if engine != "reference":
        raise ValueError(f"unknown engine {engine!r}; use 'state' or 'reference'")
    if adaptive_growth:
        raise ValueError("adaptive_growth requires engine='state'")
    if skew is not None:
        raise ValueError("skew requires engine='state'")
    if rec is None:
        return _refine_reference(etg, cluster, max_rounds, tol, allow_add)
    with rec.activate(), rec.span(
        "refine", cat="refine", engine=engine, backend=backend
    ) as sp:
        result = _refine_reference(etg, cluster, max_rounds, tol, allow_add)
        sp["args"]["applied_moves"] = len(result.moves)
        sp["args"]["throughput"] = float(result.throughput)
    return result


# --------------------------------------------------------------- reference


def _refine_reference(
    etg: ExecutionGraph,
    cluster: Cluster,
    max_rounds: int,
    tol: float,
    allow_add: bool,
) -> RefineResult:
    """Original implementation: one ``ExecutionGraph`` copy + scalar
    ``max_stable_rate`` per candidate move. O(T·m + T²) copies per round."""
    current = etg.copy()
    best = _score(current, cluster)
    moves: list[str] = []
    m = cluster.n_machines
    n = current.utg.n_components
    candidates = 0

    for _ in range(max_rounds):
        best_move: tuple[float, str, ExecutionGraph] | None = None
        # Greedy growth prefixes whose m trials were already counted.
        stepped: set[tuple[int, ...]] = set()

        def consider(cand: ExecutionGraph, desc: str, fresh: bool = True) -> None:
            nonlocal best_move, candidates
            candidates += fresh
            s = _score(cand, cluster)
            if s > best + tol and (best_move is None or s > best_move[0]):
                best_move = (s, desc, cand)

        # RELOCATE: every instance to every other machine.
        for c in range(n):
            for k in range(int(current.n_instances[c])):
                src = int(current.assignment[c][k])
                for w in range(m):
                    if w == src:
                        continue
                    cand = current.copy()
                    cand.assignment[c] = cand.assignment[c].copy()
                    cand.assignment[c][k] = w
                    consider(cand, f"relocate c{c}#{k} m{src}->m{w}")

        # SWAP: instances of different components on different machines.
        flat = [
            (c, k, int(current.assignment[c][k]))
            for c in range(n)
            for k in range(int(current.n_instances[c]))
        ]
        for a in range(len(flat)):
            ca, ka, wa = flat[a]
            for b in range(a + 1, len(flat)):
                cb, kb, wb = flat[b]
                if wa == wb or ca == cb:
                    continue
                cand = current.copy()
                cand.assignment[ca] = cand.assignment[ca].copy()
                cand.assignment[cb] = cand.assignment[cb].copy()
                cand.assignment[ca][ka] = wb
                cand.assignment[cb][kb] = wa
                consider(cand, f"swap c{ca}#{ka}<->c{cb}#{kb}")

        if allow_add:
            # ADD: one more instance of any component on any machine.
            for c in range(n):
                for w in range(m):
                    consider(current.with_new_instance(c, w), f"add c{c}->m{w}")
                stepped.add((c,))
            # GROW: k instances of one component at once, placed greedily —
            # the eq. 6 re-split means gains often appear only at specific
            # counts, invisible to single adds (e.g. 2 extra instances so a
            # fast machine carries 2 of N chunks).
            def greedy_grow(base, adds):
                nonlocal candidates
                cand = base
                for i, c in enumerate(adds):
                    if tuple(adds[: i + 1]) not in stepped:
                        stepped.add(tuple(adds[: i + 1]))
                        candidates += m
                    step_best = None
                    for w in range(m):
                        trial = cand.with_new_instance(c, w)
                        sc = _score(trial, cluster)
                        if step_best is None or sc > step_best[0]:
                            step_best = (sc, trial)
                    cand = step_best[1]
                return cand

            for c in range(n):
                for k in (2, 3, 4):
                    consider(
                        greedy_grow(current, [c] * k), f"grow c{c}x{k}", fresh=False
                    )
            # PAIRGROW: components often need to grow *together* — the eq. 6
            # re-split creates valleys between (x, y) and (x+a, y+b) that
            # per-component moves cannot cross.
            for ci in range(n):
                for cj in range(ci + 1, n):
                    for a, b in ((1, 1), (2, 1), (1, 2), (2, 2)):
                        adds = [ci] * a + [cj] * b
                        consider(greedy_grow(current, adds),
                                 f"pairgrow c{ci}x{a}+c{cj}x{b}", fresh=False)
            # DROP: remove an instance (keeps >= 1 per component).
            for c in range(n):
                if int(current.n_instances[c]) < 2:
                    continue
                for k in range(int(current.n_instances[c])):
                    cand = current.copy()
                    cand.n_instances = cand.n_instances.copy()
                    cand.n_instances[c] -= 1
                    cand.assignment[c] = np.delete(cand.assignment[c], k)
                    consider(cand, f"drop c{c}#{k}")

        if best_move is None:
            break
        best, desc, current = best_move
        moves.append(desc)

    rate, thpt = max_stable_rate(current, cluster)
    return RefineResult(
        etg=current, rate=rate, throughput=thpt, moves=moves, candidates=candidates
    )


# ------------------------------------------------------------ state engine


class _GrowCursor:
    """Flat task->machine row + block offsets threaded through a greedy
    growth chain, so each step avoids rebuilding them from the state."""

    __slots__ = ("row", "offsets")

    def __init__(self, row: np.ndarray, offsets: np.ndarray):
        self.row = row
        self.offsets = offsets

    def copy(self) -> "_GrowCursor":
        # Steps rebind (never mutate) row/offsets, so a shallow copy is a
        # valid fork point.
        return _GrowCursor(self.row, self.offsets)


class _GrowChain:
    """One greedy growth chain: its current exported row, block offsets and
    instance-count vector, plus the placements/scores of every step so far.

    After j steps, ``scores[j - 1]`` is the closed-form throughput of the
    j-step prefix and ``placements[:j]`` is the move that realizes it —
    uniform across single-component chains (ADD/GROW) and pair chains
    (PAIRGROW), which fork from a single chain's prefix.
    """

    __slots__ = ("row", "offsets", "n_inst", "placements", "scores")

    def __init__(self, row: np.ndarray, offsets: np.ndarray, n_inst: np.ndarray):
        self.row = row
        self.offsets = offsets
        self.n_inst = n_inst
        self.placements: list[tuple[int, int]] = []
        self.scores: list[float] = []

    def fork(self) -> "_GrowChain":
        # Steps rebind row/offsets and copy-on-write n_inst, so forking a
        # prefix shares the arrays and copies only the Python lists.
        child = _GrowChain(self.row, self.offsets, self.n_inst.copy())
        child.placements = list(self.placements)
        child.scores = list(self.scores)
        return child


def _with_task(row: np.ndarray, pos: int, machine: int) -> np.ndarray:
    """``row`` with one more task, on ``machine``, at column ``pos``."""
    return np.concatenate((row[:pos], [machine], row[pos:]))


def _grow_step(
    state: ScheduleState, c: int, backend: str, cur: _GrowCursor
) -> tuple[float, int]:
    """One greedy growth step: score adding an instance of ``c`` on every
    machine (one batched sweep), apply the winner to ``state`` and ``cur``.

    Matches the reference ``greedy_grow`` inner loop exactly: strict-``>``
    first-max over machines in index order is ``np.argmax`` on the batch.
    """
    scores = state.score_grow_steps(cur.row, state.n_instances, c, backend)
    w = int(np.argmax(scores))
    state.add_instance(c, w)
    with trace.span("refine.build", "refine"):
        cur.row = _with_task(cur.row, int(cur.offsets[c + 1]), w)
        new_off = cur.offsets.copy()
        new_off[c + 1 :] += 1
        cur.offsets = new_off
    return float(scores[w]), w


def _lockstep_extend(
    state: ScheduleState,
    chains: list[_GrowChain],
    comps: list[int],
    backend: str,
) -> None:
    """One lockstep depth: score every live chain's next greedy step in a
    single sweep and apply each chain's winner.

    Chain i appends one instance of ``comps[i]`` at the end of its block;
    its m candidates (one per machine) score as one
    ``ScheduleState.score_grow_steps`` sweep of every chain, which stands
    for len(chains) * m rows with per-row counts. Each chain's winner is
    the strict first-max over its own m candidates in machine order, so
    scores and winners are those of stepping the chains one ``_grow_step``
    sweep at a time; only the winning rows are built.
    """
    if not chains:
        return
    scores = state.score_grow_steps(
        np.stack([ch.row for ch in chains]),
        np.stack([ch.n_inst for ch in chains]),
        np.asarray(comps, dtype=np.int64),
        backend,
    )
    winners = scores.argmax(axis=1)
    with trace.span("refine.build", "refine"):
        for i, (ch, c) in enumerate(zip(chains, comps)):
            w = int(winners[i])
            ch.row = _with_task(ch.row, int(ch.offsets[c + 1]), w)
            new_off = ch.offsets.copy()
            new_off[c + 1 :] += 1
            ch.offsets = new_off
            ch.n_inst[c] += 1
            ch.placements.append((c, w))
            ch.scores.append(float(scores[i, w]))


def _adaptive_live(chains: list[tuple[_GrowChain, int]]) -> list[tuple[_GrowChain, int]]:
    """Chains that keep extending: last step strictly improved, cap not hit.

    The stopping rule both explorers share — a chain whose deepest step did
    not strictly beat the one before it has crossed its eq. 6 re-split
    valley floor and stops.
    """
    return [
        (ch, c)
        for ch, c in chains
        if len(ch.scores) < _ADAPTIVE_GROW_CAP and ch.scores[-1] > ch.scores[-2]
    ]


def _adaptive_extend_lockstep(
    state: ScheduleState,
    singles: list[_GrowChain],
    pair_a: dict,
    pair_b: dict,
    pairs: list[tuple[int, int]],
    backend: str,
) -> None:
    """Depth-adaptive continuation: extend every still-improving chain one
    step per sweep until none improves.

    Chains at different depths carry different task totals, so each
    iteration groups live chains by row length and runs one per-row-count
    sweep per group — still O(depth) sweeps per round, independent of
    component count.
    """
    live = [(singles[c], c) for c in range(len(singles))]
    live += [(pair_a[p], p[1]) for p in pairs]
    live += [(pair_b[p], p[1]) for p in pairs]
    while True:
        live = _adaptive_live(live)
        if not live:
            return
        groups: dict[int, list[tuple[_GrowChain, int]]] = {}
        for ch, c in live:
            groups.setdefault(int(ch.row.shape[0]), []).append((ch, c))
        for length in sorted(groups):
            _lockstep_extend(
                state,
                [ch for ch, _ in groups[length]],
                [c for _, c in groups[length]],
                backend,
            )


def _growth_chains_lockstep(
    state: ScheduleState,
    base_tm: np.ndarray,
    offsets: np.ndarray,
    n_inst: np.ndarray,
    backend: str,
    adaptive: bool = False,
) -> tuple[list[_GrowChain], dict, dict, list[tuple[int, int]]]:
    """Explore every greedy growth chain in four depth-lockstep sweeps.

    Single chains (one per component, 4 steps each: ADD + GROW k=2/3/4) and
    pair chains (PAIRGROW (a, b) forks off the single chain's a-step
    prefix, then adds cj) advance together: every chain at depth d has the
    same task total T + d, so one rectangular per-row-count sweep scores
    all of them. A refine round's growth exploration is 4 sweeps total,
    independent of component count — versus ~4n + 4·C(n,2) m-row sweeps
    for the sequential path (``_growth_chains_sequential``).
    """
    n = state.utg.n_components
    pairs = [(ci, cj) for ci in range(n) for cj in range(ci + 1, n)]
    singles = [_GrowChain(base_tm, offsets, n_inst.copy()) for _ in range(n)]
    # Depth 1: each single chain's first step (the ADD candidate).
    _lockstep_extend(state, singles, list(range(n)), backend)
    # PAIRGROW (1, b) forks off the 1-step prefix before depth 2 extends it.
    pair_a = {p: singles[p[0]].fork() for p in pairs}
    # Depth 2: singles (GROW k=2) + first cj of every (1, b) pair chain.
    _lockstep_extend(
        state,
        singles + [pair_a[p] for p in pairs],
        list(range(n)) + [cj for _, cj in pairs],
        backend,
    )
    # PAIRGROW (2, b) forks off the 2-step prefix before depth 3.
    pair_b = {p: singles[p[0]].fork() for p in pairs}
    # Depth 3: singles (GROW k=3), second cj of (1, b), first cj of (2, b).
    _lockstep_extend(
        state,
        singles + [pair_a[p] for p in pairs] + [pair_b[p] for p in pairs],
        list(range(n)) + [cj for _, cj in pairs] * 2,
        backend,
    )
    # Depth 4: singles (GROW k=4) + second cj of (2, b).
    _lockstep_extend(
        state,
        singles + [pair_b[p] for p in pairs],
        list(range(n)) + [cj for _, cj in pairs],
        backend,
    )
    if adaptive:
        _adaptive_extend_lockstep(state, singles, pair_a, pair_b, pairs, backend)
    return singles, pair_a, pair_b, pairs


def _growth_chains_sequential(
    state: ScheduleState,
    base_tm: np.ndarray,
    offsets: np.ndarray,
    n_inst: np.ndarray,
    backend: str,
    adaptive: bool = False,
) -> tuple[list[_GrowChain], dict, dict, list[tuple[int, int]]]:
    """Sequential chain exploration (one m-row sweep per step).

    The pre-lockstep state-engine path, kept for the
    ``refine(..., lockstep=False)`` escape hatch and as the benchmark
    baseline the lockstep speedup is measured against
    (benchmarks/bench_refine.py). Scores and winners are bit-identical to
    the lockstep path — rows score independently either way.
    """
    n = state.utg.n_components
    pairs = [(ci, cj) for ci in range(n) for cj in range(ci + 1, n)]
    singles = []
    forks: list[dict[int, _GrowCursor]] = []
    for c in range(n):
        snap = state.snapshot()
        cur = _GrowCursor(base_tm, offsets)
        ch = _GrowChain(base_tm, offsets, n_inst.copy())
        fk: dict[int, _GrowCursor] = {}
        for step in range(1, 5):
            sc, w = _grow_step(state, c, backend, cur)
            ch.placements.append((c, w))
            ch.scores.append(sc)
            ch.n_inst[c] += 1
            if step <= 2:
                fk[step] = cur.copy()
        while adaptive and _adaptive_live([(ch, c)]):
            sc, w = _grow_step(state, c, backend, cur)
            ch.placements.append((c, w))
            ch.scores.append(sc)
            ch.n_inst[c] += 1
        ch.row, ch.offsets = cur.row, cur.offsets
        state.restore(snap)
        singles.append(ch)
        forks.append(fk)
    pair_a: dict[tuple[int, int], _GrowChain] = {}
    pair_b: dict[tuple[int, int], _GrowChain] = {}
    for ci, cj in pairs:
        ci_chain = singles[ci]
        for prefix, out in ((1, pair_a), (2, pair_b)):
            snap0 = state.snapshot()
            for c, w in ci_chain.placements[:prefix]:
                state.add_instance(c, w)
            cur = forks[ci][prefix].copy()
            ch = _GrowChain(cur.row, cur.offsets, n_inst.copy())
            ch.placements = list(ci_chain.placements[:prefix])
            ch.scores = list(ci_chain.scores[:prefix])
            ch.n_inst[ci] += prefix
            for _ in range(2):
                sc, w = _grow_step(state, cj, backend, cur)
                ch.placements.append((cj, w))
                ch.scores.append(sc)
                ch.n_inst[cj] += 1
            while adaptive and _adaptive_live([(ch, cj)]):
                sc, w = _grow_step(state, cj, backend, cur)
                ch.placements.append((cj, w))
                ch.scores.append(sc)
                ch.n_inst[cj] += 1
            ch.row, ch.offsets = cur.row, cur.offsets
            state.restore(snap0)
            out[(ci, cj)] = ch
    return singles, pair_a, pair_b, pairs


def _refine_state(
    etg: ExecutionGraph,
    cluster: Cluster,
    max_rounds: int,
    tol: float,
    allow_add: bool,
    backend: str,
    lockstep: bool = True,
    adaptive_growth: bool = False,
    skew=None,
    recorder=None,
) -> RefineResult:
    """Incremental-engine hill climb: identical decisions, batched scoring.

    Per round, every move family is expressed as edits on the flattened
    (T,) task->machine row exported from ``ScheduleState`` and scored in
    vectorized ``max_stable_rate_batch`` sweeps — one sweep covers all
    RELOCATE+SWAP candidates (``ScheduleState.best_relocate_swap``, which
    on the device selects the winner there and fetches it alone, four
    float64s a sweep, not the candidates' scores), four
    depth-lockstep sweeps cover every growth chain (ADD/GROW/PAIRGROW;
    ``score_grow_steps``), and one more covers all DROP candidates
    (``score_drops``): ~6 sweeps per round. NumPy-scored candidates are
    bit-identical to the reference engine's scalar scoring (same
    ``max_stable_rate_batch`` row computation); device sweeps agree to
    ~1e-15. Winners are selected
    with the same strict-``>`` first-max semantics in the same enumeration
    order, so both engines apply the same move sequence. Applying a move is
    an O(m) ``ScheduleState`` delta; growth exploration carries candidate
    rows/counts per chain, never mutating the live state.
    """
    state = ScheduleState.from_etg(etg, cluster, skew=skew)
    if skew is None:
        best = _score(state.to_etg(), cluster)
    else:
        # The incumbent must score under the same skew-aware bound as the
        # candidates, or offers get compared against the even-split score.
        best = float(
            state.score_task_machine_batch(state.task_machine()[None, :])[1][0]
        )
    rows0 = state.rows_scored  # the incumbent is no candidate
    moves: list[str] = []
    n = state.utg.n_components

    for round_idx in range(max_rounds):
        # Per-round profiling span; ``sp`` is its record (None unrecorded).
        round_span = (
            contextlib.nullcontext()
            if recorder is None
            else recorder.span("refine.round", cat="refine", round=round_idx)
        )
        with round_span as sp:
            best_move: tuple[float, str, "function"] | None = None

            def offer(score: float, desc: str, apply_fn) -> None:
                nonlocal best_move
                if score > best + tol and (best_move is None or score > best_move[0]):
                    best_move = (score, desc, apply_fn)

            base_tm = state.task_machine()
            offsets = state.component_offsets()
            # Copy: growth exploration below mutates state.n_instances in place
            # before snapshot/restore swaps in a fresh array.
            n_inst = state.n_instances.copy()
            comp_of = np.repeat(np.arange(n), n_inst)

            # RELOCATE + SWAP share the template (counts unchanged): candidates
            # are 1-2 column edits on the base row, scored in one sweep (the
            # rows are built only where NumPy scores them). The winner is the
            # first strict maximum in [relocate..., swap...] order, the
            # reference's first strictly-greater winner; a device sweep picks
            # it on the device and fetches it alone.
            won = state.best_relocate_swap(
                base_tm, backend=backend, row_chunk=_effective_chunk(cluster, n)
            )
            if won is not None:
                (pa, wa, pb, wb), s = won
                if pa == pb:
                    c = int(comp_of[pb])
                    k, src = pb - int(offsets[c]), int(base_tm[pb])
                    offer(
                        s,
                        f"relocate c{c}#{k} m{src}->m{wb}",
                        lambda c=c, k=k, w=wb: state.relocate_instance(c, k, w),
                    )
                else:
                    ca, cb = int(comp_of[pa]), int(comp_of[pb])
                    ka, kb = pa - int(offsets[ca]), pb - int(offsets[cb])
                    offer(
                        s,
                        f"swap c{ca}#{ka}<->c{cb}#{kb}",
                        lambda ca=ca, ka=ka, cb=cb, kb=kb: state.swap_instances(
                            ca, ka, cb, kb
                        ),
                    )

            if allow_add:
                def apply_adds(placements):
                    for c, w in placements:
                        state.add_instance(c, w)

                # Greedy growth is deterministic, so the reference's independent
                # greedy_grow re-runs traverse shared prefixes: one 4-step chain
                # per component yields the ADD candidate (step 1) and the
                # GROW k=2/3/4 candidates (steps 2-4); PAIRGROW forks off the
                # first one or two steps of the first component's chain. The
                # lockstep explorer advances every chain together — 4
                # per-row-count sweeps per round regardless of component count;
                # the sequential explorer steps chains one m-row sweep at a
                # time. Both produce bit-identical chain scores. Offers follow
                # the reference enumeration order (ADD..., GROW..., PAIRGROW...,
                # DROP...), which matters for exact-tie breaking under the
                # strict-> first-max rule.
                explore = (
                    _growth_chains_lockstep if lockstep else _growth_chains_sequential
                )
                singles, pair_a, pair_b, pairs = explore(
                    state, base_tm, offsets, n_inst, backend, adaptive_growth
                )
                # ADD: the reference's first-max over machines is exactly the
                # chain's first greedy step (same scores, same argmax).
                for c in range(n):
                    ch = singles[c]
                    offer(
                        ch.scores[0],
                        f"add c{c}->m{ch.placements[0][1]}",
                        lambda p=ch.placements[:1]: apply_adds(p),
                    )
                # GROW: k instances of one component at once — the eq. 6
                # re-split means gains often appear only at specific counts,
                # invisible to single adds. Adaptive chains extend the menu
                # past k=4 for as deep as their scores kept improving.
                for c in range(n):
                    ch = singles[c]
                    for k in range(2, len(ch.scores) + 1):
                        offer(
                            ch.scores[k - 1],
                            f"grow c{c}x{k}",
                            lambda p=ch.placements[:k]: apply_adds(p),
                        )
                # PAIRGROW: components often need to grow *together* — the
                # eq. 6 re-split creates valleys between (x, y) and
                # (x+a, y+b) that per-component moves cannot cross. The (a, b)
                # combo is the (a + b)-step prefix of the (a, ·) pair chain.
                for ci, cj in pairs:
                    pa, pb = pair_a[(ci, cj)], pair_b[(ci, cj)]
                    for (a, b), ch in (
                        ((1, 1), pa),
                        ((2, 1), pb),
                        ((1, 2), pa),
                        ((2, 2), pb),
                    ):
                        offer(
                            ch.scores[a + b - 1],
                            f"pairgrow c{ci}x{a}+c{cj}x{b}",
                            lambda p=ch.placements[: a + b]: apply_adds(p),
                        )
                    # Adaptive extension of the pair menu: (a, b > 2) combos
                    # for as deep as each pair chain kept improving.
                    max_b = max(len(pa.scores) - 1, len(pb.scores) - 2)
                    for b in range(3, max_b + 1):
                        for a, ch in ((1, pa), (2, pb)):
                            if len(ch.scores) - a >= b:
                                offer(
                                    ch.scores[a + b - 1],
                                    f"pairgrow c{ci}x{a}+c{cj}x{b}",
                                    lambda p=ch.placements[: a + b]: apply_adds(p),
                                )
                # DROP: which instance to delete, over every component with
                # >= 2 instances — column removals on the base row, all scored
                # in one sweep (winner still picked per component to preserve
                # the reference offer order).
                drops = state.score_drops(base_tm, n_inst, backend)
                for c in range(n):
                    if int(n_inst[c]) < 2:
                        continue
                    sd = drops[offsets[c] : offsets[c + 1]]
                    k = int(np.argmax(sd))
                    offer(
                        float(sd[k]),
                        f"drop c{c}#{k}",
                        lambda c=c, k=k: state.drop_instance(c, k),
                    )

            if best_move is None:
                if sp is not None:
                    sp["args"]["move"] = None
                break
            best, desc, apply_fn = best_move
            apply_fn()
            moves.append(desc)
            if sp is not None:
                sp["args"]["move"] = desc
                sp["args"]["score"] = float(best)

    final = state.to_etg()
    rate, thpt = max_stable_rate(final, cluster, skew=skew)
    return RefineResult(
        etg=final, rate=rate, throughput=thpt, moves=moves,
        candidates=state.rows_scored - rows0,
    )
