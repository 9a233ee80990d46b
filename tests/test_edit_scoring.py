"""RELOCATE+SWAP candidates scored as edits of one base row.

``sim_jax``'s ``msr_edits`` kernel against ``msr_shared`` on the same
candidates materialised as rows, ``msr_edits_resources`` fed neutral
resource tables against ``msr_edits``, the winners the kernel selects
against ``np.argmax`` over its grids, and refine's routing of its
relocate+swap sweep through ``ScheduleState.best_relocate_swap``: device
sweeps take the edit path, count it in ``sweep.edit_rows`` and fetch only
the winners; NumPy sweeps keep rows. Clusters with resources:
``tests/test_net_edit_scoring.py``.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import linear_topology, paper_cluster, schedule
from repro.core.refine import refine
from repro.core.schedule_state import ScheduleState
from repro.core.sim_jax import (
    closed_form_rates_jax,
    relocate_swap_scores_jax,
    relocate_swap_winners_jax,
)
from repro.obs import TraceRecorder

from neutral_tables import assert_edit_parity, neutral_tail

# Machine types of the kernel scenario: machines 1-3 share a type.
MTYPE = np.array([0, 1, 1, 1, 2, 2])


def _scenario(seed, tight=True):
    """A small cluster and base row with the cases the kernel must meet:
    machine 0 holds task 0 alone (moving it away empties it), and machines
    1 and 2, of one type, hold one instance of each component at the same
    places of their blocks (moves onto either tie). ``tight`` leaves
    machine 0 no spare fixed capacity (moving any task onto it turns the
    row infeasible); otherwise every machine has room and the binding
    machine varies from row to row."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(3, 5, size=3)
    comp = np.repeat(np.arange(3), counts)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    e_cm = rng.uniform(0.5, 2.0, size=(3, 3))[:, MTYPE]
    met_cm = rng.uniform(0.05, 0.3, size=(3, 3))[:, MTYPE]
    # Per-task unit rates (as a skew model gives), equal on the twins.
    unit_ir = rng.uniform(0.2, 1.0, size=comp.size)
    unit_ir[offsets[:3] + 2] = unit_ir[offsets[:3] + 1]
    base = rng.integers(3, 6, size=comp.size)
    base[0] = 0
    base[offsets[:3] + 1] = 1
    base[offsets[:3] + 2] = 2
    met_base = np.bincount(base, met_cm[comp, base], minlength=MTYPE.size)
    cap = met_base + rng.uniform(0.5, 3.0, size=MTYPE.size)
    if tight:
        cap[0] = met_base[0] + 0.01
    cap[2] = cap[1]
    return base, comp, unit_ir, e_cm, met_cm, cap


def _menu(base, m):
    """Edits of every relocation, then of every swap of tasks on different
    machines, each family in task order, and the grid cells they score."""
    moves = np.arange(m)[None, :] != base[:, None]
    pairs = np.triu(base[:, None] != base[None, :], k=1)
    p, w = np.nonzero(moves)
    a, b = np.nonzero(pairs)
    relocate = np.stack([p, w, p, w])
    swap = np.stack([a, base[b], b, base[a]])
    return relocate, swap, moves, pairs


def _rows(base, edits):
    tm = np.tile(base, (edits.shape[1], 1))
    r = np.arange(edits.shape[1])
    tm[r, edits[0]] = edits[1]
    tm[r, edits[2]] = edits[3]
    return tm


@pytest.mark.parametrize("tight", [True, False], ids=["tight", "roomy"])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("family", ["relocate", "swap"])
def test_edit_kernel_agrees_with_the_row_kernel(seed, family, tight):
    base, comp, unit_ir, e_cm, met_cm, cap = _scenario(seed, tight)
    relocate, swap, moves, pairs = _menu(base, cap.size)
    grids = relocate_swap_scores_jax(
        base, np.arange(base.size), comp, unit_ir, e_cm, met_cm, cap
    )
    assert grids[0].shape == moves.shape and grids[1].shape == pairs.shape
    edits, got = (relocate, grids[0][moves]) if family == "relocate" else (
        swap, grids[1][pairs]
    )
    rows = _rows(base, edits)
    want = closed_form_rates_jax(rows, comp, unit_ir, e_cm, met_cm, cap)[1]
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=0.0)
    # Exact ties of the row kernel stay exact ties.
    assert np.all((got[:, None] == got[None, :])[want[:, None] == want[None, :]])
    # Task 0 leaving machine 0 empties it.
    assert np.any((edits[0] == 0) & (got > 0.0))
    if family == "relocate":
        src, dst = base[edits[0]], edits[1]
        # A task joining machine 0 with no spare fixed capacity makes the
        # row infeasible.
        onto_0 = dst == 0
        assert onto_0.any() and np.all((want[onto_0] == 0.0) == tight)
        assert np.all((got[onto_0] == 0.0) == tight)
        # A task from machines 3-5 onto machine 1 or onto its twin 2.
        to_1, to_2 = (dst == 1) & (src > 2), (dst == 2) & (src > 2)
        assert to_1.any() and np.array_equal(edits[0][to_1], edits[0][to_2])
        np.testing.assert_array_equal(want[to_1], want[to_2])
        np.testing.assert_array_equal(got[to_1], got[to_2])


@pytest.mark.parametrize("tight", [True, False], ids=["tight", "roomy"])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("family", ["relocate", "swap"])
def test_resource_edit_kernel_on_neutral_tables_agrees_with_the_plain_one(
    seed, family, tight
):
    base, comp, unit_ir, e_cm, met_cm, cap = _scenario(seed, tight)
    _, _, moves, pairs = _menu(base, cap.size)
    args = (base, np.arange(base.size), comp, unit_ir, e_cm, met_cm, cap)
    plain = relocate_swap_scores_jax(*args)
    neutral = relocate_swap_scores_jax(*args, neutral_tail(e_cm.shape[0], cap.size))
    cells, grid = (moves, 0) if family == "relocate" else (pairs, 1)
    assert neutral[grid].shape == plain[grid].shape == cells.shape
    assert_edit_parity(neutral[grid][cells], plain[grid][cells])
    # Moves onto machine 0 with no spare fixed capacity are infeasible.
    if tight and family == "relocate":
        assert np.any(plain[0][cells] == 0.0)


def test_edit_kernel_scores_a_block_of_moving_tasks():
    base, comp, unit_ir, e_cm, met_cm, cap = _scenario(1)
    full = relocate_swap_scores_jax(
        base, np.arange(base.size), comp, unit_ir, e_cm, met_cm, cap
    )
    rows = np.arange(2, 5)
    block = relocate_swap_scores_jax(base, rows, comp, unit_ir, e_cm, met_cm, cap)
    for f, b in zip(full, block):
        np.testing.assert_array_equal(b, f[rows])


def first_maxima(grids, base, moving, comp):
    """``np.argmax`` over each grid's cells that are candidates of the host
    menu, row-major: ``[relocate index, its score, swap index, its
    score]``, what ``relocate_swap_winners_jax`` must return."""
    moves = np.arange(grids[0].shape[1])[None, :] != base[:, None]
    pairs = np.triu((comp[:, None] != comp[None, :]) & (base[:, None] != base[None, :]), k=1)
    out = []
    for grid, cells in zip(grids, (moves[moving], pairs[moving])):
        flat = np.where(cells, grid, -np.inf).ravel()
        i = int(np.argmax(flat))
        out += [float(i), flat[i]]
    return out


@pytest.mark.parametrize("tight", [True, False], ids=["tight", "roomy"])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("menu", ["all", "block", "no_swap"])
def test_edit_kernel_winners_are_the_first_maxima_of_its_grids(seed, tight, menu):
    base, comp, unit_ir, e_cm, met_cm, cap = _scenario(seed, tight)
    moving = np.arange(2, 5) if menu == "block" else np.arange(base.size)
    if menu == "no_swap":
        base = np.zeros_like(base)
    args = (base, moving, comp, unit_ir, e_cm, met_cm, cap)
    got = relocate_swap_winners_jax(*args)
    want = first_maxima(relocate_swap_scores_jax(*args), base, moving, comp)
    assert got.dtype == np.float64 and got.tolist() == want
    # A grid without a candidate reads index 0 and -inf.
    assert (got[3] == -np.inf) == (menu == "no_swap")


def test_edit_kernel_ships_the_base_row_and_tables():
    base, comp, unit_ir, e_cm, met_cm, cap = _scenario(0)
    rec = TraceRecorder()
    with rec.activate():
        relocate_swap_scores_jax(
            base, np.arange(base.size), comp, unit_ir, e_cm, met_cm, cap
        )
    counters = {m["name"]: m["value"] for m in rec.metrics.snapshot()}
    int32_bytes = 4 * 3 * base.size
    f64_bytes = unit_ir.nbytes + e_cm.nbytes + met_cm.nbytes + cap.nbytes
    assert counters["sweep.h2d_bytes"] == int32_bytes + f64_bytes
    assert [r["name"] for r in rec.records] == ["sweep.put", "sweep.run", "sweep.fetch"]
    # The grids come back, 8 bytes a cell.
    assert counters["sweep.d2h_bytes"] == 8 * base.size * (cap.size + base.size)


def test_edit_kernel_carries_a_stable_name():
    import jax

    from repro.core.sim_jax import _msr_kernel

    T, n, m = 3, 2, 4
    args = [
        np.zeros(T, np.int32), np.arange(T, dtype=np.int32), np.zeros(T, np.int32),
        np.ones(T), np.ones((n, m)), np.ones((n, m)), np.ones(m),
    ]
    with jax.enable_x64(True):
        text = _msr_kernel(edits=True).lower(*args).as_text(debug_info=True)
    assert "jit_msr_edits" in text
    assert '"msr_edits/' in text or "jit(msr_edits)/msr_edits/" in text


@pytest.fixture(scope="module")
def browned_out():
    cluster = paper_cluster((2, 3, 4))
    etg = schedule(linear_topology(), cluster, r0=1.0, rate_epsilon=1.0).etg
    cap = cluster.capacity.copy()
    cap[[0, 3]] *= 0.5
    return etg, cluster.with_capacity(cap)


def _relocate_swap_rows(monkeypatch):
    """Candidates of every ``best_relocate_swap`` call, each checked
    against the relocate+swap menu of the base row it was handed, as is the
    winning edit."""
    seen = []
    best = ScheduleState.best_relocate_swap

    def logged(self, base, *args, **kw):
        before = self.rows_scored
        won = best(self, base, *args, **kw)
        scored = self.rows_scored - before
        comp = np.repeat(np.arange(self.utg.n_components), self.n_instances)
        a, b = np.triu_indices(base.size, 1)
        swaps = np.count_nonzero((comp[a] != comp[b]) & (base[a] != base[b]))
        assert scored == base.size * (self.cluster.n_machines - 1) + swaps
        assert _in_menu(won[0], base, comp, self.cluster.n_machines)
        seen.append(scored)
        return won

    monkeypatch.setattr(ScheduleState, "best_relocate_swap", logged)
    return seen


def _in_menu(edit, base, comp, m):
    """Whether ``(pos_a, val_a, pos_b, val_b)`` is a candidate of the
    relocate+swap menu of ``base``."""
    pa, wa, pb, wb = edit
    if pa == pb:
        return wa == wb and 0 <= wa < m and wa != base[pa]
    return (
        pa < pb and comp[pa] != comp[pb] and base[pa] != base[pb]
        and (wa, wb) == (base[pb], base[pa])
    )


def _counters(rec):
    return {m["name"]: m["value"] for m in rec.metrics.snapshot()}


def test_device_sweeps_score_relocate_swap_as_edits(monkeypatch, browned_out):
    etg, cluster = browned_out
    seen = _relocate_swap_rows(monkeypatch)
    rec = TraceRecorder()
    res = refine(etg, cluster, max_rounds=4, backend="jax", recorder=rec)
    counters = _counters(rec)
    # Growth steps and drops are count edits of a base row on the device.
    assert counters["sweep.edit_rows"] == counters["refine.rows"] == res.candidates
    assert res.candidates > sum(seen) > 0
    edit_sweeps = [d for d in rec.dispatch_log if d.site == "score_relocate_swap"]
    assert len(edit_sweeps) == len(seen)
    assert all(d.backend == "jax" and d.regime == "shared" for d in edit_sweeps)
    same = refine(etg, cluster, max_rounds=4, backend="numpy")
    assert res.moves == same.moves
    assert res.throughput == pytest.approx(same.throughput, rel=1e-12)


@pytest.mark.parametrize("cells", [1 << 22, 64])
def test_device_and_numpy_sweeps_score_one_menu(monkeypatch, browned_out, cells):
    """Both backends give the same candidates in the same order, with the
    same scores to 1e-12, in one sweep or in several."""
    from repro.core import schedule_state

    monkeypatch.setattr(schedule_state, "_EDIT_SWEEP_CELLS", cells)
    etg, cluster = browned_out
    state = ScheduleState.from_etg(etg, cluster)
    base = state.task_machine()
    rec = TraceRecorder()
    with rec.activate():
        edits_np, numpy_ = state.score_relocate_swap(base, "numpy", 16_384)
        edits_jx, jax_ = state.score_relocate_swap(base, "jax", 16_384)
    np.testing.assert_array_equal(edits_np, edits_jx)
    np.testing.assert_allclose(jax_, numpy_, rtol=1e-12, atol=0.0)
    sweeps = [d for d in rec.dispatch_log if d.site == "score_relocate_swap"]
    block = cells // (cluster.n_machines + base.size)
    assert len(sweeps) == 2 * -(-base.size // block) == (2 if cells > 64 else 18)
    np.testing.assert_array_equal(
        numpy_,
        state._score_rows(
            np.concatenate([base[None, :], _rows(base, edits_np)]),
            np.repeat(np.arange(state.utg.n_components), state.n_instances),
            (state.cir_unit / state.n_instances)[
                np.repeat(np.arange(state.utg.n_components), state.n_instances)
            ],
            "numpy",
        )[1][1:],
    )


def test_numpy_sweeps_keep_rows(monkeypatch, browned_out):
    etg, cluster = browned_out
    seen = _relocate_swap_rows(monkeypatch)
    rec = TraceRecorder()
    res = refine(etg, cluster, max_rounds=4, backend="numpy", recorder=rec)
    assert "sweep.edit_rows" not in _counters(rec)
    assert seen and res.moves == refine(
        etg, cluster, max_rounds=4, engine="reference"
    ).moves


def test_network_clusters_keep_rows_on_the_device(monkeypatch, browned_out):
    """Device sweeps of a network cluster score relocate+swap as edits too,
    on ``msr_edits_resources``: no row is built, and every candidate's cut
    traffic is computed on the device."""
    from repro.core import rack_distance_matrix, schedule_state

    etg, cluster = browned_out
    racks = np.arange(cluster.n_machines) % 2
    net = cluster.with_resources(distance=rack_distance_matrix(racks), net_penalty=0.05)
    seen = _relocate_swap_rows(monkeypatch)
    monkeypatch.setattr(
        schedule_state, "_edited_rows", lambda *a: pytest.fail("rows built")
    )
    rec = TraceRecorder()
    res = refine(etg, net, max_rounds=2, backend="jax", recorder=rec)
    counters = _counters(rec)
    assert seen and counters["sweep.edit_rows"] == res.candidates > sum(seen)
    assert counters["refine.rows"] == counters["sweep.net_rows"] == res.candidates
    edit_sweeps = [d for d in rec.dispatch_log if d.site == "score_relocate_swap"]
    assert len(edit_sweeps) == len(seen)


@pytest.mark.parametrize("cells", [1 << 22, 64])
def test_device_winner_is_the_first_maximum_of_the_menu(monkeypatch, browned_out, cells):
    """``best_relocate_swap`` on the device against ``np.argmax`` over
    ``score_relocate_swap``'s device scores: the same edit and the same
    float, in one sweep or in several, with no candidate built and 32 bytes
    fetched a sweep. The menu's maximum is an exact tie of relocations and
    swaps, and a tied swap moves an earlier task than the first tied
    relocation (another sweep, when there are several): the first in menu
    order, a relocation, wins."""
    from repro.core import schedule_state

    monkeypatch.setattr(schedule_state, "_EDIT_SWEEP_CELLS", cells)
    etg, cluster = browned_out
    state = ScheduleState.from_etg(etg, cluster)
    base = state.task_machine()
    edits, thpt = state.score_relocate_swap(base, "jax", 16_384)
    i = int(np.argmax(thpt))
    tied = np.flatnonzero(thpt == thpt[i])
    swaps = tied[edits[0, tied] != edits[2, tied]]
    assert edits[0, i] == edits[2, i] and swaps.size and edits[0, swaps].min() < edits[0, i]
    rec = TraceRecorder()
    with monkeypatch.context() as mp, rec.activate():
        mp.setattr(schedule_state, "_edited_rows", lambda *a: pytest.fail("rows built"))
        won = state.best_relocate_swap(base, "jax", 16_384)
    assert won == (tuple(int(x) for x in edits[:, i]), float(thpt[i]))
    sweeps = [d for d in rec.dispatch_log if d.site == "score_relocate_swap"]
    assert len(sweeps) == (1 if cells > 64 else 9)
    assert all(d.backend == "jax" and d.regime == "shared" for d in sweeps)
    counters = _counters(rec)
    assert counters["sweep.d2h_bytes"] == 32 * len(sweeps)
    assert counters["sweep.edit_rows"] == counters["refine.rows"] == thpt.size
    on_host = state.best_relocate_swap(base, "numpy", 16_384)
    assert on_host[0] == won[0] and on_host[1] == pytest.approx(won[1], rel=1e-12)


def test_device_winner_of_a_menu_without_swaps(browned_out):
    etg, cluster = browned_out
    one = dataclasses.replace(etg, assignment=[np.zeros_like(a) for a in etg.assignment])
    state = ScheduleState.from_etg(one, cluster)
    base = state.task_machine()
    edits, thpt = state.score_relocate_swap(base, "jax", 16_384)
    assert thpt.size == base.size * (cluster.n_machines - 1)
    i = int(np.argmax(thpt))
    won = state.best_relocate_swap(base, "jax", 16_384)
    assert won == (tuple(int(x) for x in edits[:, i]), float(thpt[i]))


def test_refine_makes_the_same_moves_in_several_device_sweeps(monkeypatch, browned_out):
    from repro.core import schedule_state

    monkeypatch.setattr(schedule_state, "_EDIT_SWEEP_CELLS", 64)
    etg, cluster = browned_out
    res = refine(etg, cluster, max_rounds=4, backend="jax")
    same = refine(etg, cluster, max_rounds=4, backend="numpy")
    assert res.moves and res.moves == same.moves
    assert res.throughput == pytest.approx(same.throughput, rel=1e-12)
    assert res.candidates == same.candidates
