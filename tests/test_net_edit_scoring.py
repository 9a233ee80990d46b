"""Device scoring of clusters with network and memory resources.

``sim_jax``'s ``msr_edits_resources`` kernel (refine's RELOCATE and SWAP
candidates of one base row, each scored on every machine) and the resource
row kernel's device cut-traffic term, each against the NumPy reference:
``cost_model.closed_form_rates`` and ``network_unit_load`` on the same
candidates materialised as rows. Then refine's routing on a rack-aware
cluster with memory: device and NumPy sweeps make the same moves, and the
``sweep.net_rows`` counter and ``net.host`` span appear where the device or
the host priced the cut traffic.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import linear_topology, paper_cluster, rack_distance_matrix, schedule
from repro.core.cost_model import closed_form_rates, network_unit_load
from repro.core.refine import refine
from repro.core.schedule_state import ScheduleState
from repro.core.sim_jax import (
    closed_form_rates_jax,
    edge_counts,
    relocate_swap_scores_jax,
    relocate_swap_winners_jax,
)
from repro.obs import TraceRecorder

from test_edit_scoring import first_maxima

# Machine types and racks of the kernel scenario: machines 1 and 2 are twins
# (one type, one rack), and three racks make moves within and across racks.
MTYPE = np.array([0, 1, 1, 1, 2, 2, 2])
RACK = np.array([0, 0, 0, 1, 1, 2, 2])
EDGES = ((0, 1), (1, 2), (2, 3))
PENALTY = 0.4


def _scenario(seed, tight=True):
    """A 4-component chain on 7 machines in 3 racks, with per-task unit
    rates as a skew model gives them. Machine 0 holds task 0 alone (moving
    it away empties it); machines 1 and 2 hold one instance of each
    component at the same places of their blocks, with equal rates (moves
    onto either tie). ``tight`` leaves machine 0 no spare fixed capacity and
    machine 5 no spare memory, so moves onto them turn rows infeasible."""
    rng = np.random.default_rng(seed)
    n = 4
    counts = rng.integers(3, 5, size=n)
    comp = np.repeat(np.arange(n), counts)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    e_cm = rng.uniform(0.5, 2.0, size=(n, 3))[:, MTYPE]
    met_cm = rng.uniform(0.05, 0.3, size=(n, 3))[:, MTYPE]
    unit_ir = rng.uniform(0.2, 1.0, size=comp.size)
    unit_ir[offsets[:n] + 2] = unit_ir[offsets[:n] + 1]
    base = rng.integers(3, 7, size=comp.size)
    base[0] = 0
    base[offsets[:n] + 1] = 1
    base[offsets[:n] + 2] = 2
    m = MTYPE.size
    met_base = np.bincount(base, met_cm[comp, base], minlength=m)
    cap = met_base + rng.uniform(2.0, 6.0, size=m)
    mem = np.array([1.0, 2.0, 1.5, 0.5])
    mem_base = np.bincount(base, mem[comp], minlength=m)
    mem_cap = mem_base + 2.0
    if tight:
        cap[0] = met_base[0] + 0.01
        mem_cap[5] = mem_base[5]
    cap[2], mem_cap[2] = cap[1], mem_cap[1]
    alpha = rng.uniform(0.5, 1.5, size=n)
    cir_unit = rng.uniform(0.5, 2.0, size=n)
    distance = rack_distance_matrix(RACK, cross_rack=2.5)
    resources = [
        mem, mem_cap, distance, edge_counts(n, EDGES), alpha, cir_unit,
        np.float64(PENALTY),
    ]
    return base, comp, unit_ir, e_cm, met_cm, cap, resources


def _reference(rows, comp, unit_ir, e_cm, met_cm, cap, resources):
    """NumPy throughput of materialised rows, cut traffic on the host."""
    mem, mem_cap, distance, _, alpha, cir_unit, penalty = resources
    net = network_unit_load(
        rows, comp, unit_ir, alpha, cir_unit, EDGES, distance, penalty
    )
    return closed_form_rates(
        rows, e_cm[comp, rows], met_cm[comp, rows], unit_ir, cap,
        net_var=net, mem=mem[comp], mem_capacity=mem_cap,
    )[1]


def _menu(base, m):
    """Edits of every relocation, then of every swap of tasks on different
    machines, each family in task order, and the grid cells they score."""
    moves = np.arange(m)[None, :] != base[:, None]
    pairs = np.triu(base[:, None] != base[None, :], k=1)
    p, w = np.nonzero(moves)
    a, b = np.nonzero(pairs)
    return np.stack([p, w, p, w]), np.stack([a, base[b], b, base[a]]), moves, pairs


def _rows(base, edits):
    tm = np.tile(base, (edits.shape[1], 1))
    r = np.arange(edits.shape[1])
    tm[r, edits[0]] = edits[1]
    tm[r, edits[2]] = edits[3]
    return tm


@pytest.mark.parametrize("tight", [True, False], ids=["tight", "roomy"])
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("family", ["relocate", "swap"])
def test_resource_edit_kernel_agrees_with_the_numpy_rows(seed, family, tight):
    base, comp, unit_ir, e_cm, met_cm, cap, res = _scenario(seed, tight)
    relocate, swap, moves, pairs = _menu(base, cap.size)
    grids = relocate_swap_scores_jax(
        base, np.arange(base.size), comp, unit_ir, e_cm, met_cm, cap, res
    )
    assert grids[0].shape == moves.shape and grids[1].shape == pairs.shape
    edits, got = (relocate, grids[0][moves]) if family == "relocate" else (
        swap, grids[1][pairs]
    )
    want = _reference(_rows(base, edits), comp, unit_ir, e_cm, met_cm, cap, res)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=0.0)
    # Exact ties of the reference stay exact ties.
    assert np.all((got[:, None] == got[None, :])[want[:, None] == want[None, :]])
    src, dst = base[edits[0]], edits[1]
    # Moves within one rack and across racks, and rows that the cut traffic
    # decides: without it, some score differently.
    assert np.any(RACK[src] == RACK[dst]) and np.any(RACK[src] != RACK[dst])
    blind = res[:2] + [res[2] * 0.0] + res[3:]
    assert np.any(
        _reference(_rows(base, edits), comp, unit_ir, e_cm, met_cm, cap, blind) != want
    )
    # Task 0 leaving machine 0 empties it.
    assert np.any((edits[0] == 0) & (got > 0.0))
    if family == "relocate":
        # A task joining machine 0 (no spare fixed capacity) or machine 5
        # (no spare memory) makes the row infeasible.
        for full in (0, 5):
            onto = dst == full
            assert onto.any() and np.all((want[onto] == 0.0) == tight)
            assert np.all((got[onto] == 0.0) == tight)
        # A task from machines 3-6 onto machine 1 or onto its twin 2 ties
        # exactly (the reference's distance matmul may round them 1 ulp
        # apart).
        to_1, to_2 = (dst == 1) & (src > 2), (dst == 2) & (src > 2)
        assert to_1.any() and np.array_equal(edits[0][to_1], edits[0][to_2])
        np.testing.assert_array_equal(got[to_1], got[to_2])
    else:
        # Swaps of adjacent and of non-adjacent components.
        gap = np.abs(comp[edits[0]] - comp[edits[2]])
        assert np.any(gap == 1) and np.any(gap > 1)


def test_resource_edit_kernel_scores_a_block_of_moving_tasks():
    base, comp, unit_ir, e_cm, met_cm, cap, res = _scenario(1)
    full = relocate_swap_scores_jax(
        base, np.arange(base.size), comp, unit_ir, e_cm, met_cm, cap, res
    )
    rows = np.arange(2, 5)
    block = relocate_swap_scores_jax(
        base, rows, comp, unit_ir, e_cm, met_cm, cap, res
    )
    for f, b in zip(full, block):
        np.testing.assert_array_equal(b, f[rows])


@pytest.mark.parametrize("tight", [True, False], ids=["tight", "roomy"])
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("menu", ["all", "block", "no_swap"])
def test_resource_edit_kernel_winners_are_the_first_maxima_of_its_grids(
    seed, tight, menu
):
    base, comp, unit_ir, e_cm, met_cm, cap, res = _scenario(seed, tight)
    moving = np.arange(2, 5) if menu == "block" else np.arange(base.size)
    if menu == "no_swap":
        base = np.zeros_like(base)
    args = (base, moving, comp, unit_ir, e_cm, met_cm, cap, res)
    got = relocate_swap_winners_jax(*args)
    want = first_maxima(relocate_swap_scores_jax(*args), base, moving, comp)
    assert got.dtype == np.float64 and got.tolist() == want
    assert (got[3] == -np.inf) == (menu == "no_swap")


def test_resource_edit_kernel_carries_a_stable_name():
    import jax

    from repro.core.sim_jax import _msr_kernel

    base, comp, unit_ir, e_cm, met_cm, cap, res = _scenario(0)
    args = [base.astype(np.int32), np.arange(3, dtype=np.int32),
            comp.astype(np.int32), unit_ir, e_cm, met_cm, cap, *res]
    with jax.enable_x64(True):
        text = _msr_kernel(edits=True, with_resources=True).lower(*args).as_text(
            debug_info=True
        )
    assert "jit_msr_edits_resources" in text
    assert (
        '"msr_edits_resources/' in text
        or "jit(msr_edits_resources)/msr_edits_resources/" in text
    )


@pytest.mark.parametrize("maps", ["shared", "per_row", "skew"])
def test_device_cut_traffic_agrees_with_network_unit_load(maps):
    """Each machine's cut-traffic load as the device row kernel computes it,
    read from rows whose only binding machine is that one: with no CPU
    load, machine w at capacity 1 limits the rate to 1 / net_w."""
    base, comp, unit_ir, e_cm, met_cm, cap, res = _scenario(2)
    rng = np.random.default_rng(7)
    B, T, m = 6, base.size, cap.size
    rows = rng.integers(0, m, size=(B, T))
    alpha, cir_unit = res[4], res[5]
    if maps == "shared":
        unit_ir = (cir_unit / np.bincount(comp))[comp]
    elif maps == "per_row":
        # Rows with their own instance counts, as growth sweeps have.
        counts = np.stack([rng.multinomial(T - 4, np.ones(4) / 4) + 1 for _ in range(B)])
        comp = np.stack([np.repeat(np.arange(4), c) for c in counts])
        unit_ir = (cir_unit[None, :] / counts)[np.arange(B)[:, None], comp]
    want = network_unit_load(
        rows, comp, unit_ir, alpha, cir_unit, EDGES, res[2], PENALTY
    )
    loaded = want > 0.0
    assert loaded.sum() > B * m // 2
    tm = np.repeat(rows, m, axis=0)
    probe = np.full((B * m, m), 1e30)
    probe[np.arange(B * m), np.tile(np.arange(m), B)] = 1.0
    zero = np.zeros_like(e_cm)
    resources = [np.zeros(4), np.full(m, np.inf), *res[2:]]
    cmap, umap = comp, unit_ir
    if comp.ndim == 2:
        cmap, umap = np.repeat(comp, m, axis=0), np.repeat(unit_ir, m, axis=0)
    rates = closed_form_rates_jax(tm, cmap, umap, zero, zero, probe, resources)[0]
    rates = rates.reshape(B, m)
    np.testing.assert_allclose(1.0 / rates[loaded], want[loaded], rtol=1e-12, atol=0.0)
    # A machine without cut traffic never binds.
    assert np.all(rates[~loaded] > 1e20)


def test_device_memory_mask_agrees_with_the_numpy_rows():
    base, comp, unit_ir, e_cm, met_cm, cap, res = _scenario(0, tight=True)
    rng = np.random.default_rng(11)
    rows = np.concatenate([base[None, :], rng.integers(0, cap.size, size=(40, base.size))])
    want = _reference(rows, comp, unit_ir, e_cm, met_cm, cap, res)
    got = closed_form_rates_jax(rows, comp, unit_ir, e_cm, met_cm, cap, res)[1]
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=0.0)
    # Memory alone turns some rows infeasible.
    roomy = [res[0], np.full(cap.size, np.inf)] + res[2:]
    assert np.any((want == 0.0) & (_reference(rows, comp, unit_ir, e_cm, met_cm, cap, roomy) > 0))


@pytest.fixture(scope="module")
def racks():
    """The browned-out 2/3/4 cluster in 3 racks, with memory that binds."""
    cluster = paper_cluster((2, 3, 4))
    etg = schedule(linear_topology(), cluster, r0=1.0, rate_epsilon=1.0).etg
    cap = cluster.capacity.copy()
    cap[[0, 3]] *= 0.5
    # One unit per task; odd machines have room for one more task.
    machines = np.arange(cluster.n_machines)
    mem_cap = np.bincount(etg.task_machine(), minlength=machines.size) + machines % 2
    cluster = dataclasses.replace(
        cluster.with_capacity(cap).with_resources(
            mem_capacity=mem_cap,
            distance=rack_distance_matrix(machines % 3),
            net_penalty=0.05,
        ),
        profile=cluster.profile.with_mem(np.ones(cluster.profile.n_task_types)),
    )
    assert cluster.has_network and cluster.has_memory
    return etg, cluster


def _counters(rec):
    return {m["name"]: m["value"] for m in rec.metrics.snapshot()}


def test_refine_makes_the_same_moves_on_device_and_numpy(racks):
    etg, cluster = racks
    res = refine(etg, cluster, max_rounds=4, backend="jax")
    same = refine(etg, cluster, max_rounds=4, backend="numpy")
    assert res.moves and res.moves == same.moves
    assert res.throughput == pytest.approx(same.throughput, rel=1e-12)
    assert res.candidates == same.candidates


@pytest.mark.parametrize("cells", [1 << 26, 512])
def test_device_and_numpy_sweeps_score_one_resource_menu(monkeypatch, racks, cells):
    """Both backends give the same candidates in the same order, with the
    same scores to 1e-12, in one sweep or in several."""
    from repro.core import schedule_state

    monkeypatch.setattr(schedule_state, "_NET_EDIT_SWEEP_CELLS", cells)
    etg, cluster = racks
    state = ScheduleState.from_etg(etg, cluster)
    base = state.task_machine()
    rec = TraceRecorder()
    with rec.activate():
        edits_np, numpy_ = state.score_relocate_swap(base, "numpy", 1024)
        edits_jx, jax_ = state.score_relocate_swap(base, "jax", 1024)
    np.testing.assert_array_equal(edits_np, edits_jx)
    np.testing.assert_allclose(jax_, numpy_, rtol=1e-12, atol=0.0)
    assert np.any(numpy_ == 0.0)
    m = cluster.n_machines
    block = max(1, cells // ((m + base.size) * m))
    sweeps = [d for d in rec.dispatch_log if d.site == "score_relocate_swap"]
    assert len(sweeps) == 2 * -(-base.size // block) == (2 if cells > 512 else 18)
    counters = _counters(rec)
    assert counters["sweep.net_rows"] == counters["sweep.edit_rows"] == numpy_.size


@pytest.mark.parametrize("cells", [1 << 26, 512])
def test_device_winner_is_the_first_maximum_of_the_resource_menu(
    monkeypatch, racks, cells
):
    """``best_relocate_swap`` on the device against ``np.argmax`` over
    ``score_relocate_swap``'s device scores, in one sweep or in several:
    the same edit and the same float, 32 bytes fetched a sweep. The menu's
    maximum is an exact tie of swaps of one task: the first wins."""
    from repro.core import schedule_state

    monkeypatch.setattr(schedule_state, "_NET_EDIT_SWEEP_CELLS", cells)
    etg, cluster = racks
    state = ScheduleState.from_etg(etg, cluster)
    base = state.task_machine()
    edits, thpt = state.score_relocate_swap(base, "jax", 1024)
    i = int(np.argmax(thpt))
    assert np.count_nonzero(thpt == thpt[i]) > 1
    rec = TraceRecorder()
    with rec.activate():
        won = state.best_relocate_swap(base, "jax", 1024)
    assert won == (tuple(int(x) for x in edits[:, i]), float(thpt[i]))
    sweeps = [d for d in rec.dispatch_log if d.site == "score_relocate_swap"]
    assert len(sweeps) == (1 if cells > 512 else 9)
    counters = _counters(rec)
    assert counters["sweep.d2h_bytes"] == 32 * len(sweeps)
    assert counters["sweep.net_rows"] == counters["sweep.edit_rows"] == thpt.size
    on_host = state.best_relocate_swap(base, "numpy", 1024)
    assert on_host[0] == won[0] and on_host[1] == pytest.approx(won[1], rel=1e-12)


def test_refine_makes_the_same_moves_in_several_device_sweeps(monkeypatch, racks):
    from repro.core import schedule_state

    monkeypatch.setattr(schedule_state, "_NET_EDIT_SWEEP_CELLS", 512)
    etg, cluster = racks
    res = refine(etg, cluster, max_rounds=4, backend="jax")
    same = refine(etg, cluster, max_rounds=4, backend="numpy")
    assert res.moves and res.moves == same.moves
    assert res.throughput == pytest.approx(same.throughput, rel=1e-12)
    assert res.candidates == same.candidates


def test_net_rows_and_host_spans_follow_the_backend(racks):
    etg, cluster = racks
    runs = {}
    for backend in ("jax", "numpy"):
        rec = TraceRecorder()
        res = refine(etg, cluster, max_rounds=2, backend=backend, recorder=rec)
        runs[backend] = (res, _counters(rec), [r["name"] for r in rec.records])
    res, counters, names = runs["jax"]
    # Every candidate's cut traffic and memory mask on the device; the host
    # prices only the incumbent and the result, outside every sweep.
    assert counters["sweep.net_rows"] == counters["refine.rows"] == res.candidates
    assert names.count("net.host") == 2
    res, counters, names = runs["numpy"]
    assert "sweep.net_rows" not in counters
    sweeps = names.count("refine.sweep")
    assert sweeps > 0 and names.count("net.host") >= sweeps + 2
    rec = TraceRecorder()
    refine(etg, paper_cluster((2, 3, 4)), max_rounds=2, backend="jax", recorder=rec)
    assert "sweep.net_rows" not in _counters(rec)
    assert "net.host" not in [r["name"] for r in rec.records]


@pytest.mark.parametrize("memory", [False, True], ids=["network", "network_memory"])
def test_tenant_batches_price_their_own_cut_traffic_on_the_device(memory):
    """Rows of different tenants in one device sweep: each row's cut traffic
    from its own topology's tables, as the NumPy dispatch prices it per
    tenant block on the host."""
    from test_multitenant_golden import _fleet_plain, _relocation_sweeps

    from repro.multitenant import (
        MultiTenantState,
        TenantBatchScorer,
        TenantSet,
        schedule_tenants,
    )

    base = paper_cluster((2, 2, 2))
    cluster = base.with_resources(
        distance=rack_distance_matrix(np.arange(base.n_machines) % 2), net_penalty=0.05
    )
    if memory:
        cluster = dataclasses.replace(
            cluster.with_resources(mem_capacity=np.full(base.n_machines, 40.0)),
            profile=cluster.profile.with_mem(np.array([1.0, 2.0, 3.0, 4.0])),
        )
    # The fleet's network-blind allocation at a third of its rates, priced
    # on the resource cluster.
    tenants = _fleet_plain()
    ms = schedule_tenants(tenants, base)
    states = [ScheduleState.from_etg(a.etg, cluster) for a in ms.allocations]
    mt = MultiTenantState(TenantSet(tenants), cluster, states, rates=ms.rates / 3)
    sweeps = _relocation_sweeps(mt)
    scored_np = TenantBatchScorer(mt, backend="numpy").score(sweeps)
    rec = TraceRecorder()
    with rec.activate():
        scored_jax = TenantBatchScorer(mt, backend="jax").score(sweeps)
    assert "net.host" not in [r["name"] for r in rec.records]
    assert all(np.any(t > 0.0) for _, t in scored_np)
    for (np_r, np_t), (jx_r, jx_t) in zip(scored_np, scored_jax):
        np.testing.assert_allclose(jx_r, np_r, rtol=1e-12, atol=0.0)
        np.testing.assert_allclose(jx_t, np_t, rtol=1e-12, atol=0.0)
