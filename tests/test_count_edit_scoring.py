"""Growth steps and drops scored as count edits of a base row.

``sim_jax``'s ``msr_count_edits`` and ``msr_count_edits_resources`` kernels
against the row kernels (``msr_per_row``, ``msr_resources_per_row``) and the
NumPy rows on the same candidates materialised, ``msr_count_edits_resources``
fed neutral resource tables against ``msr_count_edits``, then refine's
routing through ``ScheduleState.score_grow_steps`` / ``score_drops``: device sweeps
ship the base rows and tables and count their candidates in
``sweep.edit_rows``; NumPy sweeps and skew rows keep the row path.
"""

import dataclasses
from math import comb

import numpy as np
import pytest

from repro.core import (
    SkewModel,
    linear_topology,
    paper_cluster,
    rack_distance_matrix,
    schedule,
)
from repro.core.refine import refine
from repro.core.schedule_state import ScheduleState
from repro.core.sim_jax import closed_form_rates_jax, count_edit_scores_jax
from repro.obs import TraceRecorder

from neutral_tables import assert_edit_parity, neutral_tail


def _linear():
    """The linear topology's Alg. 1+2 placement on a 2/3/4 cluster, with
    two machines then slowed to half capacity."""
    cluster = paper_cluster((2, 3, 4))
    etg = schedule(linear_topology(), cluster, r0=1.0, rate_epsilon=1.0).etg
    cap = cluster.capacity.copy()
    cap[[0, 3]] *= 0.5
    return etg, cluster.with_capacity(cap)


def _racks():
    """The same in 3 racks, with one memory unit per task and room for one
    more task on odd machines only."""
    etg, cluster = _linear()
    machines = np.arange(cluster.n_machines)
    mem_cap = np.bincount(etg.task_machine(), minlength=machines.size) + machines % 2
    cluster = dataclasses.replace(
        cluster.with_resources(
            mem_capacity=mem_cap,
            distance=rack_distance_matrix(machines % 3),
            net_penalty=0.05,
        ),
        profile=cluster.profile.with_mem(np.ones(cluster.profile.n_task_types)),
    )
    assert cluster.has_network and cluster.has_memory
    return etg, cluster


FIXTURES = {"linear": _linear, "racks": _racks}


@pytest.fixture(scope="module", params=sorted(FIXTURES))
def fixture(request):
    return request.param, FIXTURES[request.param]()


def _chains(state, k, depth, rng):
    """k growth chains ``depth`` steps deep: the state's row with ``depth``
    instances of random components added on random machines, each at the
    end of its block, and a random component to grow next."""
    n, m = state.utg.n_components, state.cluster.n_machines
    rows, counts = [], []
    for _ in range(k):
        row, count = state.task_machine(), state.n_instances.copy()
        for c in rng.integers(0, n, size=depth):
            row = np.insert(row, int(count[: c + 1].sum()), rng.integers(0, m))
            count[c] += 1
        rows.append(row)
        counts.append(count)
    return np.stack(rows), np.stack(counts), rng.integers(0, n, size=k)


def _grow_rows(rows, counts, comps, m):
    """The (k·m, T + 1) rows the grow grid stands for, and their counts."""
    tm, n_rows = [], []
    for row, count, c in zip(rows, counts, comps):
        grown = count.copy()
        grown[c] += 1
        for v in range(m):
            tm.append(np.insert(row, int(count[: c + 1].sum()), v))
            n_rows.append(grown)
    return np.stack(tm), np.stack(n_rows)


def _row_kernel(state, tm, n_rows):
    """The device row kernel (``msr_per_row`` / ``msr_resources_per_row``)
    on materialised rows."""
    comp, unit_ir, _ = state._task_maps(n_rows, *tm.shape)
    return closed_form_rates_jax(
        tm, comp, unit_ir, state.e_cm, state.met_cm, state.cluster.capacity,
        state._device_resources(),
    )[1]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("depth", [0, 1, 2], ids=["k=n", "k=n+C(n,2)", "k=n^2"])
def test_grow_kernel_agrees_with_the_rows(fixture, seed, depth):
    _, (etg, cluster) = fixture
    state = ScheduleState.from_etg(etg, cluster)
    n, m = state.utg.n_components, cluster.n_machines
    k = (n, n + comb(n, 2), n * n)[depth]
    rows, counts, comps = _chains(state, k, depth, np.random.default_rng(seed))
    got = state.score_grow_steps(rows, counts, comps, "jax")
    assert got.shape == (k, m)
    tm, n_rows = _grow_rows(rows, counts, comps, m)
    numpy_ = state.score_task_machine_batch(tm, n_rows, "numpy")[1].reshape(k, m)
    device = _row_kernel(state, tm, n_rows).reshape(k, m)
    np.testing.assert_allclose(got, numpy_, rtol=1e-12, atol=0.0)
    np.testing.assert_allclose(got, device, rtol=1e-12, atol=0.0)
    np.testing.assert_array_equal(got.argmax(axis=1), numpy_.argmax(axis=1))
    np.testing.assert_array_equal(got.argmax(axis=1), device.argmax(axis=1))
    assert np.any(got > 0.0)
    # The NumPy sweep builds exactly those rows.
    np.testing.assert_array_equal(
        state.score_grow_steps(rows, counts, comps, "numpy"), numpy_
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_drop_kernel_agrees_with_the_rows(fixture, seed):
    _, (etg, cluster) = fixture
    state = ScheduleState.from_etg(etg, cluster)
    rng = np.random.default_rng(seed)
    # A base row with three pairs of tasks swapped (each machine keeps its
    # task count, so memory still fits), so drops meet varied machines.
    base = state.task_machine()
    a, b = rng.choice(base.size, size=(2, 3), replace=False)
    base[a], base[b] = base[b], base[a]
    counts = state.n_instances
    got = state.score_drops(base, counts, "jax")
    offsets = np.concatenate([[0], np.cumsum(counts)])
    droppable = np.repeat(counts >= 2, counts)
    assert droppable.any() and not droppable.all()
    assert np.all(np.isnan(got[~droppable]))
    numpy_ = state.score_drops(base, counts, "numpy")
    np.testing.assert_array_equal(np.isnan(numpy_), ~droppable)
    np.testing.assert_allclose(got[droppable], numpy_[droppable], rtol=1e-12, atol=0.0)
    tm, n_rows = [], []
    for p in np.flatnonzero(droppable):
        c = np.searchsorted(offsets, p, side="right") - 1
        count = counts.copy()
        count[c] -= 1
        tm.append(np.delete(base, p))
        n_rows.append(count)
    device = _row_kernel(state, np.stack(tm), np.stack(n_rows))
    np.testing.assert_allclose(got[droppable], device, rtol=1e-12, atol=0.0)
    for c in np.flatnonzero(counts >= 2):
        block = slice(offsets[c], offsets[c + 1])
        assert np.argmax(got[block]) == np.argmax(numpy_[block])
    assert np.any(got[droppable] > 0.0)


def _neutral_pair(state, rows, counts, comps, drop):
    """Throughput grids of ``msr_count_edits_resources`` on the neutral
    tail and of ``msr_count_edits``, on the count edits of ``rows``."""
    new = counts.copy()
    new[np.arange(comps.size), comps] += -1 if drop else 1
    args = (
        rows, counts, state.cir_unit[None, :] / new, comps,
        state.e_cm, state.met_cm, state.cluster.capacity,
    )
    tail = neutral_tail(state.utg.n_components, state.cluster.n_machines)
    return (
        count_edit_scores_jax(*args, tail, drop=drop)[1],
        count_edit_scores_jax(*args, drop=drop)[1],
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("depth", [0, 1, 2], ids=["k=n", "k=n+C(n,2)", "k=n^2"])
def test_resource_grow_kernel_on_neutral_tables_agrees_with_the_plain_one(seed, depth):
    state = ScheduleState.from_etg(*_linear())
    n = state.utg.n_components
    k = (n, n + comb(n, 2), n * n)[depth]
    rows, counts, comps = _chains(state, k, depth, np.random.default_rng(seed))
    neutral, plain = _neutral_pair(state, rows, counts, comps, drop=False)
    assert neutral.shape == plain.shape == (k, state.cluster.n_machines)
    for got, want in zip(neutral, plain):
        assert_edit_parity(got, want)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_resource_drop_kernel_on_neutral_tables_agrees_with_the_plain_one(seed):
    state = ScheduleState.from_etg(*_linear())
    rng = np.random.default_rng(seed)
    base = state.task_machine()
    a, b = rng.choice(base.size, size=(2, 3), replace=False)
    base[a], base[b] = base[b], base[a]
    counts = state.n_instances
    comps = np.flatnonzero(counts >= 2)
    k = comps.size
    neutral, plain = _neutral_pair(
        state, np.tile(base, (k, 1)), np.tile(counts, (k, 1)), comps, drop=True
    )
    assert neutral.shape == plain.shape == (k, base.size)
    # Row i's candidates: the tasks of component comps[i].
    task_comp = np.repeat(np.arange(counts.size), counts)
    for i, c in enumerate(comps):
        cells = task_comp == c
        assert_edit_parity(neutral[i][cells], plain[i][cells])


def _counters(rec):
    return {m["name"]: m["value"] for m in rec.metrics.snapshot()}


@pytest.mark.parametrize("drop", [False, True], ids=["grow", "drop"])
def test_count_edit_sweeps_ship_the_base_rows_and_tables(fixture, drop):
    name, (etg, cluster) = fixture
    state = ScheduleState.from_etg(etg, cluster)
    n, m = state.utg.n_components, cluster.n_machines
    base, counts = state.task_machine(), state.n_instances
    rec = TraceRecorder()
    with rec.activate():
        if drop:
            state.score_drops(base, counts, "jax")
            k, rows = int(np.sum(counts >= 2)), int(counts[counts >= 2].sum())
        else:
            k, rows = n, n * m
            state.score_grow_steps(
                np.tile(base, (k, 1)), np.tile(counts, (k, 1)), np.arange(n), "jax"
            )
    counters = _counters(rec)
    tables = state.e_cm.nbytes + state.met_cm.nbytes + cluster.capacity.nbytes
    resources = state._device_resources()
    if resources is not None:
        tables += sum(np.asarray(x).nbytes for x in resources)
    # int32 rows, counts and components, float64 unit rates per component.
    shipped = 4 * k * (base.size + n + 1) + 8 * k * n
    assert counters["sweep.h2d_bytes"] == shipped + tables
    assert counters["sweep.edit_rows"] == counters["refine.rows"] == rows
    assert counters.get("sweep.net_rows", 0) == (rows if name == "racks" else 0)
    names = [r["name"] for r in rec.records if r.get("type") != "dispatch"]
    assert names == ["refine.sweep", "sweep.put", "sweep.run", "sweep.fetch"]
    (sweep,) = rec.dispatch_log
    width = base.size - 1 if drop else base.size + 1
    assert (sweep.backend, sweep.regime, sweep.elements) == ("jax", "per_row", rows * width)


def _no_count_edits(monkeypatch):
    from repro.core import sim_jax

    monkeypatch.setattr(
        sim_jax, "count_edit_scores_jax", lambda *a, **k: pytest.fail("count edits")
    )


def test_skew_rows_keep_the_row_path(monkeypatch, fixture):
    """Under a skew model each instance keeps its own key share, so the
    counts do not rescale evenly: device sweeps score built rows."""
    _, (etg, cluster) = fixture
    skew = SkewModel(etg.utg, {})
    state = ScheduleState.from_etg(etg, cluster, skew=skew)
    even = ScheduleState.from_etg(etg, cluster)
    base, counts = state.task_machine(), state.n_instances
    want_grow = even.score_grow_steps(base, counts, 1, "jax")
    want_drop = even.score_drops(base, counts, "jax")
    _no_count_edits(monkeypatch)
    rec = TraceRecorder()
    with rec.activate():
        grow = state.score_grow_steps(base, counts, 1, "jax")
        drops = state.score_drops(base, counts, "jax")
    assert [(d.backend, d.regime) for d in rec.dispatch_log] == [("jax", "skew")] * 2
    assert "sweep.edit_rows" not in _counters(rec)
    # A skew model without keyed components is the even split.
    np.testing.assert_allclose(grow, want_grow, rtol=1e-12, atol=0.0)
    np.testing.assert_allclose(drops, want_drop, rtol=1e-12, atol=0.0)


def test_numpy_sweeps_keep_the_row_path(monkeypatch, fixture):
    _, (etg, cluster) = fixture
    state = ScheduleState.from_etg(etg, cluster)
    _no_count_edits(monkeypatch)
    rec = TraceRecorder()
    res = refine(etg, cluster, max_rounds=2, backend="numpy", recorder=rec)
    assert "sweep.edit_rows" not in _counters(rec)
    assert res.moves == refine(etg, cluster, max_rounds=2, engine="reference").moves
    # Auto resolves the fixture's small sweeps to NumPy on the CPU.
    rec = TraceRecorder()
    with rec.activate():
        state.score_grow_steps(state.task_machine(), state.n_instances, 0, "auto")
        state.score_drops(state.task_machine(), state.n_instances, "auto")
    assert [(d.backend, d.regime) for d in rec.dispatch_log] == [
        ("numpy", "shared"), ("numpy", "per_row"),
    ]


@pytest.mark.parametrize("lockstep", [True, False], ids=["lockstep", "sequential"])
def test_refine_makes_the_same_moves_on_device_and_numpy(fixture, lockstep):
    _, (etg, cluster) = fixture
    rec = TraceRecorder()
    res = refine(
        etg, cluster, max_rounds=4, backend="jax", lockstep=lockstep, recorder=rec
    )
    same = refine(etg, cluster, max_rounds=4, backend="numpy", lockstep=lockstep)
    assert res.moves and res.moves == same.moves
    assert res.throughput == pytest.approx(same.throughput, rel=1e-12)
    assert res.candidates == same.candidates
    # Every candidate of the climb was scored as an edit of a base row.
    counters = _counters(rec)
    assert counters["sweep.edit_rows"] == counters["refine.rows"] == res.candidates


@pytest.mark.parametrize("resources", [False, True], ids=["linear", "racks"])
def test_count_edit_kernels_carry_a_stable_name(resources):
    import jax

    from repro.core.sim_jax import _msr_kernel

    etg, cluster = (_racks if resources else _linear)()
    state = ScheduleState.from_etg(etg, cluster)
    k, n = 2, state.utg.n_components
    counts = np.tile(state.n_instances, (k, 1)).astype(np.int32)
    args = [
        np.tile(state.task_machine(), (k, 1)).astype(np.int32), counts,
        state.cir_unit / (counts + 1), np.arange(k, dtype=np.int32),
        state.e_cm, state.met_cm, cluster.capacity,
    ]
    if resources:
        args += state._device_resources()
    name = "msr_count_edits" + ("_resources" if resources else "")
    kernel = _msr_kernel(count_edits=True, with_resources=resources)
    for drop in (False, True):
        with jax.enable_x64(True):
            text = kernel.lower(*args, drop=drop).as_text(debug_info=True)
        assert f"jit_{name}" in text
        assert f'"{name}/' in text or f"jit({name})/{name}/" in text
