"""Property suite for the resource-vector & network-aware objective (ISSUE 10).

Four families, each a helper shared between a deterministic seeded sweep
(runs everywhere) and a hypothesis section (CI dev image):

* **neutral bit-identity** — a cluster with an all-zero distance matrix and
  infinite memory capacities exercises every resource code path yet must
  reproduce the scalar-CPU engines bit-for-bit;
* **memory hard mask** — engines never *return* an over-memory placement
  with a positive rate;
* **distance monotonicity** — R* of a fixed placement is non-increasing in
  any distance entry (cut traffic only ever adds CPU load);
* **backend parity** — NumPy vs the XLA contraction agree to 1e-12 with
  identical feasibility masks and argmax across the shared / per-row /
  skew scoring regimes on resource clusters.
"""

import numpy as np
import pytest

from repro.core import (
    Cluster,
    UserGraph,
    max_stable_rate,
    max_stable_rate_batch,
    paper_cluster,
    rack_distance_matrix,
    refine,
    schedule,
)
from repro.core.schedule_state import ScheduleState

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    from sched_strategies import (
        PROFILE,
        random_cluster,
        random_dag,
        random_resource_cluster,
        resource_attachment,
    )

    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

MEM = np.array([1.0, 2.0, 3.0, 4.0])


# ------------------------------------------------------------ check helpers


def _neutral_twin(cluster):
    """Zero-distance / infinite-memory view: resources active, never bind."""
    m = cluster.n_machines
    return Cluster(
        machine_types=cluster.machine_types,
        capacity=cluster.capacity,
        profile=cluster.profile.with_mem(MEM[: cluster.profile.n_task_types]),
        mem_capacity=np.full(m, np.inf),
        distance=np.zeros((m, m)),
        net_penalty=0.9,
    )


def _check_neutral_bit_identity(utg, cluster, seed=0):
    neutral = _neutral_twin(cluster)
    assert neutral.has_resources
    s0 = schedule(utg, cluster, r0=1.0, rate_epsilon=1.0)
    s1 = schedule(utg, neutral, r0=1.0, rate_epsilon=1.0)
    assert s0.rate == s1.rate
    assert np.array_equal(s0.etg.task_machine(), s1.etg.task_machine())
    r0 = refine(s0.etg, cluster, backend="numpy", max_rounds=2)
    r1 = refine(s1.etg, neutral, backend="numpy", max_rounds=2)
    assert float(r0.throughput) == float(r1.throughput)
    assert np.array_equal(r0.etg.task_machine(), r1.etg.task_machine())
    # Batched scoring of random rows is bitwise identical too.
    rng = np.random.default_rng(seed)
    T = int(s0.etg.total_tasks)
    tm = rng.integers(0, cluster.n_machines, size=(16, T))
    base = ScheduleState.from_etg(s0.etg, cluster)
    twin = ScheduleState.from_etg(s0.etg, neutral)
    for a, b in zip(
        base.score_task_machine_batch(tm, backend="numpy"),
        twin.score_task_machine_batch(tm, backend="numpy"),
    ):
        assert np.array_equal(a, b)


def _mem_load(etg, cluster):
    mem_c = cluster.profile.mem[etg.utg.component_types]
    load = np.zeros(cluster.n_machines)
    np.add.at(load, etg.task_machine(), mem_c[etg.task_component()])
    return load


def _check_memory_feasibility(utg, cluster):
    assert cluster.has_memory
    sched = schedule(utg, cluster, r0=1.0, rate_epsilon=1.0)
    if sched.rate > 0.0:
        assert np.all(_mem_load(sched.etg, cluster) <= cluster.mem_capacity)
    res = refine(sched.etg, cluster, backend="numpy", max_rounds=2)
    if float(res.throughput) > 0.0:
        assert np.all(_mem_load(res.etg, cluster) <= cluster.mem_capacity)


def _check_distance_monotone(utg, cluster, i, j, delta):
    assert cluster.has_network
    etg = schedule(utg, cluster, r0=1.0, rate_epsilon=1.0).etg
    before, _ = max_stable_rate(etg, cluster)
    bumped = cluster.distance.copy()
    bumped[i, j] += delta
    bumped[j, i] += delta
    after, _ = max_stable_rate(etg, cluster.with_resources(distance=bumped))
    assert after <= before


def _assert_parity(got, ref):
    r_ref, t_ref = ref
    r_got, t_got = got
    np.testing.assert_allclose(r_got, r_ref, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(t_got, t_ref, rtol=1e-12, atol=1e-12)
    assert np.array_equal(r_got == 0.0, r_ref == 0.0)
    if r_ref.size:
        assert int(np.argmax(t_got)) == int(np.argmax(t_ref))


def _check_backend_parity(utg, cluster, seed=0, per_row=False):
    """NumPy vs the XLA contraction on resource clusters."""
    pytest.importorskip("jax")

    etg = schedule(utg, cluster, r0=1.0, rate_epsilon=1.0).etg
    state = ScheduleState.from_etg(etg, cluster)
    rng = np.random.default_rng(seed)
    T = int(etg.total_tasks)
    tm = rng.integers(0, cluster.n_machines, size=(8, T))
    n_inst = np.tile(etg.n_instances, (tm.shape[0], 1)) if per_row else None
    ref = state.score_task_machine_batch(tm, n_instances=n_inst, backend="numpy")
    got = state.score_task_machine_batch(tm, n_instances=n_inst, backend="jax")
    _assert_parity(got, ref)


def _check_skew_parity(seed=0):
    """Skew regime: keyed rows score the resource objective identically on
    every backend (the kernels are skew-agnostic — only unit rates move)."""
    pytest.importorskip("jax")
    from repro.core import keyed_rolling_count_topology
    from repro.runtime_stream import StreamExecutor, TraceSpec

    cluster = paper_cluster((1, 1, 1)).with_resources(
        distance=rack_distance_matrix(np.array([0, 0, 1])), net_penalty=0.3
    )
    utg = keyed_rolling_count_topology(n_keys=12, zipf_s=1.2)
    etg = schedule(utg, cluster, r0=1.0, rate_epsilon=0.5).etg
    probe = StreamExecutor(
        etg, cluster, TraceSpec(name="probe", n_windows=2, base_rate=1.0),
        seed=seed + 3,
    )
    skew = probe.skew_model_at(0)
    assert skew is not None
    rng = np.random.default_rng(seed)
    T = int(etg.total_tasks)
    tm = rng.integers(0, cluster.n_machines, size=(12, T))
    ref = max_stable_rate_batch(etg, cluster, tm, backend="numpy", skew=skew)
    got = max_stable_rate_batch(etg, cluster, tm, backend="jax", skew=skew)
    _assert_parity(got, ref)


# ------------------------------------------------- deterministic seed sweep


def _pinned_utg(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 6))
    types = np.concatenate([[0], rng.integers(1, 4, size=n - 1)])
    edges = set()
    for j in range(1, n):
        edges.add((int(rng.integers(0, j)), j))
    alpha = np.concatenate([[1.0], rng.uniform(0.5, 3.0, size=n - 1)])
    return UserGraph(
        name=f"pin{seed}",
        component_types=types,
        edges=tuple(sorted(edges)),
        alpha=alpha,
    )


def _pinned_resource_cluster(seed, with_memory=True, with_network=True):
    rng = np.random.default_rng(seed + 100)
    counts = tuple(int(c) for c in rng.integers(0, 3, size=3))
    if sum(counts) == 0:
        counts = (1, 1, 1)
    profile = paper_cluster((1, 1, 1)).profile
    mem_capacity = None
    if with_memory:
        profile = profile.with_mem(MEM)
        m = sum(counts)
        mem_capacity = rng.uniform(float(MEM.max()), 4.0 * float(MEM.sum()), m)
    cluster = paper_cluster(counts, profile)
    distance = None
    pen = 1.0
    if with_network:
        racks = rng.integers(0, 3, size=cluster.n_machines)
        distance = rack_distance_matrix(racks, cross_rack=2.5)
        pen = float(rng.uniform(0.0, 0.5))
    return cluster.with_resources(
        mem_capacity=mem_capacity, distance=distance, net_penalty=pen
    )


@pytest.mark.parametrize("seed", range(4))
def test_neutral_bit_identity_seeded(seed):
    _check_neutral_bit_identity(_pinned_utg(seed), _pinned_resource_cluster(
        seed, with_memory=False, with_network=False
    ).without_network())


@pytest.mark.parametrize("seed", range(4))
def test_memory_feasibility_seeded(seed):
    _check_memory_feasibility(
        _pinned_utg(seed), _pinned_resource_cluster(seed, with_network=False)
    )


@pytest.mark.parametrize("seed", range(4))
def test_distance_monotone_seeded(seed):
    cluster = _pinned_resource_cluster(seed, with_memory=False)
    m = cluster.n_machines
    if m < 2:
        pytest.skip("needs two machines for an off-diagonal entry")
    rng = np.random.default_rng(seed + 7)
    i, j = rng.choice(m, size=2, replace=False)
    _check_distance_monotone(
        _pinned_utg(seed), cluster, int(i), int(j), float(rng.uniform(0.1, 3.0))
    )


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("per_row", [False, True])
def test_backend_parity_seeded(seed, per_row):
    _check_backend_parity(
        _pinned_utg(seed), _pinned_resource_cluster(seed), seed, per_row=per_row
    )


def test_backend_parity_skew_seeded():
    _check_skew_parity(seed=1)


# ------------------------------------------------------------ hypothesis

if HAS_HYPOTHESIS:

    @given(random_dag(), random_cluster())
    @settings(max_examples=15, deadline=None)
    def test_neutral_bit_identity(topo, cluster):
        _check_neutral_bit_identity(topo, cluster)

    @given(random_dag(), random_resource_cluster(with_memory=True))
    @settings(max_examples=15, deadline=None)
    def test_memory_feasible_or_zero(topo, cluster):
        _check_memory_feasibility(topo, cluster)

    @given(
        random_dag(),
        random_resource_cluster(with_network=True),
        st.data(),
    )
    @settings(max_examples=15, deadline=None)
    def test_rstar_monotone_in_distance(topo, cluster, data):
        m = cluster.n_machines
        if m < 2:
            return
        i = data.draw(st.integers(0, m - 1))
        j = data.draw(st.integers(0, m - 1).filter(lambda x: x != i))
        delta = data.draw(st.floats(0.01, 5.0))
        _check_distance_monotone(topo, cluster, i, j, delta)

    @given(
        random_dag(),
        random_resource_cluster(),
        st.integers(0, 2**16),
        st.booleans(),
    )
    @settings(max_examples=10, deadline=None)
    def test_backend_parity(topo, cluster, seed, per_row):
        _check_backend_parity(topo, cluster, seed, per_row=per_row)

    @given(st.integers(0, 2**8))
    @settings(max_examples=5, deadline=None)
    def test_backend_parity_skew(seed):
        _check_skew_parity(seed)
