"""Parity suite for the scatter-free closed-form scoring kernels.

The XLA one-hot contraction (``sim_jax._msr_kernel``) against the NumPy
reference (``cost_model.closed_form_rates`` — sequential ``np.add.at``, the
bit-exact oracle), across all three scoring regimes — shared (T,) maps,
per-row (B, T) maps, and skew rows — plus the dispatch table's
regime/machine-gate semantics. Each resource row kernel
(``msr_resources_shared``, ``msr_resources_per_row``) fed the neutral tail
(no memory demand, unbounded memory, no network component) is bit-identical
to its plain twin (``msr_shared``, ``msr_per_row``) in every regime.

When hypothesis is installed (CI dev image), a property section fuzzes
shapes/values; the deterministic seed sweep below keeps kernel coverage in
environments without it.
"""

from fractions import Fraction

import numpy as np
import pytest

from repro.core import (
    keyed_rolling_count_topology,
    max_stable_rate_batch,
    paper_cluster,
    schedule,
)
from repro.core.cost_model import closed_form_rates
from repro.core.schedule_state import ScheduleState

jax = pytest.importorskip("jax")

from repro.core.sim_jax import closed_form_rates_jax  # noqa: E402
from neutral_tables import neutral_tail  # noqa: E402

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False


def _random_problem(seed, B, T, m, n, infeasible_rows=0):
    """Random scoring instance + the NumPy reference result."""
    rng = np.random.default_rng(seed)
    tm = rng.integers(0, m, size=(B, T))
    comp = np.sort(rng.integers(0, n, size=T))
    unit_ir = rng.uniform(0.05, 1.5, size=T)
    e_cm = rng.uniform(0.3, 3.0, size=(n, m))
    met_cm = rng.uniform(0.0, 0.4, size=(n, m))
    cap = rng.uniform(2.0, 12.0, size=m)
    if infeasible_rows and B:
        # Saturate a machine's base load on some rows so the feasibility
        # mask (rate == 0) is exercised, not just the happy path.
        met_cm = met_cm.copy()
        hot = rng.integers(0, B, size=infeasible_rows)
        tm[hot, :] = 0
        met_cm[:, 0] = cap[0]
    e = e_cm[comp[None, :], tm]
    met = met_cm[comp[None, :], tm]
    ref = closed_form_rates(tm, e, met, unit_ir, cap)
    return tm, comp, unit_ir, e_cm, met_cm, cap, ref


def _exact_throughput(tm, e, met, unit_ir, cap):
    """Row throughputs of ``closed_form_rates`` in exact rational arithmetic
    (every float operand is converted exactly, nothing rounds)."""
    out = []
    for b in range(tm.shape[0]):
        u = [Fraction(x) for x in (unit_ir[b] if unit_ir.ndim == 2 else unit_ir)]
        var, load = {}, {}
        for t, w in enumerate(tm[b]):
            var[w] = var.get(w, 0) + Fraction(e[b, t]) * u[t]
            load[w] = load.get(w, 0) + Fraction(met[b, t])
        head = {w: Fraction(cap[w]) - load[w] for w in load}
        if any(h < 0 for h in head.values()):
            out.append(Fraction(0))
            continue
        rate = min(head[w] / var[w] for w in var if var[w] > 0)
        out.append(max(rate, Fraction(0)) * sum(u))
    return out


def _assert_parity(got, ref, exact=None):
    """``got`` matches ``ref`` to 1e-12 with the same feasibility mask and
    the same best row. A different best row is accepted only when it ties
    the reference's pick exactly: bit for bit in ``ref``, or, when the
    exact row throughputs are given, in exact arithmetic (rows that are the
    same value in real numbers but round differently, such as every row of
    a one-machine, one-component problem with per-row instance rates)."""
    r_ref, t_ref = ref
    r_got, t_got = got
    np.testing.assert_allclose(r_got, r_ref, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(t_got, t_ref, rtol=1e-12, atol=1e-12)
    assert np.array_equal(r_got == 0.0, r_ref == 0.0)
    if r_ref.size:
        pick, want = int(np.argmax(t_got)), int(np.argmax(t_ref))
        if exact is None:
            assert t_ref[pick] == t_ref[want]
        else:
            assert exact[pick] == exact[want]


SHAPES = [
    (0, 7, 3, 4),        # empty batch
    (1, 5, 1, 3),        # single machine, single row
    (17, 14, 3, 6),      # small-cluster refine sweep shape
    (64, 15, 6, 7),      # medium cluster
    (33, 54, 15, 7),     # large realistic cluster (4,5,6)
    (9, 130, 16, 5),     # T above 128 lanes
]


@pytest.mark.parametrize("B,T,m,n", SHAPES)
@pytest.mark.parametrize("seed", [0, 1])
def test_contraction_parity_shared(B, T, m, n, seed):
    tm, comp, unit_ir, e_cm, met_cm, cap, ref = _random_problem(
        seed, B, T, m, n, infeasible_rows=min(B, 3)
    )
    got = closed_form_rates_jax(tm, comp, unit_ir, e_cm, met_cm, cap)
    _assert_parity(got, ref)


def _assert_neutral_identity(tm, comp, unit_ir, e_cm, met_cm, cap):
    """The resource row kernel on the neutral tail gives the plain row
    kernel's rates and throughputs bit for bit."""
    plain = closed_form_rates_jax(tm, comp, unit_ir, e_cm, met_cm, cap)
    neutral = closed_form_rates_jax(
        tm, comp, unit_ir, e_cm, met_cm, cap,
        neutral_tail(e_cm.shape[0], cap.size),
    )
    for got, want in zip(neutral, plain):
        assert np.array_equal(got, want)


@pytest.mark.parametrize("B,T,m,n", SHAPES)
@pytest.mark.parametrize("seed", [0, 1])
def test_resource_row_kernel_on_neutral_tables_is_the_plain_one(B, T, m, n, seed):
    tm, comp, unit_ir, e_cm, met_cm, cap, _ = _random_problem(
        seed, B, T, m, n, infeasible_rows=min(B, 3)
    )
    _assert_neutral_identity(tm, comp, unit_ir, e_cm, met_cm, cap)


@pytest.mark.parametrize("B,T,m,n", [(21, 14, 3, 6), (13, 54, 15, 7)])
def test_per_row_parity(B, T, m, n):
    rng = np.random.default_rng(7)
    tm, comp1, _, e_cm, met_cm, cap, _ = _random_problem(7, B, T, m, n)
    comp = np.broadcast_to(comp1, (B, T)).copy()
    unit_ir = rng.uniform(0.05, 1.5, size=(B, T))
    e = e_cm[comp, tm]
    met = met_cm[comp, tm]
    ref = closed_form_rates(tm, e, met, unit_ir, cap)
    _assert_parity(
        closed_form_rates_jax(tm, comp, unit_ir, e_cm, met_cm, cap), ref
    )


@pytest.mark.parametrize("B,T,m,n", [(21, 14, 3, 6), (13, 54, 15, 7)])
def test_resource_per_row_kernel_on_neutral_tables_is_the_plain_one(B, T, m, n):
    rng = np.random.default_rng(7)
    tm, comp1, _, e_cm, met_cm, cap, _ = _random_problem(7, B, T, m, n)
    comp = np.broadcast_to(comp1, (B, T)).copy()
    unit_ir = rng.uniform(0.05, 1.5, size=(B, T))
    _assert_neutral_identity(tm, comp, unit_ir, e_cm, met_cm, cap)


# ------------------------------------------------------------- skew rows


@pytest.fixture(scope="module")
def skew_state():
    from repro.runtime_stream import StreamExecutor, TraceSpec

    cluster = paper_cluster((2, 2, 2))
    utg = keyed_rolling_count_topology(n_keys=16, zipf_s=1.5)
    etg = schedule(utg, cluster, r0=1.0, rate_epsilon=0.05).etg
    probe = StreamExecutor(
        etg, cluster, TraceSpec(name="probe", n_windows=2, base_rate=1.0),
        seed=5,
    )
    skew = probe.skew_model_at(0)
    assert skew is not None
    return ScheduleState.from_etg(etg, cluster, skew=skew), etg, cluster, skew


def test_skew_shared_jax_matches_numpy(skew_state):
    state, etg, cluster, skew = skew_state
    rng = np.random.default_rng(11)
    T = int(state.n_instances.sum())
    tm = rng.integers(0, cluster.n_machines, size=(40, T))
    ref = state.score_task_machine_batch(tm, backend="numpy")
    got = state.score_task_machine_batch(tm, backend="jax")
    _assert_parity(got, ref)
    # Same parity through the batch-scoring module entry point.
    _assert_parity(
        max_stable_rate_batch(etg, cluster, tm, backend="jax", skew=skew),
        max_stable_rate_batch(etg, cluster, tm, backend="numpy", skew=skew),
    )


def test_skew_per_row_jax_matches_numpy(skew_state):
    state, etg, cluster, skew = skew_state
    rng = np.random.default_rng(13)
    B = 24
    n_inst = np.tile(state.n_instances, (B, 1))
    T = int(state.n_instances.sum())
    tm = rng.integers(0, cluster.n_machines, size=(B, T))
    ref = state.score_task_machine_batch(tm, n_instances=n_inst, backend="numpy")
    got = state.score_task_machine_batch(tm, n_instances=n_inst, backend="jax")
    _assert_parity(got, ref)
    _assert_parity(
        max_stable_rate_batch(
            etg, cluster, tm, backend="jax", n_instances=n_inst, skew=skew
        ),
        max_stable_rate_batch(
            etg, cluster, tm, backend="numpy", n_instances=n_inst, skew=skew
        ),
    )


@pytest.mark.parametrize("per_row", [False, True], ids=["shared", "per_row"])
def test_resource_kernels_on_neutral_tables_score_skew_rows_as_plain(
    skew_state, per_row
):
    state, _, cluster, _ = skew_state
    rng = np.random.default_rng(17)
    B, T = 16, int(state.n_instances.sum())
    tm = rng.integers(0, cluster.n_machines, size=(B, T))
    n_inst = np.tile(state.n_instances, (B, 1)) if per_row else state.n_instances
    comp, unit_ir, _ = state._task_maps(n_inst, B, T)
    assert comp.ndim == (2 if per_row else 1)
    _assert_neutral_identity(
        tm, comp, unit_ir, state.e_cm, state.met_cm, cluster.capacity
    )


# ------------------------------------------------- dispatch regime/gating


def test_auto_dispatch_regimes_and_machine_gate(monkeypatch):
    from repro.core.simulator import (
        _AUTO_MAX_MACHINES,
        _AUTO_MAX_WORK,
        _CLOSED_FORM_AUTO_THRESHOLDS,
        _jax_accelerator_available,
        resolve_closed_form_backend,
    )

    for var in (
        "REPRO_CLOSED_FORM_JAX_THRESHOLD",
        "REPRO_CLOSED_FORM_JAX_THRESHOLD_SHARED",
        "REPRO_CLOSED_FORM_JAX_THRESHOLD_PER_ROW",
        "REPRO_CLOSED_FORM_JAX_THRESHOLD_SKEW",
    ):
        monkeypatch.delenv(var, raising=False)
    if _jax_accelerator_available():
        pytest.skip("machine gate only applies on CPU backends")
    for regime, floor in _CLOSED_FORM_AUTO_THRESHOLDS.items():
        floor = int(floor)
        # Below the regime floor: NumPy. At it, narrow cluster: JAX.
        assert resolve_closed_form_backend(
            "auto", floor - 1, regime=regime, n_machines=3
        ) == "numpy"
        assert resolve_closed_form_backend(
            "auto", floor, regime=regime, n_machines=3
        ) == "jax"
        # Wide clusters stay NumPy regardless of size (contraction is
        # B*T*m); unknown width skips the gate.
        assert resolve_closed_form_backend(
            "auto", 10 * floor, regime=regime,
            n_machines=_AUTO_MAX_MACHINES + 1,
        ) == "numpy"
        assert resolve_closed_form_backend(
            "auto", floor, regime=regime, n_machines=None
        ) == "jax"
        # Out-of-cache sweeps stay NumPy even on mid-width clusters: the
        # work ceiling caps elements * machines.
        over_work = _AUTO_MAX_WORK // 15 + 1
        if over_work >= floor:
            assert resolve_closed_form_backend(
                "auto", over_work, regime=regime, n_machines=15
            ) == "numpy"
        assert resolve_closed_form_backend(
            "auto", _AUTO_MAX_WORK // 15, regime=regime, n_machines=15
        ) == "jax"
    with pytest.raises(ValueError, match="regime"):
        resolve_closed_form_backend("auto", 10, regime="banana")


def test_regime_env_override_bypasses_gate(monkeypatch):
    from repro.core.simulator import resolve_closed_form_backend

    monkeypatch.delenv("REPRO_CLOSED_FORM_JAX_THRESHOLD", raising=False)
    monkeypatch.setenv("REPRO_CLOSED_FORM_JAX_THRESHOLD_SKEW", "50")
    # The skew-specific floor applies to skew rows only — and bypasses the
    # machine gate (the override is the explicit recalibration escape).
    assert resolve_closed_form_backend(
        "auto", 50, regime="skew", n_machines=500
    ) == "jax"
    assert resolve_closed_form_backend(
        "auto", 49, regime="skew", n_machines=3
    ) == "numpy"
    assert resolve_closed_form_backend(
        "auto", 50, regime="shared", n_machines=3
    ) == "numpy"
    # The regime-specific variable wins over the all-regime one.
    monkeypatch.setenv("REPRO_CLOSED_FORM_JAX_THRESHOLD", "10")
    assert resolve_closed_form_backend(
        "auto", 49, regime="skew", n_machines=3
    ) == "numpy"
    assert resolve_closed_form_backend(
        "auto", 10, regime="shared", n_machines=500
    ) == "jax"


# ------------------------------------------------------------ hypothesis

if HAS_HYPOTHESIS:

    @settings(max_examples=40, deadline=None)
    @given(
        seed=st.integers(0, 2**32 - 1),
        B=st.integers(0, 48),
        T=st.integers(1, 80),
        m=st.integers(1, 20),
        n=st.integers(1, 8),
    )
    def test_fuzz_parity_shared(seed, B, T, m, n):
        tm, comp, unit_ir, e_cm, met_cm, cap, ref = _random_problem(
            seed, B, T, m, n, infeasible_rows=min(B, 2)
        )
        got = closed_form_rates_jax(tm, comp, unit_ir, e_cm, met_cm, cap)
        exact = _exact_throughput(
            tm, e_cm[comp[None, :], tm], met_cm[comp[None, :], tm], unit_ir, cap
        )
        _assert_parity(got, ref, exact)

    @settings(max_examples=20, deadline=None)
    @given(
        seed=st.integers(0, 2**32 - 1),
        B=st.integers(1, 32),
        T=st.integers(1, 40),
        m=st.integers(1, 12),
        n=st.integers(1, 6),
    )
    def test_fuzz_parity_per_row(seed, B, T, m, n):
        rng = np.random.default_rng(seed)
        tm, comp1, _, e_cm, met_cm, cap, _ = _random_problem(seed, B, T, m, n)
        comp = np.broadcast_to(comp1, (B, T)).copy()
        unit_ir = rng.uniform(0.05, 1.5, size=(B, T))
        e, met = e_cm[comp, tm], met_cm[comp, tm]
        ref = closed_form_rates(tm, e, met, unit_ir, cap)
        exact = _exact_throughput(tm, e, met, unit_ir, cap)
        _assert_parity(
            closed_form_rates_jax(tm, comp, unit_ir, e_cm, met_cm, cap),
            ref,
            exact,
        )
