"""Hypothesis property tests on the scheduling system's invariants."""

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed (see requirements-dev.txt)")
from hypothesis import given, settings
from hypothesis import strategies as st

from sched_strategies import PROFILE, random_cluster, random_dag

from repro.core import (
    component_rates,
    first_assignment,
    max_stable_rate,
    paper_cluster,
    predict,
    schedule,
    simulate,
)


@given(random_dag(), st.floats(0.5, 50.0))
@settings(max_examples=40, deadline=None)
def test_rate_propagation_is_linear(topo, r0):
    """CIR(k*r) == k*CIR(r): eq. 6 is homogeneous of degree 1."""
    c1 = component_rates(topo, r0)
    c2 = component_rates(topo, 2 * r0)
    assert np.allclose(c2, 2 * c1, rtol=1e-9)


@given(random_dag(), random_cluster())
@settings(max_examples=30, deadline=None)
def test_schedule_invariants(topo, cluster):
    sched = schedule(topo, cluster, r0=1.0, rate_epsilon=1.0)
    # 1) every component keeps >= 1 instance (paper constraint)
    assert np.all(sched.etg.n_instances >= 1)
    # 2) all assignments land on real machines
    assert np.all(sched.etg.task_machine() < cluster.n_machines)
    assert np.all(sched.etg.task_machine() >= 0)
    # 3) the returned state is feasible: no machine over-utilized (MAC >= 0)
    if sched.rate > 0:
        assert predict(sched.etg, cluster, sched.rate).feasible


@given(random_dag(), random_cluster())
@settings(max_examples=30, deadline=None)
def test_stable_rate_is_simulator_fixed_point(topo, cluster):
    """At (just under) the closed-form max stable rate the simulator applies
    no throttling; prediction and simulation agree."""
    etg = first_assignment(topo, cluster, 1.0)
    rate, thpt = max_stable_rate(etg, cluster)
    if rate <= 0:
        return
    sim = simulate(etg, cluster, rate * 0.99)
    pred = predict(etg, cluster, rate * 0.99)
    assert np.allclose(sim.pr, pred.ir, rtol=1e-5)
    assert sim.throughput <= thpt + 1e-6


@given(random_dag(), random_cluster(), st.floats(1.0, 1e5))
@settings(max_examples=30, deadline=None)
def test_simulator_never_overutilizes(topo, cluster, rate):
    """Proportional throttling keeps every machine at or under capacity."""
    etg = first_assignment(topo, cluster, 1.0)
    sim = simulate(etg, cluster, rate)
    assert np.all(sim.machine_util <= cluster.capacity + 1e-6)
    assert np.all(sim.pr <= sim.ir + 1e-9)  # back-pressure only reduces


@given(random_dag(), random_cluster())
@settings(max_examples=20, deadline=None)
def test_adding_machines_never_hurts(topo, cluster):
    sched1 = schedule(topo, cluster, r0=1.0, rate_epsilon=1.0)
    bigger = paper_cluster((2, 2, 2), PROFILE)
    if bigger.n_machines <= cluster.n_machines:
        return
    sched2 = schedule(topo, bigger, r0=1.0, rate_epsilon=1.0)
    if cluster.n_machines < 6:
        assert sched2.predicted_throughput >= 0.7 * sched1.predicted_throughput
