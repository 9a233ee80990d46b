"""The plain references, their controls and the faults they must catch,
at a size a CPU test run holds.

The control of each cell is the reference climb put in the program's
place in the precision below float64 (float32), and it must come out not
correct. Each fault breaks the timed path underneath a whole run
(tiny.run_main) and must turn ``correct`` false.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from tiny import run_main, tiny_cell

import cells
from closed_form import Scorer
from system import cluster_arrays, program_cluster, program_placement, program_topology

REPLAN = "linear_20x70x90.replan"
CELLS = [w["name"] for w in cells.load_spec()["workloads"]]


def _kind(cell):
    return cells.load_module("kinds", cell.traffic["kind"])


def _workload(cell):
    return _kind(cell).Workload(cell.config, cell.traffic)


def _over(numbers, limits):
    return [k for k in limits if numbers[k] > limits[k]]


def test_closed_form_matches_the_program():
    from repro.core.cost_model import max_stable_rate

    cell = tiny_cell(REPLAN)
    rng = np.random.default_rng(3)
    topo = program_topology(cell.config["topology"])
    for _ in range(20):
        cap = rng.uniform(20, 100, size=sum(cell.config["cluster"]["counts"]))
        sc = Scorer(cell.config["topology"], cluster_arrays(cell.config["cluster"], cap))
        asg = [list(rng.integers(0, sc.m, size=rng.integers(1, 6))) for _ in range(sc.n)]
        etg = program_placement(topo, asg)
        _, want = max_stable_rate(etg, program_cluster(cell.config["cluster"], cap))
        assert sc.throughput(asg) == pytest.approx(want, rel=1e-13, abs=1e-12)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("name", CELLS)
def test_replan_answers_pass_and_the_float32_control_fails(name, seed):
    cell = tiny_cell(name)
    wl = _workload(cell)
    requests = _kind(cell).requests(cell.config, cell.traffic, seed)
    for request in requests:
        answer, _ = wl.decide(request)
        assert not _over(wl.check(request, answer), wl.limits)
    # The control: at least one number of some request over its limit.
    failed = [_over(wl.check(r, wl.control(r, np.float32)), wl.limits) for r in requests]
    assert any(failed)


# ------------------------------------------------------------- faults


def test_fault_replan_returns_its_state_unchanged(monkeypatch):
    import repro.core

    def unchanged(etg, cluster, max_rounds=200, **kw):
        from repro.core.cost_model import max_stable_rate

        rate, thpt = max_stable_rate(etg, cluster)
        return repro.core.RefineResult(etg=etg.copy(), rate=rate, throughput=thpt, moves=[])

    monkeypatch.setattr(repro.core, "refine", unchanged)
    line, _ = run_main(monkeypatch, REPLAN)
    assert line["correct"] is False
    assert line["checks"]["move_gap"]["value"] > line["checks"]["move_gap"]["limit"]


def test_fault_replan_scores_half_the_batch(monkeypatch):
    from repro.core.schedule_state import ScheduleState

    score = ScheduleState.score_task_machine_batch

    def half(self, tm, *args, **kw):
        rates, thpt = score(self, tm, *args, **kw)
        keep = np.arange(thpt.shape[0]) < (thpt.shape[0] + 1) // 2
        return np.where(keep, rates, 0.0), np.where(keep, thpt, 0.0)

    monkeypatch.setattr(ScheduleState, "score_task_machine_batch", half)
    line, _ = run_main(monkeypatch, REPLAN)
    assert line["correct"] is False


def test_fault_replan_answer_altered(monkeypatch):
    import repro.core

    refine = repro.core.refine

    def altered(*args, **kw):
        res = refine(*args, **kw)
        return dataclasses.replace(res, throughput=res.throughput * (1 + 1e-7))

    monkeypatch.setattr(repro.core, "refine", altered)
    line, _ = run_main(monkeypatch, REPLAN)
    assert line["correct"] is False
    assert line["checks"]["throughput_dev"]["value"] > line["checks"]["throughput_dev"]["limit"]


def test_fault_replan_placement_altered(monkeypatch):
    import repro.core

    refine = repro.core.refine

    def altered(*args, **kw):
        res = refine(*args, **kw)
        etg = res.etg.copy()
        etg.assignment[-1] = etg.assignment[-1].copy()
        etg.assignment[-1][0] = (etg.assignment[-1][0] + 1) % len(args[1].capacity)
        return dataclasses.replace(res, etg=etg)

    monkeypatch.setattr(repro.core, "refine", altered)
    line, _ = run_main(monkeypatch, REPLAN)
    assert line["correct"] is False
    assert line["checks"]["replay_mismatch"]["value"] > 0
