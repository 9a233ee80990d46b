"""The rack-aware deployment's reference, its control and the faults it
must catch, on a 2/3/4 cluster in 2 racks, and its menu against
``refine_racks_ref``'s on the blind 20/70/90 placement.

``refine_racks_classes_ref.ClassMenu`` scores each distinct count matrix
of the relocation and swap menu once; every candidate must get exactly
the score ``refine_racks_ref.RackMenu`` gives it. Each planted fault
breaks the program underneath a whole run of the cell's kind
(``tiny.run_main``) and must turn ``correct`` false: the cut-traffic term
dropped, memory ignored, and only the two machines a relocation or swap
touches rescored.
"""

from __future__ import annotations

import contextlib
import io
import json

import numpy as np
import pytest

import test_reference_racks as racks_tests
import tiny
from tiny import run_main, tiny_cell

import cells
import refine_racks_classes_ref
import refine_racks_ref
from closed_form_racks import RackScorer, rack_arrays
from system import cluster_arrays

AWARE = "racks_aware_20x70x90.replan"


def _cell():
    """The tiny cell in 2 racks (5 and 4 machines), with memory for 4 tasks
    a machine, so that it binds."""
    cell = tiny_cell(AWARE)
    cell.config["racks"]["machines_per_rack"] = 5
    cell.config["memory"]["machine_mb"] = 512.0
    return cell


def _run(monkeypatch, trace=0):
    cell = _cell()
    monkeypatch.setattr(tiny, "tiny_cell", lambda name: cell)
    return run_main(monkeypatch, AWARE, trace=trace)


def _workload():
    cell = _cell()
    kind = cells.load_module("kinds", cell.traffic["kind"])
    return cell, kind, kind.Workload(cell.config, cell.traffic)


def test_class_menu_scores_every_candidate_as_the_rack_menu():
    cell, kind, wl = _workload()
    for request in kind.requests(cell.config, cell.traffic, 0):
        sc = wl.scorer(request)
        # The deployed placement and the states of the reference's climb.
        states = [wl.deployed]
        asg = wl.deployed
        for _ in range(3):
            menu = refine_racks_ref.RackMenu(sc, asg)
            asg = menu.pick(int(np.argmax(menu.scores())))[1]
            states.append(asg)
        for asg in states:
            got = refine_racks_classes_ref.ClassMenu(sc, asg)
            want = refine_racks_ref.RackMenu(sc, asg)
            np.testing.assert_array_equal(got.edit_scores, want.edit_scores)
            np.testing.assert_array_equal(got.scores(), want.scores())
            assert got.reloc[0].size and got.swap[0].size


def test_class_menu_scores_the_blind_deployment_as_the_rack_menu():
    """One menu of ``racks_20x70x90``'s deployed placement: 154,056
    candidates, each with ``RackMenu``'s score bit for bit."""
    config = cells.find_cell(cells.load_spec(), "racks_20x70x90.replan").config
    sc = RackScorer(config["topology"], cluster_arrays(config["cluster"]), rack_arrays(config))
    got = refine_racks_classes_ref.ClassMenu(sc, config["deployed"])
    want = refine_racks_ref.RackMenu(sc, config["deployed"])
    assert got.edit_scores.size == 154_056
    np.testing.assert_array_equal(got.edit_scores, want.edit_scores)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_aware_answers_pass_and_the_float32_control_fails(seed):
    cell, kind, wl = _workload()
    limits = wl.limits
    requests = kind.requests(cell.config, cell.traffic, seed)
    over = lambda numbers: [k for k in limits if numbers[k] > limits[k]]  # noqa: E731
    for request in requests:
        assert not over(wl.check(request, wl.decide(request)[0]))
    assert any(over(wl.check(r, wl.control(r, np.float32))) for r in requests)


def test_control_script_fails_the_limits(monkeypatch):
    import control

    cell = _cell()
    monkeypatch.setattr(cells, "find_cell", lambda *a, **k: cell)
    out = io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(io.StringIO()):
        assert control.main(["--workload", AWARE, "--pool-seeds", "1000", "1001"]) == 0
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert any(last["control"][k] > v for k, v in last["limits"].items())


def test_sound_run_is_correct(monkeypatch):
    line, err = _run(monkeypatch)
    assert line["correct"] is True
    assert "reference judged request" in err


# ------------------------------------------------------------- faults


@pytest.mark.parametrize(
    "fault",
    ["cut_traffic_dropped", "memory_ignored", "only_touched_machines_rescored"],
)
def test_fault_turns_correct_false(monkeypatch, fault):
    """The racks cell's planted fault, run as a whole run of this cell's
    kind: the racks test's runs go through this file's ``_run``."""
    monkeypatch.setattr(racks_tests, "_run", _run)
    getattr(racks_tests, f"test_fault_{fault}")(monkeypatch)
