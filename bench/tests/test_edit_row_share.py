"""The ``edit_row_share`` reader on the CPU: what it reads from the
program's per-decision summaries in a traced run of a tiny cell."""

from __future__ import annotations

import pytest

from tiny import run_main

import cells

REPLAN = "linear_10x10x10.replan"


def test_reads_zero_when_no_sweep_scores_edits(monkeypatch):
    line, _ = run_main(monkeypatch, REPLAN, trace=1)
    assert line["metrics"]["edit_row_share"] == {"value": 0.0, "unit": "%"}


def test_reads_the_edit_rows_of_the_window(monkeypatch):
    from repro.core import simulator
    from repro.obs.trace import recent

    resolve = simulator.resolve_closed_form_backend

    def edits_on_jax(backend, elements=None, regime="shared", n_machines=None, site=None):
        got = resolve(backend, elements, regime, n_machines, site)
        return "jax" if site == "score_relocate_swap" else got

    monkeypatch.setattr(simulator, "resolve_closed_form_backend", edits_on_jax)
    line, _ = run_main(monkeypatch, REPLAN, trace=1)
    share = line["metrics"]["edit_row_share"]["value"]
    held = [s for s in recent() if s["name"] == "refine"][-line["attempted"]:]
    edits = sum(s["counters"]["sweep.edit_rows"] for s in held)
    rows = sum(s["counters"]["refine.rows"] for s in held)
    assert 0 < share < 100
    assert share == pytest.approx(100 * edits / rows, rel=1e-12)


def test_reads_nothing_without_the_edit_path(monkeypatch):
    from repro.core.schedule_state import ScheduleState

    reader = cells.load_module("metrics", "edit_row_share")
    assert reader.read({"decisions": 0}) is None
    monkeypatch.delattr(ScheduleState, "score_relocate_swap")
    assert reader.read({"decisions": 5}) is None
