"""The ``d2h_bytes_per_decision`` reader on the CPU: what it reads from the
program's per-decision summaries in a traced run of a tiny cell."""

from __future__ import annotations

import pytest

from tiny import run_main, tiny_cell

import cells

REPLAN = "linear_10x10x10.replan"


def test_reads_zero_when_every_sweep_scores_on_the_host(monkeypatch):
    line, _ = run_main(monkeypatch, REPLAN, trace=1)
    assert line["metrics"]["d2h_bytes_per_decision"] == {"value": 0.0, "unit": "bytes"}


def test_reads_the_winners_not_the_grids(monkeypatch):
    from repro.core import simulator
    from repro.obs.trace import recent

    resolve = simulator.resolve_closed_form_backend

    def edits_on_jax(backend, elements=None, regime="shared", n_machines=None, site=None):
        got = resolve(backend, elements, regime, n_machines, site)
        return "jax" if site == "score_relocate_swap" else got

    monkeypatch.setattr(simulator, "resolve_closed_form_backend", edits_on_jax)
    line, _ = run_main(monkeypatch, REPLAN, trace=1)
    value = line["metrics"]["d2h_bytes_per_decision"]["value"]
    held = [s for s in recent() if s["name"] == "refine"][-line["attempted"]:]
    fetched = sum(s["counters"]["sweep.d2h_bytes"] for s in held)
    assert value == pytest.approx(fetched / len(held), rel=1e-12)
    # Four float64s a sweep, one sweep a round: at most max_rounds sweeps a
    # decision, where one sweep's grids alone are 8·T·(m + T) bytes.
    cell = tiny_cell(REPLAN)
    machines = sum(cell.config["cluster"]["counts"])
    tasks = sum(len(a) for a in cell.config["deployed"])
    rounds = cell.config["replan"]["max_rounds"]
    assert fetched % 32 == 0
    assert 0 < value <= 32 * rounds < 8 * tasks * (machines + tasks)


def test_reads_nothing_without_its_source(monkeypatch):
    from repro.core.schedule_state import ScheduleState

    reader = cells.load_module("metrics", "d2h_bytes_per_decision")
    assert reader.read({"decisions": 0}) is None
    monkeypatch.delattr(ScheduleState, "best_relocate_swap")
    assert reader.read({"decisions": 5}) is None
