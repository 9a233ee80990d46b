"""The rack-aware deployment's configuration against the program, and the
counters and readers of its multi-sweep relocate+swap rounds.

The configuration's ``deployed`` is the program's Alg. 1+2 on the
rack-aware cluster, and the program's relocate+swap menu there is 7 device
sweeps of at most 230 moving tasks that count exactly the candidates the
reference's menu builds. With the sweep budget cut down, the counters
``sweep.rs_sweeps`` and ``refine.rs_menus`` count each sweep and each menu,
``sweep.table_h2d_bytes`` each sweep's tables, and the readers
``rs_sweeps_per_round`` and ``table_h2d_bytes_per_decision`` read them.
"""

from __future__ import annotations

import numpy as np
import pytest

import tiny
from tiny import run_main, tiny_cell

import cells
import refine_racks_classes_ref
from closed_form_racks import RackScorer, rack_arrays
from system import cluster_arrays, program_placement, program_topology

AWARE = "racks_aware_20x70x90.replan"


@pytest.fixture(scope="module")
def aware():
    """The cell's configuration, kind and the program's rack-aware cluster."""
    cell = cells.find_cell(cells.load_spec(), AWARE)
    kind = cells.load_module("kinds", cell.traffic["kind"])
    return cell.config, kind.replan_racks.program_cluster(cell.config)


def test_deployed_is_alg_1_2_on_the_rack_aware_cluster(aware):
    from repro.core import schedule

    config, cluster = aware
    etg = schedule(
        program_topology(config["topology"]), cluster, r0=1.0, rate_epsilon=1.0
    ).etg
    assert [a.tolist() for a in etg.assignment] == config["deployed"]
    assert etg.n_instances.tolist() == [14, 110, 440, 870]
    assert config["reduced"] == []
    # Every machine holds 4 to 14 tasks: memory never binds at the start.
    per_machine = np.bincount(etg.task_machine(), minlength=cluster.n_machines)
    assert per_machine.min() == 4 and per_machine.max() == 14
    assert per_machine.max() * 128.0 <= config["memory"]["machine_mb"]


def test_menu_is_seven_sweeps_of_the_reference_candidates(aware):
    from repro.core.schedule_state import ScheduleState

    config, cluster = aware
    etg = program_placement(program_topology(config["topology"]), config["deployed"])
    state = ScheduleState.from_etg(etg, cluster)
    sweeps = state._relocate_swap_sweeps(state.task_machine())
    assert len(sweeps) == 7
    assert max(a1 - a0 for a0, a1, _, _ in sweeps) == 230
    assert [s[0] for s in sweeps] == list(range(0, 1434, 230))
    sc = RackScorer(config["topology"], cluster_arrays(config["cluster"]), rack_arrays(config))
    menu = refine_racks_classes_ref.ClassMenu(sc, config["deployed"])
    assert sum(s[2] for s in sweeps) == menu.reloc[0].size
    assert sum(s[3] for s in sweeps) == menu.swap[0].size
    assert menu.edit_scores.size == 801_184


def _counters(rec):
    return {m["name"]: m["value"] for m in rec.metrics.snapshot()}


@pytest.mark.parametrize("call", ["best_relocate_swap", "score_relocate_swap"])
def test_counters_of_each_device_sweep(monkeypatch, call):
    """Several sweeps a menu on the tiny rack-aware cell: one
    ``sweep.rs_sweeps`` per sweep, one ``refine.rs_menus`` per menu, and
    each sweep's tables in ``sweep.table_h2d_bytes``, a part of
    ``sweep.h2d_bytes``."""
    from repro.core import schedule_state
    from repro.core.schedule_state import ScheduleState
    from repro.obs import TraceRecorder

    monkeypatch.setattr(schedule_state, "_NET_EDIT_SWEEP_CELLS", 2048)
    cell = tiny_cell(AWARE)
    kind = cells.load_module("kinds", cell.traffic["kind"])
    wl = kind.Workload(cell.config, cell.traffic)
    state = ScheduleState.from_etg(wl.start, wl.cluster)
    base = state.task_machine()
    rec = TraceRecorder()
    with rec.activate():
        getattr(state, call)(base, "jax", 1024)
    sweeps = [d for d in rec.dispatch_log if d.site == "score_relocate_swap"]
    assert len(sweeps) == len(state._relocate_swap_sweeps(base)) > 1
    counters = _counters(rec)
    assert counters["sweep.rs_sweeps"] == len(sweeps)
    assert counters["refine.rs_menus"] == 1
    tables = [state.e_cm, state.met_cm, wl.cluster.capacity, *state._device_resources()]
    per_sweep = sum(np.asarray(x).nbytes for x in tables)
    assert counters["sweep.table_h2d_bytes"] == per_sweep * len(sweeps)
    assert counters["sweep.table_h2d_bytes"] < counters["sweep.h2d_bytes"]


def test_host_sweeps_count_the_menu_and_no_device_sweep(monkeypatch):
    from repro.core.schedule_state import ScheduleState
    from repro.obs import TraceRecorder

    cell = tiny_cell(AWARE)
    kind = cells.load_module("kinds", cell.traffic["kind"])
    wl = kind.Workload(cell.config, cell.traffic)
    state = ScheduleState.from_etg(wl.start, wl.cluster)
    rec = TraceRecorder()
    with rec.activate():
        state.best_relocate_swap(state.task_machine(), "numpy", 1024)
    counters = _counters(rec)
    assert counters["refine.rs_menus"] == 1
    assert "sweep.rs_sweeps" not in counters and "sweep.table_h2d_bytes" not in counters


def _on_jax(monkeypatch):
    """Every relocate+swap sweep resolved to JAX."""
    from repro.core import simulator

    resolve = simulator.resolve_closed_form_backend

    def sweeps_on_jax(backend, elements=None, regime="shared", n_machines=None, site=None):
        got = resolve(backend, elements, regime, n_machines, site)
        return "jax" if site == "score_relocate_swap" else got

    monkeypatch.setattr(simulator, "resolve_closed_form_backend", sweeps_on_jax)


@pytest.mark.parametrize("budget", [None, 2048])
def test_readers_read_the_counters_of_a_traced_run(monkeypatch, budget):
    from repro.core import schedule_state
    from repro.obs.trace import recent

    if budget is not None:
        monkeypatch.setattr(schedule_state, "_NET_EDIT_SWEEP_CELLS", budget)
    _on_jax(monkeypatch)
    cell = tiny_cell(AWARE)
    monkeypatch.setattr(tiny, "tiny_cell", lambda name: cell)
    line, _ = run_main(monkeypatch, AWARE, trace=1)
    assert line["correct"] is True
    held = [s for s in recent() if s["name"] == "refine"][-line["attempted"]:]
    total = {
        k: sum(s["counters"].get(k, 0.0) for s in held)
        for k in ("sweep.rs_sweeps", "refine.rs_menus", "sweep.table_h2d_bytes",
                  "sweep.h2d_bytes")
    }
    per_round = line["metrics"]["rs_sweeps_per_round"]
    assert per_round["unit"] == "sweeps"
    assert per_round["value"] == pytest.approx(
        total["sweep.rs_sweeps"] / total["refine.rs_menus"], rel=1e-12
    )
    if budget is None:
        assert per_round["value"] == 1.0
    else:
        assert per_round["value"] > 1.0
    tables = line["metrics"]["table_h2d_bytes_per_decision"]
    assert tables["value"] == pytest.approx(total["sweep.table_h2d_bytes"] / len(held))
    assert 0 < total["sweep.table_h2d_bytes"] < total["sweep.h2d_bytes"]


def test_readers_on_host_sweeps_and_without_their_sources(monkeypatch):
    per_round = cells.load_module("metrics", "rs_sweeps_per_round")
    tables = cells.load_module("metrics", "table_h2d_bytes_per_decision")
    assert per_round.read({"decisions": 0}) is None and tables.read({"decisions": 0}) is None
    # On the CPU every sweep of the tiny cell resolves to NumPy: menus and
    # no device sweep, so no table shipped.
    line, _ = run_main(monkeypatch, "linear_10x10x10.replan", trace=1)
    assert line["metrics"]["rs_sweeps_per_round"] == {"value": 0.0, "unit": "sweeps"}
    assert "table_h2d_bytes_per_decision" not in line["metrics"]
    # A program without the menu counter reports nothing.
    from repro.obs import trace

    summaries = [dict(s, counters={}) for s in trace.recent()]
    monkeypatch.setattr(trace, "recent", lambda: summaries)
    assert per_round.read({"decisions": line["attempted"]}) is None
    assert tables.read({"decisions": line["attempted"]}) is None
