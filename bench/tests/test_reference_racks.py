"""The rack-aware reference, its control, the faults it must catch and the
readers of the cell's own metrics, on a 2/3/4 cluster in 2 racks.

``closed_form_racks.RackScorer`` is held to a per-task-pair double loop of
the same model, and ``refine_racks_ref.RackMenu`` to rescoring each
relocation and swap as a placement of its own. Each planted fault breaks
the program underneath a whole run (``tiny.run_main``) and must turn
``correct`` false: the cut-traffic term dropped, memory ignored, and only
the two machines a relocation or swap touches rescored.
"""

from __future__ import annotations

import contextlib
import io
import json

import numpy as np
import pytest

import tiny
from tiny import run_main, tiny_cell

import cells
import refine_racks_ref
from closed_form_racks import RackScorer, rack_arrays
from system import cluster_arrays

RACKS = "racks_20x70x90.replan"


def _cell():
    """The tiny cell in 2 racks (5 and 4 machines), with memory for 4 tasks
    a machine, so that it binds."""
    cell = tiny_cell(RACKS)
    cell.config["racks"]["machines_per_rack"] = 5
    cell.config["memory"]["machine_mb"] = 512.0
    return cell


def _run(monkeypatch, trace=0):
    cell = _cell()
    monkeypatch.setattr(tiny, "tiny_cell", lambda name: cell)
    return run_main(monkeypatch, RACKS, trace=trace)


def _pairwise_throughput(config, capacity, assignment):
    """Throughput by a double loop over every pair of tasks on every edge:
    the flow from instance i of a to instance j of b costs its penalty
    times its distance on both of their machines."""
    arrays = cluster_arrays(config["cluster"], capacity)
    racks = rack_arrays(config)
    topo = config["topology"]
    types = topo["component_types"]
    n = len(types)
    cir = np.zeros(n)
    for c in range(n):
        parents = [a for a, b in topo["edges"] if b == c]
        cir[c] = sum(topo["alpha"][a] * cir[a] for a in parents) if parents else 1.0
    m = arrays["capacity"].size
    var, met, net, mem = (np.zeros(m) for _ in range(4))
    for c, machines in enumerate(assignment):
        for w in machines:
            t = arrays["machine_types"][w]
            var[w] += arrays["e_points"][types[c], t] * cir[c] / len(machines)
            met[w] += arrays["met_points"][types[c], t]
            mem[w] += racks["mem"][c]
    for a, b in topo["edges"]:
        for v in assignment[a]:
            for w in assignment[b]:
                flow = topo["alpha"][a] * cir[a] / len(assignment[a]) / len(assignment[b])
                cost = racks["net_penalty"] * flow * racks["distance"][v, w]
                net[v] += cost
                net[w] += cost
    head = arrays["capacity"] - met
    if np.any(head < 0) or np.any(mem > racks["mem_capacity"]):
        return 0.0
    load = var + net
    rate = min(h / x for h, x in zip(head, load) if x > 0)
    return max(rate, 0.0) * cir.sum()


def test_rack_scorer_matches_the_pairwise_loop():
    config = _cell().config
    rng = np.random.default_rng(5)
    m = sum(config["cluster"]["counts"])
    feasible = 0
    for _ in range(30):
        cap = rng.uniform(20, 100, size=m)
        asg = [list(rng.integers(0, m, size=rng.integers(1, 7))) for _ in range(4)]
        sc = RackScorer(config["topology"], cluster_arrays(config["cluster"], cap),
                        rack_arrays(config))
        want = _pairwise_throughput(config, cap, asg)
        assert sc.throughput(asg) == pytest.approx(want, rel=1e-12, abs=1e-12)
        feasible += want > 0
    assert 0 < feasible < 30


def test_rack_scorer_matches_the_program():
    from repro.core.cost_model import max_stable_rate

    cell = _cell()
    kind = cells.load_module("kinds", cell.traffic["kind"])
    wl = kind.Workload(cell.config, cell.traffic)
    for request in kind.requests(cell.config, cell.traffic, 3):
        sc = wl.scorer(request)
        etg = wl.start
        cluster = wl.cluster.with_capacity(np.asarray(request["capacity"]))
        want = max_stable_rate(etg, cluster)[1]
        assert sc.throughput(wl.deployed) == pytest.approx(want, rel=1e-13)


def test_rack_menu_rescores_every_relocation_and_swap_whole():
    cell = _cell()
    kind = cells.load_module("kinds", cell.traffic["kind"])
    wl = kind.Workload(cell.config, cell.traffic)
    request = kind.requests(cell.config, cell.traffic, 0)[0]
    sc = wl.scorer(request)
    menu = refine_racks_ref.RackMenu(sc, wl.deployed)
    for i in range(menu.edit_scores.size):
        _, asg = menu.pick(i)
        assert menu.edit_scores[i] == pytest.approx(sc.throughput(asg), rel=1e-13, abs=0)
    # The two-machine patch of the plain menu misses what the cut traffic
    # moves elsewhere.
    assert np.any(menu.edit_scores != refine_racks_ref.refine_ref.Menu(sc, wl.deployed).edit_scores)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_racks_answers_pass_and_the_float32_control_fails(seed):
    cell = _cell()
    kind = cells.load_module("kinds", cell.traffic["kind"])
    wl = kind.Workload(cell.config, cell.traffic)
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
        assert control.main(["--workload", RACKS, "--pool-seeds", "1000", "1001"]) == 0
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert any(last["control"][k] > v for k, v in last["limits"].items())


def test_sound_run_is_correct(monkeypatch):
    line, err = _run(monkeypatch)
    assert line["correct"] is True
    assert "reference judged request" in err


def test_program_without_device_resources_is_refused_at_once(monkeypatch):
    """A program that prices cut traffic on the host exits before set-up,
    with a non-zero code and the reason, rather than outlasting the run."""
    import jax

    import run
    from repro.core import sim_jax

    cell = _cell()
    monkeypatch.delattr(sim_jax, "device_resources")
    monkeypatch.setattr(cells, "find_cell", lambda spec, n, root=cells.ROOT: cell)
    monkeypatch.setattr(run, "accelerator", lambda chips: jax.devices())
    served = []
    monkeypatch.setattr(run, "serve", lambda *a, **k: served.append(a))
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", RACKS, "--seed", "3", "--seconds", "0.3", "--trace", "0"])
    assert exc.value.code not in (None, 0)
    assert "device_resources" in str(exc.value.code)
    assert not served


# ------------------------------------------------------------- faults


def test_fault_cut_traffic_dropped(monkeypatch):
    from repro.core import cost_model

    monkeypatch.setattr(
        cost_model, "network_unit_load",
        lambda task_machine, *a, **k: np.zeros((len(task_machine), len(a[5]))),
    )
    line, _ = _run(monkeypatch)
    assert line["correct"] is False
    assert line["checks"]["throughput_dev"]["value"] > line["checks"]["throughput_dev"]["limit"]


def test_fault_memory_ignored(monkeypatch):
    from repro.core.profiles import Cluster

    monkeypatch.setattr(Cluster, "has_memory", property(lambda self: False))
    line, _ = _run(monkeypatch)
    assert line["correct"] is False


def test_fault_only_touched_machines_rescored(monkeypatch):
    """Relocations and swaps priced with the base's cut traffic on every
    machine but the two they touch."""
    from repro.core import cost_model
    from repro.core.schedule_state import ScheduleState, _edited_rows

    score = ScheduleState.score_relocate_swap

    def two_machines(self, base, backend, row_chunk):
        edits, _ = score(self, base, backend, row_chunk)
        rows = np.concatenate([base[None, :], _edited_rows(base, edits)])
        comp, unit_ir, _ = self._task_maps(self.n_instances, *rows.shape)
        net, mem, mem_cap = self._resource_operands(rows, comp, unit_ir)
        touched = np.zeros(net.shape, dtype=bool)
        k = np.arange(1, rows.shape[0])
        touched[k, base[edits[0]]] = touched[k, edits[1]] = True
        net = np.where(touched, net, net[:1])
        thpt = cost_model.closed_form_rates(
            rows, self.e_cm[comp, rows], self.met_cm[comp, rows], unit_ir,
            self.cluster.capacity, net_var=net, mem=mem, mem_capacity=mem_cap,
        )[1]
        return edits, thpt[1:]

    monkeypatch.setattr(ScheduleState, "score_relocate_swap", two_machines)
    line, _ = _run(monkeypatch)
    assert line["correct"] is False
    assert line["checks"]["move_gap"]["value"] > line["checks"]["move_gap"]["limit"]


# ------------------------------------------------------------- metrics


def test_net_metrics_read_the_host_and_device_shares(monkeypatch):
    from repro.core import simulator
    from repro.obs.trace import recent

    line, _ = _run(monkeypatch, trace=1)
    metrics = line["metrics"]
    # On the CPU every sweep of the tiny cell resolves to NumPy: the host
    # prices all cut traffic.
    assert metrics["net_device_row_share"] == {"value": 0.0, "unit": "%"}
    host = metrics["net_host_s.decision"]["value"]
    held = [s for s in recent() if s["name"] == "refine"][-line["attempted"]:]
    assert host == pytest.approx(sum(s["self_s"]["net.host"] for s in held) / len(held))

    resolve = simulator.resolve_closed_form_backend

    def sweeps_on_jax(backend, elements=None, regime="shared", n_machines=None, site=None):
        got = resolve(backend, elements, regime, n_machines, site)
        return "jax" if site in ("score_relocate_swap", "score_task_machine_batch") else got

    monkeypatch.setattr(simulator, "resolve_closed_form_backend", sweeps_on_jax)
    line, _ = _run(monkeypatch, trace=1)
    assert line["correct"] is True
    assert line["metrics"]["net_device_row_share"]["value"] == 100.0
    # Only the incumbent and the result are priced on the host.
    assert 0 < line["metrics"]["net_host_s.decision"]["value"] < host


def test_net_metrics_read_nothing_without_their_sources(monkeypatch):
    from repro.core import sim_jax

    host = cells.load_module("metrics", "net_host_s.decision")
    share = cells.load_module("metrics", "net_device_row_share")
    assert host.read({"decisions": 0}) is None and share.read({"decisions": 0}) is None
    # A run without cut traffic has no net.host span.
    line, _ = run_main(monkeypatch, "linear_10x10x10.replan", trace=1)
    assert host.read({"decisions": line["attempted"]}) is None
    monkeypatch.delattr(sim_jax, "device_resources")
    assert share.read({"decisions": line["attempted"]}) is None

