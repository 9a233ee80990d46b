"""The benchmark harness on the CPU: discovery by name, requests from the
seed, the result line, the trace reduction and the refusal without a chip."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from tiny import BENCH, ROOT, run_main

import cells
import devtrace

SPEC = cells.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_by_name(name):
    cell = cells.find_cell(SPEC, name)
    kind = cells.load_module("kinds", cell.traffic["kind"])
    assert hasattr(kind, "Workload")
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cells.load_module("metrics", m["name"]).read)
    assert set(cell.traffic["limits"]) <= set(_numbers(cell))


def _numbers(cell):
    return {
        "replan": {"move_gap", "throughput_dev", "replay_mismatch"},
    }[cell.traffic["kind"]]


def test_a_cell_and_a_metric_added_as_files_only(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for sub in ("configs", "traffic", "kinds", "metrics"):
        shutil.copytree(BENCH / sub, tmp_path / "bench" / sub)
    extra = json.loads((BENCH / "traffic" / "replan.json").read_text())
    extra["slow_machines"] = 8
    (tmp_path / "bench" / "traffic" / "replan_wide.json").write_text(json.dumps(extra))
    (tmp_path / "bench" / "metrics" / "decisions.py").write_text(
        "def read(run):\n    return run['decisions']\n"
    )
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({
        "name": "linear_20x70x90.replan_wide", "config": "linear_20x70x90",
        "traffic": "replan_wide", "chips": 1, "why": "test",
    })
    spec["per_layer"].append({
        "name": "decisions", "unit": "decisions", "better": "higher",
        "source": "host_clock", "layer": "host search", "moves": "decision_s",
        "workloads": ["linear_20x70x90.replan_wide"],
    })
    cell = cells.find_cell(spec, "linear_20x70x90.replan_wide", root=tmp_path)
    assert "decisions" in [m["name"] for m in cell.per_layer]
    reader = cells.load_module("metrics", "decisions", root=tmp_path)
    assert reader.read({"decisions": 3}) == 3
    kind = cells.load_module("kinds", cell.traffic["kind"], root=tmp_path)
    requests = kind.requests(cell.config, cell.traffic, 5)
    assert all(len(r["slow"]) == 8 for r in requests)
    other = cells.find_cell(spec, "linear_20x70x90.replan", root=tmp_path)
    assert "decisions" not in [m["name"] for m in other.per_layer]


@pytest.mark.parametrize("name", CELLS)
def test_requests_follow_the_seed(name):
    cell = cells.find_cell(SPEC, name)
    kind = cells.load_module("kinds", cell.traffic["kind"])
    big = 2**31 + 7
    a = kind.requests(cell.config, cell.traffic, big)
    assert a == kind.requests(cell.config, cell.traffic, big)
    orders = {
        tuple(r["id"] for r in kind.requests(cell.config, cell.traffic, s))
        for s in range(20)
    }
    assert len(orders) > 1
    # Every seed serves the same pool: the same set of sizes, another order.
    assert {tuple(sorted(o)) for o in orders} == {tuple(range(cell.traffic["pool"]))}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_result_line_schema(monkeypatch, name, trace):
    line, err = run_main(monkeypatch, name, trace=trace)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    cell = cells.find_cell(SPEC, name)
    wanted = cell.per_layer if trace else cell.end_to_end
    units = {m["name"]: m["unit"] for m in wanted}
    assert set(line["metrics"]) <= set(units)
    for k, v in line["metrics"].items():
        assert v["unit"] == units[k] and isinstance(v["value"], (int, float))
    if not trace:
        assert set(line["metrics"]) == set(units)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    tail = err.strip().splitlines()[-len(line["checks"]):]
    for (k, c), text in zip(line["checks"].items(), tail):
        assert c["value"] <= c["limit"]
        assert text.startswith(f"check {k} ")


def test_trace_reduction_on_a_recorded_trace():
    events = json.loads((BENCH / "tests" / "fixtures" / "trace_small.json").read_text())
    got = devtrace.reduce(events, 1)
    (line, _, w0, wdur), = [h for h in events["host"] if h[1] == devtrace.WINDOW]
    assert set(events["device"]) == {"/device:TPU:0", "/device:CUSTOM:Megascale Trace"}
    ops = [(s, s + d) for _, s, d in events["device"]["/device:TPU:0"]]
    # Busy time by brute force: every nanosecond covered by some op.
    edges = sorted({max(min(t, w0 + wdur), w0) for a, b in ops for t in (a, b)})
    busy = sum(
        b - a for a, b in zip(edges, edges[1:])
        if any(s <= a and b <= e for s, e in ops)
    )
    assert got["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)
    assert got["window_s"] == pytest.approx(wdur * 1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["idle_gaps"][0][0] == "host.pause"
    assert [g[1] for g in got["idle_gaps"]] == sorted(
        (g[1] for g in got["idle_gaps"]), reverse=True
    )
    times = [v for _, v in got["device_ops"]]
    assert times == sorted(times, reverse=True) and times[0] > 0
    assert all(" = " not in name for name, _ in got["device_ops"])


def test_reduction_finds_nothing_without_a_device():
    assert devtrace.reduce({"device": {}, "host": [["t", devtrace.WINDOW, 0, 10]]}, 1) is None


def _bare_run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def _cpu_env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_exits_nonzero_without_a_tpu(tmp_path):
    env = _cpu_env()
    env["HOME"] = str(tmp_path)
    proc = _bare_run(ROOT, env)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    proc = _bare_run(tmp_path, _cpu_env())
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
