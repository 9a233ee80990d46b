"""Spans on the profiler's clock, per-decision summaries and the refine
counters: ``refine.build`` / ``refine.sweep`` / ``sweep.*`` spans reach a
``jax.profiler`` trace, ``RefineResult.candidates`` counts the rows the
climb scored on every engine and backend, ``sweep.h2d_bytes`` counts the
operands a device sweep ships, and ``recent()`` keeps a bounded
time breakdown per outermost span."""

import glob
import subprocess
import sys

import numpy as np
import pytest

from repro.core import linear_topology, paper_cluster, schedule
from repro.core.refine import refine
from repro.core.schedule_state import ScheduleState
from repro.obs import TraceRecorder, trace

REFINE_SPANS = {"refine", "refine.round", "refine.build", "refine.sweep"}
SWEEP_SPANS = {"sweep.put", "sweep.run", "sweep.fetch"}


@pytest.fixture(scope="module")
def browned_out():
    """The linear topology's Alg. 1+2 placement on a 2/3/4 cluster, with
    two machines then slowed to half capacity (a replan's input)."""
    cluster = paper_cluster((2, 3, 4))
    etg = schedule(linear_topology(), cluster, r0=1.0, rate_epsilon=1.0).etg
    cap = cluster.capacity.copy()
    cap[[0, 3]] *= 0.5
    return etg, cluster.with_capacity(cap)


def _host_lines(out_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(out_dir / "**" / "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path)
    return [
        {ev.name for ev in line.events}
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
    ]


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_spans_appear_on_the_profiler_trace(tmp_path, browned_out, backend):
    import jax

    etg, cluster = browned_out
    refine(etg, cluster, max_rounds=2, backend=backend)  # compile outside
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        refine(etg, cluster, max_rounds=2, backend=backend, recorder=TraceRecorder())
    finally:
        jax.profiler.stop_trace()
    # One thread's line (the caller's) holds every span of the climb.
    (line,) = [names for names in _host_lines(tmp_path) if "refine" in names]
    assert REFINE_SPANS <= line
    if backend == "jax":
        assert SWEEP_SPANS <= line
    else:
        assert not SWEEP_SPANS & line


def test_trace_module_imports_no_jax():
    code = "import sys, repro.obs.trace; assert 'jax' not in sys.modules"
    subprocess.run([sys.executable, "-c", code], check=True)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_candidates_equal_the_rows_of_every_sweep(monkeypatch, browned_out, backend):
    etg, cluster = browned_out
    shapes = []
    score_rows = ScheduleState._score_batch
    score_moves = ScheduleState._score_moves
    move_winners = ScheduleState._move_winners

    def logged_rows(self, task_machine, n_instances, backend):
        shapes.append(np.shape(task_machine))
        return score_rows(self, task_machine, n_instances, backend)

    def logged_moves(self, base, edits, parts, *rest):
        shapes.append((sum(p.stop - p.start for p in parts), base.shape[0]))
        return score_moves(self, base, edits, parts, *rest)

    def logged_winners(self, base, tasks, rows, backend):
        shapes.append((rows, base.shape[0]))
        return move_winners(self, base, tasks, rows, backend)

    monkeypatch.setattr(ScheduleState, "_score_batch", logged_rows)
    monkeypatch.setattr(ScheduleState, "_score_moves", logged_moves)
    monkeypatch.setattr(ScheduleState, "_move_winners", logged_winners)
    rec = TraceRecorder()
    res = refine(etg, cluster, max_rounds=4, backend=backend, recorder=rec)
    assert res.candidates == sum(b for b, _ in shapes) > 0
    # Σ over the dispatch log of elements ÷ row width, sweep by sweep.
    sites = {"score_task_machine_batch", "score_relocate_swap"}
    sweeps = [d for d in rec.dispatch_log if d.site in sites]
    assert [d.elements for d in sweeps] == [b * t for b, t in shapes]
    assert res.candidates == sum(d.elements // t for d, (_, t) in zip(sweeps, shapes))
    counters = {m["name"]: m["value"] for m in rec.metrics.snapshot()}
    assert counters["refine.rows"] == res.candidates


def test_candidates_agree_across_engines_and_backends(browned_out):
    etg, cluster = browned_out
    runs = [
        refine(etg, cluster, max_rounds=4, engine="state", backend="numpy"),
        refine(etg, cluster, max_rounds=4, engine="state", backend="jax"),
        refine(etg, cluster, max_rounds=4, engine="state", lockstep=False),
        refine(etg, cluster, max_rounds=4, engine="reference"),
        refine(etg, cluster, max_rounds=4, engine="reference", recorder=TraceRecorder()),
    ]
    assert len({tuple(r.moves) for r in runs}) == 1 and runs[0].moves
    assert len({r.candidates for r in runs}) == 1
    count_only = refine(etg, cluster, max_rounds=2, allow_add=False)
    assert 0 < count_only.candidates < runs[0].candidates


def test_h2d_bytes_zero_for_numpy_sweeps(browned_out):
    etg, cluster = browned_out
    rec = TraceRecorder()
    refine(etg, cluster, max_rounds=2, backend="numpy", recorder=rec)
    assert "sweep.h2d_bytes" not in {m["name"] for m in rec.metrics.snapshot()}
    assert trace.recent()[-1]["counters"].get("sweep.h2d_bytes", 0.0) == 0.0


@pytest.mark.parametrize("variant", ["shared", "per_row", "resources"])
def test_h2d_bytes_equal_the_operands_of_a_jax_sweep(variant):
    from repro.core.sim_jax import closed_form_rates_jax

    rng = np.random.default_rng(3)
    B, T, n, m = 5, 7, 3, 4
    tm = rng.integers(0, m, size=(B, T))
    comp = np.repeat(np.arange(n), [2, 2, 3])
    unit_ir = rng.random(T)
    if variant == "per_row":
        comp, unit_ir = np.tile(comp, (B, 1)), np.tile(unit_ir, (B, 1))
    e_cm, met_cm, cap = rng.random((n, m)), rng.random((n, m)), 10 + rng.random(m)
    operands = [tm, comp, unit_ir, e_cm, met_cm, cap]
    resources = None
    if variant == "resources":
        # Absent memory is shipped as zeros per component and +inf per
        # machine; the network as its distance matrix and topology tables.
        resources = [
            np.zeros(n), np.full(m, np.inf), rng.random((m, m)),
            np.eye(n, k=1), rng.random(n), 1 + rng.random(n), np.float64(0.5),
        ]
        operands += resources
    rec = TraceRecorder()
    with rec.activate():
        closed_form_rates_jax(tm, comp, unit_ir, e_cm, met_cm, cap, resources)
    counters = {m_["name"]: m_["value"] for m_ in rec.metrics.snapshot()}
    assert counters["sweep.h2d_bytes"] == sum(x.nbytes for x in operands)
    assert [r["name"] for r in rec.records] == ["sweep.put", "sweep.run", "sweep.fetch"]


def test_recent_holds_one_summary_per_outermost_span():
    rec = TraceRecorder()
    before = len(trace.recent())
    with rec.span("outer"):
        with rec.span("a"):
            rec.metrics.counter("c").add(3)
            with rec.span("b"):
                pass
        with rec.span("a"):
            pass
    with rec.activate(), trace.span("second"):
        trace.count("c", 2)
    got = trace.recent()[-2:]
    assert len(trace.recent()) == min(before + 2, trace.RECENT_CAPACITY)
    assert [s["name"] for s in got] == ["outer", "second"]
    assert set(got[0]["self_s"]) == {"outer", "a", "b"}
    assert [s["counters"] for s in got] == [{"c": 3.0}, {"c": 2.0}]
    for s in got:
        assert all(v >= 0 for v in s["self_s"].values())
        assert sum(s["self_s"].values()) <= s["wall_s"] + 1e-12


def test_recent_is_bounded():
    rec = TraceRecorder()
    for i in range(trace.RECENT_CAPACITY + 10):
        with rec.span("tick"):
            pass
    held = trace.recent()
    assert len(held) == trace.RECENT_CAPACITY
    assert all(s["name"] == "tick" for s in held)


def test_no_active_recorder_gives_the_shared_null_context():
    assert trace.active_recorder() is None
    assert trace.span("refine.build") is trace.span("sweep.put")
    with trace.span("refine.build") as sp:
        trace.count("refine.rows", 5)
    assert sp is None


def test_round_span_closes_when_scoring_raises(monkeypatch, browned_out):
    etg, cluster = browned_out

    def broken(self, task_machine, n_instances, backend):
        raise RuntimeError("sweep failed")

    monkeypatch.setattr(ScheduleState, "_score_batch", broken)
    rec = TraceRecorder()
    with pytest.raises(RuntimeError):
        refine(etg, cluster, max_rounds=2, recorder=rec)
    spans = [r for r in rec.records if r["type"] == "span"]
    assert {"refine", "refine.round", "refine.sweep"} <= {r["name"] for r in spans}
    assert all("dur" in r for r in spans)
    assert trace.recent()[-1]["name"] == "refine"


@pytest.mark.parametrize(
    "per_row,with_resources,name",
    [
        (False, False, "msr_shared"),
        (True, False, "msr_per_row"),
        (False, True, "msr_resources_shared"),
        (True, True, "msr_resources_per_row"),
    ],
)
def test_scoring_kernels_carry_stable_names(per_row, with_resources, name):
    import jax

    from repro.core.sim_jax import _msr_kernel

    B, T, n, m = 2, 3, 2, 4
    tm = np.zeros((B, T), dtype=np.int64)
    comp = np.zeros((B, T) if per_row else T, dtype=np.int64)
    unit_ir = np.ones((B, T) if per_row else T)
    args = [tm, comp, unit_ir, np.ones((n, m)), np.ones((n, m)), np.ones(m)]
    if with_resources:
        args += [
            np.zeros(n), np.full(m, np.inf), np.ones((m, m)), np.eye(n, k=1),
            np.ones(n), np.ones(n), np.float64(1.0),
        ]
    with jax.enable_x64(True):
        text = _msr_kernel(per_row, with_resources).lower(*args).as_text(
            debug_info=True
        )
    assert f"jit_{name}" in text
    assert f'"{name}/' in text or f"jit({name})/{name}/" in text


def test_fixed_point_kernel_carries_a_stable_name(browned_out):
    import jax

    from repro.core.sim_jax import _compiled_kernel, _static_descriptor

    etg, cluster = browned_out
    kernel = _compiled_kernel(_static_descriptor(etg))
    T, m = int(etg.n_instances.sum()), cluster.n_machines
    n = etg.utg.n_components
    with jax.enable_x64(True):
        text = kernel.lower(
            np.zeros((1, T), dtype=np.int64), etg.task_component(),
            np.ones(n), np.ones((n, m)), np.ones((n, m)), np.ones(m), np.ones(1),
        ).as_text(debug_info=True)
    assert "jit_simulate_fixed_point" in text and "simulate_fixed_point/" in text
