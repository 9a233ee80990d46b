"""Observability layer: determinism, ledger, dispatch log, exporters.

The critical contract is *zero perturbation*: running with a
``TraceRecorder`` attached must leave every executor fingerprint
bit-identical to the recorder-off run (the four pinned pre-PR shuffle
digests), and two recorder-on reruns must export byte-identical JSONL
once wall-clock fields are stripped.
"""

import json

import numpy as np
import pytest

from repro.core import (
    keyed_rolling_count_topology,
    linear_topology,
    paper_cluster,
    rolling_count_topology,
    schedule,
)
from repro.core.refine import refine
from repro.obs import (
    NULL_RECORDER,
    MetricsRegistry,
    ReplanDecision,
    ReplanLedger,
    TraceRecorder,
    summary,
    to_chrome_trace,
    to_jsonl,
)
from repro.obs.validate import validate_chrome, validate_file, validate_jsonl
from repro.runtime_stream import (
    OnlineController,
    StreamExecutor,
    TraceSpec,
    burst_trace,
    ramp_trace,
)


@pytest.fixture(scope="module")
def cluster():
    return paper_cluster((1, 1, 1))


@pytest.fixture(scope="module")
def full_linear(cluster):
    return refine(
        schedule(linear_topology(), cluster, r0=1.0, rate_epsilon=0.05).etg, cluster
    )


# Same pins as tests/test_runtime_stream.py::_SHUFFLE_GOLDEN_FPS (recorded
# from commit 12cf43e, before fields grouping): the recorder must not move
# them. Kept as a literal copy so a drift here cannot hide behind a shared
# constant changing.
_SHUFFLE_GOLDEN_FPS = {
    ("linear", "burst"): "26fc286367d2ab03eba1c45d9417a04b",
    ("linear", "ramp"): "ca9542d22a245bc90ba588543f47f041",
    ("rolling_count", "burst"): "2b6e1b64c419dd53f37337ab3c5e45e3",
    ("rolling_count", "ramp"): "c160b175553ae57f70c3e0a9cdf263eb",
}


def test_recorder_on_keeps_pinned_fingerprints(cluster, full_linear):
    """Recorder-enabled runs reproduce all four pinned pre-PR digests."""
    for topo in (linear_topology(), rolling_count_topology()):
        if topo.name == "linear":
            full = full_linear
        else:
            full = refine(
                schedule(topo, cluster, r0=1.0, rate_epsilon=0.05).etg, cluster
            )
        rec = TraceRecorder(name=f"golden-{topo.name}")
        burst = StreamExecutor(
            full.etg, cluster, burst_trace(full.rate * 0.8, n_windows=100, jitter=4),
            seed=11, recorder=rec,
        ).run()
        ramp = StreamExecutor(
            full.etg, cluster,
            ramp_trace(0.3 * full.rate, 1.5 * full.rate, n_windows=120),
            seed=3, recorder=rec,
        ).run()
        assert burst.fingerprint() == _SHUFFLE_GOLDEN_FPS[(topo.name, "burst")]
        assert ramp.fingerprint() == _SHUFFLE_GOLDEN_FPS[(topo.name, "ramp")]
        assert rec.records  # the recorder actually saw the runs


def _controlled_run(cluster, full, recorder=None, **ctl_kwargs):
    """Under-provisioned schedule + rate ramp: the controller must grow
    (accepted replans) and also hit guard rejections along the way."""
    from repro.runtime_stream import provision_schedule

    topo = linear_topology()
    prov = provision_schedule(topo, cluster, full.rate * 0.3)
    ctl = OnlineController(topo, cluster, period=10, recorder=recorder, **ctl_kwargs)
    trace = ramp_trace(0.3 * full.rate, 1.2 * full.rate, n_windows=160)
    res = StreamExecutor(
        prov, cluster, trace, seed=3, recorder=recorder
    ).run(controller=ctl)
    return res, ctl


def test_jsonl_export_byte_identical_across_reruns(cluster, full_linear):
    """Two recorder-on reruns (wall clock enabled) export byte-identical
    JSONL once ``strip_wall=True`` removes the wall fields."""
    texts = []
    for _ in range(2):
        rec = TraceRecorder(name="rerun", wall_clock=True)
        _controlled_run(cluster, full_linear, recorder=rec)
        texts.append(to_jsonl(rec, strip_wall=True))
    assert texts[0] == texts[1]
    # Wall fields really were present before stripping.
    assert any("wall_s" in json.loads(l) for l in to_jsonl(rec).splitlines())
    n, errors = validate_jsonl(texts[0])
    assert not errors and n > 10


def test_recorder_does_not_change_controlled_run(cluster, full_linear):
    """Fingerprint, migrations and the controller decisions are identical
    with and without a recorder attached."""
    res_off, ctl_off = _controlled_run(cluster, full_linear, recorder=None)
    rec = TraceRecorder(name="on")
    res_on, ctl_on = _controlled_run(cluster, full_linear, recorder=rec)
    assert res_on.fingerprint() == res_off.fingerprint()
    assert ctl_on.log == ctl_off.log
    assert ctl_on.ledger == ctl_off.ledger


def test_ledger_guard_breakdown_and_legacy_view(cluster, full_linear):
    """Every consult that reaches the guard carries the full two-sided
    breakdown; the legacy string log derives tuple-for-tuple."""
    res, ctl = _controlled_run(cluster, full_linear)
    assert ctl.ledger, "ramp run should trigger at least one decision"
    assert ctl.log == ctl.ledger.legacy_view()
    accepted = ctl.ledger.accepted
    assert len(accepted) == int((res.migrations > 0).sum())
    assert accepted, "ramp run should accept at least one replan"
    for dec in ctl.ledger:
        assert dec.outcome in ("no_move", "budget", "skip", "replan")
        w, msg = dec.legacy_entry()
        assert w == dec.window and msg == dec.message
        if dec.has_guard_breakdown:
            assert dec.moves > 0
            assert dec.cost == pytest.approx(dec.move_cost + dec.state_cost)
            assert dec.move_cost == pytest.approx(dec.moves * ctl.migration_cost)
            assert dec.horizon_windows == ctl.horizon_windows
            assert dec.candidate_moves  # refine applied at least one move
            assert f"moves={dec.moves}" in dec.message
        if dec.outcome == "replan":
            assert dec.benefit > dec.cost


def test_ledger_records_budget_rejections(cluster, full_linear):
    """A zero elastic budget turns every would-be replan into a recorded
    ``budget`` rejection with the full breakdown — nothing migrates."""
    res, ctl = _controlled_run(cluster, full_linear, elastic_budget=0.0)
    assert int(res.migrations.sum()) == 0
    budget = [d for d in ctl.ledger if d.outcome == "budget"]
    assert budget, "guard must have rejected at least one plan on budget"
    for dec in budget:
        assert dec.cost > dec.budget == 0.0
        assert dec.message.startswith(f"{dec.trigger}:budget cost=")


def test_replan_decision_message_formats():
    d = ReplanDecision(window=7, trigger="hot", outcome="no_move")
    assert d.legacy_entry() == (7, "hot:no_move")
    d = ReplanDecision(
        window=3, trigger="saturated", outcome="skip",
        moves=2, state_shipped=10.4, gain_rate=1.236,
    )
    assert d.message == "saturated:skip gain=1.24/s moves=2 state=10"
    d = ReplanDecision(
        window=4, trigger="drain", outcome="replan",
        moves=5, state_shipped=0.0, gain_rate=12.5,
    )
    assert d.message == "drain:replan gain=12.50/s moves=5 state=0"
    d = ReplanDecision(
        window=9, trigger="scale_out", outcome="budget", moves=3,
        state_shipped=2.0, cost=77.3,
    )
    assert d.message == "scale_out:budget cost=77 moves=3 state=2"
    d = ReplanDecision(window=5, trigger="hot", outcome="deferred", moves=4)
    assert d.legacy_entry() == (5, "deferred:arbiter", 4.0)
    ledger = ReplanLedger([d])
    assert ledger.rejected == [d] and not ledger.accepted
    rec = d.to_record()
    assert rec["budget"] == "inf"  # non-finite floats stringified for JSON


def test_dispatch_log_covers_keyed_refine(cluster):
    """Every closed-form sweep in a keyed refine run lands in the dispatch
    log with its regime, sizes and resolved backend."""
    utg = keyed_rolling_count_topology(n_keys=16, zipf_s=1.5)
    etg = schedule(utg, cluster, r0=1.0, rate_epsilon=0.05).etg
    probe = StreamExecutor(
        etg, cluster, TraceSpec(name="probe", n_windows=2, base_rate=1.0), seed=5
    )
    skew = probe.skew_model_at(0)
    rec = TraceRecorder(name="keyed-refine")
    refine(etg, cluster, skew=skew, recorder=rec)
    assert rec.dispatch_log
    assert any(d.regime == "skew" for d in rec.dispatch_log)
    for d in rec.dispatch_log:
        assert d.backend in ("numpy", "jax")
        assert d.requested in ("numpy", "jax", "auto")
        assert d.site in (
            "max_stable_rate_batch", "score_task_machine_batch", "score_relocate_swap"
        )
        assert d.elements is None or d.elements > 0
    # The dispatch stream also lands in the record list for exporters.
    assert sum(r["type"] == "dispatch" for r in rec.records) == len(rec.dispatch_log)


def test_executor_metrics_and_events(cluster, full_linear):
    """The recorder's new series agree with the result arrays they mirror."""
    rec = TraceRecorder(name="metrics")
    res, _ = _controlled_run(cluster, full_linear, recorder=rec)
    names = {m["name"]: m for m in rec.metrics.snapshot()}
    n_comp = linear_topology().n_components
    thpt = sum(
        names[f"executor.throughput.c{i}"]["value"] for i in range(n_comp)
    )
    assert thpt == pytest.approx(float(res.throughput.sum()) * res.window_s)
    assert names["executor.queue_max"]["hwm"] == pytest.approx(
        float(res.queue_max.max())
    )
    assert names["executor.replans_applied"]["value"] == int(
        (res.migrations > 0).sum()
    )
    assert names["controller.drift_checks"]["value"] > 0
    event_names = {r["name"] for r in rec.records if r["type"] == "event"}
    assert "run_start" in event_names and "drift" in event_names
    # Summary renders without blowing up and mentions the dispatch table.
    text = summary(rec)
    assert "refine.round" in text and "metrics:" in text


def test_metrics_registry_kinds():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.add(2.0)
    c.add()
    assert c.value == 3.0 and c.count == 2
    g = reg.gauge("g")
    g.set(5.0)
    g.set(2.0)
    assert g.value == 2.0 and g.hwm == 5.0
    h = reg.histogram("h", edges=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.record(v)
    assert h.counts == [1, 1, 1] and h.count == 3
    with pytest.raises(TypeError):
        reg.gauge("c")
    assert [m["name"] for m in reg.snapshot()] == ["c", "g", "h"]
    assert len(reg) == 3


def test_null_recorder_is_inert(cluster, full_linear):
    assert not NULL_RECORDER.enabled
    with NULL_RECORDER.span("x"):
        NULL_RECORDER.event("y")
    assert NULL_RECORDER.records == [] and len(NULL_RECORDER.metrics) == 0
    ex = StreamExecutor(
        full_linear.etg, cluster,
        burst_trace(full_linear.rate * 0.8, n_windows=10, jitter=4), seed=11,
    )
    assert ex.recorder is NULL_RECORDER


def test_validate_accepts_good_and_rejects_malformed(tmp_path, cluster, full_linear):
    rec = TraceRecorder(name="validate")
    _controlled_run(cluster, full_linear, recorder=rec)
    jsonl = tmp_path / "trace.jsonl"
    chrome = tmp_path / "trace.json"
    to_jsonl(rec, path=jsonl)
    to_chrome_trace(rec, path=chrome)
    for path in (jsonl, chrome):
        n, errors = validate_file(path)
        assert not errors and n > 0

    # Malformed JSONL: unknown type, missing ts, clock going backwards.
    bad = tmp_path / "bad.jsonl"
    bad.write_text(
        '{"type":"meta","name":"x","wall_clock":false,"records":2}\n'
        '{"type":"banana"}\n'
        '{"type":"event","name":"a","cat":"c","window":0}\n'
        '{"type":"event","name":"b","cat":"c","window":0,"ts":5}\n'
        '{"type":"event","name":"c","cat":"c","window":0,"ts":4}\n'
    )
    n, errors = validate_file(bad)
    assert len(errors) == 3
    # Malformed Chrome trace: bad phase, X event without dur.
    bad_chrome = tmp_path / "bad.json"
    bad_chrome.write_text(json.dumps({
        "traceEvents": [
            {"name": "ok", "ph": "i", "s": "t", "ts": 1, "pid": 0, "tid": 0},
            {"name": "bad-ph", "ph": "Z", "ts": 2, "pid": 0, "tid": 0},
            {"name": "no-dur", "ph": "X", "ts": 3, "pid": 0, "tid": 0},
        ]
    }))
    n, errors = validate_file(bad_chrome)
    assert len(errors) == 2
    from repro.obs.validate import main as validate_main
    assert validate_main([str(jsonl), str(chrome)]) == 0
    assert validate_main([str(bad)]) == 1
    assert validate_main([]) == 2


def test_chrome_trace_schema(cluster, full_linear):
    rec = TraceRecorder(name="chrome")
    _controlled_run(cluster, full_linear, recorder=rec)
    trace = to_chrome_trace(rec)
    n, errors = validate_chrome(trace)
    assert not errors
    phases = {ev["ph"] for ev in trace["traceEvents"]}
    assert "X" in phases and "i" in phases and "M" in phases
    spans = [ev for ev in trace["traceEvents"] if ev["ph"] == "X"]
    assert all(ev["dur"] >= 1 for ev in spans)
    # One thread per category, named via metadata events.
    thread_names = {
        ev["args"]["name"]
        for ev in trace["traceEvents"]
        if ev["ph"] == "M" and ev["name"] == "thread_name"
    }
    assert {"executor", "controller", "refine"} <= thread_names


def test_multitenant_arbiter_surface():
    """Per-tenant grants/denials/budget land on the runtime result and
    agree with the raw arbiter ledger; deferred decisions reproduce the
    legacy in-band 3-tuple."""
    from repro.core import diamond_topology
    from repro.multitenant import (
        MultiTenantRuntime,
        Tenant,
        TenantSet,
        compile_tenant_traces,
        schedule_tenants,
    )

    tenants = TenantSet(
        [
            Tenant(name="alice", utg=linear_topology(), target_rate=6.0),
            Tenant(name="bob", utg=diamond_topology(), target_rate=6.0),
        ]
    )
    cluster = paper_cluster((2, 2, 2))
    ms = schedule_tenants(list(tenants), cluster)
    specs = [
        TraceSpec(name="alice", n_windows=24, base_rate=min(4.0, ms.rates[0])),
        TraceSpec(name="bob", n_windows=24, base_rate=min(4.0, ms.rates[1])),
    ]
    mtrace = compile_tenant_traces(tenants, specs, cluster, seed=7)
    rt = MultiTenantRuntime(ms, tenants, cluster, mtrace)
    rec = TraceRecorder(name="mt")
    res = rt.run(online=True, moves_per_period=4, recorder=rec)
    assert tuple(l.name for l in res.arbiter) == res.names
    for ledger in res.arbiter:
        rows = [r for r in res.arbiter_log if r[0] == ledger.name]
        assert ledger.grants == sum(1 for r in rows if r[3])
        assert ledger.denials == sum(1 for r in rows if not r[3])
        assert ledger.moves_admitted == sum(r[2] for r in rows if r[3])
        assert ledger.moves_per_period == 4
        for _period, left in ledger.budget_remaining:
            assert 0 <= left <= 4
    assert res.arbiter_for("alice") is res.arbiter[0]
    # Tenant spans landed in the shared recorder.
    span_names = {r["name"] for r in rec.records if r["type"] == "span"}
    assert {"tenant:alice", "tenant:bob"} <= span_names
