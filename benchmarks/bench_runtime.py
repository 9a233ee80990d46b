"""Online-runtime benchmark (``BENCH_runtime.json``).

Executes the policy ladder against every drift scenario in the streaming
runtime (``repro.runtime_stream``):

* **static** — a schedule provisioned for the scenario's *initial* rate
  (``provision_schedule``, the paper's size-to-observed-load protocol),
  then frozen for the whole trace;
* **online** — the same starting schedule driven by ``OnlineController``
  (windowed drift detection, incremental ``refine``-move replanning, the
  state-aware migration cost/benefit guard);
* **online_blind** (keyed/stateful rows only) — the same controller with
  ``state_aware=False``: flat per-move pricing, no state in the ledger —
  the pre-ISSUE-8 cost model, kept as the ablation baseline;
* **oracle** — a full ``schedule()`` re-plan at every window with free
  migrations (``OracleRescheduler`` + ``migration_pause=0``), cached per
  *(capacity, skew epoch)* and polished skew-aware on keyed rows — the
  adaptation upper bound.

Per row the JSON records sustained throughput for each policy, the
latency-SLO column (fraction of tail windows whose Little's-law latency
estimate meets ``SLO_S`` seconds), migration counts, and the acceptance
booleans. The elastic rows (``machine_addition``) run on a *fleet*
cluster whose spare machine's capacity column switches on mid-trace; the
stateful keyed rows ship keyed operator state at a finite
``state_transfer_rate``, which is where the state-aware controller
separates from the blind one.

``--check BENCH.json`` is the CI smoke gate: it fails unless every row
has ``beats_static`` (online sustained >= static), every row's replan
audit ledger is complete (accepted decisions == applied replans, full
guard breakdown on every guard verdict), the recorded evaluator parity
holds, and the observability overhead rows stay under 5%.

``--trace-out PREFIX`` additionally runs one instrumented scenario with
a ``repro.obs.TraceRecorder`` and writes ``PREFIX.jsonl`` +
``PREFIX.trace.json`` (Chrome trace-event format — load in Perfetto);
CI validates both with ``python -m repro.obs.validate`` and uploads them
as artifacts.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import numpy as np

from benchmarks.common import emit
from repro.core import linear_topology, paper_cluster, schedule
from repro.obs import TraceRecorder, to_chrome_trace, to_jsonl
from repro.core.graph import keyed_rolling_count_topology, rolling_count_topology
from repro.core.refine import refine
from repro.runtime_stream import (
    OnlineController,
    OracleRescheduler,
    RuntimeConfig,
    StreamExecutor,
    evaluate_policies_batch,
    provision_schedule,
)
from repro.runtime_stream.traces import (
    TraceSpec,
    burst_trace,
    elastic_trace,
    failure_trace,
    key_skew_shift,
    machine_addition,
    machine_slowdown,
    ramp_trace,
    rate_ramp,
    sine_trace,
    slowdown_trace,
)

N_WINDOWS = 240
SEED = 0
SLO_S = 5.0  # latency SLO: tail windows must estimate <= 5 s queueing delay
STATE_PER_TUPLE = 25.0   # keyed state retained per unit tuple rate (stateful rows)
STATE_RATE = 25.0        # state tuples shippable per second while migrating
# One event-loop config for every policy and scenario: a 120-tuple queue
# bound makes sustained overload trip real back-pressure (the default 500
# lets short transients hide entirely inside the queues).
CONFIG = RuntimeConfig(max_queue=120.0)
ORACLE_CONFIG = RuntimeConfig(max_queue=120.0, migration_pause=0)
# Stateful keyed rows: migrations ship keyed state at a finite rate, so a
# hot instance's restart pauses for multiple windows. The oracle keeps its
# idealized free migrations (instant state transfer).
STATE_CONFIG = RuntimeConfig(max_queue=120.0, state_transfer_rate=STATE_RATE)
DRAIN_CONFIG = RuntimeConfig(
    max_queue=120.0, state_transfer_rate=STATE_RATE, capacity_notice=25
)


def _scenarios(topo, cluster) -> list[tuple[TraceSpec, float, object, object]]:
    """(trace spec, provisioning rate, exec cluster, config) per scenario.

    Rates are expressed against the cluster's maximum stable rate for the
    topology (schedule+refine), so scenarios scale with cluster shape.
    The elastic row runs on a fleet with one spare i5 whose capacity
    column switches on mid-trace (``machine_addition``).
    """
    full = refine(schedule(topo, cluster, r0=1.0, rate_epsilon=0.05).etg, cluster)
    r = full.rate
    big = int(np.argmax(cluster.capacity))  # the most capable machine
    rows: list[tuple[TraceSpec, float, object, object]] = [
        (ramp_trace(0.3 * r, 1.2 * r, n_windows=N_WINDOWS), 0.3 * r, cluster, CONFIG),
        (burst_trace(0.5 * r, factor=3.0, n_windows=N_WINDOWS, every=60,
                     width=20, jitter=3), 0.5 * r, cluster, CONFIG),
        (sine_trace(0.65 * r, amplitude=0.45, n_windows=N_WINDOWS, period=160),
         0.65 * r, cluster, CONFIG),
        (slowdown_trace(0.9 * r, machine=big, factor=0.5, n_windows=N_WINDOWS),
         0.9 * r, cluster, CONFIG),
        (failure_trace(0.85 * r, machine=big, n_windows=N_WINDOWS), 0.85 * r,
         cluster, CONFIG),
        (
            TraceSpec(
                name="ramp_slowdown",
                n_windows=N_WINDOWS,
                base_rate=0.4 * r,
                events=(
                    rate_ramp(1.1 * r, start=20, end=120),
                    machine_slowdown(big, 0.6, start=150),
                ),
            ),
            0.4 * r,
            cluster,
            CONFIG,
        ),
    ]
    # Cloud scale-out: a spare i5 (fleet machine 3) joins after the rate
    # ramp passes the initial fleet's bound — only a controller that grows
    # onto the new capacity column rides the ramp.
    fleet = paper_cluster((1, 1, 2))
    r4 = refine(schedule(topo, fleet, r0=1.0, rate_epsilon=0.05).etg, fleet).rate
    rows.append(
        (
            elastic_trace(0.5 * r, 1.05 * r4, machine=3, n_windows=N_WINDOWS,
                          join=120),
            0.5 * r,
            fleet,
            CONFIG,
        )
    )
    return rows


def _keyed_scenarios(topo, cluster) -> list[tuple[TraceSpec, float, object, object]]:
    """Keyed-skew drift rows: the static baseline provisions by the
    even-split closed form for the offered rate; the realized key skew
    saturates a hot instance well below that, so only the skew-aware
    online controller sustains the load. All rows run with operator state
    (``state_per_tuple`` > 0) shipping at a finite transfer rate — the
    regime separating the state-aware controller from the blind one.

    * ``keyed_hot`` — constant offered load between the skew-aware and the
      even-split stable rate: the static schedule back-pressures from the
      start, the controller replans against the realized shares;
    * ``keyed_shift`` — sustainable start, then ``key_skew_shift`` re-rolls
      the hot keys onto new instances mid-trace (rate and capacity never
      change — drift the even-split signals cannot see);
    * ``keyed_elastic`` — scale-out under keyed state: a spare machine
      joins mid-ramp, then leaves with ``capacity_notice`` windows of
      warning (drain-before-removal under a stateful migration cost).
    """
    full = refine(schedule(topo, cluster, r0=1.0, rate_epsilon=0.05).etg, cluster)
    r = full.rate  # even-split closed form — intentionally skew-blind
    rows: list[tuple[TraceSpec, float, object, object]] = [
        (
            TraceSpec(name="keyed_hot", n_windows=N_WINDOWS, base_rate=1.0 * r),
            1.0 * r,
            cluster,
            STATE_CONFIG,
        ),
        (
            TraceSpec(
                name="keyed_shift",
                n_windows=N_WINDOWS,
                base_rate=0.8 * r,
                events=(key_skew_shift(start=N_WINDOWS // 3, zipf_s=2.0),),
            ),
            0.8 * r,
            cluster,
            STATE_CONFIG,
        ),
    ]
    fleet = paper_cluster((1, 1, 2))
    rows.append(
        (
            TraceSpec(
                name="keyed_elastic",
                n_windows=N_WINDOWS,
                base_rate=0.7 * r,
                events=(
                    rate_ramp(1.2 * r, start=20, end=100),
                    machine_addition(3, start=80, end=160),
                ),
            ),
            0.7 * r,
            fleet,
            DRAIN_CONFIG,
        )
    )
    return rows


def _start_etg(topo, trace, provision_rate: float, cluster):
    """Provision against the machines alive at window 0 (an elastic fleet's
    spare column is off until its machine_addition fires)."""
    alive0 = trace.capacity[0] > 0.0
    prov_cluster = (
        cluster if alive0.all() else paper_cluster(
            tuple(
                int(np.sum(cluster.machine_types[alive0] == t))
                for t in range(cluster.profile.n_machine_types)
            )
        )
    )
    return provision_schedule(topo, prov_cluster, provision_rate)


def _ledger_complete(ctl: OnlineController, online) -> bool:
    """Acceptance: every accepted AND rejected replan is in the audit
    ledger with a full guard breakdown, and accepted decisions match the
    replans the executor actually applied."""
    guard = [d for d in ctl.ledger if d.has_guard_breakdown]
    return bool(
        len(ctl.ledger.accepted) == int((online.migrations > 0).sum())
        and all(
            d.moves > 0
            and abs(d.cost - (d.move_cost + d.state_cost)) < 1e-9
            and d.candidate_moves
            for d in guard
        )
    )


def run_scenario(topo, spec: TraceSpec, provision_rate: float, cluster,
                 config: RuntimeConfig) -> dict:
    trace = spec.compile(cluster, seed=SEED, utg=topo)
    start_etg = _start_etg(topo, trace, provision_rate, cluster)
    oracle_config = ORACLE_CONFIG

    t0 = time.perf_counter()
    static = StreamExecutor(start_etg, cluster, trace, config=config).run()
    t_static = time.perf_counter() - t0

    ctl = OnlineController(topo, cluster, period=10)
    t0 = time.perf_counter()
    online = StreamExecutor(start_etg, cluster, trace, config=config).run(
        controller=ctl
    )
    t_online = time.perf_counter() - t0

    t0 = time.perf_counter()
    oracle = StreamExecutor(
        start_etg, cluster, trace, config=oracle_config
    ).run(controller=OracleRescheduler(topo, cluster))
    t_oracle = time.perf_counter() - t0

    s_static = static.sustained_throughput()
    s_online = online.sustained_throughput()
    s_oracle = oracle.sustained_throughput()
    row = {
        "scenario": trace.name,
        "windows": trace.n_windows,
        "provision_rate": round(provision_rate, 3),
        "sustained_static": round(s_static, 3),
        "sustained_online": round(s_online, 3),
        "sustained_oracle": round(s_oracle, 3),
        "online_vs_static": round(s_online / max(s_static, 1e-9), 3),
        "online_vs_oracle": round(s_online / max(s_oracle, 1e-9), 3),
        "latency_slo_s": SLO_S,
        "latency_slo_static": round(static.latency_slo_frac(SLO_S), 3),
        "latency_slo_online": round(online.latency_slo_frac(SLO_S), 3),
        "latency_slo_oracle": round(oracle.latency_slo_frac(SLO_S), 3),
        "online_migrations": int(online.migrations.sum()),
        "online_replans": int((online.migrations > 0).sum()),
        "oracle_migrations": int(oracle.migrations.sum()),
        "controller_log_tail": [f"w{w}:{msg}" for w, msg in ctl.log[-3:]],
        "ledger_decisions": len(ctl.ledger),
        "ledger_accepted": len(ctl.ledger.accepted),
        "ledger_rejected": len(ctl.ledger.rejected),
        "ledger_complete": _ledger_complete(ctl, online),
        "beats_static": bool(s_online >= s_static),
        "within_10pct_of_oracle": bool(s_online >= 0.9 * s_oracle),
        "static_s": round(t_static, 3),
        "online_s": round(t_online, 3),
        "oracle_s": round(t_oracle, 3),
    }
    if topo.groupings:
        # Ablation on keyed/stateful rows: the state-blind controller
        # prices the same replans flat (no state in the ledger, no pause
        # loss from state shipping) — the pre-ISSUE-8 guard.
        blind = OnlineController(topo, cluster, period=10, state_aware=False)
        res_blind = StreamExecutor(
            start_etg, cluster, trace, config=config
        ).run(controller=blind)
        s_blind = res_blind.sustained_throughput()
        row["sustained_online_blind"] = round(s_blind, 3)
        row["latency_slo_online_blind"] = round(
            res_blind.latency_slo_frac(SLO_S), 3
        )
        row["blind_migrations"] = int(res_blind.migrations.sum())
        row["aware_beats_blind"] = bool(s_online >= s_blind)
        if bool(np.all(trace.capacity == trace.capacity[:1])):
            # The re-keyed-oracle acceptance (ISSUE 8): on a fixed fleet
            # the per-(capacity, skew-epoch) oracle must not lose to the
            # online controller. Elastic keyed rows are exempt — there
            # the oracle replans from scratch at every capacity flip and
            # refine's non-convex landscape can land it in a worse basin
            # than the controller's state-aware inertia holds; the raw
            # sustained numbers stay recorded for inspection.
            row["oracle_not_below_online"] = bool(s_oracle >= 0.99 * s_online)
    return row


def overhead_rows(cluster) -> list[dict]:
    """Recorder-on vs recorder-off CPU time on one shuffle and one keyed
    scenario; ``--check`` gates at < 5%.

    A single run is ~50-150 ms — the same order as scheduler jitter and
    CPU-frequency drift on shared runners, so mean/median ratios flap by
    several percent between invocations.  Off and on runs are therefore
    interleaved one-by-one (drift slower than a run cancels out of each
    sample's ratio of sums), timed with ``time.process_time_ns`` (immune
    to preemption), and the reported overhead is the *minimum* sample
    ratio — the least noise-contaminated measurement, as in min-of-N
    timing.  The gate exists to catch gross instrumentation regressions
    (per-window allocation in the hot loop, accidental always-on wall
    probes); differences below the runner noise floor are not resolvable
    and not what it polices."""
    rows: list[dict] = []
    keyed = keyed_rolling_count_topology(
        n_keys=16, zipf_s=1.5, state_per_tuple=STATE_PER_TUPLE
    )
    for topo, scen_fn in ((linear_topology(), _scenarios),
                          (keyed, _keyed_scenarios)):
        spec, rate, clu, cfg = scen_fn(topo, cluster)[0]
        trace = spec.compile(clu, seed=SEED, utg=topo)
        start_etg = _start_etg(topo, trace, rate, clu)

        def run_once(recorder=None) -> float:
            ctl = OnlineController(topo, clu, period=10, recorder=recorder)
            t0 = time.process_time_ns()
            StreamExecutor(
                start_etg, clu, trace, config=cfg, recorder=recorder
            ).run(controller=ctl)
            return (time.process_time_ns() - t0) / 1e9

        def make_rec():
            return TraceRecorder(
                name=f"overhead-{trace.name}", wall_clock=True
            )

        run_once()  # warm-up: imports, caches, first-touch allocations
        rec = make_rec()
        run_once(rec)
        ratios: list[float] = []
        off_times: list[float] = []
        on_times: list[float] = []
        for _ in range(7):
            t_off = t_on = 0.0
            for _ in range(4):  # interleave singles within the sample
                t_off += run_once()
                rec = make_rec()
                t_on += run_once(rec)
            off_times.append(t_off / 4)
            on_times.append(t_on / 4)
            ratios.append(t_on / max(t_off, 1e-12))
        off = statistics.median(off_times)
        on = statistics.median(on_times)
        frac = min(ratios) - 1.0
        rows.append(
            {
                "scenario": trace.name,
                "recorder_off_s": round(off, 4),
                "recorder_on_s": round(on, 4),
                "overhead_pct": round(100.0 * frac, 2),
                "within_5pct": bool(frac < 0.05),
                "records": len(rec.records),
            }
        )
    return rows


def export_demo_trace(prefix: str, cluster=None) -> tuple[str, str]:
    """One instrumented controller run exported for the CI artifacts.

    Writes ``<prefix>.jsonl`` and ``<prefix>.trace.json`` (Chrome
    trace-event format — open https://ui.perfetto.dev and drag the file
    in); returns the two paths.
    """
    cluster = cluster if cluster is not None else paper_cluster((1, 1, 1))
    topo = linear_topology()
    spec, rate, clu, cfg = _scenarios(topo, cluster)[0]
    trace = spec.compile(clu, seed=SEED, utg=topo)
    start_etg = _start_etg(topo, trace, rate, clu)
    rec = TraceRecorder(name=f"bench_runtime_{trace.name}", wall_clock=True)
    ctl = OnlineController(topo, clu, period=10, recorder=rec)
    StreamExecutor(start_etg, clu, trace, config=cfg, recorder=rec).run(
        controller=ctl
    )
    jsonl_path = f"{prefix}.jsonl"
    chrome_path = f"{prefix}.trace.json"
    to_jsonl(rec, path=jsonl_path)
    to_chrome_trace(rec, path=chrome_path)
    print(f"trace export: {jsonl_path} ({len(rec.records)} records), "
          f"{chrome_path} (Perfetto-loadable)")
    return jsonl_path, chrome_path


def parity_case(topo, cluster):
    """(etg, traces, policies) of the evaluator parity scenario: the refined
    schedule's placement under a ramp and a machine-slowdown trace."""
    full = refine(schedule(topo, cluster, r0=1.0, rate_epsilon=0.05).etg, cluster)
    traces = [
        ramp_trace(0.3 * full.rate, 1.5 * full.rate, n_windows=N_WINDOWS).compile(
            cluster, seed=1
        ),
        slowdown_trace(0.9 * full.rate, machine=2, n_windows=N_WINDOWS).compile(
            cluster, seed=2
        ),
    ]
    return full.etg, traces, full.etg.task_machine()[None, :]


def parity_smoke(topo, cluster) -> dict:
    """JAX scan vs Python loop on a shared scenario (max |diff|)."""
    etg, traces, policies = parity_case(topo, cluster)
    a = evaluate_policies_batch(etg, cluster, traces, policies,
                                backend="numpy")
    b = evaluate_policies_batch(etg, cluster, traces, policies,
                                backend="auto")
    diff = float(np.max(np.abs(a.throughput - b.throughput)))
    lat_diff = float(np.max(np.abs(a.latency() - b.latency())))
    return {
        "max_abs_throughput_diff": diff,
        "max_abs_latency_diff": lat_diff,
        "within_1e9": bool(diff <= 1e-9),
    }


def check(json_path: str) -> int:
    """CI smoke gate: every recorded row must have online >= static, a
    complete replan audit ledger, the keyed ablation rows must not lose
    to the blind controller, the evaluator parity must hold, and the
    recorder overhead rows must stay under 5%."""
    with open(json_path) as f:
        data = json.load(f)
    bad: list[str] = []
    for topo_name, rows in data["scenarios"].items():
        for row in rows:
            tag = f"{topo_name}/{row['scenario']}"
            if not row.get("beats_static", False):
                bad.append(f"{tag}: online < static")
            if not row.get("ledger_complete", False):
                bad.append(f"{tag}: replan audit ledger incomplete")
            if "aware_beats_blind" in row and not row["aware_beats_blind"]:
                bad.append(f"{tag}: state-aware < state-blind")
            if "oracle_not_below_online" in row and not row["oracle_not_below_online"]:
                bad.append(f"{tag}: oracle lost to the online controller")
    parity = data.get("parity", {})
    if not parity.get("within_1e9", False):
        bad.append("parity: JAX evaluator drifted past 1e-9")
    overhead = data.get("overhead", [])
    if not overhead:
        bad.append("overhead: recorder overhead rows missing")
    for row in overhead:
        if not row.get("within_5pct", False):
            bad.append(
                f"overhead/{row['scenario']}: recorder overhead "
                f"{row.get('overhead_pct')}% >= 5%"
            )
    if bad:
        for line in bad:
            print(f"runtime check FAILED: {line}")
        return 1
    n = sum(len(rows) for rows in data["scenarios"].values())
    print(f"runtime check ok: {n} rows, online >= static on all, "
          "ledgers complete, keyed ablation, parity and recorder "
          "overhead hold")
    return 0


def main(json_path: str | None = None, trace_out: str | None = None) -> None:
    cluster = paper_cluster((1, 1, 1))
    results = {}
    for topo_name, topo, scen_fn in (
        ("linear", linear_topology(), _scenarios),
        ("rolling_count", rolling_count_topology(), _scenarios),
        (
            "keyed_rolling_count",
            keyed_rolling_count_topology(
                n_keys=16, zipf_s=1.5, state_per_tuple=STATE_PER_TUPLE
            ),
            _keyed_scenarios,
        ),
    ):
        rows = [
            run_scenario(topo, spec, rate, clu, cfg)
            for spec, rate, clu, cfg in scen_fn(topo, cluster)
        ]
        results[topo_name] = rows
        for row in rows:
            extra = (
                f";blind={row['sustained_online_blind']}"
                if "sustained_online_blind" in row
                else ""
            )
            emit(
                f"runtime_{topo_name}_{row['scenario']}",
                row["online_s"] * 1e6,
                f"online={row['sustained_online']};static={row['sustained_static']};"
                f"oracle={row['sustained_oracle']};migrations={row['online_migrations']};"
                f"slo={row['latency_slo_online']};beats_static={row['beats_static']};"
                f"within_10pct={row['within_10pct_of_oracle']}{extra}",
            )
    parity = parity_smoke(linear_topology(), cluster)
    emit(
        "runtime_eval_parity",
        0.0,
        f"max_diff={parity['max_abs_throughput_diff']:.2e};"
        f"within_1e9={parity['within_1e9']}",
    )
    overhead = overhead_rows(cluster)
    for row in overhead:
        emit(
            f"runtime_obs_overhead_{row['scenario']}",
            row["recorder_on_s"] * 1e6,
            f"off={row['recorder_off_s']}s;on={row['recorder_on_s']}s;"
            f"overhead={row['overhead_pct']}%;within_5pct={row['within_5pct']};"
            f"records={row['records']}",
        )
    if trace_out:
        export_demo_trace(trace_out, cluster)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(
                {"scenarios": results, "parity": parity, "overhead": overhead},
                f,
                indent=2,
            )
            f.write("\n")


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--json", default=None, help="write BENCH_runtime.json here")
    parser.add_argument("--check", default=None, metavar="JSON",
                        help="validate a recorded BENCH_runtime.json and exit")
    parser.add_argument(
        "--trace-out", default=None, metavar="PREFIX",
        help="export one instrumented run as PREFIX.jsonl + PREFIX.trace.json",
    )
    args = parser.parse_args()
    if args.check:
        sys.exit(check(args.check))
    main(json_path=args.json, trace_out=args.trace_out)
