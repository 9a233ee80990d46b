"""Batched policy evaluation: B traces × P policies in one ``lax.scan``.

Vectorizes the ``StreamExecutor`` window step the same way
``sim_jax.simulate_batch_jax`` vectorizes the §6.3 simulator: the loop
state is ``(backlog (B,P,T), prev_out (B,P,n), throttle (B,P))``, the scan
consumes the stacked per-window trace arrays, and the topology recurrence
unrolls over the (few) components with the structure baked in statically.
Per-machine scatter/gather run as one-hot einsum contractions against a
precomputed (P, T, m) placement tensor. Fields-grouped edges route through
per-key share grids — dense (W, B, N) expansions of each realization
segment's hash→instance map — threaded through the scan as per-window
inputs, so keyed runs stay bit-compatible with the Python loop.

Everything runs in float64 (inside ``jax.enable_x64(True)``; emulated on
TPU): the window step is the exact formula sequence of
``StreamExecutor.run`` (no controller, no migrations — this is the
*static-policy* sweep evaluator), so the backends agree to ~1e-9 over
hundreds of windows; the NumPy backend loops the reference executor over
every (trace, policy) pair and is the reference.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.graph import ExecutionGraph
from repro.core.profiles import Cluster
from repro.obs.trace import record_dispatch

from repro.runtime_stream.executor import RuntimeConfig, StreamExecutor
from repro.runtime_stream.traces import CompiledTrace

__all__ = ["PolicyEvalResult", "evaluate_policies_batch"]


@dataclasses.dataclass(frozen=True)
class PolicyEvalResult:
    """Windowed metrics for every (trace b, policy p) pair.

    Shapes: (B, P, W) unless noted. ``sustained`` is the mean throughput
    of the trailing half of the horizon, matching
    ``RuntimeResult.sustained_throughput()``.
    """

    throughput: np.ndarray
    admitted: np.ndarray
    dropped: np.ndarray
    queue_total: np.ndarray
    throttle: np.ndarray
    machine_util_mean: np.ndarray  # (B, P, m) mean over windows
    sustained: np.ndarray          # (B, P)
    window_s: float = 1.0          # trace dt, for the derived latency view

    def latency(self) -> np.ndarray:
        """(B, P, W) Little's-law end-to-end latency estimate per window —
        the same derived view as ``RuntimeResult.latency`` (queued tuples
        over drain rate, capped at the horizon), so batch sweeps and the
        Python executor report one latency definition."""
        horizon = self.throughput.shape[-1] * self.window_s
        with np.errstate(divide="ignore", invalid="ignore"):
            lat = np.where(
                self.queue_total > 0.0,
                self.queue_total / np.maximum(self.throughput, 1e-300),
                0.0,
            )
        return np.minimum(lat, horizon)

    def latency_slo_frac(self, slo_s: float, tail_frac: float = 0.5) -> np.ndarray:
        """(B, P) fraction of trailing-``tail_frac`` windows within the
        latency SLO — mirrors ``RuntimeResult.latency_slo_frac``."""
        W = self.throughput.shape[-1]
        start = int(W * (1.0 - tail_frac))
        return (self.latency()[..., start:] <= slo_s).mean(axis=-1)


def _validate(
    etg: ExecutionGraph,
    cluster: Cluster,
    traces: list[CompiledTrace],
    policies: np.ndarray,
) -> np.ndarray:
    policies = np.asarray(policies, dtype=np.int64)
    T = etg.total_tasks
    if policies.ndim != 2 or policies.shape[1] != T:
        raise ValueError("policies must be (P, T) task->machine rows")
    if policies.size and (
        policies.min() < 0 or policies.max() >= cluster.n_machines
    ):
        # Negative indices would wrap silently through the profile gathers
        # and the one-hot scatter, yielding plausible-looking wrong metrics.
        raise ValueError("policy machine indices must lie in [0, n_machines)")
    if not traces:
        raise ValueError("need at least one trace")
    W = traces[0].n_windows
    want_edges = {g.edge for g in etg.utg.groupings}
    for tr in traces:
        if tr.n_windows != W or tr.window_s != traces[0].window_s:
            raise ValueError("traces must share n_windows and window_s")
        if tr.capacity.shape[1] != cluster.n_machines:
            raise ValueError("trace capacity grid does not match the cluster")
        if {kt.edge for kt in tr.keyed} != want_edges:
            raise ValueError(
                "trace keyed edges do not match the topology's fields "
                "groupings — compile every trace with utg=etg.utg"
            )
    return policies


def _edge_share_grid(tr, edge: tuple[int, int], n_inst: int) -> np.ndarray:
    """(W, n_inst) per-window instance shares of one fields edge (dense
    realization-segment expansion of the hash→instance map)."""
    kt = next(k for k in tr.keyed if k.edge == edge)
    per_seg = np.stack([r.shares(n_inst) for _, r in kt.segments])
    return per_seg[kt.segment_indices(tr.n_windows)]


def evaluate_policies_batch(
    etg: ExecutionGraph,
    cluster: Cluster,
    traces: list[CompiledTrace],
    policies: np.ndarray,
    config: RuntimeConfig | None = None,
    backend: str = "auto",
    external_load: np.ndarray | None = None,
) -> PolicyEvalResult:
    """Run every trace against every static placement in one sweep.

    Args:
      etg: supplies the topology and instance counts (its own assignment
        is ignored — placements come in as ``policies`` rows, like
        ``simulate_batch``).
      cluster: the cluster; each trace's capacity grid modulates it.
      traces: B compiled traces sharing one horizon (W windows, same dt).
      policies: (P, T) machine index per task per candidate placement.
      config: event-loop constants (must match the Python executor's for
        parity comparisons).
      backend: ``"numpy"`` (reference: the Python executor per pair),
        ``"jax"`` (one jitted ``lax.scan``, ~1e-9 agreement), or
        ``"auto"`` (JAX). The resolution lands in the active ``repro.obs``
        recorder's dispatch log.
      external_load: optional (W, m) or (m,) load held by co-tenants of
        the shared machines, subtracted (clipped at zero) from every
        trace's capacity grid before evaluation — the tenant dimension of
        the batch evaluator, matching ``StreamExecutor(background_load=)``.
    """
    if backend not in ("auto", "numpy", "jax"):
        raise ValueError(f"unknown backend {backend!r}")
    config = config or RuntimeConfig()
    if external_load is not None and traces:
        import dataclasses as _dc

        bg = np.asarray(external_load, dtype=np.float64)
        shape = traces[0].capacity.shape
        if bg.ndim == 1:
            bg = np.broadcast_to(bg, shape)
        if bg.shape != shape:
            raise ValueError(
                f"external_load must be (m,) or match the (W, m) capacity grid {shape}"
            )
        traces = [
            _dc.replace(tr, capacity=np.clip(tr.capacity - bg, 0.0, None))
            for tr in traces
        ]
    policies = _validate(etg, cluster, traces, policies)
    requested = backend
    if backend == "auto":
        backend = "jax"
    record_dispatch(
        requested, backend, "policy_eval", len(traces) * policies.size,
        cluster.n_machines, "evaluate_policies_batch",
    )
    if backend == "numpy":
        return _evaluate_numpy(etg, cluster, traces, policies, config)
    return _evaluate_jax(etg, cluster, traces, policies, config)


def _policy_etg(etg: ExecutionGraph, row: np.ndarray) -> ExecutionGraph:
    comp = etg.task_component()
    return ExecutionGraph(
        utg=etg.utg,
        n_instances=etg.n_instances.copy(),
        assignment=[row[comp == c] for c in range(etg.utg.n_components)],
    )


def _evaluate_numpy(etg, cluster, traces, policies, config) -> PolicyEvalResult:
    """Reference backend: the executor, once per (trace, policy) pair."""
    B, P, W = len(traces), policies.shape[0], traces[0].n_windows
    m = cluster.n_machines
    out = {
        k: np.zeros((B, P, W))
        for k in ("throughput", "admitted", "dropped", "queue_total", "throttle")
    }
    util = np.zeros((B, P, m))
    sustained = np.zeros((B, P))
    for b, tr in enumerate(traces):
        for p in range(P):
            res = StreamExecutor(
                _policy_etg(etg, policies[p]), cluster, tr, config=config
            ).run()
            out["throughput"][b, p] = res.throughput
            out["admitted"][b, p] = res.admitted
            out["dropped"][b, p] = res.dropped
            out["queue_total"][b, p] = res.queue_total
            out["throttle"][b, p] = res.throttle
            util[b, p] = res.machine_util.mean(axis=0)
            sustained[b, p] = res.sustained_throughput()
    return PolicyEvalResult(
        machine_util_mean=util,
        sustained=sustained,
        window_s=traces[0].window_s,
        **out,
    )


def _evaluate_jax(etg, cluster, traces, policies, config) -> PolicyEvalResult:
    import jax
    import jax.numpy as jnp

    utg = etg.utg
    n = utg.n_components
    comp = etg.task_component()
    T = comp.shape[0]
    m = cluster.n_machines
    B, P = len(traces), policies.shape[0]
    W = traces[0].n_windows
    dt = traces[0].window_s
    topo = tuple(utg.topo_order())
    sources = frozenset(utg.sources)
    alpha = tuple(float(a) for a in utg.alpha)
    # Fields edges route per key share; only shuffle in-edges stay in the
    # even-split component recurrence. Static per-edge structure: parent,
    # the destination's task block [lo, hi), and a (W, B, N) share grid
    # threaded through the scan as per-window inputs.
    keyed_edges = tuple(g.edge for g in utg.groupings)
    parents = tuple(
        tuple(p for p in utg.parents(i) if (p, i) not in keyed_edges)
        for i in range(n)
    )
    offsets = etg.component_offsets()
    keyed_static = tuple(
        (p, int(offsets[i]), int(offsets[i + 1])) for p, i in keyed_edges
    )
    key_shares = tuple(
        np.stack(
            [_edge_share_grid(tr, (p, i), int(etg.n_instances[i])) for tr in traces],
            axis=1,
        )  # (W, B, N)
        for p, i in keyed_edges
    )

    ttypes = utg.component_types[comp]
    mtypes = cluster.machine_types[policies]             # (P, T)
    e = cluster.profile.e[ttypes[None, :], mtypes]       # (P, T)
    met = cluster.profile.met[ttypes[None, :], mtypes]
    onehot = np.zeros((P, T, m), dtype=np.float64)
    onehot[np.arange(P)[:, None], np.arange(T)[None, :], policies] = 1.0
    n_task = etg.n_instances.astype(np.float64)[comp]          # (T,)

    rates = np.stack([tr.rates for tr in traces], axis=1)          # (W, B)
    caps = np.stack([tr.capacity for tr in traces], axis=1)        # (W, B, m)

    cfg = config

    def step(carry, xs):
        with jax.named_scope("policy_eval"):
            return _window(carry, xs)

    def _window(carry, xs):
        backlog, prev_out, throttle = carry       # (B,P,T) (B,P,n) (B,P)
        r_t, cap, shares_t = xs                   # (B,) (B,m) tuple of (B,N)
        r_adm = r_t[:, None] * throttle           # (B,P)
        # 1. Arrivals (one hop per window): even split for spout injection
        # and shuffle edges, then each fields edge adds its keyed
        # contribution at the window's hash shares — same composition
        # order as the Python executor's arr_inst.
        arr = [None] * n
        for i in topo:
            if i in sources:
                arr[i] = r_adm
            else:
                a = jnp.zeros_like(r_adm)
                for p_ in parents[i]:
                    a = a + alpha[p_] * prev_out[:, :, p_]
                arr[i] = a
        arr_n = jnp.stack(arr, axis=2)            # (B,P,n)
        arr_task = arr_n[:, :, comp] / n_task[None, None, :]
        for (p_, lo, hi), s_e in zip(keyed_static, shares_t):
            contrib = alpha[p_] * prev_out[:, :, p_]          # (B,P)
            arr_task = arr_task.at[:, :, lo:hi].add(
                contrib[:, :, None] * s_e[:, None, :]
            )
        backlog = backlog + arr_task * dt
        over = jnp.clip(backlog - cfg.max_queue, 0.0, None)
        backlog = backlog - over
        dropped = over.sum(axis=2) / dt
        # 2. Service under proportional fair machine throttling.
        desired = backlog / dt
        var_w = jnp.einsum("bpt,ptm->bpm", e[None] * desired, onehot)
        met_w = jnp.broadcast_to(
            jnp.einsum("pt,ptm->pm", met, onehot)[None], (B, P, m)
        )
        head = jnp.maximum(cap[:, None, :] - met_w, 0.0)
        s = jnp.where(var_w > head, head / jnp.maximum(var_w, 1e-300), 1.0)
        s_task = jnp.einsum("bpm,ptm->bpt", s, onehot)
        processed = desired * s_task
        backlog = jnp.maximum(backlog - processed * dt, 0.0)
        alive_task = jnp.einsum("bm,ptm->bpt", (cap > 0.0).astype(e.dtype), onehot)
        tcu = e[None] * processed + met[None] * alive_task
        prev_out = jnp.stack(
            [processed[:, :, comp == c].sum(axis=2) for c in range(n)], axis=2
        )
        # 3. Metrics + spout back-pressure for the next window.
        util = jnp.einsum("bpt,ptm->bpm", tcu, onehot)
        q_frac = backlog.max(axis=2) / cfg.max_queue
        throttle_next = jnp.where(
            q_frac > cfg.bp_high,
            jnp.maximum(cfg.throttle_min, throttle * cfg.throttle_down),
            jnp.where(
                q_frac < cfg.bp_low,
                jnp.minimum(1.0, throttle * cfg.throttle_up),
                throttle,
            ),
        )
        metrics = (
            processed.sum(axis=2),
            r_adm,
            dropped,
            backlog.sum(axis=2),
            throttle,
            util,
        )
        return (backlog, prev_out, throttle_next), metrics

    # Named by what it computes: the module ``jit_policy_eval`` in a profiler
    # trace, its scan body under the ``policy_eval`` scope.
    @jax.jit
    def policy_eval(rates, caps, key_shares):
        carry0 = (
            jnp.zeros((B, P, T)),
            jnp.zeros((B, P, n)),
            jnp.ones((B, P)),
        )
        _, ms = jax.lax.scan(step, carry0, (rates, caps, key_shares))
        return ms

    with jax.enable_x64(True):
        thpt, adm, drp, qtot, thr, util = policy_eval(rates, caps, key_shares)

    def wbp(x):  # (W, B, P) -> (B, P, W)
        return np.asarray(x).transpose(1, 2, 0)

    thpt = wbp(thpt)
    start = W // 2  # == RuntimeResult.sustained_throughput's tail split
    return PolicyEvalResult(
        throughput=thpt,
        admitted=wbp(adm),
        dropped=wbp(drp),
        queue_total=wbp(qtot),
        throttle=wbp(thr),
        machine_util_mean=np.asarray(util).mean(axis=0),
        sustained=thpt[:, :, start:].mean(axis=2),
        window_s=dt,
    )
