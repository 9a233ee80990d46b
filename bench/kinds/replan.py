"""Replan after capacity drift: the online controller's bounded refine.

Requests (``requests``): a pool of ``pool`` brown-outs drawn from the
traffic file's ``pool_seed``, each slowing ``slow_machines`` distinct
machines, drawn uniformly, to ``factor`` of their capacity (the drift
``slowdown_trace`` applies); ``--seed`` draws the order of the cycle.
Every seed serves the same pool, because every new brown-out is a new
climb with candidate batches of new sizes, each a program to compile.

The program answers a request with ``refine(deployed, cluster.with_capacity
(cap), max_rounds)`` on its default backend, as ``OnlineController`` does,
with a ``TraceRecorder`` attached as the controller attaches its own: the
recorder's dispatch log feeds the per-layer metrics, and its per-round
span carries the score the program claimed for each move it applied.
"""

from __future__ import annotations

import numpy as np

import refine_ref
from closed_form import Scorer
from system import cluster_arrays, program_cluster, program_placement, program_topology


def requests(config: dict, traffic: dict, seed: int) -> list[dict]:
    """The pool in the order ``seed`` draws; each request keeps its pool
    index under ``"id"``."""
    full = cluster_arrays(config["cluster"])["capacity"]
    pool = []
    for i in range(int(traffic["pool"])):
        rng = np.random.default_rng(int(traffic["pool_seed"]) + i)
        slow = rng.choice(full.shape[0], size=int(traffic["slow_machines"]), replace=False)
        cap = full.copy()
        cap[slow] *= float(traffic["factor"])
        pool.append({"slow": sorted(int(w) for w in slow), "capacity": cap.tolist()})
    order = np.random.default_rng(seed).permutation(len(pool))
    return [dict(pool[i], id=int(i)) for i in order]


class Workload:
    def __init__(self, config: dict, traffic: dict):
        self.config = config
        self.max_rounds = int(config["replan"]["max_rounds"])
        self.topology = program_topology(config["topology"])
        self.deployed = config["deployed"]
        self.start = program_placement(self.topology, self.deployed)
        self.cluster = program_cluster(config["cluster"])
        self.limits = traffic["limits"]

    def decide(self, request: dict):
        """(answer, tally): the tally's ``sweeps`` are the (backend, regime)
        of every scoring sweep the decision dispatched."""
        from repro.core import refine
        from repro.obs import TraceRecorder

        rec = TraceRecorder(name="replan")
        cluster = self.cluster.with_capacity(np.asarray(request["capacity"]))
        res = refine(self.start, cluster, max_rounds=self.max_rounds, recorder=rec)
        claimed = [
            r["args"]["score"]
            for r in rec.records
            if r["name"] == "refine.round" and r["args"].get("move") is not None
        ]
        answer = {
            "moves": list(res.moves),
            "claimed": claimed,
            "final": [a.tolist() for a in res.etg.assignment],
            "throughput": float(res.throughput),
        }
        return answer, {"sweeps": [(d.backend, d.regime) for d in rec.dispatch_log]}

    def scorer(self, request: dict, dtype=np.float64) -> Scorer:
        arrays = cluster_arrays(self.config["cluster"], request["capacity"])
        return Scorer(self.config["topology"], arrays, dtype)

    def check(self, request: dict, answer: dict) -> dict:
        return refine_ref.check(
            self.scorer(request), self.deployed, answer, self.max_rounds
        )

    def control(self, request: dict, dtype) -> dict:
        """The reference climb in ``dtype``, in the program's place."""
        moves, claimed, final, thpt = refine_ref.climb(
            self.scorer(request, dtype), self.deployed, self.max_rounds
        )
        return {"moves": moves, "claimed": claimed, "final": final, "throughput": thpt}
