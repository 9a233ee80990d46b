"""Replan after capacity drift on a rack-aware cluster with memory.

The ``replan`` kind's requests and decisions (``kinds/replan.py``), on a
cluster that carries R-Storm's resource model from the configuration's
``racks`` and ``memory`` numbers: the network distance between machines
(racks of ``machines_per_rack`` in machine order), the CPU points each
tuple/s of cut traffic costs per distance unit, and memory per task
against a hard limit per machine. Answers are judged by
``refine_racks_ref``, which rescores every relocation and swap on every
machine; the standard error gets the wall time of each judgement.

The cell needs a program that prices the cut traffic and the memory limit
of a device sweep on the device (``repro.core.sim_jax.device_resources``,
the operand tail of its resource kernels). A program that prices them on
the host takes ~80 s a decision here and ~10 minutes a run, past the time
a run is given, so ``Workload`` refuses it at once and the run exits with
a message and a non-zero code instead of being cut.
"""

from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

import cells
import refine_racks_ref
import system
from closed_form_racks import RackScorer, rack_arrays

replan = cells.load_module("kinds", "replan")
requests = replan.requests


def program_cluster(config: dict):
    """The program's ``Cluster`` of the configuration, with its distance
    matrix, cut-traffic penalty and memory model."""
    from repro.core import rack_distance_matrix

    racks, memory = config["racks"], config["memory"]
    cluster = system.program_cluster(config["cluster"])
    m = cluster.n_machines
    cluster = cluster.with_resources(
        mem_capacity=np.full(m, float(memory["machine_mb"])),
        distance=rack_distance_matrix(
            np.arange(m) // int(racks["machines_per_rack"]),
            same_rack=float(racks["same_rack_distance"]),
            cross_rack=float(racks["cross_rack_distance"]),
        ),
        net_penalty=float(racks["net_penalty"]),
    )
    return dataclasses.replace(
        cluster, profile=cluster.profile.with_mem(np.asarray(memory["task_mb"]))
    )


def require_device_resources() -> None:
    """Exit (code 1, with the reason) when the program has no device
    pricing of cut traffic and memory."""
    from repro.core import sim_jax

    if not hasattr(sim_jax, "device_resources"):
        raise SystemExit(
            "bench: this program prices cut traffic and memory on the host "
            "(no repro.core.sim_jax.device_resources); a run of this cell "
            "would outlast its time limit"
        )


class Workload(replan.Workload):
    def __init__(self, config: dict, traffic: dict):
        require_device_resources()
        super().__init__(config, traffic)
        self.cluster = program_cluster(config)
        self.racks = rack_arrays(config)

    def scorer(self, request: dict, dtype=np.float64) -> RackScorer:
        arrays = system.cluster_arrays(self.config["cluster"], request["capacity"])
        return RackScorer(self.config["topology"], arrays, self.racks, dtype)

    def check(self, request: dict, answer: dict) -> dict:
        t0 = time.perf_counter()
        numbers = refine_racks_ref.check(
            self.scorer(request), self.deployed, answer, self.max_rounds
        )
        print(
            f"reference judged request {request['id']} in "
            f"{time.perf_counter() - t0!r} s",
            file=sys.stderr,
        )
        return numbers

    def control(self, request: dict, dtype) -> dict:
        """The reference climb in ``dtype``, in the program's place."""
        moves, claimed, final, thpt = refine_racks_ref.climb(
            self.scorer(request, dtype), self.deployed, self.max_rounds
        )
        return {"moves": moves, "claimed": claimed, "final": final, "throughput": thpt}
