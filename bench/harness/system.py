"""The configuration's numbers, as the program's inputs and as plain arrays.

The benchmark builds the program's cluster and topologies from the
configuration file, and hands the reference the same numbers as plain
arrays: nothing the reference reads comes from the program.
"""

from __future__ import annotations

import numpy as np


def cluster_arrays(spec: dict, capacity=None) -> dict:
    """machine_types, capacity, e_points and met_points of a cluster spec:
    ``counts`` machines of each type at ``capacity`` CPU points, with the
    profile's per-tuple times in seconds scaled to CPU points (x100)."""
    types = np.concatenate(
        [np.full(c, t, dtype=np.int64) for t, c in enumerate(spec["counts"])]
    )
    cap = (
        np.full(types.shape, float(spec["capacity"]))
        if capacity is None
        else np.asarray(capacity, dtype=np.float64)
    )
    return {
        "machine_types": types,
        "capacity": cap,
        "e_points": np.asarray(spec["profile"]["e_seconds"], dtype=np.float64) * 100.0,
        "met_points": np.asarray(spec["profile"]["met_points"], dtype=np.float64),
    }


def program_cluster(spec: dict, capacity=None):
    from repro.core import Cluster, Profile

    arrays = cluster_arrays(spec, capacity)
    profile = Profile(
        e=arrays["e_points"],
        met=arrays["met_points"],
        type_names=tuple(spec["profile"]["task_types"]),
        machine_type_names=tuple(spec["profile"]["machine_types"]),
    )
    return Cluster(
        machine_types=arrays["machine_types"],
        capacity=arrays["capacity"],
        profile=profile,
    )


def program_topology(spec: dict):
    from repro.core import UserGraph

    return UserGraph(
        name=spec["name"],
        component_types=np.asarray(spec["component_types"], dtype=np.int64),
        edges=tuple(tuple(e) for e in spec["edges"]),
        alpha=np.asarray(spec["alpha"], dtype=np.float64),
    )


def program_placement(topology, placement: dict):
    from repro.core import ExecutionGraph

    return ExecutionGraph(
        utg=topology,
        n_instances=np.array([len(a) for a in placement], dtype=np.int64),
        assignment=[np.asarray(a, dtype=np.int64) for a in placement],
    )
