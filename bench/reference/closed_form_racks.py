"""Plain closed form of a placement on a rack-aware cluster with memory
(R-Storm's resource model, arXiv 1904.05456): CPU load, cut traffic priced
by network distance, and memory as a hard limit per machine.

Independent of the program under test: it reads only the numbers of a
configuration file. A placement is scored from its count matrix
``cnt[c, w]`` as a whole, on every machine. To ``closed_form.Scorer``'s
CPU load (shuffle grouping: each instance of c takes ``cir[c] / n[c]`` of
the topology's input rate R) it adds the cut traffic: along every edge
(a, b) each instance of a sends ``alpha[a] * cir[a] / n[a]`` per unit of R,
split over b's instances by their share of b's input, and a flow between
machines v and w at distance ``D[v, w]`` costs ``net_penalty * D[v, w]``
CPU points per tuple/s on each of its two machines. Per machine w, with
``send[c, w]`` and ``recv[c, w]`` the masses the instances of c on w send
and receive,

    net_w = net_penalty * sum_(a, b) (send[a, w] * (D recv[b])[w]
                                      + recv[b, w] * (D send[a])[w]),

so ``load_w(R) = met_w + R * (var_w + net_w)`` and ``R* = min_w (cap_w -
met_w) / (var_w + net_w)``. Memory is rate-independent: a placement whose
instances' memory exceeds some machine's capacity scores 0. ``dtype`` sets
the precision of every step, as in ``closed_form``.
"""

from __future__ import annotations

import numpy as np

from closed_form import Scorer


def rack_arrays(config: dict) -> dict:
    """The configuration's distance matrix, per-component memory demand and
    per-machine memory capacity: machines in index order fill racks of
    ``machines_per_rack``; distance 0 on a machine, ``same_rack_distance``
    within a rack, ``cross_rack_distance`` across racks."""
    racks, memory = config["racks"], config["memory"]
    m = int(sum(config["cluster"]["counts"]))
    rack = np.arange(m) // int(racks["machines_per_rack"])
    distance = np.where(
        rack[:, None] == rack[None, :],
        float(racks["same_rack_distance"]),
        float(racks["cross_rack_distance"]),
    )
    np.fill_diagonal(distance, 0.0)
    types = np.asarray(config["topology"]["component_types"], dtype=np.int64)
    return {
        "distance": distance,
        "net_penalty": float(racks["net_penalty"]),
        "mem": np.asarray(memory["task_mb"], dtype=np.float64)[types],
        "mem_capacity": np.full(m, float(memory["machine_mb"])),
    }


class RackScorer(Scorer):
    """Closed form of one topology on one rack-aware cluster with memory,
    in one precision."""

    def __init__(self, topology: dict, cluster: dict, racks: dict, dtype=np.float64):
        super().__init__(topology, cluster, dtype)
        self.edges = [tuple(int(x) for x in e) for e in topology["edges"]]
        self.alpha = np.asarray(topology["alpha"], dtype=np.float64).astype(dtype)
        self.D = np.asarray(racks["distance"], dtype=np.float64).astype(dtype)
        self.penalty = dtype(racks["net_penalty"])
        self.mem = np.asarray(racks["mem"], dtype=np.float64).astype(dtype)
        self.mem_capacity = np.asarray(racks["mem_capacity"], dtype=np.float64).astype(dtype)

    def loads(self, cnt: np.ndarray, n_inst: np.ndarray):
        """(var, met) per machine, as ``Scorer.loads``, each in one pass."""
        c = np.asarray(cnt, dtype=self.dtype)
        u = self.cir / np.asarray(n_inst).astype(self.dtype)
        var = np.einsum("...nm,...n,nm->...m", c, u, self.E)
        met = np.einsum("...nm,nm->...m", c, self.M)
        return var, met

    def net(self, cnt: np.ndarray, n_inst: np.ndarray) -> np.ndarray:
        """(..., m) cut-traffic load per unit rate of count matrices
        ``cnt`` (..., n, m) with instance counts ``n_inst`` (..., n): along
        edge (a, b) every pair of an instance of a on v and one of b on w
        carries ``alpha[a] * u[a] * share[b]`` and charges ``D[v, w]``
        times it to both v and w."""
        dt = self.dtype
        c = np.asarray(cnt, dtype=dt)
        u = self.cir / np.asarray(n_inst).astype(dt)                      # (..., n)
        share = np.where(self.cir > 0, u / np.where(self.cir > 0, self.cir, dt(1)), dt(0))
        # dc[..., x, w] = sum_v D[w, v] * c[..., x, v]
        dc = (c.reshape(-1, self.m) @ self.D.T).reshape(c.shape)
        acc = np.zeros(c.shape[:-2] + (self.m,), dtype=dt)
        for a, b in self.edges:
            pair = (self.alpha[a] * u[..., a] * share[..., b])[..., None]
            acc += pair * (c[..., a, :] * dc[..., b, :] + c[..., b, :] * dc[..., a, :])
        return acc * self.penalty

    def rate(self, cnt: np.ndarray, n_inst: np.ndarray) -> np.ndarray:
        """R* of count matrices (..., n, m)."""
        var, met = self.loads(cnt, n_inst)
        lim, bad = self.limits(var + self.net(cnt, n_inst), met, self.cap)
        mem = np.einsum("...nm,n->...m", np.asarray(cnt, dtype=self.dtype), self.mem)
        bad = bad.any(axis=-1) | (mem > self.mem_capacity).any(axis=-1)
        r = np.maximum(lim.min(axis=-1), self.dtype(0))
        return np.where(bad, self.dtype(0), r)
