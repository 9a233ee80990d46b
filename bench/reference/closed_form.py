"""Plain closed-form throughput of a stream placement (paper eq. 5 and 6).

Independent of the program under test: it reads only the numbers of a
configuration file and a placement given as per-component machine lists.

A placement is scored from its count matrix ``cnt[c, w]`` (instances of
component c on machine w) and instance counts ``n[c]``. Every instance of
component c takes ``cir[c] / n[c]`` of the topology's input rate R
(shuffle grouping, eq. 6), so machine w carries

    load_w(R) = met_w + R * var_w,
    var_w = sum_c cnt[c, w] * e[c, w] * cir[c] / n[c],
    met_w = sum_c cnt[c, w] * met[c, w],

and the largest sustainable rate is ``R* = min_w (cap_w - met_w) / var_w``
over machines with ``var_w > 0``: 0 when some machine's fixed load alone
exceeds its capacity. Throughput is the sum of every task's processing
rate at R*, ``R* * sum_c cir[c]``. ``dtype`` sets the precision of every
step, so the same code computes the float64 reference and its float32
control.
"""

from __future__ import annotations

import numpy as np


def unit_rates(n_components: int, edges, alpha, dtype=np.float64) -> np.ndarray:
    """(n,) component input rate per unit of topology input rate: 1 at
    every source, ``sum alpha[a] * cir[a]`` over in-edges elsewhere."""
    parents = [[a for a, b in edges if b == c] for c in range(n_components)]
    cir = np.zeros(n_components, dtype=dtype)
    done = [False] * n_components
    while not all(done):
        for c in range(n_components):
            if done[c] or not all(done[p] for p in parents[c]):
                continue
            if parents[c]:
                cir[c] = sum(
                    (dtype(alpha[p]) * cir[p] for p in parents[c]), dtype(0)
                )
            else:
                cir[c] = 1
            done[c] = True
    return cir


class Scorer:
    """Closed form of one topology on one cluster, in one precision."""

    def __init__(self, topology: dict, cluster: dict, dtype=np.float64):
        self.dtype = dtype
        types = np.asarray(topology["component_types"], dtype=np.int64)
        self.n = types.shape[0]
        self.cir = unit_rates(self.n, topology["edges"], topology["alpha"], dtype)
        mtypes = np.asarray(cluster["machine_types"], dtype=np.int64)
        self.m = mtypes.shape[0]
        e = np.asarray(cluster["e_points"], dtype=np.float64)
        met = np.asarray(cluster["met_points"], dtype=np.float64)
        self.E = e[types][:, mtypes].astype(dtype)        # (n, m)
        self.M = met[types][:, mtypes].astype(dtype)      # (n, m)
        self.cap = np.asarray(cluster["capacity"], dtype=np.float64).astype(dtype)
        self.cir_sum = self.cir.sum(dtype=dtype)

    def counts(self, assignment) -> np.ndarray:
        """(n, m) instance count matrix of a placement."""
        cnt = np.zeros((self.n, self.m), dtype=np.int64)
        for c, machines in enumerate(assignment):
            np.add.at(cnt[c], np.asarray(machines, dtype=np.int64), 1)
        return cnt

    def loads(self, cnt: np.ndarray, n_inst: np.ndarray):
        """(var, met) per machine for count matrices ``cnt`` (..., n, m)
        with instance counts ``n_inst`` (..., n)."""
        u = self.cir / np.asarray(n_inst).astype(self.dtype)
        c = cnt.astype(self.dtype)
        var = (c * self.E * u[..., :, None]).sum(axis=-2, dtype=self.dtype)
        met = (c * self.M).sum(axis=-2, dtype=self.dtype)
        return var, met

    def limits(self, var: np.ndarray, met: np.ndarray, cap: np.ndarray):
        """(per-machine rate limit, per-machine infeasible flag)."""
        head = cap - met
        safe = np.where(var > 0, var, self.dtype(1))
        lim = np.where(var > 0, head / safe, self.dtype(np.inf))
        return lim, head < 0

    def rate(self, cnt: np.ndarray, n_inst: np.ndarray) -> np.ndarray:
        """R* of count matrices (..., n, m)."""
        var, met = self.loads(cnt, n_inst)
        lim, bad = self.limits(var, met, self.cap)
        r = np.maximum(lim.min(axis=-1), self.dtype(0))
        return np.where(bad.any(axis=-1), self.dtype(0), r)

    def throughput(self, assignment) -> float:
        cnt = self.counts(assignment)
        n_inst = np.array([len(a) for a in assignment])
        return float(self.rate(cnt, n_inst) * self.cir_sum)

