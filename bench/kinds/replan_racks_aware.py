"""Replan after capacity drift on a rack-aware cluster deployed with the
rack-aware cost.

The ``replan_racks`` kind's requests, program and cluster
(``kinds/replan_racks.py``), judged by ``refine_racks_classes_ref``, which
scores each distinct count matrix of a relocation and swap menu once,
whole and on every machine, as ``refine_racks_ref`` scores every
candidate. At the rack-aware deployment (1,434 tasks, ~801k relocations
and swaps a state) that reference fits a run where ``refine_racks_ref``
would take ~20 s a state. The standard error gets the wall time of each
judgement.
"""

from __future__ import annotations

import sys
import time

import cells
import refine_racks_classes_ref

replan_racks = cells.load_module("kinds", "replan_racks")
requests = replan_racks.requests


class Workload(replan_racks.Workload):
    def check(self, request: dict, answer: dict) -> dict:
        t0 = time.perf_counter()
        numbers = refine_racks_classes_ref.check(
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
        moves, claimed, final, thpt = refine_racks_classes_ref.climb(
            self.scorer(request, dtype), self.deployed, self.max_rounds
        )
        return {"moves": moves, "claimed": claimed, "final": final, "throughput": thpt}
