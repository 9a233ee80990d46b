"""Plain reference of a bounded hill-climb replan on a rack-aware cluster
with memory: ``refine_ref``'s semantics, with every candidate scored by
``closed_form_racks.RackScorer``.

``refine_ref.Menu`` scores a relocation or a swap by patching the two
machines it touches, which holds only while a move changes nothing else.
Under cut traffic a move changes the load of every machine that hosts a
neighbouring component, so ``RackMenu`` builds each relocation's and
swap's whole count matrix and rescores it on every machine, in blocks of
candidates. Growth and drop offers already score whole count matrices
(``refine_ref.Menu``'s own code, through ``RackScorer.rate``), and so do
the replay and the move parsing, which are reused as they are.

``check`` and ``climb`` are ``refine_ref``'s, on ``RackMenu``.
"""

from __future__ import annotations

import numpy as np

import refine_ref
from closed_form_racks import RackScorer

# Candidates whose count matrices are built and scored at a time.
BLOCK = 4096


class RackMenu(refine_ref.Menu):
    """Scores of every move from one placement, in the program's offer
    order, each candidate rescored on every machine."""

    def _edits(self):
        sc, T, m = self.sc, self.tm.shape[0], self.sc.m
        W = np.tile(np.arange(m), (T, 1))
        keep = (W != self.tm[:, None]).ravel()
        r_pos = np.repeat(np.arange(T), m)[keep]
        r_w = W.ravel()[keep]
        a_idx, b_idx = np.triu_indices(T, 1)
        ok = (self.comp[a_idx] != self.comp[b_idx]) & (self.tm[a_idx] != self.tm[b_idx])
        s_a, s_b = a_idx[ok], b_idx[ok]
        self.reloc = (r_pos, r_w)
        self.swap = (s_a, s_b)
        # Each candidate: one instance of component c1 leaves x for y, and
        # (swaps) one of c2 leaves y for x.
        x = np.concatenate([self.tm[r_pos], self.tm[s_a]])
        y = np.concatenate([r_w, self.tm[s_b]])
        c1 = np.concatenate([self.comp[r_pos], self.comp[s_a]])
        c2 = np.concatenate([self.comp[r_pos], self.comp[s_b]])
        is_swap = np.concatenate([np.zeros(r_pos.size, bool), np.ones(s_a.size, bool)])
        scores = np.empty(x.size, dtype=sc.dtype)
        base = self.cnt.astype(sc.dtype)                          # counts, exact
        for lo in range(0, x.size, BLOCK):
            sl = slice(lo, lo + BLOCK)
            k = np.arange(x[sl].size)
            cnt = np.repeat(base[None], k.size, axis=0)           # (B, n, m)
            np.add.at(cnt, (k, c1[sl], x[sl]), -1)
            np.add.at(cnt, (k, c1[sl], y[sl]), 1)
            sw = k[is_swap[sl]]
            np.add.at(cnt, (sw, c2[sl][sw], y[sl][sw]), -1)
            np.add.at(cnt, (sw, c2[sl][sw], x[sl][sw]), 1)
            scores[sl] = sc.rate(cnt, self.n_inst) * sc.cir_sum
        self.edit_scores = scores


def climb(sc: RackScorer, assignment, max_rounds: int, tol: float = 1e-9):
    """``refine_ref.climb`` on ``RackMenu``: the reference in the program's
    place, in ``sc``'s precision."""
    asg = [list(map(int, a)) for a in assignment]
    moves, claimed = [], []
    best = None
    for _ in range(max_rounds):
        menu = RackMenu(sc, asg)
        if best is None:
            best = menu.current
        s = menu.scores()
        i = int(np.argmax(s))
        if not s[i] > best + sc.dtype(tol):
            break
        desc, asg = menu.pick(i)
        best = s[i]
        moves.append(desc)
        claimed.append(float(s[i]))
    return moves, claimed, asg, sc.throughput(asg)


def check(sc: RackScorer, start, answer: dict, max_rounds: int, tol: float = 1e-9) -> dict:
    """``refine_ref.check`` on ``RackMenu``: move_gap, throughput_dev and
    replay_mismatch of an answer against the float64 reference
    (``throughput_dev`` is 1 where the returned placement is infeasible and
    reported otherwise)."""
    moves = list(answer["moves"])
    # The replay scores only growth placements (its forward branch, where a
    # DROP is among the moves), from whole count matrices: valid here.
    states, mismatch = refine_ref._replay(sc, start, answer["final"], moves)
    gap = 0.0
    for j in range(len(moves)):
        best = RackMenu(sc, states[j]).best()
        realised = sc.throughput(states[j + 1])
        claimed = float(answer["claimed"][j])
        # From a state over some machine's memory every offer scores 0; the
        # claim is then the scale of its own error.
        scale = best if best > 0 else max(abs(claimed), realised)
        if scale > 0:
            gap = max(gap, abs(claimed - realised) / scale, (best - realised) / scale)
    if len(moves) < max_rounds:
        menu = RackMenu(sc, states[-1])
        best = menu.best()
        if best > 0:
            gap = max(gap, (best - float(menu.current) - tol) / best)
    final = sc.throughput(answer["final"])
    reported = float(answer["throughput"])
    # A returned placement over some machine's memory scores 0: any other
    # reported throughput is off by all of it.
    dev = abs(reported - final) / final if final > 0 else float(reported != final)
    return {
        "move_gap": max(gap, 0.0),
        "throughput_dev": dev,
        "replay_mismatch": float(mismatch),
    }

