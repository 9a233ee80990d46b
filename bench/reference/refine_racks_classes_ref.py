"""``refine_racks_ref`` with each distinct count matrix of the relocation
and swap menu scored once.

A relocation or a swap changes the placement's count matrix ``cnt[c, w]``
by one instance of component c1 leaving machine x for y and, for a swap,
one of c2 leaving y for x; its score depends on that matrix alone. On a
deployment of many instances per (component, machine) cell most
candidates share their matrix with others: the rack-aware 20/70/90
placement's 801,184 candidates make 118,272 distinct matrices.
``ClassMenu`` keys each candidate by its edit (c1, x, y and, for a swap,
c2), builds each distinct matrix once, rescores it whole on every machine
with ``RackScorer.rate`` in blocks, as ``RackMenu`` does, and hands every
candidate its matrix's score, in the program's menu order. Nothing is
dropped: every machine of every matrix is rescored in float64.

``check`` and ``climb`` are ``refine_racks_ref``'s, on ``ClassMenu``.
"""

from __future__ import annotations

import numpy as np

import refine_ref
from closed_form_racks import RackScorer
from refine_racks_ref import BLOCK, RackMenu


class ClassMenu(RackMenu):
    """``RackMenu``'s scores, each distinct count matrix scored once."""

    def _edits(self):
        sc, T, m, n = self.sc, self.tm.shape[0], self.sc.m, self.sc.n
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
        # (swaps) one of c2 leaves y for x; a relocation takes c2 = n.
        x = np.concatenate([self.tm[r_pos], self.tm[s_a]])
        y = np.concatenate([r_w, self.tm[s_b]])
        c1 = np.concatenate([self.comp[r_pos], self.comp[s_a]])
        c2 = np.concatenate([np.full(r_pos.size, n), self.comp[s_b]])
        key = ((c1 * m + x) * m + y) * (n + 1) + c2
        classes, of = np.unique(key, return_inverse=True)
        rest, k2 = np.divmod(classes, n + 1)
        rest, ky = np.divmod(rest, m)
        k1, kx = np.divmod(rest, m)
        scores = np.empty(classes.size, dtype=sc.dtype)
        base = self.cnt.astype(sc.dtype)                          # counts, exact
        for lo in range(0, classes.size, BLOCK):
            sl = slice(lo, lo + BLOCK)
            k = np.arange(classes[sl].size)
            cnt = np.repeat(base[None], k.size, axis=0)           # (B, n, m)
            np.add.at(cnt, (k, k1[sl], kx[sl]), -1)
            np.add.at(cnt, (k, k1[sl], ky[sl]), 1)
            sw = k[k2[sl] < n]
            np.add.at(cnt, (sw, k2[sl][sw], ky[sl][sw]), -1)
            np.add.at(cnt, (sw, k2[sl][sw], kx[sl][sw]), 1)
            scores[sl] = sc.rate(cnt, self.n_inst) * sc.cir_sum
        self.edit_scores = scores[of]


def climb(sc: RackScorer, assignment, max_rounds: int, tol: float = 1e-9):
    """``refine_racks_ref.climb`` on ``ClassMenu``: the reference in the
    program's place, in ``sc``'s precision."""
    asg = [list(map(int, a)) for a in assignment]
    moves, claimed = [], []
    best = None
    for _ in range(max_rounds):
        menu = ClassMenu(sc, asg)
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
    """``refine_racks_ref.check`` on ``ClassMenu``: move_gap, throughput_dev
    and replay_mismatch of an answer against the float64 reference."""
    moves = list(answer["moves"])
    states, mismatch = refine_ref._replay(sc, start, answer["final"], moves)
    gap = 0.0
    for j in range(len(moves)):
        best = ClassMenu(sc, states[j]).best()
        realised = sc.throughput(states[j + 1])
        claimed = float(answer["claimed"][j])
        # From a state over some machine's memory every offer scores 0; the
        # claim is then the scale of its own error.
        scale = best if best > 0 else max(abs(claimed), realised)
        if scale > 0:
            gap = max(gap, abs(claimed - realised) / scale, (best - realised) / scale)
    if len(moves) < max_rounds:
        menu = ClassMenu(sc, states[-1])
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
