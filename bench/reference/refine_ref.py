"""Plain reference of a bounded hill-climb replan (``refine``'s semantics).

One round scores every move of the menu from the current placement and
applies the first best one, if it beats the current throughput by more
than ``tol``:

- RELOCATE: one instance to any other machine;
- SWAP: two instances of different components on different machines;
- ADD / GROW k=2..4: k more instances of one component, each placed on
  the machine that scores best at that step (first best in index order);
- PAIRGROW (a, b) for a, b in {1, 2}: a instances of ci then b of cj > ci,
  placed the same greedy way;
- DROP: one instance of a component that has two or more.

New instances go to the end of their component's list; DROP removes
the instance at its index. Moves are named as the program names them, so
an answer's move list can be replayed here.

``check`` judges an answer (its moves, the score it claimed for each, its
final placement and reported throughput) against this reference in
float64. ``climb`` is the reference put in the program's place, in any
precision: in float32 it is the control that ``check`` must refuse.
"""

from __future__ import annotations

import re

import numpy as np

from closed_form import Scorer

PAIRS = ((1, 1), (2, 1), (1, 2), (2, 2))


def _flat(assignment):
    comp = np.concatenate(
        [np.full(len(a), c, dtype=np.int64) for c, a in enumerate(assignment)]
    )
    tm = np.concatenate([np.asarray(a, dtype=np.int64) for a in assignment])
    return comp, tm


class Menu:
    """Scores of every move from one placement, in the program's offer
    order: relocations, swaps, adds, grows, pairgrows, drops."""

    def __init__(self, sc: Scorer, assignment):
        self.sc = sc
        self.asg = [list(map(int, a)) for a in assignment]
        self.n_inst = np.array([len(a) for a in self.asg], dtype=np.int64)
        self.cnt = sc.counts(self.asg)
        self.comp, self.tm = _flat(self.asg)
        self.offsets = np.concatenate([[0], np.cumsum(self.n_inst)])
        self.current = self.sc.rate(self.cnt, self.n_inst) * sc.cir_sum
        self._edits()
        self._growth()
        self._drops()

    # Relocations and swaps change the counts on two machines only: their
    # limits are recomputed from those two count columns, and every other
    # machine keeps its limit, exactly as a full recomputation would.
    def _edits(self):
        sc, T, m = self.sc, self.tm.shape[0], self.sc.m
        var, met = sc.loads(self.cnt, self.n_inst)
        lim, bad = sc.limits(var, met, sc.cap)
        order = np.argsort(lim, kind="stable")[:3]
        n_bad = int(bad.sum())

        W = np.tile(np.arange(m), (T, 1))
        keep = (W != self.tm[:, None]).ravel()
        r_pos = np.repeat(np.arange(T), m)[keep]
        r_w = W.ravel()[keep]
        a_idx, b_idx = np.triu_indices(T, 1)
        ok = (self.comp[a_idx] != self.comp[b_idx]) & (
            self.tm[a_idx] != self.tm[b_idx]
        )
        s_a, s_b = a_idx[ok], b_idx[ok]
        self.reloc = (r_pos, r_w)
        self.swap = (s_a, s_b)
        # Each candidate: machines x (source) and y (target), with the
        # count changes of up to two components.
        x = np.concatenate([self.tm[r_pos], self.tm[s_a]])
        y = np.concatenate([r_w, self.tm[s_b]])
        c1 = np.concatenate([self.comp[r_pos], self.comp[s_a]])
        c2 = np.concatenate([self.comp[r_pos], self.comp[s_b]])
        is_swap = np.concatenate([np.zeros(r_pos.size, bool), np.ones(s_a.size, bool)])
        scores = np.empty(x.size, dtype=sc.dtype)
        for lo in range(0, x.size, 8192):
            sl = slice(lo, lo + 8192)
            xs, ys, a, b, sw = x[sl], y[sl], c1[sl], c2[sl], is_swap[sl]
            k = np.arange(xs.size)
            cx = self.cnt[:, xs].T.copy()             # (B, n) counts on x
            cy = self.cnt[:, ys].T.copy()
            cx[k, a] -= 1
            cy[k, a] += 1
            cx[k[sw], b[sw]] += 1
            cy[k[sw], b[sw]] -= 1
            lims = []
            bads = []
            for cols, mach in ((cx, xs), (cy, ys)):
                u = sc.cir / self.n_inst.astype(sc.dtype)
                cf = cols.astype(sc.dtype)
                v = (cf * sc.E[:, mach].T * u).sum(axis=1, dtype=sc.dtype)
                mt = (cf * sc.M[:, mach].T).sum(axis=1, dtype=sc.dtype)
                li, bi = sc.limits(v, mt, sc.cap[mach])
                lims.append(li)
                bads.append(bi)
            rest = np.full(xs.size, np.inf, dtype=sc.dtype)
            for j in order[::-1]:
                outside = (xs != j) & (ys != j)
                rest = np.where(outside, lim[j], rest)
            # Base-infeasible machines other than x and y keep the row at 0.
            bad_rest = (n_bad - bad[xs].astype(int) - bad[ys].astype(int)) > 0
            r = np.minimum(rest, np.minimum(lims[0], lims[1]))
            r = np.maximum(r, sc.dtype(0))
            r = np.where(bad_rest | bads[0] | bads[1], sc.dtype(0), r)
            scores[sl] = r * sc.cir_sum
        self.edit_scores = scores

    def _grow_chain(self, adds):
        """Greedy placements and score after each step of adding ``adds``."""
        sc = self.sc
        cnt, n_inst = self.cnt.copy(), self.n_inst.copy()
        placements, scores = [], []
        for c in adds:
            n_inst[c] += 1
            trial = np.repeat(cnt[None], sc.m, axis=0)
            trial[np.arange(sc.m), c, np.arange(sc.m)] += 1
            s = sc.rate(trial, n_inst) * sc.cir_sum
            w = int(np.argmax(s))
            cnt = trial[w]
            placements.append((c, w))
            scores.append(s[w])
        return placements, scores

    def _growth(self):
        n = self.sc.n
        self.grow = []   # (desc, score, placements)
        singles = [self._grow_chain([c] * 4) for c in range(n)]
        for c, (pl, s) in enumerate(singles):
            self.grow.append((f"add c{c}->m{pl[0][1]}", s[0], pl[:1]))
        for c, (pl, s) in enumerate(singles):
            for k in (2, 3, 4):
                self.grow.append((f"grow c{c}x{k}", s[k - 1], pl[:k]))
        for ci in range(n):
            for cj in range(ci + 1, n):
                for a, b in PAIRS:
                    pl, s = self._grow_chain([ci] * a + [cj] * b)
                    self.grow.append((f"pairgrow c{ci}x{a}+c{cj}x{b}", s[-1], pl))

    def _drops(self):
        sc = self.sc
        self.drop = []   # (desc, score, (c, k))
        for c in range(sc.n):
            nk = int(self.n_inst[c])
            if nk < 2:
                continue
            machines = np.asarray(self.asg[c])
            trial = np.repeat(self.cnt[None], nk, axis=0)
            trial[np.arange(nk), c, machines] -= 1
            n_new = self.n_inst.copy()
            n_new[c] -= 1
            s = sc.rate(trial, n_new) * sc.cir_sum
            k = int(np.argmax(s))
            self.drop.append((f"drop c{c}#{k}", s[k], (c, k)))

    def scores(self) -> np.ndarray:
        """Every offer's score, in offer order."""
        return np.concatenate([
            self.edit_scores,
            np.array([g[1] for g in self.grow], dtype=self.sc.dtype),
            np.array([d[1] for d in self.drop], dtype=self.sc.dtype),
        ])

    def best(self) -> float:
        return float(self.scores().max())

    def pick(self, index: int):
        """(desc, new assignment) of the offer at ``index``."""
        asg = [list(a) for a in self.asg]
        n_reloc = self.reloc[0].size
        n_edit = self.edit_scores.size
        if index < n_reloc:
            p, w = int(self.reloc[0][index]), int(self.reloc[1][index])
            c = int(self.comp[p])
            k, src = p - int(self.offsets[c]), int(self.tm[p])
            asg[c][k] = w
            return f"relocate c{c}#{k} m{src}->m{w}", asg
        if index < n_edit:
            pa = int(self.swap[0][index - n_reloc])
            pb = int(self.swap[1][index - n_reloc])
            ca, cb = int(self.comp[pa]), int(self.comp[pb])
            ka, kb = pa - int(self.offsets[ca]), pb - int(self.offsets[cb])
            asg[ca][ka], asg[cb][kb] = asg[cb][kb], asg[ca][ka]
            return f"swap c{ca}#{ka}<->c{cb}#{kb}", asg
        index -= n_edit
        if index < len(self.grow):
            desc, _, placements = self.grow[index]
            for c, w in placements:
                asg[c].append(w)
            return desc, asg
        desc, _, (c, k) = self.drop[index - len(self.grow)]
        del asg[c][k]
        return desc, asg

    def placements_of(self, desc: str):
        for d, _, pl in self.grow:
            if d == desc:
                return pl
        return None


def climb(sc: Scorer, assignment, max_rounds: int, tol: float = 1e-9):
    """The reference in the program's place: (moves, claimed scores, final
    assignment, reported throughput), all in ``sc``'s precision."""
    asg = [list(map(int, a)) for a in assignment]
    moves, claimed = [], []
    best = None
    for _ in range(max_rounds):
        menu = Menu(sc, asg)
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


_RE = {
    "relocate": re.compile(r"relocate c(\d+)#(\d+) m(\d+)->m(\d+)$"),
    "swap": re.compile(r"swap c(\d+)#(\d+)<->c(\d+)#(\d+)$"),
    "add": re.compile(r"add c(\d+)->m(\d+)$"),
    "grow": re.compile(r"grow c(\d+)x(\d+)$"),
    "pairgrow": re.compile(r"pairgrow c(\d+)x(\d+)\+c(\d+)x(\d+)$"),
    "drop": re.compile(r"drop c(\d+)#(\d+)$"),
}


def _parse(desc: str):
    for kind, rx in _RE.items():
        hit = rx.match(desc)
        if hit:
            return kind, tuple(int(g) for g in hit.groups())
    raise ValueError(f"unknown move {desc!r}")


def _undo(asg, desc):
    """State before ``desc`` from the state after it; the number of
    entries that disagree with the move (0 when it replays cleanly)."""
    kind, g = _parse(desc)
    bad = 0
    if kind == "relocate":
        c, k, src, w = g
        bad += asg[c][k] != w
        asg[c][k] = src
    elif kind == "swap":
        ca, ka, cb, kb = g
        asg[ca][ka], asg[cb][kb] = asg[cb][kb], asg[ca][ka]
    elif kind == "add":
        c, w = g
        bad += asg[c].pop() != w
    elif kind == "grow":
        c, k = g
        del asg[c][-k:]
    elif kind == "pairgrow":
        ci, a, cj, b = g
        del asg[cj][-b:]
        del asg[ci][-a:]
    return bad


def _replay(sc: Scorer, start, final, moves):
    """States S_0..S_K of an answer, and how many entries disagree.

    Every move but DROP can be undone from the state after it, so the
    states are rebuilt backwards from the final placement and S_0 is
    checked against the start. With a DROP among the moves they are
    rebuilt forwards from the start instead, growth placed greedily by the
    reference, and the end is checked against the final placement."""
    if not any(d.startswith("drop") for d in moves):
        states = [[list(map(int, a)) for a in final]]
        bad = 0
        for desc in reversed(moves):
            prev = [list(a) for a in states[0]]
            bad += _undo(prev, desc)
            states.insert(0, prev)
        return states, bad + _differ(states[0], start)
    states = [[list(map(int, a)) for a in start]]
    bad = 0
    for desc in moves:
        asg = [list(a) for a in states[-1]]
        kind, g = _parse(desc)
        if kind == "relocate":
            c, k, src, w = g
            bad += asg[c][k] != src
            asg[c][k] = w
        elif kind == "swap":
            ca, ka, cb, kb = g
            asg[ca][ka], asg[cb][kb] = asg[cb][kb], asg[ca][ka]
        elif kind == "drop":
            c, k = g
            del asg[c][k]
        else:
            for c, w in Menu(sc, asg).placements_of(desc):
                asg[c].append(w)
        states.append(asg)
    return states, bad + _differ(states[-1], final)


def _differ(a, b) -> int:
    if [len(x) for x in a] != [len(x) for x in b]:
        return sum(max(len(x), len(y)) for x, y in zip(a, b))
    return int(sum(int(np.sum(np.asarray(x) != np.asarray(y))) for x, y in zip(a, b)))


def check(sc: Scorer, start, answer: dict, max_rounds: int, tol: float = 1e-9) -> dict:
    """Numbers that compare an answer with the reference (float64 ``sc``).

    ``answer``: moves, claimed (score per move), final (assignment) and
    throughput (as reported).

    - move_gap: widest gap, relative to the reference's best offer at that
      step, by which an applied move's realised throughput lies below that
      best, or its claimed score lies from its realised throughput; and,
      where the climb stopped before ``max_rounds``, by which the best
      offer left beat the final placement by more than ``tol``.
    - throughput_dev: reported against recomputed final throughput.
    - replay_mismatch: placement entries the move list cannot account for.
    """
    moves = list(answer["moves"])
    states, mismatch = _replay(sc, start, answer["final"], moves)
    gap = 0.0
    for j, desc in enumerate(moves):
        best = Menu(sc, states[j]).best()
        realised = sc.throughput(states[j + 1])
        claimed = float(answer["claimed"][j])
        gap = max(gap, abs(claimed - realised) / best, (best - realised) / best)
    if len(moves) < max_rounds:
        menu = Menu(sc, states[-1])
        best = menu.best()
        if best > 0:
            gap = max(gap, (best - float(menu.current) - tol) / best)
    final = sc.throughput(answer["final"])
    return {
        "move_gap": max(gap, 0.0),
        "throughput_dev": abs(float(answer["throughput"]) - final) / final,
        "replay_mismatch": float(mismatch),
    }
