"""The plain reference: the semantics of a dynamic biased random walk on
the benchmark's own host model of the graph, in numpy.

It imports nothing of the program and takes nothing the program made.
Its model is the edge universe of ``gen.Graph`` with a live mask per
generation; the program's outputs (paths, row digests, the rows of a
sample of vertices) are compared against it:

* a path is sound when it starts at its start, every hop is a live edge
  of the generation it names, an ended walk stays ended, and a DeepWalk
  walk ends only at a vertex with no live out-edge;
* the hops follow the exact transition law ``w(u, v) / sum_x w(u, x)``
  (with PPR's stop as one more outcome of probability ``stop_prob``).
  Each step gets a randomised probability-integral transform, which is
  uniform on [0, 1) under that law whatever order the program keeps its
  rows in; ``transition_z`` is the chi-square of those values over 64
  bins, as a z-score;
* an updated graph holds, per vertex, exactly the live edges of the
  model with their biases (an order-free digest of each row), and each
  sampled vertex's sampling space encodes its edges: radix group sizes
  and digit sums, member lists, and an inter-group alias table whose
  group probabilities are ``|G_k| 2^k / sum w``.
"""

from __future__ import annotations

import numpy as np

from chipbench.gen import Graph

PIT_BINS = 64
U32 = np.uint32
# Group type codes of the program's state (``gtype``): an empty group,
# and a dense one, which stores no member list and is sampled by
# rejection over the whole row.
GTYPE_EMPTY, GTYPE_DENSE = 0, 1


def fmix32(h):
    """murmur3's 32-bit finaliser on uint32 arrays (numpy)."""
    h = h ^ (h >> U32(16))
    h = h * U32(0x85EBCA6B)
    h = h ^ (h >> U32(13))
    h = h * U32(0xC2B2AE35)
    return h ^ (h >> U32(16))


def edge_hash(v, w):
    """Order-free row digest term of edge ``(., v)`` with bias ``w``."""
    return fmix32(np.asarray(v).astype(U32) * U32(0x9E3779B1)
                  + fmix32(np.asarray(w).astype(U32) ^ U32(0x5BD1E995)))


def transition_z(pit: np.ndarray):
    """Chi-square of the PIT values over ``PIT_BINS`` equal bins, as a
    z-score ``(X2 - df) / sqrt(2 df)``; about N(0, 1) for sound walks.
    None where the steps are too few to test (a run that took so few
    has failed already)."""
    n = len(pit)
    if n < 100 * PIT_BINS:
        return None
    counts = np.bincount(np.minimum((pit * PIT_BINS).astype(np.int64),
                                    PIT_BINS - 1), minlength=PIT_BINS)
    e = n / PIT_BINS
    x2 = float(np.sum((counts - e) ** 2) / e)
    df = PIT_BINS - 1
    return (x2 - df) / np.sqrt(2 * df)


class Reference:
    """Host model of one cell's graph."""

    def __init__(self, g: Graph):
        self.g = g
        V = g.num_vertices
        self.V = V
        self.keys = g.src.astype(np.int64) * V + g.dst
        self.row_ptr = np.searchsorted(g.src, np.arange(V + 1))
        # inside each row, the PIT orders edges by (bias, dst)
        self.order = np.lexsort((g.dst, g.w, g.src))
        self.pos = np.empty(len(g.src), np.int64)
        self.pos[self.order] = np.arange(len(g.src))

    def edge_ids(self, u, v):
        """Edge id of each ``(u, v)``, -1 where the universe lacks it."""
        key = u.astype(np.int64) * self.V + v
        idx = np.minimum(np.searchsorted(self.keys, key), len(self.keys) - 1)
        return np.where(self.keys[idx] == key, idx, -1)

    def live_degree(self, live):
        return np.bincount(self.g.src[live], minlength=self.V)

    def _cdf(self, live, weights=None):
        """Per-row cumulative live weight in PIT order."""
        w = self.g.w if weights is None else weights
        wl = np.where(live[self.order], w[self.order], 0).astype(np.int64)
        cum = np.cumsum(wl)
        ends = cum[np.maximum(self.row_ptr - 1, 0)]
        base = np.where(self.row_ptr > 0, ends, 0)
        return wl, cum, base[:-1], base[1:] - base[:-1]

    def check_walks(self, live, paths, starts, stop_prob, rng):
        """Soundness count and PIT values of ``paths`` against the graph
        ``live``.  Returns ``(bad, steps, pit)``."""
        paths = np.asarray(paths, np.int64)
        cur, nxt = paths[:, :-1], paths[:, 1:]
        bad = int(np.sum(paths[:, 0] != np.asarray(starts)))
        bad += int(np.sum((cur < 0) & (nxt >= 0)))
        deg = self.live_degree(live)
        at = cur >= 0
        c = np.where(at, cur, 0)
        open_ = at & (deg[c] > 0)               # a step is due here
        bad += int(np.sum(at & ~open_ & (nxt >= 0)))
        stop = open_ & (nxt < 0)
        hop = open_ & (nxt >= 0)
        if stop_prob == 0:
            bad += int(stop.sum())
        u, v = cur[hop], nxt[hop]
        eid = self.edge_ids(u, v)
        ok = eid >= 0
        ok[ok] = live[eid[ok]]
        bad += int(np.sum(~ok))
        u, eid = u[ok], eid[ok]
        wl, cum, base, tot = self._cdf(live)
        p = self.pos[eid]
        lo = (cum[p] - wl[p] - base[u]).astype(np.float64)
        q = float(stop_prob)
        hop_pit = q + (1 - q) * (lo + rng.random(len(u)) * wl[p]) / tot[u]
        n_stop = int(stop.sum()) if q > 0 else 0
        pit = np.concatenate([hop_pit, rng.random(n_stop) * q])
        return bad, int(open_.sum()), pit

    # -- updates --------------------------------------------------------------

    def row_digest(self, live, weights=None):
        """Per-vertex ``(sum of edge_hash mod 2**32, live degree)``."""
        w = self.g.w if weights is None else weights
        h = edge_hash(self.g.dst[live], w[live]).astype(np.float64)
        # a row sums at most 2**10 terms below 2**32: exact in float64
        s = np.bincount(self.g.src[live], weights=h, minlength=self.V)
        return (np.mod(s, 2.0 ** 32).astype(np.uint64).astype(U32),
                self.live_degree(live).astype(np.int64))

    def check_space(self, live, verts, rows, num_groups, weights=None):
        """Sampling space of the sampled vertices ``verts`` against their
        live edges.  ``rows`` holds the program's tables for them
        (``nbr, bias, deg, gsize, digitsum, gtype, gmem, prob, alias``).
        Returns ``(vertices at fault, widest alias-probability gap)``."""
        w_all = self.g.w if weights is None else weights
        K = num_groups
        bad = 0
        gap = 0.0
        bits = np.int64(1) << np.arange(K, dtype=np.int64)
        for i, u in enumerate(verts):
            lo, hi = self.row_ptr[u], self.row_ptr[u + 1]
            w = w_all[lo:hi][live[lo:hi]].astype(np.int64)
            member = (w[:, None] & bits[None, :]) != 0           # (d, K)
            size = member.sum(0)
            d = int(rows["deg"][i])
            ok = d == len(w)
            ok &= np.array_equal(rows["gsize"][i], size)
            ok &= np.array_equal(rows["digitsum"][i], size)   # base 2
            row_bias = rows["bias"][i][:d].astype(np.int64)
            slots = (row_bias[:, None] & bits[None, :]) != 0
            for k in range(K):
                t = int(rows["gtype"][i][k])
                if size[k] == 0:
                    ok &= t == GTYPE_EMPTY
                elif t not in (GTYPE_EMPTY, GTYPE_DENSE):
                    got = rows["gmem"][i][k]
                    want = np.flatnonzero(slots[:, k])
                    ok &= (np.array_equal(np.sort(got[:size[k]]), want)
                           and bool(np.all(got[size[k]:] < 0)))
            bad += not ok
            if len(w):
                total = float(w.sum())
                want_p = size * bits / total
                prob = rows["prob"][i].astype(np.float64)[:K]
                alias = rows["alias"][i][:K]
                got_p = prob.copy()
                np.add.at(got_p, alias, 1.0 - prob)
                gap = max(gap, float(np.max(np.abs(got_p / K - want_p))))
        return bad, gap

    # -- the reference as a walker (the control runs it in low precision) ----

    def walk(self, live, starts, length, stop_prob, rng, weights=None):
        """Walks drawn by the plain law on ``live`` (with ``weights`` in
        place of the biases, when given)."""
        wl, cum, base, tot = self._cdf(live, weights)
        W = len(starts)
        paths = np.full((W, length + 1), -1, np.int64)
        paths[:, 0] = starts
        cur = np.asarray(starts, np.int64)
        for t in range(length):
            at = cur >= 0
            c = np.where(at, cur, 0)
            go = at & (tot[c] > 0)
            if stop_prob:
                go &= rng.random(W) >= stop_prob
            r = base[c] + np.floor(rng.random(W) * tot[c]).astype(np.int64)
            p = np.minimum(np.searchsorted(cum, r, side="right"),
                           len(cum) - 1)
            cur = np.where(go, self.g.dst[self.order[p]], -1)
            paths[:, t + 1] = cur
        return paths



def int8_biases(ref: Reference, live) -> np.ndarray:
    """The control's biases: each row's biases scaled to 8 bits by the
    row's largest live bias, rounded, at least 1."""
    g = ref.g
    wmax = np.zeros(ref.V, np.int64)
    np.maximum.at(wmax, g.src[live], g.w[live])
    scale = np.maximum(wmax[g.src], 1) / 255.0
    return np.maximum(np.rint(g.w / scale), 1).astype(np.int64)
