"""Inputs of every cell, made on the host from ``--seed``.

The graph generator and the bias rule are copies of the program's
``graph/rmat.py`` (``rmat_edges``, ``degree_bias``), kept here so that a
change to the program cannot move the yardstick.  The stationary mixed
stream replaces the program's ``graph/streams.make_update_stream``,
whose Python loop and finite insert pool cannot feed a timed window.

Everything is plain numpy and deterministic in the seed.
"""

from __future__ import annotations

from collections import deque
from typing import NamedTuple

import numpy as np

SEED_MOD = 1 << 63


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator ``stream`` of ``seed`` (any whole number)."""
    return np.random.default_rng([int(seed) % SEED_MOD, stream])


# ---------------------------------------------------------------------------
# Graph500 Kronecker graph with degree-derived biases
# ---------------------------------------------------------------------------

def rmat_edges(scale, edge_factor, *, a, b, c, seed):
    """Kronecker (R-MAT) edge list with ``2**scale`` vertices: bit-by-bit
    quadrant descent, self-loops removed, duplicates collapsed.  Returns
    ``(src, dst)`` int32, sorted by ``(src, dst)``."""
    rng = rng_for(seed, 0)
    n_edges = edge_factor << scale
    src = np.zeros(n_edges, np.int64)
    dst = np.zeros(n_edges, np.int64)
    ab, abc = a + b, a + b + c
    for _ in range(scale):
        r = rng.random(n_edges)
        right = (r >= a) & (r < ab)          # quadrant b: dst bit set
        down = (r >= ab) & (r < abc)         # quadrant c: src bit set
        both = r >= abc                      # quadrant d: both bits set
        src = (src << 1) | (down | both)
        dst = (dst << 1) | (right | both)
    keep = src != dst
    key = np.unique((src[keep] << np.int64(scale)) | dst[keep])
    return ((key >> np.int64(scale)).astype(np.int32),
            (key & ((1 << scale) - 1)).astype(np.int32))


def degree_bias(src, dst, num_vertices, *, bias_bits):
    """Per-edge integer bias = in-degree of the destination, clipped to
    ``[1, 2**bias_bits)`` (the Bingo paper's §6.1 rule)."""
    deg = np.bincount(dst, minlength=num_vertices)
    return np.clip(deg[dst], 1, (1 << bias_bits) - 1).astype(np.int32)


class Graph(NamedTuple):
    """The edge universe of a cell, sorted by ``(src, dst)``.

    ``live`` marks the edges of the initial graph; the rest form the
    out-pool that the update stream inserts from.  Edge ids index these
    arrays everywhere (stream, reference)."""
    num_vertices: int
    src: np.ndarray      # (E,) int32
    dst: np.ndarray      # (E,) int32
    w: np.ndarray        # (E,) int32
    live: np.ndarray     # (E,) bool
    dropped: int         # edges the per-vertex cap removed


def permute_vertices(src, dst, num_vertices, rng):
    """Relabel the vertices by a seeded permutation, as the Graph500
    generator does, so that a vertex's id says nothing of its degree;
    returns ``(src, dst)`` sorted by ``(src, dst)`` again."""
    perm = rng.permutation(num_vertices)
    key = np.sort((perm[src].astype(np.int64) << np.int64(32))
                  | perm[dst].astype(np.int64))
    return ((key >> np.int64(32)).astype(np.int32),
            (key & np.int64(0xFFFFFFFF)).astype(np.int32))


def make_graph(cfg: dict, seed: int, holdout: float = 0.0) -> Graph:
    """The configuration's graph: Kronecker edges under a seeded vertex
    relabelling, degree biases, at most ``max_out_degree`` edges per
    source (a seeded choice of which), and a seeded ``holdout`` share of
    the edges left out of the initial graph as the out-pool."""
    if cfg["generator"] != "kronecker" or cfg["bias"] != "dst_in_degree":
        raise ValueError(f"unknown generator/bias rule in {cfg['name']}")
    V = 1 << cfg["scale"]
    src, dst = rmat_edges(cfg["scale"], cfg["edge_factor"], a=cfg["a"],
                          b=cfg["b"], c=cfg["c"], seed=seed)
    src, dst = permute_vertices(src, dst, V, rng_for(seed, 5))
    w = degree_bias(src, dst, V, bias_bits=cfg["bias_bits"])
    rng = rng_for(seed, 1)
    # rank of each edge among its source's edges in a seeded order
    order = np.lexsort((rng.random(len(src)), src))
    start = np.searchsorted(src[order], src[order], side="left")
    rank = np.empty(len(src), np.int64)
    rank[order] = np.arange(len(src)) - start
    keep = rank < cfg["max_out_degree"]
    dropped = int(len(src) - keep.sum())
    src, dst, w = src[keep], dst[keep], w[keep]
    live = rng.random(len(src)) >= holdout
    return Graph(V, src, dst, w, live, dropped)


# ---------------------------------------------------------------------------
# The stationary mixed update stream
# ---------------------------------------------------------------------------

def _take(arr, n, k, rng):
    """Remove ``k`` distinct random entries of ``arr[:n]`` in O(k),
    keeping ``arr[:n - k]`` the survivors.  Returns the removed ids."""
    pos = rng.choice(n, size=k, replace=False)
    out = arr[pos].copy()
    n2 = n - k
    tail = np.arange(n2, n)
    arr[pos[pos < n2]] = arr[tail[~np.isin(tail, pos)]]
    return out


class StationaryStream:
    """Mixed insert/delete batches that leave the graph's size and the
    out-pool's size unchanged (§6.1's mixed mode, made stationary).

    Each batch deletes ``n_del`` distinct live edges and inserts
    ``n_ins`` edges drawn without replacement from the out-pool,
    interleaved by a seeded shuffle.  Three rules keep every lane
    applicable, so that no operation fails by construction:

    * inserts skip a source whose row would exceed ``capacity``, with
      the row's length counted before any delete of the same round;
    * deletes are drawn from the live edges as they stood before the
      batch, inserts from the pool as it stood before it;
    * a deleted edge returns to the pool, and its slot counts as free,
      only once the stream has moved ``gap`` lanes past the batch, so
      an update round that coalesces consecutive batches (at most
      ``gap`` lanes) never inserts an edge it also deletes.
    """

    def __init__(self, graph: Graph, capacity: int, rng, gap: int = 0):
        self.g = graph
        self.capacity = capacity
        self.rng = rng
        self.gap = gap
        self.live = np.flatnonzero(graph.live).astype(np.int64)
        self.pool = np.flatnonzero(~graph.live).astype(np.int64)
        self.n_live = len(self.live)
        self.n_pool = len(self.pool)
        self.live = np.concatenate([self.live, np.empty(len(self.pool),
                                                        np.int64)])
        self.pool = np.concatenate([self.pool, np.empty(len(self.live),
                                                        np.int64)])
        # row length counted with inserts at once, deletes after the gap
        self.deg_hi = np.bincount(graph.src[graph.live],
                                  minlength=graph.num_vertices)
        self.cooling = deque()       # (release_lane, deleted ids)
        self.lanes = 0

    def _release(self):
        while self.cooling and self.cooling[0][0] <= self.lanes:
            _, ids = self.cooling.popleft()
            self.pool[self.n_pool:self.n_pool + len(ids)] = ids
            self.n_pool += len(ids)
            np.subtract.at(self.deg_hi, self.g.src[ids], 1)

    def batch(self, n_ins: int, n_del: int):
        """One batch: ``(is_insert, u, v, w, edge_ids)`` host arrays."""
        self._release()
        g, rng = self.g, self.rng
        dels = _take(self.live, self.n_live, n_del, rng)
        self.n_live -= n_del
        ins = np.empty(0, np.int64)
        while len(ins) < n_ins:
            k = min(self.n_pool, 2 * (n_ins - len(ins)) + 64)
            if k == 0:
                raise ValueError("out-pool exhausted: raise the holdout")
            cand = _take(self.pool, self.n_pool, k, rng)
            self.n_pool -= k
            u = g.src[cand]
            order = np.argsort(u, kind="stable")
            first = np.searchsorted(u[order], u[order], side="left")
            rank = np.empty(k, np.int64)
            rank[order] = np.arange(k) - first
            ok = self.deg_hi[u] + rank < self.capacity
            ok &= np.cumsum(ok) <= n_ins - len(ins)
            np.add.at(self.deg_hi, u[ok], 1)
            ins = np.concatenate([ins, cand[ok]])
            back = cand[~ok]                      # untaken: to the pool
            self.pool[self.n_pool:self.n_pool + len(back)] = back
            self.n_pool += len(back)
        self.live[self.n_live:self.n_live + n_ins] = ins
        self.n_live += n_ins
        n = n_ins + n_del
        self.cooling.append((self.lanes + n + self.gap, dels))
        self.lanes += n
        ids = np.concatenate([ins, dels])
        is_insert = np.arange(n) < n_ins
        perm = rng.permutation(n)
        ids, is_insert = ids[perm], is_insert[perm]
        return (is_insert, g.src[ids], g.dst[ids], g.w[ids], ids)


def apply_lanes(live: np.ndarray, is_insert, ids) -> None:
    """The plain semantics of a run of update lanes on a live mask, in
    lane order: an edge ends as its last lane leaves it."""
    _, last = np.unique(ids[::-1], return_index=True)
    idx = len(ids) - 1 - last
    live[ids[idx]] = is_insert[idx]


# ---------------------------------------------------------------------------
# Query traffic
# ---------------------------------------------------------------------------

def zipf_starts(rng, candidates: np.ndarray, n: int, s: float):
    """``n`` start vertices, Zipf(``s``) over a seeded ranking of
    ``candidates``."""
    ranking = rng.permutation(candidates)
    p = 1.0 / np.arange(1, len(ranking) + 1, dtype=np.float64) ** s
    cdf = np.cumsum(p)
    idx = np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right")
    return ranking[np.minimum(idx, len(ranking) - 1)].astype(np.int32)


def poisson_times(rng, rate: float, seconds: float) -> np.ndarray:
    """Arrival times of a Poisson process of ``rate`` per second over
    ``[0, seconds)``."""
    n = int(rate * seconds * 1.5 + 10 * np.sqrt(rate * seconds) + 10)
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, n))])
    return t[t < seconds]
