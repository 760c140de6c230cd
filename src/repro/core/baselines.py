"""The paper's comparison sampling systems, reimplemented honestly in JAX.

Table 3 of the paper compares BINGO against KnightKing (alias method +
rejection), gSampler (matrix-API ITS-style sampling), and FlowWalker
(reservoir sampling).  Those systems "reload or reconstruct the
corresponding structure after each round of updates" (paper §6.2) — which is
exactly what these baselines do.  All four share BINGO's padded ``(V, C)``
adjacency so that comparisons isolate the *sampling-space* cost:

  * ``AliasBaseline``     — per-vertex O(d)-entry alias table; any update to
    a vertex rebuilds its whole table (KnightKing-style static sampling).
  * ``ITSBaseline``       — per-vertex CDF row; sampling is an O(log d)
    binary search (C-SAW / gSampler-style); insertion appends (O(1)),
    deletion recomputes the row (O(d)).
  * ``RejectionBaseline`` — no auxiliary structure; sample by rejection
    against max-bias (O(d·max w / Σw) expected trips).
  * ``ReservoirBaseline`` — FlowWalker-style weighted reservoir over the
    full neighbor row: O(d) work *per sample*, zero update cost.

Complexity counters (`*_ops`) return the abstract work the complexity table
(paper Table 1) predicts, as exact counts rather than wall-clock times.
Only `tests/test_baselines.py` reaches this module.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.alias import AliasTable, build_alias, sample_alias

__all__ = [
    "AdjState", "adj_from_edges", "adj_insert", "adj_delete",
    "AliasBaseline", "ITSBaseline", "RejectionBaseline", "ReservoirBaseline",
]

_MAX_REJ = 256  # rejection bound before the exact ITS fallback


class AdjState(NamedTuple):
    """Shared padded adjacency (same layout as BingoState's raw rows)."""

    nbr: jax.Array   # (V, C) int32, -1 padded
    w: jax.Array     # (V, C) float32 biases
    deg: jax.Array   # (V,) int32


def adj_from_edges(V: int, C: int, src, dst, w) -> AdjState:
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    w = jnp.asarray(w, jnp.float32)
    order = jnp.argsort(src, stable=True)
    s, d, ww = src[order], dst[order], w[order]
    idx = jnp.arange(s.shape[0], dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    rank = idx - jax.lax.cummax(jnp.where(first, idx, -1), axis=0)
    ok = rank < C
    nbr = jnp.full((V, C), -1, jnp.int32).at[s, rank].set(
        jnp.where(ok, d, -1), mode="drop")
    wm = jnp.zeros((V, C), jnp.float32).at[s, rank].set(
        jnp.where(ok, ww, 0.0), mode="drop")
    deg = jnp.zeros((V,), jnp.int32).at[s].add(ok.astype(jnp.int32),
                                               mode="drop")
    return AdjState(nbr, wm, deg)


def adj_insert(st: AdjState, u, v, w) -> AdjState:
    C = st.nbr.shape[1]
    ok = st.deg[u] < C
    slot = jnp.where(ok, st.deg[u], C)
    return AdjState(
        st.nbr.at[u, slot].set(v, mode="drop"),
        st.w.at[u, slot].set(jnp.asarray(w, jnp.float32), mode="drop"),
        st.deg.at[u].add(ok.astype(jnp.int32)),
    )


def adj_delete(st: AdjState, u, v) -> AdjState:
    """Delete-and-swap on the raw adjacency row (earliest match)."""
    C = st.nbr.shape[1]
    valid = jnp.arange(C, dtype=jnp.int32) < st.deg[u]
    m = (st.nbr[u] == v) & valid
    ok = jnp.any(m)
    slot = jnp.argmax(m).astype(jnp.int32)
    last = st.deg[u] - 1
    last_c = jnp.clip(last, 0, C - 1)
    do = ok & (slot != last)
    nbr = st.nbr.at[u, jnp.where(do, slot, C)].set(st.nbr[u, last_c],
                                                   mode="drop")
    w = st.w.at[u, jnp.where(do, slot, C)].set(st.w[u, last_c], mode="drop")
    nbr = nbr.at[u, jnp.where(ok, last, C)].set(-1, mode="drop")
    w = w.at[u, jnp.where(ok, last, C)].set(0.0, mode="drop")
    return AdjState(nbr, w, st.deg.at[u].add(-ok.astype(jnp.int32)))


def _valid_w(st: AdjState, u):
    C = st.nbr.shape[1]
    valid = jnp.arange(C, dtype=jnp.int32)[None, :] < st.deg[u][:, None]
    return jnp.where(valid, st.w[u], 0.0)


# ---------------------------------------------------------------------------
# Alias method (KnightKing-style)
# ---------------------------------------------------------------------------

class AliasBaseline(NamedTuple):
    adj: AdjState
    table: AliasTable   # (V, C)

    @classmethod
    def build(cls, adj: AdjState) -> "AliasBaseline":
        C = adj.nbr.shape[1]
        valid = (jnp.arange(C, dtype=jnp.int32)[None, :]
                 < adj.deg[:, None])
        return cls(adj, build_alias(jnp.where(valid, adj.w, 0.0)))

    def sample(self, u, key) -> jax.Array:
        u0, u1 = jax.random.uniform(key, (2,) + u.shape)
        rows = jax.tree.map(lambda t: t[u], self.table)
        slot = sample_alias(rows, u0, u1)
        return self.adj.nbr[u, slot]

    def insert(self, u, v, w) -> "AliasBaseline":
        adj = adj_insert(self.adj, u, v, w)
        return self._rebuild_row(adj, u)

    def delete(self, u, v) -> "AliasBaseline":
        adj = adj_delete(self.adj, u, v)
        return self._rebuild_row(adj, u)

    def _rebuild_row(self, adj: AdjState, u) -> "AliasBaseline":
        # O(d) per-update table rebuild — the cost BINGO's O(K) removes.
        row = _valid_w(adj, jnp.asarray(u)[None])[0]
        t = build_alias(row[None])
        return AliasBaseline(adj, AliasTable(
            self.table.prob.at[u].set(t.prob[0]),
            self.table.alias.at[u].set(t.alias[0]),
        ))

    @staticmethod
    def sample_ops(d):
        return jnp.ones_like(d)

    @staticmethod
    def update_ops(d):
        return d


# ---------------------------------------------------------------------------
# Inverse Transform Sampling (C-SAW / gSampler-style)
# ---------------------------------------------------------------------------

class ITSBaseline(NamedTuple):
    adj: AdjState
    cdf: jax.Array      # (V, C) inclusive prefix sums of biases

    @classmethod
    def build(cls, adj: AdjState) -> "ITSBaseline":
        return cls(adj, jnp.cumsum(_valid_w(adj, jnp.arange(adj.nbr.shape[0])),
                                   axis=-1))

    def sample(self, u, key) -> jax.Array:
        c = self.cdf[u]
        x = jax.random.uniform(key, u.shape) * c[..., -1]
        # binary search: first index with cdf > x
        slot = jnp.sum(c <= x[..., None], axis=-1).astype(jnp.int32)
        slot = jnp.minimum(slot, self.adj.nbr.shape[1] - 1)
        return self.adj.nbr[u, slot]

    def insert(self, u, v, w) -> "ITSBaseline":
        # O(1): append bias to the row tail, extend the prefix sum.
        C = self.adj.nbr.shape[1]
        adj = adj_insert(self.adj, u, v, w)
        slot = jnp.where(self.adj.deg[u] < C, self.adj.deg[u], C)
        prev = jnp.where(self.adj.deg[u] > 0,
                         self.cdf[u, jnp.clip(self.adj.deg[u] - 1, 0, C - 1)],
                         0.0)
        cdf = self.cdf.at[u, slot].set(prev + w, mode="drop")
        return ITSBaseline(adj, cdf)

    def delete(self, u, v) -> "ITSBaseline":
        # O(d): the row's prefix sums must be recomputed.
        adj = adj_delete(self.adj, u, v)
        row = _valid_w(adj, jnp.asarray(u)[None])[0]
        return ITSBaseline(adj, self.cdf.at[u].set(jnp.cumsum(row)))

    @staticmethod
    def sample_ops(d):
        return jnp.ceil(jnp.log2(jnp.maximum(d.astype(jnp.float32), 2.0)))

    @staticmethod
    def update_ops(d):
        return d  # deletion path; insertion is O(1)


# ---------------------------------------------------------------------------
# Rejection sampling
# ---------------------------------------------------------------------------

class RejectionBaseline(NamedTuple):
    adj: AdjState
    wmax: jax.Array     # (V,) float32 max bias per row

    @classmethod
    def build(cls, adj: AdjState) -> "RejectionBaseline":
        return cls(adj, _valid_w(adj, jnp.arange(adj.nbr.shape[0])).max(-1))

    def sample(self, u, key) -> jax.Array:
        B = u.shape[0]
        adj, wmax = self.adj, self.wmax
        dg = jnp.maximum(adj.deg[u], 1)

        def cond(c):
            _, _, ok, t = c
            return jnp.any(~ok) & (t < _MAX_REJ)

        def body(c):
            key, slot, ok, t = c
            key, k1, k2 = jax.random.split(key, 3)
            j = jnp.minimum((jax.random.uniform(k1, (B,)) * dg)
                            .astype(jnp.int32), dg - 1)
            accept = (jax.random.uniform(k2, (B,)) * wmax[u]) < adj.w[u, j]
            slot = jnp.where(~ok & accept, j, slot)
            return key, slot, ok | accept, t + 1

        _, slot, ok, _ = jax.lax.while_loop(
            cond, body,
            (key, jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool),
             jnp.int32(0)))
        # exact ITS fallback for pathological rows (keeps the distribution)
        c = jnp.cumsum(_valid_w(adj, u), axis=-1)
        x = jax.random.uniform(jax.random.fold_in(key, 1), (B,)) * c[:, -1]
        fb = jnp.minimum(jnp.sum(c <= x[:, None], axis=-1),
                         adj.nbr.shape[1] - 1).astype(jnp.int32)
        slot = jnp.where(ok, slot, fb)
        return adj.nbr[u, slot]

    def insert(self, u, v, w) -> "RejectionBaseline":
        adj = adj_insert(self.adj, u, v, w)
        return RejectionBaseline(adj, self.wmax.at[u].max(w))

    def delete(self, u, v) -> "RejectionBaseline":
        # O(d): max may shrink, rescan the row.
        adj = adj_delete(self.adj, u, v)
        row = _valid_w(adj, jnp.asarray(u)[None])[0]
        return RejectionBaseline(adj, self.wmax.at[u].set(row.max()))

    @staticmethod
    def sample_ops(d, wmax=None, wsum=None):
        if wmax is None:
            return d  # worst-case bound O(d·max/Σ) with max/Σ ≈ O(1/1)
        return d.astype(jnp.float32) * wmax / jnp.maximum(wsum, 1e-9)

    @staticmethod
    def update_ops(d):
        return d


# ---------------------------------------------------------------------------
# Weighted reservoir (FlowWalker-style)
# ---------------------------------------------------------------------------

class ReservoirBaseline(NamedTuple):
    adj: AdjState

    @classmethod
    def build(cls, adj: AdjState) -> "ReservoirBaseline":
        return cls(adj)

    def sample(self, u, key) -> jax.Array:
        """A-ExpJ weighted reservoir collapsed to its vectorized equivalent.

        Per-candidate exponential race: argmin Exp(1)/w_i over the row —
        distribution identical to weighted sampling, cost O(d) per draw,
        which is exactly the FlowWalker complexity the paper measures
        (Fig. 16(b): O(d) sampling ⇒ the TW blow-up).
        """
        w = _valid_w(self.adj, u)
        e = jax.random.exponential(key, w.shape)
        score = jnp.where(w > 0, e / jnp.maximum(w, 1e-30), jnp.inf)
        slot = jnp.argmin(score, axis=-1).astype(jnp.int32)
        return self.adj.nbr[u, slot]

    def insert(self, u, v, w) -> "ReservoirBaseline":
        return ReservoirBaseline(adj_insert(self.adj, u, v, w))

    def delete(self, u, v) -> "ReservoirBaseline":
        return ReservoirBaseline(adj_delete(self.adj, u, v))

    @staticmethod
    def sample_ops(d):
        return d

    @staticmethod
    def update_ops(d):
        return jnp.ones_like(d)
