"""Random-walk application tests: DeepWalk, node2vec, PPR (paper §2.2)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.dyngraph import BingoConfig, from_edges
from repro.core import walks
from tests.conftest import empirical_dist, random_graph, tv_distance


def _cycle_graph(V=6, w=1):
    src = np.arange(V, dtype=np.int32)
    dst = (src + 1) % V
    return src, dst, np.full(V, w, np.int32)


def test_deepwalk_shapes_and_validity():
    V, C = 10, 8
    src, dst, w = random_graph(V, C, seed=2)
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=5)
    st = from_edges(cfg, src, dst, w)
    starts = jnp.arange(V, dtype=jnp.int32)
    p = walks.deepwalk(st, cfg, starts, jax.random.key(0), length=12)
    p = np.asarray(p)
    assert p.shape == (V, 13)
    np.testing.assert_array_equal(p[:, 0], np.arange(V))
    # every emitted hop is a real edge of the graph
    adj = {(int(s), int(d)) for s, d in zip(src, dst)}
    for row in p:
        for a, b in zip(row[:-1], row[1:]):
            if b == -1:
                break
            assert (int(a), int(b)) in adj


def test_walk_holds_after_termination():
    # a path graph: walker starting at the tail dead-ends
    src = np.array([0, 1], np.int32)
    dst = np.array([1, 2], np.int32)
    w = np.ones(2, np.int32)
    cfg = BingoConfig(num_vertices=3, capacity=2, bias_bits=2)
    st = from_edges(cfg, src, dst, w)
    p = np.asarray(walks.deepwalk(st, cfg, jnp.array([0], jnp.int32),
                                  jax.random.key(0), length=6))
    np.testing.assert_array_equal(p[0, :3], [0, 1, 2])
    assert (p[0, 3:] == -1).all()


def test_ppr_terminates_geometrically():
    V = 6
    src, dst, w = _cycle_graph(V)
    cfg = BingoConfig(num_vertices=V, capacity=2, bias_bits=2)
    st = from_edges(cfg, src, dst, w)
    B = 4000
    starts = jnp.zeros((B,), jnp.int32)
    p = np.asarray(walks.ppr(st, cfg, starts, jax.random.key(0),
                             max_length=400, stop_prob=1 / 20))
    lengths = (p >= 0).sum(1) - 1
    # E[length] = 20; loose 3-sigma band
    assert 17 < lengths.mean() < 23


def test_node2vec_second_order_distribution():
    # Triangle + pendant: from cur=1 with prev=0, exact n2v probabilities
    # are computable by hand.  Graph (undirected): 0-1, 1-2, 0-2, 1-3.
    src = np.array([0, 1, 1, 2, 0, 2, 1, 3], np.int32)
    dst = np.array([1, 0, 2, 1, 2, 0, 3, 1], np.int32)
    w = np.ones(8, np.int32)
    V = 4
    cfg = BingoConfig(num_vertices=V, capacity=4, bias_bits=2)
    st = from_edges(cfg, src, dst, w)
    p_, q_ = 0.5, 2.0
    # one manual second-order step
    B = 30000
    prev = jnp.zeros((B,), jnp.int32)
    cur = jnp.ones((B,), jnp.int32)
    nxt = walks._n2v_accept(st, cfg, prev, cur, jnp.ones((B,), bool),
                            jax.random.key(0),
                            walks.WalkParams(kind="node2vec", p=p_, q=q_))
    got = empirical_dist(nxt, V)
    # neighbors of 1: {0 (dist0 → 1/p), 2 (dist1, 2∈N(0) → 1), 3 (dist2 → 1/q)}
    f = np.array([1 / p_, 0, 1.0, 1 / q_])
    want = f / f.sum()
    assert tv_distance(got, want) < 0.02


def test_walks_are_deterministic_given_key():
    V, C = 8, 8
    src, dst, w = random_graph(V, C, seed=4)
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=5)
    st = from_edges(cfg, src, dst, w)
    starts = jnp.arange(V, dtype=jnp.int32)
    a = walks.deepwalk(st, cfg, starts, jax.random.key(3), length=8)
    b = walks.deepwalk(st, cfg, starts, jax.random.key(3), length=8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_row_neighbors_matches_per_candidate_membership():
    """node2vec's exact fallback asks, for every slot of the current row,
    whether that neighbor is adjacent to the previous vertex; the sorted
    binary search must answer exactly as the per-candidate row compare,
    including padding (-1) candidates and rows with duplicates."""
    cfg = BingoConfig(num_vertices=16, capacity=8, bias_bits=4)
    rng = np.random.default_rng(5)
    src = rng.integers(0, 16, 90).astype(np.int32)
    dst = rng.integers(0, 16, 90).astype(np.int32)
    state = from_edges(cfg, src, dst, np.ones(90, np.int32))
    prev = jnp.asarray(rng.integers(0, 16, 32), jnp.int32)
    cands = state.nbr[jnp.asarray(rng.integers(0, 16, 32), jnp.int32)]
    got = walks._row_neighbors(state, cfg, prev, cands)
    want = jnp.stack([walks._is_neighbor(state, cfg, prev, cands[:, j])
                      for j in range(cfg.capacity)], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
