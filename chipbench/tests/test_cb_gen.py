"""The benchmark's generators: graph, stationary stream, query traffic."""

import numpy as np
import pytest

from chipbench import gen
from chipbench.tests import tiny


@pytest.fixture(scope="module")
def graph():
    return gen.make_graph(tiny.CONFIG, 2**31 + 11, holdout=0.2)


def test_graph_from_the_seed(graph):
    again = gen.make_graph(tiny.CONFIG, 2**31 + 11, holdout=0.2)
    assert all(np.array_equal(a, b) for a, b in zip(graph[1:5], again[1:5]))
    key = graph.src.astype(np.int64) * graph.num_vertices + graph.dst
    assert np.all(np.diff(key) > 0)                 # sorted, no duplicates
    assert np.all(graph.src != graph.dst)
    deg = np.bincount(graph.src, minlength=graph.num_vertices)
    assert deg.max() <= tiny.CONFIG["max_out_degree"]
    assert 0.15 < 1 - graph.live.mean() < 0.25


def test_vertex_ids_say_nothing_of_degree(graph):
    """Graph500 relabels the Kronecker vertices at random: without it
    the low ids would hold about 3/4 of the out-edges (A + B = 0.76)."""
    deg = np.bincount(graph.src, minlength=graph.num_vertices)
    half = graph.num_vertices // 2
    assert 0.4 < deg[:half].sum() / deg.sum() < 0.6
    src, _ = gen.rmat_edges(9, 16, a=0.57, b=0.19, c=0.19, seed=2**31 + 11)
    assert np.mean(src < half) > 0.7                # the unrelabelled ids


@pytest.mark.parametrize("gap", [0, 96])
def test_stationary_stream_invariants(graph, gap):
    s = gen.StationaryStream(graph, tiny.CONFIG["capacity"],
                             gen.rng_for(3, 2), gap=gap)
    live = graph.live.copy()
    n_live, n_pool = s.n_live, s.n_pool
    freed_at = {}
    lane = 0
    for _ in range(40):
        ins, u, v, w, ids = s.batch(16, 16)
        assert not live[ids[ins]].any()              # inserts are absent
        assert live[ids[~ins]].all()                 # deletes are live
        assert len(np.unique(ids)) == len(ids)
        assert np.array_equal(u, graph.src[ids]) and np.array_equal(
            w, graph.w[ids])
        for e in ids[ins]:                           # the gap is kept
            assert e not in freed_at or lane - freed_at[e] >= gap
        live[ids[ins]] = True
        deg_round = np.bincount(graph.src[live], minlength=512)
        assert deg_round.max() <= tiny.CONFIG["capacity"]
        live[ids[~ins]] = False
        lane += len(ids)
        for e in ids[~ins]:
            freed_at[e] = lane
        assert (s.n_live, s.n_pool + sum(len(c[1]) for c in s.cooling)) \
            == (n_live, n_pool)                      # sizes are constant
    assert live.sum() == n_live


def test_apply_lanes_is_in_lane_order():
    live = np.zeros(4, bool)
    gen.apply_lanes(live, np.array([True, False, True, True]),
                    np.array([1, 1, 1, 2]))
    assert live.tolist() == [False, True, True, False]


def test_zipf_and_poisson_from_the_seed():
    cand = np.arange(10, 5010)
    a = gen.zipf_starts(gen.rng_for(7, 1), cand, 20000, 1.0)
    b = gen.zipf_starts(gen.rng_for(7, 1), cand, 20000, 1.0)
    assert np.array_equal(a, b) and np.isin(a, cand).all()
    _, counts = np.unique(a, return_counts=True)
    top = np.sort(counts)[::-1]
    h = np.sum(1.0 / np.arange(1, len(cand) + 1))
    assert abs(top[0] / len(a) - 1 / h) < 0.02        # rank 1 takes 1/H_N
    assert top[0] / top[1] == pytest.approx(2.0, rel=0.25)
    t = gen.poisson_times(gen.rng_for(7, 2), 400.0, 30.0)
    assert np.array_equal(t, gen.poisson_times(gen.rng_for(7, 2), 400.0,
                                               30.0))
    assert np.all(np.diff(t) > 0) and t[-1] < 30.0
    assert abs(len(t) - 12000) < 5 * np.sqrt(12000)
    gaps = np.diff(t)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.05)
