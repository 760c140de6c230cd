"""The plain reference, its comparisons, the control in low precision,
and the serving arithmetic."""

import numpy as np
import pytest

from chipbench import gen
from chipbench.drivers.serve import due_latency_ms, visible_ms
from chipbench.reference import Reference, int8_biases, transition_z
from chipbench.tests import tiny


@pytest.fixture(scope="module")
def ref():
    return Reference(gen.make_graph(tiny.CONFIG, 5))


@pytest.fixture(scope="module")
def wide():
    """A graph whose rows span biases far wider than 8 bits, as the
    cells' do (scale 15: in-degrees of a few thousand)."""
    return Reference(gen.make_graph(
        dict(tiny.CONFIG, scale=15, capacity=1024, max_out_degree=1024), 5))


def walks(ref, stop, weights=None, seed=0, n=4096, length=16):
    g = ref.g
    rng = np.random.default_rng(seed)
    starts = rng.choice(np.flatnonzero(ref.live_degree(g.live) > 0), n)
    return starts, ref.walk(g.live, starts, length, stop, rng, weights)


@pytest.mark.parametrize("stop", [0.0, 0.15])
def test_reference_walks_pass_and_the_int8_control_fails(wide, stop):
    g, rng = wide.g, np.random.default_rng(1)
    starts, paths = walks(wide, stop, n=32768, length=40)
    bad, steps, pit = wide.check_walks(g.live, paths, starts, stop, rng)
    assert bad == 0 and steps == len(pit)
    assert abs(transition_z(pit)) < 5
    w8 = int8_biases(wide, g.live)
    starts, paths = walks(wide, stop, w8, n=32768, length=40)
    bad, _, pit = wide.check_walks(g.live, paths, starts, stop, rng)
    assert bad == 0 and transition_z(pit) > 10


def test_unsound_paths_are_counted(ref):
    g, rng = ref.g, np.random.default_rng(2)
    starts, paths = walks(ref, 0.0, n=256)
    hops = np.argwhere(paths[:, 1:] >= 0)
    broken = paths.copy()
    i, t = hops[0]
    broken[i, t + 1] = (broken[i, t + 1] + 1) % g.num_vertices  # not an edge
    assert ref.check_walks(g.live, broken, starts, 0.0, rng)[0] >= 1
    cut = paths.copy()
    cut[:, 5:] = -1                       # DeepWalk ended early
    assert ref.check_walks(g.live, cut, starts, 0.0, rng)[0] > 0
    gone = g.live.copy()
    gone[ref.edge_ids(paths[:, 0], paths[:, 1])[0]] = False  # stale graph
    assert ref.check_walks(gone, paths, starts, 0.0, rng)[0] >= 1


def test_device_and_host_row_digests_agree(ref):
    import jax.numpy as jnp
    from chipbench.drivers.ingest import row_digest
    g = ref.g
    C = tiny.CONFIG["capacity"]
    nbr = np.full((g.num_vertices, C), -1, np.int32)
    bias = np.zeros((g.num_vertices, C), np.int32)
    deg = np.zeros(g.num_vertices, np.int32)
    for e in np.flatnonzero(g.live)[::-1]:      # any slot order
        u = g.src[e]
        nbr[u, deg[u]], bias[u, deg[u]] = g.dst[e], g.w[e]
        deg[u] += 1
    got = np.asarray(row_digest(jnp.asarray(nbr), jnp.asarray(bias),
                                jnp.asarray(deg)))
    want, want_deg = ref.row_digest(g.live)
    assert np.array_equal(got, want) and np.array_equal(deg, want_deg)



def test_int8_control_fails_the_update_comparison(wide):
    g = wide.g
    want, _ = wide.row_digest(g.live)
    got, _ = wide.row_digest(g.live, int8_biases(wide, g.live))
    assert np.sum(got != want) > 0.1 * np.sum(want > 0)


def test_due_time_latency():
    due = np.array([0.0, 0.5, 1.0, 1.5])
    rid_of = np.array([0, -1, 1, 2])
    harvest = {0: (0.25, 0, None), 1: (1.01, 0, None)}   # rid 2 never came
    assert due_latency_ms(due, rid_of, harvest, 2.0).tolist() == \
        pytest.approx([250.0, 2000.0, 10.0, 2000.0])


def test_staleness_from_generations():
    # three 256-lane batches due at 0, 10 and 20 ms; windows of 512 and
    # 256 lanes make generations 1 and 2
    cum = np.array([0, 512, 768])
    results = [(0.005, 0), (0.030, 1), (0.025, 2), (0.040, 1)]
    got = visible_ms([0.0, 0.010, 0.020], [256, 256, 256], cum, results)
    assert got.tolist() == pytest.approx([25.0, 15.0, 5.0])
    late = visible_ms([0.0], [256], np.array([0, 0]), [(0.1, 0)])
    assert np.isinf(late).all()                # never held by a result
    # a stalled update path: the window's queries all name generation 0,
    # and only the probe after the drain (at 2 s) holds the batches
    stalled = visible_ms([0.0, 0.010, 0.020], [256, 256, 256], cum,
                         [(0.030, 0), (0.040, 0), (2.0, 2)])
    assert stalled.tolist() == pytest.approx([2000.0, 1990.0, 1980.0])
    lost = visible_ms([0.5], [256], np.array([0, 256]), [(0.6, 0)],
                      gave_up=60.5)
    assert lost.tolist() == pytest.approx([60000.0])   # the probe never came
