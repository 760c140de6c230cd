"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.alias_build import alias_build_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.radix_hist import radix_hist_pallas
from repro.kernels.walk_fused import walk_fused_pallas
from repro.kernels.walk_sample import (walk_sample_pallas,
                                       walk_sample_uniform_pallas)


# ---------------------------------------------------------------------------
# radix_hist
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("V,C,K", [(4, 8, 4), (17, 32, 16), (64, 128, 8)])
def test_radix_hist_matches_ref(V, C, K):
    rng = np.random.default_rng(V * C)
    bias = jnp.asarray(rng.integers(0, 1 << K, (V, C)), jnp.int32)
    deg = jnp.asarray(rng.integers(0, C + 1, V), jnp.int32)
    ds_k, gs_k = radix_hist_pallas(bias, deg, num_k=K, block_v=16,
                                   interpret=True)
    ds_r, gs_r = ref.radix_hist_ref(bias, deg, K)
    np.testing.assert_array_equal(np.asarray(ds_k), np.asarray(ds_r))
    np.testing.assert_array_equal(np.asarray(gs_k), np.asarray(gs_r))


# ---------------------------------------------------------------------------
# alias_build
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("V,K", [(1, 2), (7, 5), (33, 16), (128, 33)])
def test_alias_build_matches_ref(V, K):
    rng = np.random.default_rng(V + K)
    w = jnp.asarray(rng.random((V, K)) * rng.integers(1, 100, (V, K)),
                    jnp.float32)
    # a few empty + single-entry rows
    w = w.at[0].set(0.0)
    if V > 2:
        w = w.at[1, 1:].set(0.0)
    p_k, a_k = alias_build_pallas(w, block_v=32, interpret=True)
    p_r, a_r = ref.alias_build_ref(w)
    np.testing.assert_allclose(np.asarray(p_k), np.asarray(p_r), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))


def test_alias_build_encodes_distribution():
    from repro.core.alias import AliasTable, alias_probs
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.integers(0, 50, (16, 9)), jnp.float32)
    w = w.at[:, 0].max(1.0)
    p, a = alias_build_pallas(w, interpret=True)
    enc = np.asarray(alias_probs(AliasTable(p, a)))
    want = np.asarray(w) / np.asarray(w).sum(-1, keepdims=True)
    np.testing.assert_allclose(enc, want, atol=1e-5)


# ---------------------------------------------------------------------------
# walk_sample
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,C,K", [(8, 16, 8), (300, 64, 16), (64, 256, 12)])
def test_walk_sample_matches_ref(B, C, K):
    rng = np.random.default_rng(B + C + K)
    bias = jnp.asarray(rng.integers(0, 1 << K, (B, C)), jnp.int32)
    nbr = jnp.asarray(rng.integers(0, 1000, (B, C)), jnp.int32)
    deg = jnp.asarray(rng.integers(1, C + 1, B), jnp.int32)
    from repro.core.alias import build_alias
    ws = jnp.where(
        jnp.arange(C)[None, :] < deg[:, None], bias, 0)
    digs = ((ws[..., None] >> jnp.arange(K)) & 1).sum(1) * (2 ** jnp.arange(K))
    t = build_alias(digs.astype(jnp.float32))
    u = jnp.asarray(rng.random((B, 3)), jnp.float32)
    nxt_k, slot_k = walk_sample_pallas(t.prob, t.alias, bias, nbr, deg, u,
                                       block_b=64, interpret=True)
    nxt_r, slot_r = ref.walk_sample_ref(t.prob, t.alias, bias, nbr, deg,
                                        u[:, 0], u[:, 1], u[:, 2])
    np.testing.assert_array_equal(np.asarray(slot_k), np.asarray(slot_r))
    np.testing.assert_array_equal(np.asarray(nxt_k), np.asarray(nxt_r))


@pytest.mark.parametrize("base_log2,fp", [(2, False), (1, True), (2, True)])
def test_walk_sample_extended_matches_ref(base_log2, fp):
    """Extended kernel paths (bases > 2, fp decimal group) vs the oracle."""
    from repro.core.alias import build_alias
    rng = np.random.default_rng(7 * base_log2 + fp)
    B, C, bits = 200, 32, 12
    K = -(-bits // base_log2)
    bias = jnp.asarray(rng.integers(0, 1 << bits, (B, C)), jnp.int32)
    nbr = jnp.asarray(rng.integers(0, 1000, (B, C)), jnp.int32)
    deg = jnp.asarray(rng.integers(1, C + 1, B), jnp.int32)
    valid = jnp.arange(C)[None, :] < deg[:, None]
    wb = jnp.where(valid, bias, 0)
    dmask = (1 << base_log2) - 1
    digs = (wb[..., None] >> (jnp.arange(K) * base_log2)) & dmask
    gw = digs.sum(1) * ((1 << base_log2) ** jnp.arange(K, dtype=jnp.float32))
    frac = None
    if fp:
        frac = jnp.asarray(rng.random((B, C)), jnp.float32)
        wdec = jnp.where(valid, frac, 0.0).sum(-1, keepdims=True)
        gw = jnp.concatenate([gw, wdec], -1)
    t = build_alias(gw.astype(jnp.float32))
    u = jnp.asarray(rng.random((B, 5)), jnp.float32)
    nxt_k, slot_k = walk_sample_pallas(t.prob, t.alias, bias, nbr, deg, u,
                                       frac, base_log2=base_log2,
                                       block_b=64, interpret=True)
    nxt_r, slot_r = ref.walk_sample_ref(t.prob, t.alias, bias, nbr, deg,
                                        u[:, 0], u[:, 1], u[:, 2],
                                        u[:, 3], u[:, 4], frac=frac,
                                        base_log2=base_log2)
    np.testing.assert_array_equal(np.asarray(slot_k), np.asarray(slot_r))
    np.testing.assert_array_equal(np.asarray(nxt_k), np.asarray(nxt_r))


def test_walk_sample_distribution_thm41():
    """End-to-end: the fused kernel realizes Eq. 2 on the running example."""
    from repro.core.alias import build_alias
    B = 30000
    bias_row = np.array([5, 4, 3, 0], np.int32)
    nbr_row = np.array([1, 4, 5, -1], np.int32)
    K = 4
    digs = ((bias_row[:3, None] >> np.arange(K)) & 1).sum(0) * 2 ** np.arange(K)
    t = build_alias(jnp.asarray(digs, jnp.float32)[None])
    prob = jnp.broadcast_to(t.prob, (B, K))
    alias = jnp.broadcast_to(t.alias, (B, K))
    bias = jnp.broadcast_to(jnp.asarray(bias_row), (B, 4))
    nbr = jnp.broadcast_to(jnp.asarray(nbr_row), (B, 4))
    deg = jnp.full((B,), 3, jnp.int32)
    u = jax.random.uniform(jax.random.key(0), (B, 3))
    nxt, _ = walk_sample_pallas(prob, alias, bias, nbr, deg, u,
                                interpret=True)
    counts = np.bincount(np.asarray(nxt), minlength=6)
    got = counts / counts.sum()
    want = np.zeros(6)
    want[[1, 4, 5]] = np.array([5, 4, 3]) / 12
    assert 0.5 * np.abs(got - want).sum() < 0.015


@pytest.mark.parametrize("B,C", [(8, 16), (300, 64)])
def test_walk_sample_uniform_matches_ref(B, C):
    """Degree-based unbiased pick kernel vs oracle (incl. deg == 0 rows)."""
    rng = np.random.default_rng(B * C)
    nbr = jnp.asarray(rng.integers(0, 1000, (B, C)), jnp.int32)
    deg = jnp.asarray(rng.integers(0, C + 1, B), jnp.int32)
    u = jnp.asarray(rng.random((B, 1)), jnp.float32)
    nxt_k, slot_k = walk_sample_uniform_pallas(nbr, deg, u, block_b=64,
                                               interpret=True)
    nxt_r, slot_r = ref.walk_sample_uniform_ref(nbr, deg, u[:, 0])
    np.testing.assert_array_equal(np.asarray(slot_k), np.asarray(slot_r))
    np.testing.assert_array_equal(np.asarray(nxt_k), np.asarray(nxt_r))
    assert (np.asarray(nxt_k)[np.asarray(deg) == 0] == -1).all()


# ---------------------------------------------------------------------------
# walk_fused — the whole-walk megakernel (DESIGN.md §8)
# ---------------------------------------------------------------------------

def _fused_case(seed=5, V=12, C=16, bits=6, base_log2=1, fp=False):
    from repro.core.dyngraph import BingoConfig, from_edges
    from tests.conftest import random_graph
    src, dst, w = random_graph(V, C, max_bias=63, seed=seed)
    wf = w.astype(np.float32) + 0.37 if fp else w
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=bits,
                      base_log2=base_log2, fp_bias=fp, lam=4.0)
    return from_edges(cfg, src, dst, wf), cfg


@pytest.mark.parametrize("base_log2,fp,stop", [
    (1, False, 0.0),        # base-2 integer happy path
    (2, False, 0.0),        # digit acceptance + masked-ITS fallback
    (1, True, 0.0),         # fp decimal group
    (2, True, 0.15),        # everything at once, incl. PPR termination
])
def test_walk_fused_matches_scan_ref(base_log2, fp, stop):
    """Megakernel (interpret) pinned step-by-step against the scan oracle
    under *fed* uniforms — bit-exact per step, including buffer rotation
    (L > 2), the in-kernel alive mask, and base>2/fp lane passes."""
    st, cfg = _fused_case(base_log2=base_log2, fp=fp)
    B, L = 37, 9
    starts = jnp.arange(B, dtype=jnp.int32) % cfg.num_vertices
    u = jax.random.uniform(jax.random.key(0), (L, B, 6))
    seed = jnp.zeros((1,), jnp.int32)
    frac = st.frac if fp else None
    path_k = walk_fused_pallas(st.itable.prob, st.itable.alias, st.bias,
                               st.nbr, st.deg, frac, starts, seed, u,
                               length=L, base_log2=base_log2,
                               stop_prob=stop, block_b=16, interpret=True)
    path_r = ref.walk_fused_ref(st.itable.prob, st.itable.alias, st.bias,
                                st.nbr, st.deg, frac, starts, u,
                                base_log2=base_log2, stop_prob=stop)
    np.testing.assert_array_equal(np.asarray(path_k), np.asarray(path_r))


def test_walk_fused_uniform_matches_scan_ref():
    """simple-kind megakernel: degree pick per step, no bias/alias DMAs."""
    st, cfg = _fused_case()
    B, L = 23, 7
    starts = jnp.arange(B, dtype=jnp.int32) % cfg.num_vertices
    u = jax.random.uniform(jax.random.key(1), (L, B, 6))
    seed = jnp.zeros((1,), jnp.int32)
    path_k = walk_fused_pallas(None, None, None, st.nbr, st.deg, None,
                               starts, seed, u, length=L, uniform=True,
                               block_b=8, interpret=True)
    path_r = ref.walk_fused_ref(None, None, None, st.nbr, st.deg, None,
                                starts, u, uniform=True)
    np.testing.assert_array_equal(np.asarray(path_k), np.asarray(path_r))


def test_walk_fused_ragged_batch_and_dead_ends():
    """B not divisible by the walker tile (padded lanes must not leak) +
    dead-end termination: once a walker hits a deg-0 vertex the kernel
    emits -1 forever and stops gathering (the in-VMEM alive mask)."""
    # path graph 0 -> 1 -> 2 (vertex 2 is a dead end)
    src = np.array([0, 1], np.int32)
    dst = np.array([1, 2], np.int32)
    from repro.core.dyngraph import BingoConfig, from_edges
    cfg = BingoConfig(num_vertices=3, capacity=2, bias_bits=2)
    st = from_edges(cfg, src, dst, np.ones(2, np.int32))
    B, L = 13, 6                      # 13 walkers, tile of 8 -> ragged
    starts = jnp.zeros((B,), jnp.int32)
    u = jax.random.uniform(jax.random.key(2), (L, B, 6))
    seed = jnp.zeros((1,), jnp.int32)
    path = np.asarray(walk_fused_pallas(
        st.itable.prob, st.itable.alias, st.bias, st.nbr, st.deg, None,
        starts, seed, u, length=L, block_b=8, interpret=True))
    assert path.shape == (B, L + 1)
    np.testing.assert_array_equal(path[:, :3],
                                  np.tile([0, 1, 2], (B, 1)))
    assert (path[:, 3:] == -1).all()


@pytest.mark.parametrize("base_log2,fp,stop", [
    (1, False, 0.0),
    (2, True, 0.15),
])
def test_walk_fused_hash_prng_matches_ref(base_log2, fp, stop):
    """Counter-based PRNG mode (u=None): the megakernel's in-loop
    (seed, walker, t) hash draw must be bit-identical to the oracle's
    materialized ``hash_uniforms_ref`` stream — the replay/resume
    contract of DESIGN.md §10."""
    st, cfg = _fused_case(base_log2=base_log2, fp=fp)
    B, L = 37, 9
    starts = jnp.arange(B, dtype=jnp.int32) % cfg.num_vertices
    seed = jnp.array([1234], jnp.int32)
    frac = st.frac if fp else None
    path_k = walk_fused_pallas(st.itable.prob, st.itable.alias, st.bias,
                               st.nbr, st.deg, frac, starts, seed, None,
                               length=L, base_log2=base_log2,
                               stop_prob=stop, block_b=16, interpret=True)
    path_r = ref.walk_fused_ref(st.itable.prob, st.itable.alias, st.bias,
                                st.nbr, st.deg, frac, starts, None,
                                base_log2=base_log2, stop_prob=stop,
                                seed=seed, length=L)
    np.testing.assert_array_equal(np.asarray(path_k), np.asarray(path_r))


def _remoteify(nbr, frac_remote=0.3, seed=0):
    """Encode a random subset of real adjacency entries as remote
    neighbors ``-(g + 2)`` — the relay_view contract."""
    rng = np.random.default_rng(seed)
    mask = jnp.asarray(rng.random(nbr.shape) < frac_remote) & (nbr >= 0)
    return jnp.where(mask, -(nbr + 2), nbr)


def _segment_layout(layout, num_verts, L):
    """Per-lane ``(starts, t0)`` and the tile size for a segment case.

    ``random``: 29 lanes in tiles of 16 (the last one ragged), about a
    fifth of them free, ``t0`` anywhere in [0, L].  ``tiles``: four
    tiles of 16, each a window case of its own — empty; late entries
    only (``t0`` of L-2, L-1 and L, the last writing its prologue
    column alone); entries at 0 and at L-2 with a gap between them;
    one live lane.
    """
    rng = np.random.default_rng(3)
    if layout == "random":
        B = 29
        starts = rng.integers(0, num_verts, B)
        starts = np.where(rng.random(B) < 0.2, -1, starts)
        return starts, rng.integers(0, L + 1, B), 16
    bt = 16
    starts = np.full(4 * bt, -1)
    t0 = np.zeros(4 * bt, np.int64)
    late = slice(bt, 2 * bt)
    starts[late] = rng.integers(0, num_verts, bt)
    t0[late] = rng.choice([L - 2, L - 1, L], bt)
    gap = slice(2 * bt, 3 * bt)
    starts[gap] = rng.integers(0, num_verts, bt)
    t0[gap] = np.where(np.arange(bt) % 2, 0, L - 2)
    starts[3 * bt + 5], t0[3 * bt + 5] = 1, 3
    return starts, t0, bt


def _tile_steps(path, starts, t0, L, bt):
    """Each tile's window from the oracle's paths: from the earliest
    ``t0`` of its walking lanes to the last step at which one of them
    is alive (a lane is alive at step t >= t0 iff its path holds a
    vertex in column t), 0 where no lane walks."""
    path, starts, t0 = (np.asarray(a) for a in (path, starts, t0))
    cols = np.arange(L)
    out = []
    for lo in range(0, len(starts), bt):
        sl = slice(lo, lo + bt)
        walk = (starts[sl] >= 0) & (t0[sl] >= 0) & (t0[sl] < L)
        alive = ((path[sl, :L] >= 0) & (cols >= t0[sl, None])
                 & walk[:, None])
        out.append(cols[alive.any(0)].max() - t0[sl][walk].min() + 1
                   if walk.any() else 0)
    return out


@pytest.mark.parametrize("base_log2,fp,stop,uniform,fed,cohorts,layout", [
    (1, False, 0.0, False, True, 1, "random"),   # base-2, fed uniforms
    (2, True, 0.15, False, True, 1, "random"),   # base-4 + fp + PPR stop
    (1, False, 0.0, True, True, 1, "random"),    # simple-kind degree pick
    (1, False, 0.0, False, False, 1, "random"),  # hash-PRNG mode
    (2, True, 0.15, False, True, 2, "random"),   # cohort interleaving
    (2, True, 0.15, False, False, 2, "random"),
    (2, True, 0.15, False, True, 4, "random"),
    (2, True, 0.15, False, False, 4, "random"),
    (2, True, 0.15, False, True, 1, "tiles"),    # per-tile windows
    (2, True, 0.15, False, False, 1, "tiles"),
    (2, True, 0.15, False, True, 2, "tiles"),
    (2, True, 0.15, False, False, 2, "tiles"),
    (2, True, 0.15, False, True, 4, "tiles"),
    (2, True, 0.15, False, False, 4, "tiles"),
    (1, False, 0.0, True, False, 1, "tiles"),
])
def test_walk_segment_matches_ref(base_log2, fp, stop, uniform, fed,
                                  cohorts, layout):
    """Resumable segment entry vs the windowed scan oracle: per-walker
    start steps t0 (incl. t0 == L final-hop-only and free starts < 0
    slots), remote-encoded adjacency entries -> (vertex, step) frontier
    records, bit-exact path AND frontier in both the fed-uniform and
    counter-hash PRNG modes and at every cohort count K (DESIGN.md
    §10; the relay's bit-equality depends on this).  Each tile walks
    only its own window of steps, which its step count reports; the
    ``tiles`` layout puts an empty tile, a late tile, a gap and a lone
    lane side by side."""
    st, cfg = _fused_case(base_log2=base_log2, fp=fp)
    L = 8
    starts, t0, bt = _segment_layout(layout, cfg.num_vertices, L)
    starts = jnp.asarray(starts, jnp.int32)
    t0 = jnp.asarray(t0, jnp.int32)
    B = starts.shape[0]
    nbr = _remoteify(st.nbr)
    u = jax.random.uniform(jax.random.key(4), (L, B, 6)) if fed else None
    seed = jnp.array([99], jnp.int32)
    frac = st.frac if fp else None
    args = ((None, None, None, nbr, st.deg, None) if uniform else
            (st.itable.prob, st.itable.alias, st.bias, nbr, st.deg, frac))
    path_k, fr_k, steps_k = walk_fused_pallas(
        *args, starts, seed, u, t0, length=L, base_log2=base_log2,
        stop_prob=stop, uniform=uniform, segment=True, block_b=bt,
        cohorts=cohorts, interpret=True)
    path_r, fr_r, _ = ref.walk_segment_ref(
        *args, starts, t0, u, length=L, base_log2=base_log2,
        stop_prob=stop, uniform=uniform, seed=seed, cohorts=cohorts)
    np.testing.assert_array_equal(np.asarray(path_k), np.asarray(path_r))
    np.testing.assert_array_equal(np.asarray(fr_k), np.asarray(fr_r))
    steps = np.asarray(steps_k).tolist()
    assert steps == _tile_steps(path_r, starts, t0, L, bt)
    if layout == "tiles":
        assert steps[0] == 0                       # the empty tile
        assert 1 <= steps[1] <= 2                  # entries at L-2, L-1
    # structural checks: free slots emit nothing; a frontier record's
    # step column is inside (0, L]; columns before t0 stay -1
    pk, fk = np.asarray(path_k), np.asarray(fr_k)
    free = np.asarray(starts) < 0
    assert (pk[free] == -1).all() and (fk[free] == -1).all()
    has_fr = fk[:, 0] >= 0
    assert ((fk[has_fr, 1] > 0) & (fk[has_fr, 1] <= L)).all()
    cols = np.arange(L + 1)[None, :]
    assert (pk[cols < np.asarray(t0)[:, None]] == -1).all()


@pytest.mark.parametrize("cohorts", [1, 2, 4])
def test_walk_segment_tile_steps(cohorts):
    """The steps a tile runs, by hand on a ring (1 -> 2 -> ... -> 7 ->
    1) beside a dead end (vertex 0), L = 6, tiles of 8: a walker on
    the ring never stops, one entering at the dead end dies at once."""
    from repro.core.dyngraph import BingoConfig, from_edges
    src = np.array([1, 2, 3, 4, 5, 6, 7], np.int32)
    dst = np.array([2, 3, 4, 5, 6, 7, 1], np.int32)
    cfg = BingoConfig(num_vertices=8, capacity=2, bias_bits=2)
    st = from_edges(cfg, src, dst, np.ones(7, np.int32))
    L, bt = 6, 8
    lanes = {  # tile: {lane: (start, t0)}, every other lane free
        0: {},                          # empty: no step
        1: {2: (3, 2)},                 # ring from step 2: steps 2..5
        2: {0: (0, 1), 7: (0, 4)},      # dead at 1 and at 4: steps 1..4
        3: {4: (5, L)},                 # prologue column only: no step
        4: {1: (0, 0), 6: (2, 5)},      # dead at 0, ring at 5: steps 0..5
    }
    starts = np.full(len(lanes) * bt, -1)
    t0 = np.zeros(len(lanes) * bt, np.int64)
    for tile, ls in lanes.items():
        for lane, (s, t) in ls.items():
            starts[tile * bt + lane], t0[tile * bt + lane] = s, t
    path, _, steps = walk_fused_pallas(
        st.itable.prob, st.itable.alias, st.bias, st.nbr, st.deg, None,
        jnp.asarray(starts, jnp.int32), jnp.array([3], jnp.int32), None,
        jnp.asarray(t0, jnp.int32), length=L, segment=True, block_b=bt,
        cohorts=cohorts, interpret=True)
    assert np.asarray(steps).tolist() == [0, 4, 4, 0, 6]
    path = np.asarray(path)
    assert path[3 * bt + 4].tolist() == [-1] * L + [5]
    assert (path[bt + 2, 2:] >= 1).all() and (path[bt + 2, :2] == -1).all()


def test_walk_segments_stitch_to_whole_walk():
    """Segment composability — the relay's core algebra: splitting a walk
    at its frontier exits and resuming each walker (same wid/slot, same
    seed) on the 'other side' reproduces the unsplit walk bit-for-bit."""
    st, cfg = _fused_case(seed=9)
    B, L = 16, 10
    starts = jnp.arange(B, dtype=jnp.int32) % cfg.num_vertices
    seed = jnp.array([5], jnp.int32)
    whole = walk_fused_pallas(st.itable.prob, st.itable.alias, st.bias,
                              st.nbr, st.deg, None, starts, seed, None,
                              length=L, block_b=16, interpret=True)
    # split the vertex set in two halves; each "shard" keeps its own
    # half's neighbors and remote-encodes the other's as -(g + 2)
    half = cfg.num_vertices // 2
    enc = jnp.where(st.nbr < 0, st.nbr, -(st.nbr + 2))
    nbr_lo = jnp.where((st.nbr >= 0) & (st.nbr < half), st.nbr, enc)
    nbr_hi = jnp.where(st.nbr >= half, st.nbr, enc)

    def seg(nbr, s, t):
        return walk_fused_pallas(
            st.itable.prob, st.itable.alias, st.bias, nbr, st.deg, None,
            s, seed, None, t, length=L, segment=True, block_b=16,
            interpret=True)[:2]

    acc = jnp.full((B, L + 1), -1, jnp.int32)
    s_lo = jnp.where(starts < half, starts, -1)
    s_hi = jnp.where(starts >= half, starts, -1)
    t_lo = t_hi = jnp.zeros((B,), jnp.int32)
    for _ in range(L + 1):          # bounded hand-rolled relay, 2 "shards"
        p, f = seg(nbr_lo, s_lo, t_lo)
        q, g = seg(nbr_hi, s_hi, t_hi)
        acc = jnp.maximum(acc, jnp.maximum(p, q))
        # swap frontiers: lo exits resume in hi next round, and vice versa
        s_hi = jnp.where(f[:, 0] >= 0, f[:, 0], -1)
        t_hi = jnp.where(f[:, 0] >= 0, f[:, 1], 0)
        s_lo = jnp.where(g[:, 0] >= 0, g[:, 0], -1)
        t_lo = jnp.where(g[:, 0] >= 0, g[:, 1], 0)
        if not bool(((s_lo >= 0) | (s_hi >= 0)).any()):
            break
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(whole))


def _subjaxprs(v):
    try:
        from jax.extend import core as jex_core
        jaxpr_types = (jex_core.Jaxpr, jex_core.ClosedJaxpr)
    except ImportError:
        jaxpr_types = (jax.core.Jaxpr, jax.core.ClosedJaxpr)
    vals = v if isinstance(v, (list, tuple)) else [v]
    for x in vals:
        if isinstance(x, jaxpr_types):
            yield x.jaxpr if hasattr(x, "jaxpr") else x


def _count_prims(closed_jaxpr, name, *, inside_loops_only=False, skip=(),
                 scope=None):
    """Recursively count ``name`` eqns across nested (closed) jaxprs.

    ``name`` is a primitive's, or a jitted function's (``searchsorted``).
    ``inside_loops_only`` counts only occurrences under a scan/while —
    i.e. launches that repeat at run time.  The bodies of the primitives
    named in ``skip`` (e.g. ``pallas_call``) are not searched, and with
    ``scope`` only eqns under that ``jax.named_scope`` path count."""

    def walk(j, in_loop, stack):
        n = 0
        for eqn in j.eqns:
            prim = eqn.primitive.name
            where = f"{stack}/{eqn.source_info.name_stack}"
            if (name in (prim, eqn.params.get("name"))
                    and (in_loop or not inside_loops_only)
                    and (scope is None or scope in where)):
                n += 1
            if prim in skip:
                continue
            loop = in_loop or prim in ("scan", "while")
            for v in eqn.params.values():
                for s in _subjaxprs(v):
                    n += walk(s, loop, where)
        return n

    return walk(closed_jaxpr.jaxpr, False, "")


def test_whole_walk_is_one_pallas_call():
    """The megakernel launch contract: an 80-step deepwalk through the
    pallas backend's whole-walk entry traces to EXACTLY ONE pallas_call
    with no scan/while around it (one launch per walk batch), while the
    per-step path wraps its pallas_call in a length-80 scan (80
    launches at run time)."""
    from repro.core import walks
    from repro.core.backend import get_backend
    st, cfg = _fused_case()
    starts = jnp.zeros((8,), jnp.int32)
    key = jax.random.key(0)
    params = walks.WalkParams(kind="deepwalk", length=80)

    fused = jax.make_jaxpr(
        lambda s, k: get_backend("pallas").sample_walk(st, cfg, s, k,
                                                       params))(starts, key)
    assert _count_prims(fused, "pallas_call") == 1
    # ... and that one launch is top-level: no scan/while in the trace
    # (jax.random internals use scans) contains a pallas_call, so the
    # launch count cannot multiply at run time.
    assert _count_prims(fused, "pallas_call", inside_loops_only=True) == 0

    step = jax.make_jaxpr(
        lambda s, k: walks.random_walk(st, cfg, s, k, params,
                                       backend="pallas",
                                       whole_walk=False))(starts, key)
    scans = [e for e in step.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 80
    assert _count_prims(step, "pallas_call", inside_loops_only=True) == 1


# ---------------------------------------------------------------------------
# cohort interleaving (DESIGN.md §8): K ∈ {2, 4} must be bit-exact vs
# K=1 and the jnp oracle — cohort geometry is a pure perf knob
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cohorts", [2, 4])
@pytest.mark.parametrize("base_log2,fp,fed", [
    (1, False, True),      # base-2 integer, fed uniforms
    (2, False, True),      # base-4 digit acceptance
    (1, True, False),      # fp decimal group, hash PRNG
    (2, True, True),       # base-4 + fp
])
def test_walk_fused_cohorts_bitexact(cohorts, base_log2, fp, fed):
    """Cohort-interleaved whole walk == K=1 kernel == oracle, fed AND
    hash-PRNG modes, across bases/fp and a ragged batch (B=37 is not a
    multiple of 2 or 4, so the last tile carries padded lanes in some
    cohort).  The counter PRNG keys by (seed, wid, t) — never by
    cohort, slot, or phase — so any K must reproduce the same walks."""
    st, cfg = _fused_case(base_log2=base_log2, fp=fp)
    B, L = 37, 9
    starts = jnp.arange(B, dtype=jnp.int32) % cfg.num_vertices
    u = jax.random.uniform(jax.random.key(0), (L, B, 6)) if fed else None
    seed = jnp.array([77], jnp.int32)
    frac = st.frac if fp else None

    def run(K):
        return walk_fused_pallas(
            st.itable.prob, st.itable.alias, st.bias, st.nbr, st.deg,
            frac, starts, seed, u, length=L, base_log2=base_log2,
            stop_prob=0.15, block_b=16, cohorts=K, interpret=True)

    base = np.asarray(run(1))
    np.testing.assert_array_equal(np.asarray(run(cohorts)), base)
    path_r = ref.walk_fused_ref(st.itable.prob, st.itable.alias, st.bias,
                                st.nbr, st.deg, frac, starts, u,
                                base_log2=base_log2, stop_prob=0.15,
                                seed=seed, length=L, cohorts=cohorts)
    np.testing.assert_array_equal(base, np.asarray(path_r))


@pytest.mark.parametrize("cohorts", [2, 4])
def test_walk_fused_cohorts_dead_cohort(cohorts):
    """All walkers of one cohort dead from step 1 (clustered dead-end
    starts occupying exactly the first cohort's lanes): that cohort's
    gathers go quiet (`pl.when` on its SMEM alive flags) while the
    others keep walking — the masks are per-cohort, so a dead cohort
    must not stall or corrupt the live ones."""
    from repro.core.dyngraph import BingoConfig, from_edges
    # vertex 0 is a dead end; 1..7 form a ring
    src = np.array([1, 2, 3, 4, 5, 6, 7], np.int32)
    dst = np.array([2, 3, 4, 5, 6, 7, 1], np.int32)
    cfg = BingoConfig(num_vertices=8, capacity=2, bias_bits=2)
    st = from_edges(cfg, src, dst, np.ones(7, np.int32))
    B, L, bb = 16, 6, 16            # one tile; cohort 0 = lanes [0, B/K)
    starts = jnp.asarray([0] * (B // cohorts)
                         + [1 + i % 7 for i in range(B - B // cohorts)],
                         jnp.int32)
    seed = jnp.array([3], jnp.int32)

    def run(K):
        return walk_fused_pallas(st.itable.prob, st.itable.alias, st.bias,
                                 st.nbr, st.deg, None, starts, seed, None,
                                 length=L, block_b=bb, cohorts=K,
                                 interpret=True)

    base = np.asarray(run(1))
    got = np.asarray(run(cohorts))
    np.testing.assert_array_equal(got, base)
    # dead cohort terminated at once; live walkers never did (ring)
    assert (got[:B // cohorts, 1:] == -1).all()
    assert (got[B // cohorts:] >= 0).all()


@pytest.mark.parametrize("cohorts", [1, 2, 4])
def test_whole_walk_is_one_pallas_call_any_cohorts(cohorts):
    """The launch contract survives interleaving: an 80-step deepwalk
    through the pallas backend is EXACTLY ONE pallas_call at every K —
    the phase unroll lives inside the kernel's fori_loop body, not in
    the surrounding jaxpr."""
    import dataclasses
    from repro.core import walks
    from repro.core.backend import get_backend
    st, cfg = _fused_case()
    cfg = dataclasses.replace(cfg, cohorts=cohorts)
    starts = jnp.zeros((8,), jnp.int32)
    key = jax.random.key(0)
    params = walks.WalkParams(kind="deepwalk", length=80)
    fused = jax.make_jaxpr(
        lambda s, k: get_backend("pallas").sample_walk(st, cfg, s, k,
                                                       params))(starts, key)
    assert _count_prims(fused, "pallas_call") == 1
    assert _count_prims(fused, "pallas_call", inside_loops_only=True) == 0


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,Hkv,S,T,D",
    [
        (1, 4, 4, 128, 128, 64),     # MHA square
        (2, 8, 2, 128, 128, 64),     # GQA 4:1
        (1, 4, 4, 64, 256, 64),      # decode-ish: S < T
        (1, 2, 1, 256, 256, 128),    # D=128
    ])
def test_flash_attention_matches_ref(B, H, Hkv, S, T, D, dtype):
    rng = np.random.default_rng(S + T + H)
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, T, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, T, D)), dtype)
    out_k = flash_attention_pallas(q, k, v, causal=True, block_q=64,
                                   block_k=64, interpret=True)
    out_r = ref.attention_ref(q, k, v, causal=True)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), atol=atol)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    rng = np.random.default_rng(window)
    q = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    out_k = flash_attention_pallas(q, k, v, causal=True, window=window,
                                   block_q=64, block_k=64, interpret=True)
    out_r = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5)


def test_flash_attention_noncausal():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.float32)
    out_k = flash_attention_pallas(q, k, v, causal=False, block_q=64,
                                   block_k=64, interpret=True)
    out_r = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5)
