"""Update megakernel correctness: the pallas path IS the reference path.

Unlike the sampling equivalence (distributional, chi-square), the update
contract is *bit-exact*: ``EngineBackend.apply_updates`` on the pallas
backend (``kernels/update_fused.py``, interpret mode here — the same
kernel program that compiles on TPU) must produce a ``BingoState`` whose
every leaf — including the rebuilt float alias rows and fp decimal
sums — equals ``core/updates.py:batched_update``'s output exactly, so
serving can interleave backends freely and a pallas-ingested state is
indistinguishable from a reference-ingested one.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import walks
from repro.core.backend import EngineBackend, get_backend
from repro.core.dyngraph import (DENSE, ONE, REGULAR, SPARSE, BingoConfig,
                                 from_edges)
from repro.core.sampler import transition_probs
from repro.core.updates import batched_update, make_updater
from repro.kernels.ops import update_fused
from tests.conftest import empirical_dist, random_graph, tv_distance

BACKENDS = ["reference", "pallas"]


def assert_states_equal(ref, got):
    """Bit-exact equality over every BingoState leaf (itable included)."""
    la, lb = jax.tree.leaves(ref), jax.tree.leaves(got)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def _round(rng, V, edges, Bn, mode):
    """One update batch: deletes target live edges, inserts are random.

    The last four modes stress the prepass's map from lanes to affected
    rows: "split" gives one vertex inserts only, one deletes only and
    one both; "dups" repeats live (u, v) deletes beyond their count,
    beside inserts of the same pairs; "holes" puts inactive and
    out-of-range lanes between valid ones (the batch then carries the
    ``active`` mask as a fifth element); "one_vertex" puts every lane on
    one vertex."""
    ins = {"insert": np.ones(Bn, bool), "delete": np.zeros(Bn, bool),
           "mixed": rng.random(Bn) < 0.5}.get(mode)
    if ins is None:
        ins = rng.random(Bn) < (0.25 if mode == "dups" else 0.5)
    uu = rng.integers(0, V, Bn).astype(np.int32)
    vv = rng.integers(0, V, Bn).astype(np.int32)
    ww = rng.integers(1, 32, Bn).astype(np.int32)
    srcs = sorted({e[0] for e in edges})
    if mode == "split":          # a: inserts, b: deletes, c: both
        a, b, c = rng.choice(srcs, 3, replace=False)
        role = rng.integers(0, 3, Bn)
        uu[:] = np.array([a, b, c])[role]
        ins[:] = (role == 0) | ((role == 2) & (rng.random(Bn) < 0.5))
    elif mode == "dups":         # three live pairs, deleted 4-7 times each
        pairs = [edges[int(i)] for i in rng.choice(len(edges), 3)]
        pick = rng.integers(0, 3, Bn)
        uu[:] = [pairs[k][0] for k in pick]
        vv[:] = [pairs[k][1] for k in pick]
    elif mode == "one_vertex":
        uu[:] = rng.choice(srcs)
    for i in range(Bn):
        if not ins[i] and mode != "dups" and rng.random() < 0.8:
            pool = (edges if mode in ("delete", "mixed", "holes")
                    else [e for e in edges if e[0] == uu[i]])
            if pool:
                uu[i], vv[i] = pool[int(rng.integers(len(pool)))]
    batch = (jnp.asarray(ins), jnp.asarray(uu), jnp.asarray(vv),
             jnp.asarray(ww))
    if mode != "holes":
        return batch
    bad = rng.random(Bn) < 0.2   # u = -1, u >= V or v < 0, between valid
    which = rng.integers(0, 3, Bn)
    uu = np.where(bad & (which == 0), -1, uu)
    uu = np.where(bad & (which == 1), V + rng.integers(0, 3, Bn), uu)
    vv = np.where(bad & (which == 2), -2, vv)
    act = rng.random(Bn) >= 0.25
    return batch[:1] + (jnp.asarray(uu, jnp.int32),
                        jnp.asarray(vv, jnp.int32), batch[3],
                        jnp.asarray(act))


@pytest.mark.parametrize("mode", ["insert", "delete", "mixed", "split",
                                  "dups", "holes", "one_vertex"])
@pytest.mark.parametrize("adaptive,fp,base_log2",
                         [(True, False, 1), (False, False, 1),
                          (True, True, 1), (True, False, 2),
                          (True, True, 2)])
def test_bit_exact_vs_reference(mode, adaptive, fp, base_log2):
    """Full-state bit-exactness across group-representation modes
    (adaptive GA incl. ginv-carrying BS), fp-bias, bases 2/4, and
    insert-only / delete-only / mixed rounds and the lane-map stress
    rounds of ``_round`` — chained over 3 rounds so the fused path also
    consumes its own output.  B=20 is no multiple of the default
    ``block_rows`` 8, so the last row tile is always part padding."""
    V, C = 12, 16
    rng = np.random.default_rng(base_log2 * 7 + fp * 3 + adaptive)
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=6,
                      adaptive=adaptive, fp_bias=fp, base_log2=base_log2)
    src, dst, w = random_graph(V, C, max_bias=31, seed=4, density=0.4)
    wv = w.astype(np.float32) + rng.random(len(w)).astype(np.float32) \
        if fp else w
    st_ref = from_edges(cfg, src, dst, wv)
    st_pal = st_ref
    edges = list(zip(src.tolist(), dst.tolist()))
    for r in range(3):
        batch = _round(rng, V, edges, 20, mode)
        if fp:
            batch = batch[:3] + (batch[3].astype(jnp.float32)
                                 + rng.random(20).astype(np.float32),
                                 ) + batch[4:]
        st_ref, stats_ref = batched_update(st_ref, cfg, *batch)
        st_pal, stats_pal = update_fused(st_pal, cfg, *batch)
        assert_states_equal(st_ref, st_pal)
        for a, b in zip(stats_ref, stats_pal):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mode", ["insert", "delete", "mixed"])
def test_bit_exact_fp_full_mantissa(mode):
    """fp biases drawn from (0, 1): the λ-scaled decimal parts use the
    whole mantissa, so a 64-lane decimal-group sum rounds and depends on
    the order of its additions — the kernel's W_D must still equal the
    reference's bit for bit."""
    V, C = 96, 64
    rng = np.random.default_rng(11)
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=6,
                      adaptive=True, fp_bias=True)
    src, dst, _ = random_graph(V, C, seed=5, density=0.9)
    w = rng.random(len(src)).astype(np.float32)
    st_ref = from_edges(cfg, src, dst, w)
    st_pal = st_ref
    edges = list(zip(src.tolist(), dst.tolist()))
    for _ in range(3):
        batch = _round(rng, V, edges, 24, mode)
        batch = batch[:3] + (jnp.asarray(rng.random(24).astype(np.float32)),)
        st_ref, _ = batched_update(st_ref, cfg, *batch)
        st_pal, _ = update_fused(st_pal, cfg, *batch)
        assert_states_equal(st_ref, st_pal)


def test_bit_exact_all_group_types():
    """The hub row spans DENSE/ONE/SPARSE/REGULAR before the round, and
    the batch forces transitions — gmem compaction, ginv-free GA locate,
    and the Eq. 9 reclassification all cross the kernel bit-exactly."""
    d = 24
    w = np.ones(d, np.int64)
    w[16] += 2
    w[17:19] += 4
    w[19:24] += 8 - 1
    src = np.zeros(d, np.int32)
    dst = np.arange(1, d + 1, dtype=np.int32)
    V = d + 1
    cfg = BingoConfig(num_vertices=V, capacity=32, bias_bits=4,
                      adaptive=True)
    st = from_edges(cfg, src, dst, w.astype(np.int32))
    types = set(np.asarray(st.gtype[0]).tolist())
    assert {DENSE, ONE, SPARSE, REGULAR} <= types
    ins = jnp.array([True, True, False, False, False])
    uu = jnp.zeros((5,), jnp.int32)
    vv = jnp.array([7, 9, 17, 18, 16], jnp.int32)   # kill SPARSE + ONE
    ww = jnp.array([2, 8, 0, 0, 0], jnp.int32)
    ref, sr = batched_update(st, cfg, ins, uu, vv, ww)
    got, sg = update_fused(st, cfg, ins, uu, vv, ww)
    assert_states_equal(ref, got)
    np.testing.assert_array_equal(np.asarray(sr.transitions),
                                  np.asarray(sg.transitions))
    assert int(sr.transitions.sum()) > 0    # the round really transitioned


def test_active_mask_and_engine_protocol():
    """Both registered backends satisfy the full EngineBackend protocol,
    and the pallas ``apply_updates`` honors the ``active`` routing mask
    (the sharded update_walk cell's owner-shard selection)."""
    for name in BACKENDS:
        bk = get_backend(name)
        assert isinstance(bk, EngineBackend)
        assert callable(bk.apply_updates) and callable(bk.sample_step)
    V, C = 10, 8
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=4)
    src, dst, w = random_graph(V, C, max_bias=15, seed=2, density=0.4)
    st = from_edges(cfg, src, dst, w)
    rng = np.random.default_rng(0)
    Bn = 12
    ins = jnp.asarray(rng.random(Bn) < 0.5)
    uu = jnp.asarray(rng.integers(0, V, Bn), jnp.int32)
    vv = jnp.asarray(rng.integers(0, V, Bn), jnp.int32)
    ww = jnp.asarray(rng.integers(1, 16, Bn), jnp.int32)
    act = jnp.asarray(rng.random(Bn) < 0.5)
    ref, _ = get_backend("reference").apply_updates(
        st, cfg, ins, uu, vv, ww, active=act)
    got, _ = get_backend("pallas").apply_updates(
        st, cfg, ins, uu, vv, ww, active=act)
    assert_states_equal(ref, got)


def test_make_updater_threads_donated_state():
    """The shared updater closure (serve/dynwalk): donated state threads
    through repeated rounds and ends bit-identical to the undonated
    reference chain."""
    V, C = 10, 12
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=4)
    src, dst, w = random_graph(V, C, max_bias=15, seed=6, density=0.4)
    st_ref = from_edges(cfg, src, dst, w)
    st_pal = jax.tree.map(jnp.copy, st_ref)
    run = make_updater(cfg, backend="pallas")
    rng = np.random.default_rng(3)
    edges = list(zip(src.tolist(), dst.tolist()))
    for r in range(3):
        batch = _round(rng, V, edges, 10, "mixed")
        st_ref, _ = batched_update(st_ref, cfg, *batch)
        st_pal, _ = run(st_pal, *batch)
    assert_states_equal(st_ref, st_pal)


def test_delete_heavy_single_vertex():
    """More deletes on one vertex than its row has slots, most of them
    misses — the case that overflows a C-lane delete patch.  The default
    ``block_dels = min(B, 2C)`` gives every delete a lane whenever
    B <= 2C, so the round stays bit-exact; an explicitly undersized
    ``block_dels`` must still match when the batch fits it."""
    cfg = BingoConfig(num_vertices=4, capacity=4, bias_bits=3)
    st = from_edges(cfg, np.array([0, 0, 0, 0]), np.array([1, 1, 2, 2]),
                    np.array([1, 1, 1, 1]))
    # six deletes on vertex 0: 3x v=1 (one is a dup-miss), 3x v=2
    ins = jnp.zeros((6,), bool)
    uu = jnp.zeros((6,), jnp.int32)
    vv = jnp.array([1, 1, 1, 2, 2, 2], jnp.int32)
    ww = jnp.zeros((6,), jnp.int32)
    ref, sr = batched_update(st, cfg, ins, uu, vv, ww)
    got, sg = update_fused(st, cfg, ins, uu, vv, ww)
    assert_states_equal(ref, got)
    assert int(sr.del_applied) == 4 == int(sg.del_applied)
    assert int(ref.deg[0]) == 0
    # an oversized explicit patch must agree too
    got2, _ = update_fused(st, cfg, ins, uu, vv, ww, block_dels=8)
    assert_states_equal(ref, got2)


def test_one_pallas_call_per_round():
    """The megakernel launch contract: a batched round through the
    pallas backend traces to EXACTLY ONE pallas_call, top-level (the
    ordering prepass is sorts/scatters, never a second launch), while
    the reference path traces to none."""
    from tests.test_kernels import _count_prims
    V, C = 12, 16
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=5)
    src, dst, w = random_graph(V, C, max_bias=31, seed=1, density=0.4)
    st = from_edges(cfg, src, dst, w)
    Bn = 20
    args = (jnp.ones((Bn,), bool), jnp.zeros((Bn,), jnp.int32),
            jnp.ones((Bn,), jnp.int32), jnp.ones((Bn,), jnp.int32))

    fused = jax.make_jaxpr(
        lambda s, i, u, v, w: get_backend("pallas").apply_updates(
            s, cfg, i, u, v, w))(st, *args)
    assert _count_prims(fused, "pallas_call") == 1
    assert _count_prims(fused, "pallas_call", inside_loops_only=True) == 0

    ref = jax.make_jaxpr(
        lambda s, i, u, v, w: get_backend("reference").apply_updates(
            s, cfg, i, u, v, w))(st, *args)
    assert _count_prims(ref, "pallas_call") == 0


def test_prepass_has_no_loop_or_search():
    """The prepass takes each lane's affected row from the runs of its own
    sort: outside the one pallas_call, a round traces to no searchsorted
    (on the chip a binary search is a log2(B)-step loop of B-wide
    gathers) and to one loop only, the alias rows' Vose steps in the
    epilogue (K steps over lane-dense rows; unrolled they ran slower on
    a v5e).  The reference keeps its searchsorted, which shows the count
    can see one."""
    from tests.test_kernels import _count_prims
    V, C = 12, 16
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=5)
    src, dst, w = random_graph(V, C, max_bias=31, seed=1, density=0.4)
    st = from_edges(cfg, src, dst, w)
    Bn = 20
    args = (jnp.arange(Bn) % 2 == 0, jnp.arange(Bn, dtype=jnp.int32) % V,
            jnp.ones((Bn,), jnp.int32), jnp.ones((Bn,), jnp.int32))

    fused = jax.make_jaxpr(
        lambda s, i, u, v, w: get_backend("pallas").apply_updates(
            s, cfg, i, u, v, w))(st, *args)
    outside = functools.partial(_count_prims, fused, skip=("pallas_call",))
    assert outside("searchsorted") == 0
    for loop in ("scan", "while"):
        assert outside(loop, scope="update.prepass") == 0, loop
    loops = outside("scan") + outside("while")
    assert loops == 1 == outside("scan", scope="update.epilogue/alias_rows")

    ref = jax.make_jaxpr(
        lambda s, i, u, v, w: batched_update(s, cfg, i, u, v, w))(st, *args)
    assert _count_prims(ref, "searchsorted") > 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_interleaved_update_then_walk(backend):
    """The serving round through one EngineBackend: mutate the hub's
    row with a batched round, then whole-walk — the first hop out of
    the hub must reproduce Eq. 2 of the *updated* sampling space
    (chi-square via TV distance against transition_probs), and every
    emitted hop must be a live post-update edge."""
    d = 20
    src = np.zeros(d, np.int32)
    dst = np.arange(1, d + 1, dtype=np.int32)
    w = (1 + (np.arange(d) % 7)).astype(np.int32)
    V = d + 1
    # return edges so whole walks bounce back through the hub
    src2 = np.concatenate([src, dst])
    dst2 = np.concatenate([dst, src])
    w2 = np.concatenate([w, np.ones_like(w)])
    cfg = BingoConfig(num_vertices=V, capacity=32, bias_bits=5)
    st = from_edges(cfg, src2, dst2, w2)
    bk = get_backend(backend)

    # the round rewires the hub: delete two edges, add two heavier ones
    ins = jnp.array([False, False, True, True])
    uu = jnp.zeros((4,), jnp.int32)
    vv = jnp.array([1, 2, 3, 4], jnp.int32)
    ww = jnp.array([0, 0, 9, 13], jnp.int32)
    st2, stats = bk.apply_updates(st, cfg, ins, uu, vv, ww)
    assert int(stats.ins_applied) == 2 and int(stats.del_applied) == 2

    B, L = 4000, 6
    path = np.asarray(bk.sample_walk(
        st2, cfg, jnp.zeros((B,), jnp.int32), jax.random.key(11),
        walks.WalkParams(kind="deepwalk", length=L)))
    # transitions out of the updated hub, pooled over all steps
    at_hub = path[:, :-1] == 0
    nxt = path[:, 1:][at_hub]
    nxt = nxt[nxt >= 0]
    assert nxt.size >= B
    got = empirical_dist(nxt, V)
    probs = np.asarray(transition_probs(st2, cfg,
                                        jnp.zeros((1,), jnp.int32)))[0]
    nbrs = np.asarray(st2.nbr[0])
    want = np.zeros(V)
    for slot, p in enumerate(probs):
        if p > 0:
            want[nbrs[slot]] += p
    assert want[1] == 0 and want[2] == 0          # deleted edges are gone
    assert tv_distance(got, want) < 0.03, backend
