"""Super-step walker relay: exact cross-shard whole walks (DESIGN.md §10).

The acceptance contract of the relay: on a host mesh of any shard count,
``walk_relay`` paths are *bit-identical* to the single-shard
``random_walk`` — zero walkers truncated at shard boundaries — with one
resumable-megakernel ``pallas_call`` per shard per round.  Multi-device
cases need fake host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``; the walk-relay
CI job sets it) and skip on a plain single-device run.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import walks
from repro.core.backend import get_backend
from repro.core.dyngraph import BingoConfig, from_edges
from repro.distributed.relay import make_relay, relay_local, relay_view
from repro.kernels.ops import seed_from_key
from tests.conftest import random_graph

DEVS = len(jax.devices())
multi = pytest.mark.skipif(
    DEVS < 8, reason="needs 8 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _state(V=32, C=16, base_log2=1, fp=False, seed=3):
    src, dst, w = random_graph(V, C, max_bias=63, seed=seed)
    if fp:
        w = w.astype(np.float32) + 0.37
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=6,
                      base_log2=base_log2, fp_bias=fp, lam=4.0)
    return from_edges(cfg, src, dst, w), cfg


def _relay(st, cfg, params, walkers, seed, u=None, *, num_shards,
           backend="pallas", cap=None):
    """Run the sharded relay over a (num_shards,) host mesh."""
    mesh = jax.make_mesh((num_shards,), ("data",))
    run = make_relay(get_backend(backend), cfg, params, mesh,
                     mailbox_cap=cap)
    return run(st, walkers, seed, u)


@pytest.mark.parametrize("kind,base_log2,fp", [
    ("deepwalk", 1, False),
    ("deepwalk", 2, False),
    ("deepwalk", 1, True),
    ("deepwalk", 2, True),
    ("ppr", 1, False),
    ("ppr", 2, True),
    ("simple", 1, False),
])
@pytest.mark.parametrize("num_shards", [
    1, pytest.param(8, marks=multi)])
def test_relay_bitexact_vs_single_shard(kind, base_log2, fp, num_shards):
    """The tentpole contract: sharded walk_relay paths == single-shard
    random_walk bit-for-bit under fed uniforms, for every whole-walk
    kind × radix base × fp mode, with zero boundary truncation."""
    st, cfg = _state(base_log2=base_log2, fp=fp)
    B, L = 24, 10
    walkers = jnp.arange(B, dtype=jnp.int32) % cfg.num_vertices
    key = jax.random.key(0)
    u = jax.random.uniform(key, (L, B, 6))
    params = walks.WalkParams(
        kind=kind, length=L, stop_prob=0.1 if kind == "ppr" else 0.0)
    single = walks.random_walk(st, cfg, walkers, key, params,
                               backend="pallas", uniforms=u)
    paths, rounds, ovf = _relay(st, cfg, params, walkers,
                                seed_from_key(key), u,
                                num_shards=num_shards)
    np.testing.assert_array_equal(np.asarray(paths), np.asarray(single))
    if num_shards == 1:
        assert int(rounds) == 1 and int(ovf) == 0   # nothing to relay


@pytest.mark.parametrize("num_shards", [1, pytest.param(8, marks=multi)])
def test_relay_hash_prng_matches_single_shard(num_shards):
    """Without fed uniforms the counter-based (seed, walker, t) PRNG
    contract makes the relay *still* bit-identical to the single-shard
    pallas whole walk for the same key — the stream follows the walker
    across shards."""
    st, cfg = _state()
    B, L = 24, 10
    walkers = jnp.arange(B, dtype=jnp.int32) % cfg.num_vertices
    key = jax.random.key(7)
    params = walks.WalkParams(kind="deepwalk", length=L)
    single = walks.random_walk(st, cfg, walkers, key, params,
                               backend="pallas")
    paths, _, _ = _relay(st, cfg, params, walkers, seed_from_key(key),
                         num_shards=num_shards)
    np.testing.assert_array_equal(np.asarray(paths), np.asarray(single))


@pytest.mark.parametrize("num_shards", [1, pytest.param(8, marks=multi)])
def test_relay_cohorts_bitexact(num_shards):
    """Cohort interleaving reaches the relay's segment megakernel via
    ``cfg.cohorts`` (carried through ``walk_relay``'s shard-local
    ``dataclasses.replace``) — and changes nothing: the K=2 relay is
    bit-identical to the K=1 relay AND to the single-shard whole walk,
    because the counter PRNG keys by (seed, wid, t) only (DESIGN.md
    §8/§10)."""
    st, cfg = _state()
    B, L = 24, 10
    walkers = jnp.arange(B, dtype=jnp.int32) % cfg.num_vertices
    key = jax.random.key(11)
    params = walks.WalkParams(kind="deepwalk", length=L)
    single = walks.random_walk(st, cfg, walkers, key, params,
                               backend="pallas")
    outs = {}
    for K in (1, 2):
        cfg_k = dataclasses.replace(cfg, cohorts=K)
        paths, _, _ = _relay(st, cfg_k, params, walkers,
                             seed_from_key(key), num_shards=num_shards)
        outs[K] = np.asarray(paths)
    np.testing.assert_array_equal(outs[2], outs[1])
    np.testing.assert_array_equal(outs[2], np.asarray(single))


@pytest.mark.parametrize("num_shards", [1, pytest.param(8, marks=multi)])
def test_relay_reference_backend_matches_pallas(num_shards):
    """Both EngineBackends implement sample_walk_segment bit-exactly, so
    the relay result is backend-independent."""
    st, cfg = _state(base_log2=2, fp=True)
    B, L = 16, 8
    walkers = jnp.arange(B, dtype=jnp.int32) % cfg.num_vertices
    seed = jnp.array([42], jnp.int32)
    params = walks.WalkParams(kind="deepwalk", length=L)
    p_pal, _, _ = _relay(st, cfg, params, walkers, seed,
                         num_shards=num_shards, backend="pallas")
    p_ref, _, _ = _relay(st, cfg, params, walkers, seed,
                         num_shards=num_shards, backend="reference")
    np.testing.assert_array_equal(np.asarray(p_pal), np.asarray(p_ref))


@multi
def test_relay_overflow_requeue_stays_exact():
    """A 1-record mailbox overflows constantly; the relay re-enqueues
    leftovers instead of dropping them, so the result is unchanged —
    only slower (more rounds).  Satellite: no walker lost, overflow
    counted."""
    st, cfg = _state()
    B, L = 24, 10
    walkers = jnp.arange(B, dtype=jnp.int32) % cfg.num_vertices
    key = jax.random.key(0)
    u = jax.random.uniform(key, (L, B, 6))
    params = walks.WalkParams(kind="deepwalk", length=L)
    single = walks.random_walk(st, cfg, walkers, key, params,
                               backend="pallas", uniforms=u)
    seed = seed_from_key(key)
    wide, r_wide, _ = _relay(st, cfg, params, walkers, seed, u,
                             num_shards=8)
    tight, r_tight, ovf = _relay(st, cfg, params, walkers, seed, u,
                                 num_shards=8, cap=1)
    np.testing.assert_array_equal(np.asarray(tight), np.asarray(single))
    np.testing.assert_array_equal(np.asarray(wide), np.asarray(single))
    assert int(ovf) > 0 and int(r_tight) > int(r_wide)


@multi
def test_relay_ping_pong_terminates():
    """Pathological graph: every single hop crosses a shard boundary
    (bipartite matching between shard 0 and shard 7), so every walker
    relays every step.  The loop must terminate in ~L rounds with full
    untruncated paths — the worst case walk_whole used to truncate at
    step 1."""
    S, shard_size = 8, 4
    V = S * shard_size
    lo = np.arange(shard_size, dtype=np.int32)              # shard 0
    hi = lo + (S - 1) * shard_size                          # shard 7
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    w = np.ones(2 * shard_size, np.int32)
    cfg = BingoConfig(num_vertices=V, capacity=4, bias_bits=3)
    st = from_edges(cfg, src, dst, w)
    B, L = 16, 9
    walkers = jnp.asarray(np.concatenate([lo, hi])[:B], jnp.int32)
    key = jax.random.key(1)
    params = walks.WalkParams(kind="deepwalk", length=L)
    single = walks.random_walk(st, cfg, walkers, key, params,
                               backend="pallas")
    paths, rounds, ovf = _relay(st, cfg, params, walkers,
                                seed_from_key(key), num_shards=S)
    paths = np.asarray(paths)
    np.testing.assert_array_equal(paths, np.asarray(single))
    assert (paths >= 0).all()            # zero truncation, full length
    # one relay round per step, plus overflow retries if the default
    # per-pair mailbox (B // S rows) spills on the all-to-one traffic
    assert int(rounds) <= (L + 1) * (1 + int(ovf))


@pytest.mark.parametrize("num_shards", [1, pytest.param(8, marks=multi)])
def test_relay_round_is_one_pallas_call_per_shard(num_shards):
    """Launch-count contract (acceptance criterion): the relay's traced
    per-shard while-loop body contains EXACTLY ONE pallas_call — one
    resumable megakernel launch per shard per round; routing, placement
    and merging are plain XLA around it."""
    from tests.test_kernels import _count_prims
    st, cfg = _state()
    B, L = 16, 6
    walkers = jnp.arange(B, dtype=jnp.int32) % cfg.num_vertices
    seed = jnp.array([3], jnp.int32)
    params = walks.WalkParams(kind="deepwalk", length=L)
    bk = get_backend("pallas")
    shard_size = cfg.num_vertices // num_shards
    lcfg = dataclasses.replace(cfg, num_vertices=shard_size)

    mesh = jax.make_mesh((num_shards,), ("data",))

    def local(state, wk, sd):
        sidx = jax.lax.axis_index("data")
        return relay_local(bk, lcfg, params, state, wk, sd, sidx=sidx,
                           num_shards=num_shards, shard_size=shard_size,
                           axis="data")

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(jax.tree.map(lambda _: P("data"), st), P(),
                                P()),
                      out_specs=(P("data"), P(), P()), check_vma=False)
    jaxpr = jax.make_jaxpr(f)(st, walkers, seed)
    # all pallas_calls live inside the relay while-loop, exactly one
    # (shard_map traces one per-shard SPMD program: 1 launch per shard)
    assert _count_prims(jaxpr, "pallas_call") == 1
    assert _count_prims(jaxpr, "pallas_call", inside_loops_only=True) == 1


def test_relay_rejects_ragged_inputs():
    """Divisibility guards: a walker count or vertex count that does not
    divide over the shards must raise (the per-shard block reassembly
    would otherwise silently drop tail walkers), and mailbox_cap < 1 is
    rejected up front instead of spinning the round loop dry."""
    st, cfg = _state()
    params = walks.WalkParams(kind="deepwalk", length=4)
    mesh = jax.make_mesh((1,), ("data",))
    run = make_relay(get_backend("pallas"), cfg, params, mesh)
    seed = jnp.array([1], jnp.int32)
    with pytest.raises(ValueError, match="walker count"):
        # 2-shard relay_local over 21 walkers (mesh mocking not needed:
        # the guard is in relay_local itself)
        relay_local(get_backend("pallas"), cfg, params, st,
                    jnp.zeros((21,), jnp.int32), seed, sidx=0,
                    num_shards=2, shard_size=cfg.num_vertices // 2,
                    axis="data")
    if DEVS >= 2:       # V % 1 == 0 always; needs a real 2-shard mesh
        with pytest.raises(ValueError, match="num_vertices"):
            bad = dataclasses.replace(cfg,
                                      num_vertices=cfg.num_vertices + 1)
            make_relay(get_backend("pallas"), bad, params,
                       jax.make_mesh((2,), ("data",)))
    # divisible inputs still run (smoke the factory path end to end)
    paths, _, _ = run(st, jnp.zeros((8,), jnp.int32), seed)
    assert paths.shape == (8, 5)


def test_relay_view_encoding():
    """relay_view: owned neighbors -> local ids, remote -> -(g+2),
    padding stays -1 (the segment kernel's adjacency contract)."""
    st, cfg = _state(V=16, C=8)
    view = relay_view(st, lo=8, shard_size=8)
    nbr, enc = np.asarray(st.nbr), np.asarray(view.nbr)
    owned = (nbr >= 8) & (nbr < 16)
    assert (enc[owned] == nbr[owned] - 8).all()
    remote = (nbr >= 0) & (nbr < 8)
    assert (enc[remote] == -(nbr[remote] + 2)).all()
    assert (enc[nbr == -1] == -1).all()


@pytest.mark.parametrize("num_shards", [1, pytest.param(8, marks=multi)])
def test_dynwalk_sharded_engine_matches_single(num_shards):
    """serve/dynwalk sharded mode: a vertex-partitioned engine threads
    one donated state through owner-routed update rounds and relay
    walks, and serves paths bit-identical to the single-device engine
    for the same keys (states stay bit-identical too)."""
    from repro.serve.dynwalk import DynamicWalkEngine
    st, cfg = _state()
    cfg = dataclasses.replace(cfg, backend="pallas")
    params = walks.WalkParams(kind="deepwalk", length=8)
    mesh = jax.make_mesh((num_shards,), ("data",))
    eng_s = DynamicWalkEngine(jax.tree.map(jnp.copy, st), cfg, params,
                              backend="pallas", mesh=mesh)
    eng_1 = DynamicWalkEngine(jax.tree.map(jnp.copy, st), cfg, params,
                              backend="pallas")
    ins = jnp.array([True, True, False, True])
    uu = jnp.array([3, 17, 2, 29], jnp.int32)
    vv = jnp.array([9, 4, 11, 1], jnp.int32)
    ww = jnp.array([2, 5, 1, 3], jnp.int32)
    stats_s = eng_s.ingest(ins, uu, vv, ww)
    stats_1 = eng_1.ingest(ins, uu, vv, ww)
    for a, b in zip(jax.tree.leaves((eng_s.state, stats_s)),
                    jax.tree.leaves((eng_1.state, stats_1))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    starts = jnp.arange(16, dtype=jnp.int32) % cfg.num_vertices
    key = jax.random.key(9)
    p_s = eng_s.walk(starts, key=key)
    p_1 = eng_1.walk(starts, key=key)
    np.testing.assert_array_equal(np.asarray(p_s), np.asarray(p_1))
