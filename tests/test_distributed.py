"""Distribution-layer units: sharding rules, walker routing, partition."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import (batch_pspec, cache_pspecs,
                                        fsdp_axes, param_pspecs)
from repro.distributed.walker_exchange import exchange_walkers
from repro.graph.partition import Partition1D
from repro.models import init_decode_cache, init_model


def _mesh():
    # abstract mesh over the single CPU device: spec construction only
    return jax.make_mesh((1, 1), ("data", "model"))


def _prod_mesh_shape():
    """A fake mesh-shape view for divisibility checks (16 x 16)."""
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    return FakeMesh()


def test_param_pspecs_rules_divisibility():
    mesh = _prod_mesh_shape()
    cfg = get_config("qwen2-0.5b")
    params = jax.eval_shape(lambda k: init_model(cfg, k), jax.random.key(0))
    specs = param_pspecs(params, cfg, mesh)
    # embed (151936, 896): vocab % 16 == 0 -> model; d % 16 == 0 -> data
    assert specs["embed"] in (P("model", ("data",)), P("model", "data"))
    # attention wq stacked (R, D, H*dh): H*dh = 896 % 16 == 0 -> model out
    wq = specs["stages"]["slot0"]["attn"]["wq"]
    assert wq in (P(None, ("data",), "model"), P(None, "data", "model"))
    # biases replicate
    assert specs["stages"]["slot0"]["attn"]["bq"] == P(None, None)


def test_param_pspecs_hubert_vocab_fallback():
    mesh = _prod_mesh_shape()
    cfg = get_config("hubert-xlarge")
    params = jax.eval_shape(lambda k: init_model(cfg, k), jax.random.key(0))
    specs = param_pspecs(params, cfg, mesh)
    # vocab 504 % 16 != 0 -> replicate that dim instead of failing
    assert specs["embed"][0] is None


def test_param_pspecs_expert_parallel_selection():
    mesh = _prod_mesh_shape()
    # llama4: 16 experts % 16 == 0 -> EP over model on the expert dim
    cfg = get_config("llama4-scout-17b-a16e")
    params = jax.eval_shape(lambda k: init_model(cfg, k), jax.random.key(0))
    specs = param_pspecs(params, cfg, mesh)
    wg = specs["stages"]["slot0"]["moe"]["wg"]
    assert wg[1] == "model"          # (R, E->model, D->fsdp, F)
    # mixtral: 8 experts -> no EP; F shards over model instead
    cfg2 = get_config("mixtral-8x7b")
    p2 = jax.eval_shape(lambda k: init_model(cfg2, k), jax.random.key(0))
    s2 = param_pspecs(p2, cfg2, mesh)
    wg2 = s2["stages"]["slot0"]["moe"]["wg"]
    assert wg2[1] is None and wg2[-1] == "model"


def test_cache_pspecs_shapes():
    mesh = _prod_mesh_shape()
    cfg = get_config("mixtral-8x7b")
    cache = jax.eval_shape(lambda: init_decode_cache(cfg, 128, 4096))
    specs = cache_pspecs(cfg, mesh, cache)
    k_spec = specs["slot0"]["k"]
    assert k_spec[1] in ("data", ("data",))   # batch 128 % 16
    # Hkv = 8 does not divide 16 -> sequence takes the model axis
    assert k_spec[2] is None and k_spec[3] == "model"


def test_batch_pspec():
    mesh = _prod_mesh_shape()
    cfg = get_config("qwen2-0.5b")
    b = {"inputs": jax.ShapeDtypeStruct((256, 128), jnp.int32)}
    assert batch_pspec(cfg, mesh, b)["inputs"][0] in ("data", ("data",))
    b1 = {"inputs": jax.ShapeDtypeStruct((1, 128), jnp.int32)}
    assert batch_pspec(cfg, mesh, b1)["inputs"][0] is None


def test_partition_1d():
    p = Partition1D(num_vertices=100, num_shards=8)
    assert p.padded_vertices == 104
    assert p.shard_size == 13
    np.testing.assert_array_equal(p.shard_of([0, 13, 99]), [0, 1, 7])
    lo, hi = p.vertex_range(7)
    assert (lo, hi) == (91, 100)
    np.testing.assert_array_equal(p.local_id([0, 13, 99]), [0, 0, 8])


def test_exchange_walkers_single_shard_semantics():
    """num_shards=1: routing reduces to sort-compact of live walkers."""
    mesh = jax.make_mesh((1,), ("data",))

    W = 16
    walkers = jnp.array([5, -1, 3, -1, 7, 2, -1, 9] + [-1] * 8, jnp.int32)

    f = jax.shard_map(
        lambda w: exchange_walkers(w, shard_size=100, num_shards=1,
                                   axis="data"),
        mesh=mesh, in_specs=(P("data"),), out_specs=(P("data"),) * 2 + (P(),),
        check_vma=False)
    out, leftover, overflow = f(walkers)
    out = np.asarray(out)
    live = sorted(x for x in out.tolist() if x >= 0)
    assert live == [2, 3, 5, 7, 9]
    assert len(out) == W
    assert int(overflow) == 0
    assert (np.asarray(leftover) == -1).all()


def test_exchange_multifield_overflow_conservation():
    """Mailbox overflow is returned to the sender, never dropped: for any
    cap, sent multiset == arrived ∪ leftover (satellite: conservation),
    and traffic <= cap loses nothing."""
    mesh = jax.make_mesh((1,), ("data",))

    rng = np.random.default_rng(0)
    W = 16
    rows = np.stack([rng.integers(0, 100, W),           # dest vertex
                     rng.integers(0, 8, W),             # step
                     np.arange(W)], -1).astype(np.int32)
    rows[rng.random(W) < 0.25] = -1                     # empty rows
    rows[0, 0] = 250        # unowned vertex (>= S * shard_size): no
    rows[0, 1:] = (7, 0)    # owner exists — must surface as leftover,
    payload = jnp.asarray(rows)                  # never silently drop
    sent = {tuple(r) for r in rows.tolist() if r[0] >= 0}

    for cap in (None, 2, 1):
        f = jax.shard_map(
            lambda p: exchange_walkers(p, shard_size=100, num_shards=1,
                                       axis="data", cap=cap),
            mesh=mesh, in_specs=(P("data"),),
            out_specs=(P("data"),) * 2 + (P(),), check_vma=False)
        arrived, leftover, overflow = f(payload)
        got = {tuple(r) for r in np.asarray(arrived).tolist() if r[0] >= 0}
        kept = {tuple(r) for r in np.asarray(leftover).tolist() if r[0] >= 0}
        assert got | kept == sent, cap
        assert not (got & kept), cap
        assert int(overflow) == len(kept), cap
        assert (250, 7, 0) in kept          # unowned dest is NOT dropped
        if cap is None or cap >= len(sent):
            assert kept == {(250, 7, 0)}   # traffic <= cap: nothing else

    with pytest.raises(ValueError, match="cap"):
        exchange_walkers(payload, shard_size=100, num_shards=1, cap=0)


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs >= 4 devices "
                           "(XLA_FLAGS=--xla_force_host_platform_device_count)")
def test_exchange_multishard_routing_and_conservation():
    """4 shards: every routed record lands on its destination vertex's
    owner, and arrived ∪ leftover over ALL shards is the sent multiset."""

    S, shard_size, Wl = 4, 8, 12
    mesh = jax.make_mesh((S,), ("data",))
    rng = np.random.default_rng(1)
    rows = np.stack([rng.integers(0, S * shard_size, S * Wl),
                     rng.integers(0, 9, S * Wl),
                     np.arange(S * Wl)], -1).astype(np.int32)
    rows[rng.random(S * Wl) < 0.2] = -1
    payload = jnp.asarray(rows)
    sent = {tuple(r) for r in rows.tolist() if r[0] >= 0}

    for cap in (Wl, None, 1):       # per-pair traffic <= Wl always
        def route(p):
            arrived, leftover, overflow = exchange_walkers(
                p, shard_size=shard_size, num_shards=S, axis="data",
                cap=cap)
            return arrived, leftover, overflow[None]   # (1,) per shard
        f = jax.shard_map(
            route, mesh=mesh, in_specs=(P("data"),),
            out_specs=(P("data"), P("data"), P("data")), check_vma=False)
        arrived, leftover, overflow = f(payload)
        arrived = np.asarray(arrived).reshape(S, -1, 3)
        for s in range(S):
            for v, _t, _w in arrived[s]:
                if v >= 0:
                    assert v // shard_size == s      # owner placement
        got = {tuple(r) for r in arrived.reshape(-1, 3).tolist() if r[0] >= 0}
        kept = {tuple(r) for r in np.asarray(leftover).tolist() if r[0] >= 0}
        assert got | kept == sent and not (got & kept)
        assert int(np.asarray(overflow).sum()) == len(kept)
        if cap == Wl:
            assert len(kept) == 0    # traffic <= cap: no walker lost


@pytest.mark.parametrize("shards", [1, 4])
def test_sharded_from_edges_matches_single_device_build(shards):
    """The mesh engine's state built in place on every shard equals the
    single-device ``from_edges`` build, row for row."""
    from repro.core.dyngraph import BingoConfig, from_edges
    from repro.graph.rmat import degree_bias, rmat_edges
    from repro.serve.dynwalk import sharded_from_edges
    if len(jax.devices()) < shards:
        pytest.skip(f"needs {shards} devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count)")
    src, dst = rmat_edges(8, 8, seed=1)
    w = degree_bias(src, dst, 256, bias_bits=8)
    cfg = BingoConfig(num_vertices=256, capacity=16, bias_bits=8)
    mesh = jax.make_mesh((shards,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:shards])
    built = sharded_from_edges(cfg, src, dst, w, mesh)
    assert sorted(x.data.shape[0] for x in built.nbr.addressable_shards) \
        == [256 // shards] * shards
    for a, b in zip(jax.tree.leaves(from_edges(cfg, src, dst, w)),
                    jax.tree.leaves(built)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
