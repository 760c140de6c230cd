"""The main-path kernels and the relay compile for a TPU v5e.

Interpret mode cannot see what the chip's compiler refuses: lane slices
off the 128-lane tiling, primitives Mosaic does not lower (``cumsum``,
``argmax``), scoped-VMEM overruns.  These tests hand every main-path
kernel entry to the TPU compiler for a *described* v5e chip — no chip is
needed — at C=1024, bias_bits=16, L=80 and a 256-walker tile, and check
that the program holds a Mosaic kernel and fits the chip's memory.  The
tables are cut to a few thousand rows: V sets only the HBM operand size,
not what the kernel does.

``test_relay_compiles`` compiles the engine's whole relay program at the
four-chip cell's full size (``chipbench/configs/g500-s19x4.json``) over
the described ``v5e:2x2``: the segment kernel, the all_to_all exchanges,
and one chip's share of the state within its HBM.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test collection imports this file
in every worker.  All such tests live in this one file.

The last test is a CPU check that the tiled ``from_edges`` build is
bit-identical to the untiled one.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import radix
from repro.core.dyngraph import (BingoConfig, _group_rows, _scatter_adjacency,
                                 empty_state)
from repro.graph.rmat import degree_bias, rmat_edges
from repro.kernels.update_fused import update_fused_pallas
from repro.kernels.walk_fused import walk_fused_pallas
from repro.kernels.walk_sample import (walk_sample_pallas,
                                       walk_sample_uniform_pallas)

C, BIAS_BITS, L, BT = 1024, 16, 80, 256
V = 4096                    # table rows handed to the kernels
HBM_BYTES = 16 * 2**30      # one v5e chip
ROWS_PER_CHIP = 131072      # g500-s19x4: 2^19 vertex rows over 4 chips
RELAY_WALKERS = 16384       # the corpus mix's batch


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache)


@pytest.fixture(scope="module")
def one_chip(v5e):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, kernel, **jit):
    """Compile ``fn`` (``jax.jit(fn, **jit)``) for the chip; its Mosaic
    kernel is the custom call named ``kernel`` (the ``pallas_call``'s
    ``name``, which traces show).  Returns the compiled HLO text."""
    compiled = jax.jit(fn, **jit).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{kernel}(\.\d+)? = [^\n]*custom-call", text), kernel
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used < HBM_BYTES
    return text


def _tables(s, base_log2, fp):
    kin = -(-BIAS_BITS // base_log2) + (1 if fp else 0)
    return (_spec(s, (V, kin), jnp.float32), _spec(s, (V, kin), jnp.int32),
            _spec(s, (V, C), jnp.int32), _spec(s, (V, C), jnp.int32),
            _spec(s, (V,), jnp.int32),
            _spec(s, (V, C), jnp.float32) if fp else None)


@pytest.mark.parametrize("cohorts", [1, 2, 4])
@pytest.mark.parametrize("fp", [False, True])
@pytest.mark.parametrize("base_log2", [1, 2])
def test_walk_fused_whole_walk_compiles(one_chip, cohorts, fp, base_log2):
    prob, alias, bias, nbr, deg, frac = _tables(one_chip, base_log2, fp)

    def walk(prob, alias, bias, nbr, deg, frac, starts, seed):
        return walk_fused_pallas(prob, alias, bias, nbr, deg, frac, starts,
                                 seed, length=L, base_log2=base_log2,
                                 stop_prob=0.15, block_b=BT,
                                 cohorts=cohorts)

    _compile(walk, prob, alias, bias, nbr, deg, frac,
             _spec(one_chip, (2 * BT,), jnp.int32),
             _spec(one_chip, (1,), jnp.int32), kernel="walk_whole")


@pytest.mark.parametrize("cohorts", [1, 2, 4])
def test_walk_fused_segment_compiles(one_chip, cohorts):
    prob, alias, bias, nbr, deg, _ = _tables(one_chip, 1, False)
    vec = _spec(one_chip, (2 * BT,), jnp.int32)

    def seg(prob, alias, bias, nbr, deg, starts, seed, t0, wid):
        return walk_fused_pallas(prob, alias, bias, nbr, deg, None, starts,
                                 seed, None, t0, wid, length=L,
                                 segment=True, block_b=BT, cohorts=cohorts)

    _compile(seg, prob, alias, bias, nbr, deg, vec,
             _spec(one_chip, (1,), jnp.int32), vec, vec,
             kernel="walk_segment")


@pytest.mark.parametrize("cohorts", [1, 2, 4])
def test_walk_fused_uniform_compiles(one_chip, cohorts):
    _, _, _, nbr, deg, _ = _tables(one_chip, 1, False)

    def walk(nbr, deg, starts, seed):
        return walk_fused_pallas(None, None, None, nbr, deg, None, starts,
                                 seed, length=L, uniform=True, block_b=BT,
                                 cohorts=cohorts)

    _compile(walk, nbr, deg, _spec(one_chip, (2 * BT,), jnp.int32),
             _spec(one_chip, (1,), jnp.int32), kernel="walk_whole")


@pytest.mark.parametrize("fp,base_log2", [(False, 1), (True, 1), (False, 2)])
def test_walk_sample_compiles(one_chip, fp, base_log2):
    s = one_chip
    kin = -(-BIAS_BITS // base_log2) + (1 if fp else 0)
    args = [_spec(s, (BT, kin), jnp.float32), _spec(s, (BT, kin), jnp.int32),
            _spec(s, (BT, C), jnp.int32), _spec(s, (BT, C), jnp.int32),
            _spec(s, (BT,), jnp.int32), _spec(s, (BT, 5), jnp.float32)]
    if fp:
        args.append(_spec(s, (BT, C), jnp.float32))
    _compile(lambda *a: walk_sample_pallas(*a, base_log2=base_log2), *args,
             kernel="walk_step")


def test_walk_sample_uniform_compiles(one_chip):
    s = one_chip
    _compile(walk_sample_uniform_pallas, _spec(s, (BT, C), jnp.int32),
             _spec(s, (BT,), jnp.int32), _spec(s, (BT, 1), jnp.float32),
             kernel="walk_uniform")


@pytest.mark.parametrize("capacity,adaptive,fp", [
    (1024, True, False), (1024, True, True), (1024, False, False),
    (128, True, False)])
def test_update_fused_compiles(one_chip, capacity, adaptive, fp):
    cfg = BingoConfig(num_vertices=V, capacity=capacity, bias_bits=BIAS_BITS,
                      adaptive=adaptive, fp_bias=fp)
    state = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                         jax.eval_shape(lambda: empty_state(cfg)))
    B = 2048
    lanes = [_spec(one_chip, (B,), t) for t in (
        jnp.bool_, jnp.int32, jnp.int32,
        jnp.float32 if fp else jnp.int32)]
    _compile(lambda st, i, u, v, w: update_fused_pallas(st, cfg, i, u, v, w),
             state, *lanes, kernel="update_round")


@pytest.mark.parametrize("kind", ["deepwalk", "ppr", "simple"])
def test_relay_compiles(v5e, monkeypatch, kind):
    """The sharded engine's relay (``make_relay`` as
    ``DynamicWalkEngine(mesh=...)`` builds it: overlapped rounds, device
    counters) for g500-s19x4 — 131,072 rows of C=1024 a chip, 16-bit
    integer biases, 16,384 walkers of 80 steps — compiles over the four
    described chips with the segment kernel, the walker exchange's
    all_to_all, and one chip's share within 16 GiB.  The state is donated
    and handed back, as the engine's ``engine_walk`` does, so the whole
    state counts, not only the tables the relay reads."""
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
    from repro.core.backend import get_backend
    from repro.core.walks import WalkParams
    from repro.distributed.relay import make_relay
    from repro.kernels import ops
    # the ops wrappers would pick interpret mode on this host
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    mesh = Mesh(np.array(v5e.devices), ("v",), axis_types=(AxisType.Auto,))
    cfg = BingoConfig(num_vertices=len(v5e.devices) * ROWS_PER_CHIP,
                      capacity=C, bias_bits=BIAS_BITS, fp_bias=False)
    params = WalkParams(kind, L, stop_prob=1 / 80 if kind == "ppr" else 0.0)
    relay = make_relay(get_backend("pallas"), cfg, params, mesh,
                       overlap=True, counters=True)
    state = jax.tree.map(
        lambda x: _spec(NamedSharding(mesh, P("v")), x.shape, x.dtype),
        jax.eval_shape(lambda: empty_state(cfg)))
    whole = NamedSharding(mesh, P())
    text = _compile(lambda st, w, s: (st, relay(st, w, s)), state,
                    _spec(whole, (RELAY_WALKERS,), jnp.int32),
                    _spec(whole, (1,), jnp.int32), kernel="walk_segment",
                    donate_argnums=0)
    assert "all-to-all" in text


@pytest.mark.parametrize("V_,adaptive,fp", [
    (256, True, False), (200, True, True), (256, False, False)])
def test_from_edges_tiled_build_matches_untiled(V_, adaptive, fp):
    """``from_edges`` builds the group rows in row tiles
    (``_group_rows``, the row axis padded to whole tiles); every output
    must match the one-shot build bit for bit, including a V that is not
    a multiple of the tile."""
    scale = int(np.ceil(np.log2(V_)))
    src, dst = rmat_edges(scale, 8, seed=3)
    keep = (src < V_) & (dst < V_)
    src, dst = src[keep], dst[keep]
    w = degree_bias(src, dst, V_, bias_bits=8)
    cfg = BingoConfig(num_vertices=V_, capacity=32, bias_bits=8,
                      adaptive=adaptive, fp_bias=fp)
    if fp:
        w_int, w_frac = radix.decompose_fp(w.astype(np.float32) / 3.0,
                                           cfg.lam)
    else:
        w_int, w_frac = jnp.asarray(w), jnp.zeros(len(w), jnp.float32)
    _, b, f, deg = _scatter_adjacency(cfg, jnp.asarray(src),
                                      jnp.asarray(dst), w_int, w_frac)
    whole = _group_rows(cfg, b, f, deg, chunk=V_)
    tiled = _group_rows(cfg, b, f, deg, chunk=48)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(tiled)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
