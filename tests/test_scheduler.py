"""Continuous-serving scheduler (DESIGN.md §12).

The acceptance contract: the OVERLAPPED schedule — walk cohorts
dispatched asynchronously against generation *g* while coalesced update
windows build *g+1* on the donated state — serves paths BIT-IDENTICAL
to a serial replay of the recorded admission trace, at 1 and 8 shards,
guard on and off; generation stamps are monotone; backpressure
conserves requests (admitted + rejected + queued == offered); and a
randomized request-size stream never recompiles beyond the fixed bucket
set (the zero-recompilation pin).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.dyngraph import BingoConfig, from_edges
from repro.core.updates import R_CAPACITY
from repro.core.walks import WalkParams
from repro.graph.streams import (UpdateStream, coalesce_windows,
                                 windows_on_device)
from repro.serve import dynwalk as dynwalk_mod
from repro.serve.dynwalk import DynamicWalkEngine
from repro.serve.scheduler import (DrainOp, SchedulerConfig,
                                   ServingScheduler, UpdateOp, WalkOp,
                                   replay_admission_trace)
from tests.conftest import random_graph

DEVS = len(jax.devices())
multi = pytest.mark.skipif(
    DEVS < 8, reason="needs 8 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

V, C = 64, 8


def _engine(guard=None, mesh=None, buckets=(8, 16, 32), seed=7, **kw):
    src, dst, w = random_graph(V, C, max_bias=15, seed=3)
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=4)
    return DynamicWalkEngine(
        from_edges(cfg, src, dst, w), cfg,
        WalkParams(kind="deepwalk", length=6), seed=seed, guard=guard,
        mesh=mesh, walk_buckets=buckets, **kw)


def _mixed_traffic(sched, *, n=24, seed=0, upd_batch=4, max_req=10):
    """Drive a seeded mixed stream; returns completed results by rid."""
    rng = np.random.default_rng(seed)
    for i in range(n):
        if i % 3 == 0:
            assert sched.submit_update(
                rng.random(upd_batch) < 0.7,
                rng.integers(0, V, upd_batch).astype(np.int32),
                rng.integers(0, V, upd_batch).astype(np.int32),
                np.full(upd_batch, 2, np.int32))
        else:
            nreq = int(rng.integers(1, max_req))
            assert sched.submit_walk(
                rng.integers(0, V, nreq).astype(np.int32)) is not None
        sched.tick()
    done = {r.rid: r for r in sched.drain()}
    sched.check_conservation()
    return done


def _assert_replay_equal(sched, done, fresh_engine):
    """Every served path == the serial replay of the admission trace."""
    replayed = iter(replay_admission_trace(fresh_engine, sched.trace))
    n_ops = 0
    for op in sched.trace:
        if isinstance(op, WalkOp):
            rep = next(replayed)
            off = np.cumsum([0] + list(op.sizes))
            for j, rid in enumerate(op.rids):
                np.testing.assert_array_equal(
                    done[rid].paths, rep[off[j]:off[j + 1]],
                    err_msg=f"rid {rid} diverged from serial replay")
            n_ops += 1
    assert n_ops > 0 and n_ops == sum(
        isinstance(op, WalkOp) for op in sched.trace)


@pytest.mark.parametrize("guard", [None, True],
                         ids=["guard=off", "guard=on"])
def test_overlapped_equals_serial_replay(guard):
    """The §12 staleness contract, single device."""
    eng = _engine(guard)
    sched = ServingScheduler(eng, SchedulerConfig(update_lanes=8,
                                                  max_update_delay=2))
    done = _mixed_traffic(sched)
    assert done and sched.generation > 0
    _assert_replay_equal(sched, done, _engine(guard))
    if guard:
        eng.guard.check_conservation()
        assert any(isinstance(op, DrainOp) for op in sched.trace)


@multi
@pytest.mark.parametrize("guard", [None, True],
                         ids=["guard=off", "guard=on"])
def test_overlapped_equals_serial_replay_sharded(guard):
    """Same contract in mesh= mode: relay walk cohorts against g
    overlap owner-masked ingest building g+1, 8 shards."""
    mesh = jax.make_mesh((8,), ("data",))
    eng = _engine(guard, mesh=mesh, buckets=(8, 16, 32))
    sched = ServingScheduler(eng, SchedulerConfig(update_lanes=8,
                                                  max_update_delay=2))
    done = _mixed_traffic(sched, n=15)
    assert done and sched.generation > 0
    _assert_replay_equal(sched, done, _engine(guard, mesh=mesh))


def test_replay_capacity_spill_retries_at_drain_points():
    """The hard half of the guard=on replay contract: with tiny
    capacity, inserts spill to the pending queue, a delete frees a
    slot, and the retry runs at the scheduler's DrainOp — not
    per-round.  A walk dispatched between the delete and the drain
    must sample the PRE-retry state in live and replay alike; a
    per-round replay engine would retry right after the delete round
    and diverge exactly here."""
    Vs, Cs = 8, 2
    src, dst, w = random_graph(Vs, Cs, max_bias=7, seed=9)
    cfg = BingoConfig(num_vertices=Vs, capacity=Cs, bias_bits=3)

    def mk():
        return DynamicWalkEngine(from_edges(cfg, src, dst, w), cfg,
                                 WalkParams(kind="deepwalk", length=6),
                                 seed=13, guard=True, walk_buckets=(8,))

    dst0 = int(dst[src == 0][0])       # vertex 0's single seed edge
    tgt = [x for x in range(1, Vs) if x != dst0][:3]
    eng = mk()
    sched = ServingScheduler(eng, SchedulerConfig(update_lanes=4,
                                                  max_update_delay=1))
    # 3 inserts at vertex 0, one free slot: 2 lanes spill to pending
    assert sched.submit_update(np.ones(3, bool), np.zeros(3, np.int32),
                               np.array(tgt, np.int32),
                               np.full(3, 2, np.int32))
    sched.tick()                       # deadline flush -> spill round
    # delete the seed edge: frees one slot, arms the capacity retry
    assert sched.submit_update(np.zeros(1, bool), np.zeros(1, np.int32),
                               np.array([dst0], np.int32),
                               np.ones(1, np.int32))
    sched.tick()                       # deadline flush -> delete round
    assert sched.submit_walk(np.zeros(8, np.int32)) is not None
    sched.tick()                       # walk BEFORE the drain point
    done = {r.rid: r for r in sched.drain()}   # DrainOp: retry runs here
    assert any(isinstance(op, DrainOp) for op in sched.trace)
    assert eng.guard.retried == 1      # one freed slot, one spill applied
    assert len(eng.guard.pending) == 1 # the other spilled again
    assert eng.guard.reason_counts[R_CAPACITY] >= 2
    eng.guard.check_conservation()
    # and a walk AFTER the drain sees the retried insert in both
    assert sched.submit_walk(np.zeros(8, np.int32)) is not None
    sched.tick()
    done.update({r.rid: r for r in sched.drain()})
    sched.check_conservation()
    fresh = mk()
    _assert_replay_equal(sched, done, fresh)
    assert fresh.guard.retried == eng.guard.retried
    assert fresh.guard.quarantined == eng.guard.quarantined
    assert len(fresh.guard.pending) == len(eng.guard.pending)
    np.testing.assert_array_equal(fresh.guard.reason_counts,
                                  eng.guard.reason_counts)


def test_submit_update_rejects_lossy_weight_dtype():
    """Float weights on an integer-bias engine fail loudly at
    admission — the coalescing pad buffer would silently truncate
    them at flush time otherwise."""
    sched = ServingScheduler(_engine())
    with pytest.raises(TypeError, match="safe-cast"):
        sched.submit_update(np.ones(4, bool), np.zeros(4, np.int32),
                            np.ones(4, np.int32), np.full(4, 2.5))
    assert sched.updates_offered == 0  # nothing half-admitted
    sched.check_conservation()
    # integer weights of any width still admit
    assert sched.submit_update(np.ones(4, bool), np.zeros(4, np.int32),
                               np.ones(4, np.int32), np.full(4, 2))
    sched.drain()
    sched.check_conservation()


def test_close_restores_engine_guard_mode():
    """The constructor's defer_guard flip is scoped to the scheduler:
    close() drains and restores per-round accounting for direct
    engine.ingest callers."""
    eng = _engine(guard=True)
    assert eng.defer_guard is False
    sched = ServingScheduler(eng)
    assert eng.defer_guard is True
    assert sched.submit_update(np.ones(4, bool),
                               np.arange(4, dtype=np.int32),
                               np.arange(4, dtype=np.int32) + 1,
                               np.full(4, 2, np.int32))
    sched.close()
    assert eng.defer_guard is False and eng.guard_backlog == 0
    eng.guard.check_conservation()
    # direct ingest now accounts per-round again: no backlog grows
    eng.ingest(jnp.ones(2, bool), jnp.zeros(2, jnp.int32),
               jnp.ones(2, jnp.int32), jnp.full((2,), 2, jnp.int32))
    assert eng.guard_backlog == 0
    eng.guard.check_conservation()


def test_generation_tags_monotone_and_stale():
    """Stamps are monotone in dispatch order, and a walk admitted
    before an update window flushes samples the OLDER generation."""
    eng = _engine()
    sched = ServingScheduler(eng, SchedulerConfig(update_lanes=64,
                                                  max_update_delay=100))
    r0 = sched.submit_walk(np.zeros(4, np.int32))
    sched.tick()                       # dispatches against generation 0
    for _ in range(16):
        sched.submit_update(np.ones(4, bool), np.zeros(4, np.int32),
                            np.ones(4, np.int32), np.full(4, 2, np.int32))
    sched.tick()                       # flushes -> generation 1
    r1 = sched.submit_walk(np.zeros(4, np.int32))
    sched.tick()
    done = {r.rid: r for r in sched.drain()}
    assert done[r0].generation == 0
    assert done[r1].generation == 1
    rids = [r for op in sched.trace if isinstance(op, WalkOp)
            for r in op.rids]
    gens = [done[r].generation for r in rids]
    assert gens == sorted(gens)


def test_backpressure_conserves():
    """admitted + rejected + queued == offered under overflow, and
    rejected submissions are really rejected (None / False)."""
    eng = _engine()
    sched = ServingScheduler(eng, SchedulerConfig(
        update_lanes=8, max_walk_queue=16, max_update_queue=16,
        max_inflight=1))
    rng = np.random.default_rng(1)
    w_rej = u_rej = 0
    for i in range(40):
        if i % 2:
            ok = sched.submit_update(
                np.ones(8, bool), rng.integers(0, V, 8).astype(np.int32),
                rng.integers(0, V, 8).astype(np.int32),
                np.full(8, 2, np.int32))
            u_rej += 0 if ok else 8
        else:
            rid = sched.submit_walk(
                rng.integers(0, V, 8).astype(np.int32))
            w_rej += rid is None
        sched.check_conservation()     # holds at every moment
    # oversize walk: no cohort can hold it -> backpressure, not a crash
    assert sched.submit_walk(np.zeros(33, np.int32)) is None
    w_rej += 1
    sched.check_conservation()
    sched.drain()
    sched.check_conservation()
    assert sched.walks_rejected == w_rej and w_rej > 0
    assert sched.updates_rejected == u_rej
    assert sched.stats()["updates"]["queued_lanes"] == 0


def test_zero_recompilation_across_jittered_sizes():
    """Randomized request sizes hit only the |buckets| compiled walk
    programs, and walks_served counts REAL (unpadded) lanes."""
    eng = _engine(buckets=(8, 32))
    rng = np.random.default_rng(2)
    sizes = [int(rng.integers(1, 33)) for _ in range(20)]
    for n in sizes:
        paths = eng.walk(rng.integers(0, V, n).astype(np.int32))
        assert paths.shape == (n, 7)
    assert eng.walks_served == sum(sizes)
    cache = eng.walk_cache_size()
    assert cache != -1 and cache <= 2, \
        f"{cache} compiled walk programs for 2 buckets"
    # and through the scheduler: cohorts only ever use bucket shapes
    eng2 = _engine(buckets=(8, 32))
    sched = ServingScheduler(eng2)
    for n in sizes:
        sched.submit_walk(rng.integers(0, V, n).astype(np.int32))
        sched.tick()
    sched.drain()
    assert eng2.walk_cache_size() <= 2
    assert eng2.walks_served == sum(sizes)


def test_deferred_guard_ingest_never_syncs():
    """With defer_guard the ingest hot path makes NO device->host
    transfer (the PR-8 fix for the per-round np.asarray sync); the
    drain point settles the backlog and conservation holds."""
    eng = _engine(guard=True, defer_guard=True)
    rng = np.random.default_rng(3)
    rounds = [(jnp.asarray(rng.random(4) < 0.7),
               jnp.asarray(rng.integers(-2, V, 4), jnp.int32),
               jnp.asarray(rng.integers(0, V, 4), jnp.int32),
               jnp.full((4,), 2, jnp.int32)) for _ in range(5)]
    jax.block_until_ready(rounds)

    real = dynwalk_mod.np.asarray

    def tripwire(x, *a, **k):
        # numpy is one shared module, so this intercepts jax's own
        # np.asarray calls too — only a jax.Array argument is a
        # device->host transfer (the sync this test outlaws); python
        # scalars/tuples flow through untouched.
        if isinstance(x, jax.Array):
            raise AssertionError("host sync on the deferred ingest path")
        return real(x, *a, **k)

    dynwalk_mod.np.asarray = tripwire
    try:
        for r in rounds:
            eng.ingest(*r)
    finally:
        dynwalk_mod.np.asarray = real
    assert eng.guard_backlog == 5
    assert eng.drain_guard() == 5
    assert eng.guard_backlog == 0
    eng.guard.check_conservation()
    assert eng.guard.ingested == 20


def test_deferred_guard_matches_round_mode_accounting():
    """On a dirty stream (bad endpoints, absent deletes — no capacity
    spills) deferred accounting lands the same quarantine totals and
    reason tallies as the per-round mode."""
    rng = np.random.default_rng(4)
    rounds = []
    for _ in range(6):
        u = rng.integers(0, V, 6).astype(np.int32)
        u[0] = -1                                   # R_VERTEX every round
        rounds.append((rng.random(6) < 0.5, u,
                       rng.integers(0, V, 6).astype(np.int32),
                       np.full(6, 2, np.int32)))

    def run(defer):
        eng = _engine(guard=True, defer_guard=defer)
        for r in rounds:
            eng.ingest(*map(jnp.asarray, r))
        eng.drain_guard()
        eng.guard.check_conservation()
        return eng.guard

    g_round, g_defer = run(False), run(True)
    assert g_defer.ingested == g_round.ingested
    assert g_defer.quarantined == g_round.quarantined
    assert g_defer.accepted == g_round.accepted
    np.testing.assert_array_equal(g_defer.reason_counts,
                                  g_round.reason_counts)
    assert g_defer.quarantined > 0


def test_deadline_flush_pads_partial_window():
    """A partial update window flushes once the oldest queued edge has
    waited max_update_delay ticks — padded, one compiled shape."""
    eng = _engine()
    sched = ServingScheduler(eng, SchedulerConfig(update_lanes=64,
                                                  max_update_delay=3))
    sched.submit_update(np.ones(4, bool), np.zeros(4, np.int32),
                        np.ones(4, np.int32), np.full(4, 2, np.int32))
    sched.tick()
    sched.tick()
    assert sched.generation == 0       # younger than the deadline
    sched.tick()
    assert sched.generation == 1       # deadline flush
    (op,) = [op for op in sched.trace if isinstance(op, UpdateOp)]
    assert op.n_valid == 4 and len(op.u) == 64
    assert sched.stats()["updates"]["queued_lanes"] == 0


def test_padded_cohort_bit_equal_on_counter_prng_path():
    """On the whole-walk megakernel path (counter PRNG: draws keyed by
    (seed, lane, t)) a padded cohort's real lanes are bit-identical to
    the unpadded call — padding is invisible, not just deterministic."""
    src, dst, w = random_graph(16, 4, max_bias=7, seed=5)
    cfg = BingoConfig(num_vertices=16, capacity=4, bias_bits=3,
                      backend="pallas")
    params = WalkParams(kind="deepwalk", length=5)
    starts = np.array([3, 1, 4, 1, 5], np.int32)

    def run(buckets):
        eng = DynamicWalkEngine(from_edges(cfg, src, dst, w), cfg,
                                params, seed=11, walk_buckets=buckets)
        return np.asarray(eng.walk(starts))

    np.testing.assert_array_equal(run(None), run((8, 16)))


def test_coalesce_windows_contract():
    """Fixed shape, order-preserving, deadline-flushed, lane-conserving
    — and the device variant uploads the identical windows."""
    rounds, B = 6, 3
    st = UpdateStream(
        np.zeros(0, np.int32), np.zeros(0, np.int32), np.zeros(0, np.int32),
        np.ones((rounds, B), bool),
        np.arange(rounds * B, dtype=np.int32).reshape(rounds, B),
        np.arange(rounds * B, dtype=np.int32).reshape(rounds, B),
        np.full((rounds, B), 2, np.int32))
    ws = list(coalesce_windows(st, max_lanes=4, max_delay=1))
    assert all(w[1].shape == (4,) for w in ws)
    assert sum(w[4] for w in ws) == rounds * B
    np.testing.assert_array_equal(
        np.concatenate([w[1][:w[4]] for w in ws]),
        np.arange(rounds * B))
    # max_delay=0: every arrival round flushes -> no window older than it
    assert all(w[4] <= B for w in
               coalesce_windows(st, max_lanes=4, max_delay=0))
    dev = list(windows_on_device(st, max_lanes=4, max_delay=1))
    assert len(dev) == len(ws)
    for (di, du, dv, dw, dn), (hi, hu, hv, hw, hn) in zip(dev, ws):
        assert dn == hn
        np.testing.assert_array_equal(np.asarray(du), hu)


def test_windows_feed_engine_like_rounds():
    """Padded windows through ingest(n_valid=) land the same state as
    the raw per-round stream — padding never mutates."""
    src, dst, w = random_graph(V, C, max_bias=15, seed=6)
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=4)
    rng = np.random.default_rng(8)
    rounds, B = 4, 6
    st = UpdateStream(
        src, dst, w,
        np.ones((rounds, B), bool),
        rng.integers(0, V, (rounds, B)).astype(np.int32),
        rng.integers(0, V, (rounds, B)).astype(np.int32),
        np.full((rounds, B), 2, np.int32))

    def mk():
        return DynamicWalkEngine(from_edges(cfg, src, dst, w), cfg,
                                 WalkParams(kind="deepwalk", length=5),
                                 seed=0, walk_buckets=(8,))
    e1, e2 = mk(), mk()
    for r in range(rounds):
        e1.ingest(jnp.asarray(st.is_insert[r]), jnp.asarray(st.u[r]),
                  jnp.asarray(st.v[r]), jnp.asarray(st.w[r]))
    for ins, u, v, ww_, nv in windows_on_device(st, max_lanes=16,
                                                max_delay=2):
        e2.ingest(ins, u, v, ww_, n_valid=nv)
    for a, b in zip(jax.tree.leaves(e1.state), jax.tree.leaves(e2.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(e1.walk(np.arange(8))),
                                  np.asarray(e2.walk(np.arange(8))))
