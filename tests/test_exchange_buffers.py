"""Multi-channel conservation under double-buffered mailboxes.

The overlapped relay (DESIGN.md §10) keeps exchange payloads in an
*in-flight* buffer for a full round while the next segment runs, then
merges the landing buffer into the resident pool.  This suite drives
``exchange_walkers`` + ``merge_into_free`` through exactly that
lifecycle with an explicit scan — in-flight / landed / resident /
leftover populations counted every round — and pins the conservation
ledger the relay's correctness rests on:

    sent == landed + leftover            (the exchange itself)
    resident + in-flight == total rows   (the double-buffer swap)

at every round, including the overflow-requeue path at ``cap=1`` and a
burst of new rows injected while earlier rows are still in flight.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.walker_exchange import (exchange_walkers,
                                               merge_into_free)

DEVS = len(jax.devices())
multi = pytest.mark.skipif(
    DEVS < 8, reason="needs 8 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

AXIS = "data"
# stats row layout emitted per round by the driver
SENT, LANDED, LEFT, RESIDENT, INFLIGHT, OVF, SHORT_A, SHORT_Q = range(8)


def _make_driver(mesh, num_shards, shard_size, rounds, cap=None,
                 burst_round=-1):
    """Double-buffered exchange loop: each round ships the in-flight
    buffer, merges the landing buffer into the resident pool, then
    refills the next in-flight buffer from leftovers + fresh movers —
    the same swap the overlapped relay performs, minus the walking."""

    def live(buf):
        return (buf[:, 0] >= 0).sum(dtype=jnp.int32)

    def local(resident, inflight, burst):
        sidx = jax.lax.axis_index(AXIS)

        def body(carry, r):
            resident, inflight = carry
            sent = jax.lax.psum(live(inflight), AXIS)
            arrived, leftover, ovf = exchange_walkers(
                inflight, shard_size, num_shards, AXIS, cap=cap)
            landed = jax.lax.psum(live(arrived), AXIS)
            left = jax.lax.psum(live(leftover), AXIS)

            # mid-flight burst: new rows appear while earlier rows are
            # still crossing — the hard case for the ledger.
            binj = jnp.where(jnp.equal(r, burst_round), burst,
                             jnp.full_like(burst, -1))
            resident, _ = merge_into_free(resident, binj,
                                          binj[:, 0] >= 0)
            resident, p_a = merge_into_free(resident, arrived,
                                            arrived[:, 0] >= 0)
            short_a = jax.lax.psum(live(arrived) - p_a, AXIS)

            movers = (resident[:, 0] >= 0) \
                & (resident[:, 0] // shard_size != sidx)
            nxt = jnp.full_like(inflight, -1)
            nxt, p_l = merge_into_free(nxt, leftover, leftover[:, 0] >= 0)
            nxt, p_m = merge_into_free(nxt, resident, movers)
            short_q = jax.lax.psum(
                live(leftover) - p_l + movers.sum(dtype=jnp.int32) - p_m,
                AXIS)
            resident = jnp.where(movers[:, None], jnp.int32(-1), resident)

            stats = jnp.stack([
                sent, landed, left,
                jax.lax.psum(live(resident), AXIS),
                jax.lax.psum(live(nxt), AXIS),
                jax.lax.psum(ovf, AXIS), short_a, short_q])
            return (resident, nxt), stats

        (resident, inflight), stats = jax.lax.scan(
            body, (resident, inflight),
            jnp.arange(rounds, dtype=jnp.int32))
        return resident, inflight, stats

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(AXIS), P(AXIS), P(AXIS)),
                         out_specs=(P(AXIS), P(AXIS), P()),
                         check_vma=False)


def _rows(num_shards, per_shard, rows_per_shard, dest_fn):
    """(S * rows_per_shard, 2) buffer: ``per_shard`` live rows per
    shard, fields (destination vertex, globally unique id)."""
    buf = np.full((num_shards * rows_per_shard, 2), -1, np.int32)
    for s in range(num_shards):
        for k in range(per_shard):
            wid = s * 100 + k
            buf[s * rows_per_shard + k] = (dest_fn(s, k), wid)
    return jnp.asarray(buf)


def _assert_ledger(stats, total_before, total_after, burst_round):
    stats = np.asarray(stats)
    for r, row in enumerate(stats):
        total = total_after if 0 <= burst_round <= r else total_before
        assert row[SENT] == row[LANDED] + row[LEFT], (r, row)
        assert row[RESIDENT] + row[INFLIGHT] == total, (r, row)
        assert row[LEFT] == row[OVF], (r, row)
        assert row[SHORT_A] == 0 and row[SHORT_Q] == 0, (r, row)


def _assert_delivered(resident, inflight, shard_size, rows_per_shard,
                      ids):
    resident = np.asarray(resident)
    assert (np.asarray(inflight)[:, 0] < 0).all(), "rows still in flight"
    livem = resident[:, 0] >= 0
    # every row sits on the shard that owns its destination vertex
    owner = resident[livem, 0] // shard_size
    at = np.flatnonzero(livem) // rows_per_shard
    np.testing.assert_array_equal(owner, at)
    # distinct-id census: the delivered multiset is exactly the injected
    # set — no loss, no duplication, through every buffer hand-off
    np.testing.assert_array_equal(np.sort(resident[livem, 1]),
                                  np.sort(ids))


def _run_case(num_shards, *, per_shard, dest_fn, rounds, cap=None,
              burst=None, burst_round=-1, rows_per_shard=16,
              shard_size=4):
    mesh = jax.make_mesh((num_shards,), (AXIS,))
    resident = _rows(num_shards, per_shard, rows_per_shard, dest_fn)
    inflight = jnp.full_like(resident, -1)
    if burst is None:
        burst = jnp.full_like(resident, -1)
    drv = _make_driver(mesh, num_shards, shard_size, rounds, cap=cap,
                       burst_round=burst_round)
    res, inf, stats = drv(resident, inflight, burst)
    base = np.asarray(resident)
    extra = np.asarray(burst)
    ids = np.concatenate([base[base[:, 0] >= 0, 1],
                          extra[extra[:, 0] >= 0, 1]]) \
        if burst_round >= 0 else base[base[:, 0] >= 0, 1]
    n0 = int((base[:, 0] >= 0).sum())
    _assert_ledger(stats, n0, len(ids), burst_round)
    _assert_delivered(res, inf, shard_size, rows_per_shard, ids)
    return np.asarray(stats)


@multi
def test_conservation_default_cap():
    """Scattered destinations, default mailbox cap: everything lands in
    two rounds and the ledger balances at each one."""
    stats = _run_case(8, per_shard=6, rounds=4,
                      dest_fn=lambda s, k: ((s * 100 + k) * 7) % 32)
    assert stats[0, SENT] == 0            # first round ships empty buffers
    assert stats[1, SENT] > 0


@multi
def test_conservation_cap1_overflow_requeue():
    """All rows funnel to shard 0 with one-row mailboxes: leftovers
    re-queue through the in-flight buffer for many rounds; conservation
    holds at every swap and overflow is observed, not silently eaten."""
    stats = _run_case(8, per_shard=3, rounds=8, cap=1,
                      rows_per_shard=32, dest_fn=lambda s, k: k % 4)
    assert (stats[:, OVF] > 0).any()
    # drain takes multiple rounds: 3 rows/sender through cap=1 mailboxes
    assert (stats[2, INFLIGHT] > 0) and (stats[-1, INFLIGHT] == 0)


@multi
def test_conservation_midflight_burst():
    """A burst of fresh rows arrives while cap=1 starvation still has
    earlier rows in flight — the resident + in-flight total steps up by
    exactly the burst size and stays balanced after."""
    S, RPS = 8, 32
    burst = np.full((S * RPS, 2), -1, np.int32)
    for s in range(S):
        for k in range(2):
            burst[s * RPS + k] = ((k + 1) % 4 + 4, 1000 + s * 10 + k)
    stats = _run_case(8, per_shard=3, rounds=10, cap=1,
                      rows_per_shard=32, dest_fn=lambda s, k: k % 4,
                      burst=jnp.asarray(burst), burst_round=2)
    assert (stats[:, OVF] > 0).any()


def test_conservation_single_shard():
    """Degenerate 1-shard mesh: the same loop, every destination local
    after one hop, ledger still exact (runs on any device count)."""
    _run_case(1, per_shard=6, rounds=3, shard_size=32,
              dest_fn=lambda s, k: (k * 5) % 32)
