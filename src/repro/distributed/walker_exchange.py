"""Distributed walker routing — the paper's §9.1 design on the TPU mesh.

The graph (and the whole BINGO sampling space) is 1-D vertex-partitioned
over the ``data`` (× ``pod``) axes; after every local sampling step the
walkers whose next vertex lives on another shard are shipped with one
``all_to_all`` — walkers move, structures never do (the paper's explicit
choice; P2P GPU copies become ICI all-to-all).

``shard_map`` keeps the per-shard view explicit: each shard sorts its
outgoing walkers by destination shard into fixed-size mailboxes, the
all_to_all rotates mailboxes, and arrivals are compacted locally.

Payloads are multi-field rows keyed by a *destination vertex* in field
0; everything after it is opaque freight.  The relay (DESIGN.md §10)
ships two kinds: **walker records** ``(vertex, step, wid)`` — a walker
resumes at its current vertex's owner, carrying the global walker id
that keys its PRNG stream and its home-block row — and **path
records** ``(home-tag, wid, slot, path…)`` — a finished segment's
columns routed to the walker's *home* shard (the tag is
``route_tag(home_shard, shard_size)``, a vertex the home shard owns),
with the sender's slot index riding along so overflow re-pins to the
slot it came from.  The per-step engine ships ``(vertex, walker-id)``
so hops keep their identity across shards.  Mailbox overflow is
*never* a silent drop: entries beyond a destination's capacity are
returned to the sender (``leftover``) with an overflow count, and the
relay re-enqueues them next round — conservation is exact
(``tests/test_distributed.py``).

Under the overlapped relay schedule (DESIGN.md §10) the mailboxes are
*double-buffered*: a payload sits in an in-flight buffer for one full
round while the next segment kernel runs, then lands and merges into
the resident pool, with leftovers re-queued through the next in-flight
buffer.  ``exchange_walkers`` itself is oblivious to this — it routes
whatever buffer it is handed — but the conservation ledger must hold
across the buffer hand-offs too: in-flight + landed + resident +
leftover == total at every round (``tests/test_exchange_buffers.py``).
On a 2D vertex × walker mesh (§13), ``axis`` is the *vertex* axes only
— each walker group runs its own independent transport.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["exchange_walkers", "make_walk_step", "merge_into_free",
           "route_tag"]


def merge_into_free(buf, rows, mask):
    """Scatter ``rows[mask]`` into the free rows of ``buf``.

    ``buf`` (N, F) and ``rows`` (M, F) are record buffers whose field 0
    is >= 0 on live rows; ``mask`` (M,) bool selects rows to place.
    Selected rows land in ``buf``'s free rows (field 0 < 0), first-free
    first; selection beyond the free capacity is dropped.  Returns
    ``(buf, placed)`` with ``placed`` the int32 count actually merged —
    callers that must not lose rows check ``placed == mask.sum()`` (the
    chaos harness counts the shortfall as forced drops).  Placement
    order is deterministic (stable argsorts), which keeps seeded fault
    schedules reproducible."""
    N = buf.shape[0]
    M = rows.shape[0]
    free = buf[:, 0] < 0
    forder = jnp.argsort(~free)                 # free row indices first
    rorder = jnp.argsort(~mask)                 # selected rows first
    k = jnp.arange(M, dtype=jnp.int32)
    ok = (k < mask.sum(dtype=jnp.int32)) & (k < free.sum(dtype=jnp.int32))
    tgt = jnp.where(ok, forder[jnp.minimum(k, N - 1)], N)
    buf = buf.at[tgt].set(rows[rorder], mode="drop")
    return buf, ok.sum(dtype=jnp.int32)


def route_tag(shard, shard_size: int):
    """Destination-vertex tag addressing ``shard`` for payloads routed
    by *shard* rather than by a real vertex (the relay's path records):
    ``exchange_walkers`` recovers the shard as ``tag // shard_size``.
    Negative shards (invalid rows) stay negative, i.e. unrouted."""
    return jnp.where(shard >= 0, shard * shard_size, -1)


def exchange_walkers(payload, shard_size: int, num_shards: int,
                     axis: str = "data", cap: int | None = None):
    """Route walker records to their owning shard (inside shard_map).

    ``payload`` is (Wl,) int32 global vertex ids or (Wl, F) int32 rows
    whose field 0 is the destination vertex (-1 marks an empty row).
    Each (sender, destination) pair has a mailbox of ``cap`` rows
    (default ``Wl // num_shards``); one ``all_to_all`` rotates the
    mailboxes.  Returns ``(arrived, leftover, overflow)``:

      * ``arrived``  — (num_shards * cap[, F]) rows this shard owns
        after routing (-1 gaps);
      * ``leftover`` — same shape as ``payload``: the rows that were NOT
        delivered — mailbox overflow beyond ``cap``, plus any row whose
        destination vertex falls outside ``[0, num_shards *
        shard_size)`` and so has no owner — kept on the *sender* so
        callers can re-enqueue (the relay does, every round) or flag
        them.  Nothing is ever dropped: ``arrived ∪ leftover`` over all
        shards is exactly the sent multiset;
      * ``overflow`` — scalar int32 count of this shard's leftover rows.
    """
    squeeze = payload.ndim == 1
    if squeeze:
        payload = payload[:, None]
    Wl, F = payload.shape
    if cap is None:
        cap = max(1, Wl // num_shards)
    elif cap < 1:
        raise ValueError(f"mailbox cap must be >= 1; got {cap}")
    v = payload[:, 0]
    dest = jnp.where(v >= 0, v // shard_size, num_shards)
    order = jnp.argsort(dest)
    p_sorted = payload[order]
    d_sorted = dest[order]
    idx = jnp.arange(Wl, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             d_sorted[1:] != d_sorted[:-1]])
    rank = idx - jax.lax.cummax(jnp.where(first, idx, -1), axis=0)
    live = p_sorted[:, 0] >= 0
    routed = live & (d_sorted < num_shards) & (rank < cap)
    slot = jnp.where(routed, d_sorted * cap + rank, num_shards * cap)
    mailbox = jnp.full((num_shards * cap + 1, F), -1, jnp.int32)
    mailbox = mailbox.at[slot].set(p_sorted, mode="drop")[:-1]
    mailbox = mailbox.reshape(num_shards, cap, F)
    arrived = jax.lax.all_to_all(mailbox, axis, 0, 0, tiled=False)
    arrived = arrived.reshape(num_shards * cap, F)
    spill = live & ~routed
    leftover = jnp.where(spill[:, None], p_sorted, -1)
    overflow = spill.sum(dtype=jnp.int32)
    if squeeze:
        return arrived[:, 0], leftover[:, 0], overflow
    return arrived, leftover, overflow


def make_walk_step(sample_local, shard_size: int, num_shards: int,
                   mesh, axis: str = "data"):
    """Build a shard_mapped distributed walk step that keeps identity.

    ``sample_local(vertices_local, key) -> next_global_vertex`` samples
    the next hop for walkers whose *current* vertex lives on this shard
    (callers close over the vertex-sharded BingoState).  The step state
    is (Wl, 2) int32 ``[global vertex, walker id]`` rows (-1 rows are
    empty): the id field rides the mailbox with the vertex, so a hop
    arriving on another shard still knows *which* walker it advances —
    the per-step twin of the relay's ``(vertex, step, wid)`` payload.
    Mailbox leftovers are returned alongside so callers can re-enqueue
    (a bare step has no next round to retry in).
    """
    def step(walkers, key):
        nxt = sample_local(walkers[:, 0], key)
        live = (walkers[:, 0] >= 0) & (nxt >= 0)
        payload = jnp.stack(
            [jnp.where(live, nxt, -1), jnp.where(live, walkers[:, 1], -1)],
            axis=-1)
        arrived, leftover, overflow = exchange_walkers(
            payload, shard_size, num_shards, axis)
        return arrived, leftover, overflow

    return jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=(P(axis), P(axis), P()),
        check_vma=False,
    )
