"""Walker relay: exact cross-shard whole walks, bulk or overlapped.

The whole-walk megakernel walks shard-locally; before this module, a
walker whose next hop left its shard was silently truncated
(the old DESIGN.md §8 trade).  The relay closes that gap with the
KnightKing/ThunderRW walker-centric discipline on the §9.1 vertex
partition (DESIGN.md §10): walkers move between owners in bulk
*super-steps* while the sampling structures never move.

Resident state is **slot-compacted**: each shard keeps ``Wl = W/S +
slack`` walker slots (not ``W``), sized to *active residents* rather
than the global walker-id space — the Bingo space-consumption principle
(paper §1, principle ii) applied to the distributed layer, and the same
scaling observation behind Wharf's space-efficient walk storage and
FlexiWalker's runtime-adaptive walkers.  A free-list allocator places
walkers into open slots; every array a walker touches is keyed by the
*global* walker id it carries, so placement order is irrelevant to the
result.

One bulk-synchronous round, per shard, inside ``shard_map``:

  1. **place** — the free-list allocator moves queued walkers (initial
     residents and later arrivals, held in a ``(W, 3)`` waiting queue
     of ``(vertex, step, wid)`` records) into open slots;
  2. **segment** — ONE resumable megakernel launch
     (``EngineBackend.sample_walk_segment``) walks all occupied slots:
     each walker enters at its recorded step ``t0``, draws its
     ``(seed, wid, t)`` hash stream through the slot→wid map, and walks
     until it finishes or samples a remote neighbor (encoded
     ``-(g + 2)`` by ``relay_view``), exiting with a ``(vertex, step)``
     frontier record;
  3. **route walkers** — frontier records plus previous-round outbox
     leftovers ride one ``exchange_walkers`` all_to_all as
     ``(vertex, step, wid)`` payloads; arrivals join the receiver's
     waiting queue; mailbox overflow is returned to the sender's outbox
     and re-enqueued — no walker is ever dropped;
  4. **route paths** — every slot that walked emits its freshly written
     path columns as one ``(home-tag, wid, slot, path…)`` record routed
     to the walker's *home* shard (``wid // (W/S)``), where it scatters
     into the ``(W/S, L+1)`` home-block accumulator at row
     ``wid % (W/S)`` (columns merge by ``maximum`` — segment windows
     are disjoint).  Home-local records scatter directly; records that
     overflow the path mailbox stay *pinned to their slot* (the slot is
     not reallocated until its columns are delivered), so per-shard
     path state is strictly ``O(Wl · L)``.

**Overlapped rounds** (``overlap=True``, DESIGN.md §10): the round is
re-dataflowed so the exchanges consume the *previous* round's in-flight
buffers (the outbox, and the pinned path rows) while the segment
megakernel runs on this round's placements — launch(g+1, locals) ∥
exchange(g, movers) instead of launch → exchange → barrier.  Fresh
frontier exits land in the outbox (the in-flight buffer the *next*
round's exchange drains), fresh remote path rows pin to their slots,
and arrivals merge into the waiting queue after the segment's inputs
are already fixed — double-buffered mailboxes, one swap per round.
A crossing costs one extra round of latency; in exchange the collective
is off the critical path.  Bit-exactness is schedule-invariant by
construction: the per-(walker, t) uniform stream is a pure hash of
``(seed, wid, t)``, so WHEN a walker walks cannot change WHERE.

**2D vertex × walker mesh** (``walker_axes=``, DESIGN.md §13): the mesh
axes split into vertex-shard axes (graph partitioned, S_v shards) and
walker-replica axes (graph *replicated*, S_w groups).  Walker slots,
waiting queues and home path blocks partition over the walker axes —
each group relays its own W/S_w walkers over the vertex axes, frontier
and path exchanges run ONLY along the vertex axes, and the round loop
is kept globally synchronous by psum'ing the pending count over the
whole mesh.  Walk throughput scales in S_w without re-sharding the
graph; PRNG keys stay GLOBAL wids, so any (S_v, S_w) factorization is
bit-identical to the single-shard walk.

The loop runs until no walker is resident, queued, in an outbox, or
pinned anywhere (a psum'd count), bounded by ``max_rounds`` (default:
the tight ``round_bound`` below; tripping it raises
``RelayIntegrityError`` under ``strict=True``).  Because the
per-(walker, t) uniform stream is a pure hash of ``(seed, wid, t)``
(``kernels/walk_fused.py:uniforms_at``) — or fed explicitly and
gathered per slot — a resumed walker draws exactly what it would have
drawn locally, so the home blocks concatenate to a (W, L+1) array
*bit-identical* to the single-shard ``random_walk`` at any shard count
and any schedule (``tests/test_walk_relay.py``,
``tests/test_relay_overlap.py``), with per-shard resident state ~S×
smaller than the wid-indexed layout it replaced (DESIGN.md §10).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.walker_exchange import exchange_walkers, route_tag

__all__ = ["relay_view", "relay_local", "make_relay", "shard_index",
           "slot_count", "round_bound", "RelayIntegrityError",
           "RelayPendingCensus"]


def _astuple(axis):
    return (axis,) if isinstance(axis, str) else tuple(axis)


def shard_index(mesh, axes=None):
    """This shard's linear index over ``axes`` (default: ALL mesh axes),
    inside shard_map."""
    axes = tuple(mesh.axis_names) if axes is None else _astuple(axes)
    if not axes:
        return jnp.int32(0)
    s = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        s = s * mesh.shape[a] + jax.lax.axis_index(a)
    return s


def slot_count(W: int, num_shards: int, slack: int | None = None) -> int:
    """Compacted slots per shard: ``Wl = min(W, W/S + slack)``.

    The default slack — ``max(8, ceil(W/S / 2))``, i.e. half a home
    block — absorbs arrival bursts of up to 1.5× a uniform resident
    load without queueing; anything beyond waits in the ``(W, 3)``
    queue (exact, just more rounds).  ``slack=0`` is legal and exact:
    every shard then holds at most one home block of residents.
    """
    Wb = W // num_shards
    if slack is None:
        slack = max(8, -(-Wb // 2))
    elif slack < 0:
        raise ValueError(f"slot slack must be >= 0; got {slack}")
    return min(W, Wb + slack)


def round_bound(W: int, L: int, num_shards: int, *,
                slot_slack: int | None = None,
                mailbox_cap: int | None = None,
                path_cap: int | None = None,
                overlap: bool = False) -> int:
    """Tight ``while_loop`` termination bound for one relay group.

    The old safety bound, ``2·W·(L+2)``, charged every walker a full
    mailbox drain per step — ~671M rounds at FULL sizing, which turned
    a hung transport into an hours-long stall before anything raised.
    This bound follows the actual progress guarantees; with a working
    transport the loop *cannot* run longer (``exchange_walkers``'s
    stable argsorts make each (sender, dest) mailbox FIFO, so every
    wait below is a finite queue drain, not starvation):

      * a frontier record waits at most ``ceil(W / c_w)`` rounds in the
        outbox (at most W live walker records exist anywhere, its
        mailbox delivers ``c_w`` of them per round, FIFO);
      * a queued walker waits at most ``ceil(W / Wl)`` placement waves;
        each wave lasts at most ``ceil(Wl / c_p) + 1`` rounds (a slot
        is reusable once its pinned path row delivers — FIFO again);
      * pipeline lag: 1 round per crossing bulk-synchronous, 2
        overlapped (fresh records spend one round in the in-flight
        buffer before their exchange departs);

    summed over the at-most ``L + 1`` segment entries of one walker,
    plus one final path-drain and a small constant.  At FULL sizing
    (W=4.2M, L=80, S=256) this is ~3.6M rounds — ~190× tighter — and
    at test scales it stays a comfortable 10–30× above observed rounds
    (``tests/test_relay_overlap.py`` pins both directions).  ``c_w`` /
    ``c_p`` are the walker / path mailbox caps (defaults mirror
    ``exchange_walkers``: payload rows / S).
    """
    Wl = slot_count(W, num_shards, slot_slack)
    payload_w = W if overlap else W + Wl
    c_w = mailbox_cap if mailbox_cap else max(1, payload_w // num_shards)
    c_p = path_cap if path_cap else max(1, Wl // num_shards)
    waves = -(-W // Wl)
    drain_p = -(-Wl // c_p)
    lag = 2 if overlap else 1
    per_step = -(-W // c_w) + waves * (drain_p + 1) + lag
    return (L + 1) * per_step + drain_p + 8


@dataclasses.dataclass(frozen=True)
class RelayPendingCensus:
    """What the relay knew when it hit ``max_rounds`` with work left —
    the pending census ``RelayIntegrityError`` carries in strict mode."""
    rounds: int             # rounds executed (== max_rounds)
    pending_at_exit: int    # walkers still queued/in-flight/pinned
    max_rounds: int         # the tripped bound


class RelayIntegrityError(RuntimeError):
    """The relay lost work, stalled, or produced malformed paths.

    Carries a census as ``.report`` — a ``ChaosReport`` from the fault
    harness (``distributed/chaos.py``) or a ``RelayPendingCensus`` from
    a strict-mode ``max_rounds`` trip — and the path-audit findings as
    ``.problems``: the structured diagnostic DESIGN.md §11 demands in
    place of silent truncation.  The message is built defensively
    (``getattr``) because the two census types share only a subset of
    fields.
    """

    def __init__(self, report, problems=()):
        self.report = report
        self.problems = list(problems)
        bits = []
        lost = getattr(report, "lost", None)
        if lost is not None:
            bits.append(f"{lost} of {getattr(report, 'walkers', '?')} "
                        f"walker(s) lost")
        pending = getattr(report, "pending_at_exit", 0)
        if pending:
            bits.append(f"{pending} pending at exit "
                        f"after {getattr(report, 'rounds', '?')} rounds")
        if self.problems:
            bits.append(f"{len(self.problems)} malformed path row(s): "
                        + "; ".join(self.problems[:5]))
        super().__init__("relay integrity violated: " + ", ".join(bits)
                         + f" [{report}]")


def relay_view(state, lo: int, shard_size: int):
    """Shard-local adjacency view that *keeps* remote neighbors.

    Owned neighbors ``[lo, lo + shard_size)`` become local row ids;
    remote ones are encoded ``-(g + 2)`` so the segment kernel can emit
    them as frontier records (-1 padding stays -1).  Contrast with the
    ``walk_whole`` cell's truncating view, which maps remote to -1 and
    ends the walk there."""
    owned = (state.nbr >= lo) & (state.nbr < lo + shard_size)
    enc = jnp.where(state.nbr < 0, state.nbr, -(state.nbr + 2))
    return state._replace(nbr=jnp.where(owned, state.nbr - lo, enc))


def _compact_rows(rows, limit: int):
    """Valid rows (field 0 >= 0) first, truncated to ``limit`` rows.

    Callers only pass row sets whose valid count is <= ``limit`` by
    construction (each row is a distinct walker and there are at most W
    walkers anywhere — walker pools are deduped by wid first), so the
    truncation never drops a valid row."""
    order = jnp.argsort(rows[:, 0] < 0)         # stable: valid first
    return rows[order][:limit]


def _dedup_wid(rows, col: int = 2):
    """Blank all but one copy of each walker id in a record pool.

    Idempotent arrival handling (DESIGN.md §11): an at-least-once
    transport may deliver the same walker record twice (the chaos
    harness injects exactly that).  Any two in-flight records carrying
    the same wid are stages of the *same* deterministic walk — the
    (seed, wid, t) hash PRNG fixes the path — so keeping one arbitrary
    copy is lossless, and without dedup duplicate copies would breed
    through re-exchange until they overrun the (W,)-row pool bounds.
    Production streams never duplicate, making this a pure no-op there.
    """
    wid = rows[:, col]
    big = jnp.int32(2 ** 30)
    key = jnp.where(wid >= 0, wid, big)
    order = jnp.argsort(key)                    # stable
    srt = key[order]
    dup_sorted = jnp.concatenate(
        [jnp.zeros((1,), bool), (srt[1:] == srt[:-1]) & (srt[1:] < big)])
    dup = jnp.zeros_like(dup_sorted).at[order].set(dup_sorted)
    return jnp.where(dup[:, None], -1, rows)


def relay_local(bk, lcfg, params, state, walkers, seed, u=None, *,
                sidx, num_shards: int, shard_size: int, axis,
                mailbox_cap: int | None = None,
                max_rounds: int | None = None,
                slot_slack: int | None = None,
                path_cap: int | None = None,
                diagnostics: bool = False,
                exchange_fn=None, census: bool = False,
                overlap: bool = False, wid_base=0, sync_axes=None,
                with_pending: bool = False, counters: bool = False):
    """Per-shard body of the super-step relay (call inside shard_map).

    ``bk``/``lcfg``/``params`` — an ``EngineBackend`` with
    ``sample_walk_segment``, the shard-local config
    (``num_vertices == shard_size``), and the walk params
    (deepwalk/ppr/simple); ``state`` — this shard's vertex slice of the
    ``BingoState`` (adjacency still holding *global* neighbor ids);
    ``walkers`` (W,) int32 — this group's global start vertices,
    replicated over the vertex axes (each shard adopts its residents);
    ``seed`` (1,) int32 — the shared counter-PRNG seed
    (``ops.seed_from_key``); ``u`` — optional (L, W_global, 6) fed
    uniforms, replicated (gathered per slot through the slot→wid map
    each round — global wids index it directly).

    ``slot_slack`` sizes the compacted slot arrays (``slot_count``);
    ``mailbox_cap``/``path_cap`` bound the walker / path-record
    mailboxes per (sender, destination) pair — overflow of either is
    re-enqueued, never dropped.  ``max_rounds`` defaults to the tight
    ``round_bound``.

    ``overlap=True`` switches the round body to the overlapped schedule
    (module docstring): the walker/path exchanges drain the carry's
    in-flight buffers — filled by the *previous* round — concurrently
    with this round's placement + segment, whose inputs are fixed
    before any arrival merges.  Identical results, one extra round of
    latency per crossing, collectives off the critical path.

    ``wid_base``/``sync_axes`` are the 2D-mesh hooks (``make_relay``'s
    ``walker_axes``): ``wid_base`` is this walker group's global wid
    offset (slot→wid maps carry ``wid_base + local id``, so the PRNG
    and fed-uniform gathers stay keyed by GLOBAL wid — the invariant
    that makes every mesh factorization bit-identical), and
    ``sync_axes`` names ALL mesh axes so the loop-condition psum keeps
    every group iterating in lockstep (a group exiting early would
    desynchronize the other groups' collectives).  Defaults (0, axis)
    are the 1D relay.

    ``lcfg.cohorts`` (inherited from the global config by the
    ``dataclasses.replace`` in ``walk_relay``) reaches the segment
    megakernel unchanged, so cross-shard rounds get the same DMA-hiding
    cohort interleaving as single-shard whole walks — and because the
    PRNG keys by (seed, wid, t), any K yields the bit-identical relay.

    Returns ``(paths (W//num_shards, L+1) int32, rounds, overflow)`` —
    this shard's *home block* of the stitched global path array (vertex
    ids global, the ``random_walk`` contract; walker ``wid``'s row
    lives on shard ``(wid - wid_base) // (W/S)`` of its group), the
    number of relay rounds executed, and the total mailbox-overflow
    re-enqueues observed (both replicated scalars).  With
    ``diagnostics=True`` a fourth replicated scalar is appended: the
    peak number of slots in use on any shard in any round (resident
    walkers + pinned path rows) — the allocator-pressure signal the
    chaos harness and the compaction tests read.

    Fault-injection hooks (DESIGN.md §11 — ``distributed/chaos.py``):
    ``exchange_fn(payload, cap=, r=, channel=)`` replaces the mailbox
    all_to_all (channel 0 = walker records, 1 = path records) and must
    return ``(arrived, leftover, overflow, faults (3,) int32)`` — the
    extra vector counts injected drop/dup/delay events and is
    accumulated across rounds.  ``census=True`` appends three outputs
    after the optional peak: the number of DISTINCT walker ids that
    reached a terminal step anywhere (a per-shard wid bitmap, psum'd
    once at exit — duplicates from chaos cannot mask a dropped walker),
    the pending count at loop exit (> 0 means the relay gave up with
    work outstanding — only possible against ``max_rounds``), and the
    psum'd fault counts.  ``counters=True`` appends two: each shard's
    count of walker records delivered to it across shards (arrivals,
    not sends: a mailbox spill counts once, when it lands), gathered
    once at exit into a replicated (shards,) int32 in the order of the
    mesh's devices — the relay's cross-shard traffic, one record per
    hop that leaves a shard; and the segment kernel's step-loop
    iterations, summed over tiles, rounds and shards (a replicated
    scalar; at most ``rounds × shards × tiles × L``).
    ``with_pending=True`` appends the pending count once more as the
    very last output (the strict-mode hook).
    All default off; the production path is unchanged.
    """
    W = walkers.shape[0]
    L = params.length
    if W % num_shards:
        # The stitched output is reassembled from per-shard (W // S)
        # home blocks; a ragged W would silently drop the tail walkers.
        raise ValueError(
            f"walker count {W} must divide over {num_shards} shards "
            f"(pad starts with -1 free slots)")
    if max_rounds is None:
        max_rounds = round_bound(W, L, num_shards, slot_slack=slot_slack,
                                 mailbox_cap=mailbox_cap,
                                 path_cap=path_cap, overlap=overlap)
    if sync_axes is None:
        sync_axes = axis
    Wb = W // num_shards
    Wl = slot_count(W, num_shards, slot_slack)
    lo = sidx * shard_size
    view = relay_view(state, lo, shard_size)
    slot_ids = jnp.arange(Wl, dtype=jnp.int32)
    group_axes = tuple(a for a in _astuple(sync_axes)
                       if a not in _astuple(axis))

    if exchange_fn is None:
        def exchange_fn(payload, *, cap, r, channel):
            a, left, n = exchange_walkers(payload, shard_size, num_shards,
                                          axis, cap=cap)
            return a, left, n, jnp.zeros((3,), jnp.int32)

    # Initial residents queue at the shard owning their start vertex;
    # the allocator drains the queue into slots from round 1 on (a
    # start-vertex hot spot may exceed Wl — exactness does not care).
    wid0 = jnp.arange(W, dtype=jnp.int32) + wid_base
    resident0 = (walkers >= 0) & (walkers // shard_size == sidx)
    waiting0 = jnp.stack(
        [jnp.where(resident0, walkers, -1),
         jnp.zeros((W,), jnp.int32),
         jnp.where(resident0, wid0, -1)], axis=-1)
    outbox0 = jnp.full((W, 3), -1, jnp.int32)
    pend_path0 = jnp.full((Wl, L + 1), -1, jnp.int32)
    pend_wid0 = jnp.full((Wl,), -1, jnp.int32)
    acc0 = jnp.full((Wb, L + 1), -1, jnp.int32)
    pending0 = jax.lax.psum(resident0.sum(dtype=jnp.int32),
                            axis_name=sync_axes)
    # Census/fault carries (dead weight unless census=True): a per-shard
    # wid bitmap of walkers seen reaching a terminal step here, and the
    # accumulated (drop, dup, delay) injection counts from exchange_fn.
    fin0 = jnp.zeros((W,), bool)
    faults0 = jnp.zeros((3,), jnp.int32)

    def arrivals(rows):
        return (rows[:, 0] >= 0).sum(dtype=jnp.int32)

    def cond(c):
        r = c[0]
        pending = c[-1]
        return (pending > 0) & (r < max_rounds)

    def body(c):
        (r, pend_path, pend_wid, waiting, outbox, acc, ovf, peak,
         fin, faults, dlv, seg, _p) = c

        # -- place: free-list allocator drains the waiting queue into
        # open slots (a slot stays pinned while it holds an undelivered
        # path row).  Placement order never affects the result: every
        # per-walker quantity downstream is keyed by the wid the slot
        # carries, not by the slot index.
        with jax.named_scope("relay.merge"):
            free = pend_wid < 0
            forder = jnp.argsort(~free)             # free slot indices first
            nfree = free.sum(dtype=jnp.int32)
            ws = _compact_rows(waiting, W)
            k = jnp.arange(W, dtype=jnp.int32)
            place = (k < nfree) & (ws[:, 0] >= 0)
            tgt = jnp.where(place, forder[jnp.minimum(k, Wl - 1)], Wl)
            slot_wid = jnp.full((Wl,), -1, jnp.int32).at[tgt].set(
                ws[:, 2], mode="drop")
            slot_cur = jnp.full((Wl,), -1, jnp.int32).at[tgt].set(
                ws[:, 0] - lo, mode="drop")
            slot_t0 = jnp.zeros((Wl,), jnp.int32).at[tgt].set(
                ws[:, 1], mode="drop")
            waiting = jnp.where(place[:, None], -1, ws)
            occupied = slot_wid >= 0
            # local max only — max over rounds and shards commute, so the
            # cross-shard pmax happens ONCE after the loop (diagnostics
            # path), not as a per-round collective in the hot loop.
            peak = jnp.maximum(
                peak,
                occupied.sum(dtype=jnp.int32) + (~free).sum(dtype=jnp.int32))

        if overlap:
            # -- in-flight exchanges: drain the buffers the PREVIOUS
            # round filled.  Both payloads are pure functions of the
            # carry — nothing below them feeds the segment's inputs —
            # so XLA's latency-hiding scheduler is free to run the
            # all_to_alls concurrently with the megakernel launch:
            # launch(g+1, locals) ∥ exchange(g, movers).
            with jax.named_scope("relay.exchange"):
                arrived, spill_w, n_spill_w, f_w = exchange_fn(
                    outbox, cap=mailbox_cap, r=r, channel=0)
                dlv = dlv + arrivals(arrived)
                pinned = pend_wid >= 0
                in_home = jnp.where(pinned, (pend_wid - wid_base) // Wb, -1)
                pay_p = jnp.concatenate(
                    [jnp.where(pinned, route_tag(in_home, shard_size),
                               -1)[:, None],
                     jnp.where(pinned, pend_wid, -1)[:, None],
                     jnp.where(pinned, slot_ids, -1)[:, None],
                     jnp.where(pinned[:, None], pend_path, -1)], axis=1)
                got, spill_p, n_spill_p, f_p = exchange_fn(
                    pay_p, cap=path_cap, r=r, channel=1)

        # -- segment: one resumable megakernel launch over the compacted
        # slots; the slot→wid map keys the hash PRNG (and gathers the
        # fed stream) so each walker draws its own columns.
        with jax.named_scope("relay.segment"):
            u_slots = None if u is None else jnp.take(
                u, jnp.maximum(slot_wid, 0), axis=1)
            starts = jnp.where(occupied, slot_cur, -1)
            paths, frontier, steps = bk.sample_walk_segment(
                view, lcfg, starts, slot_t0, seed, params, u=u_slots,
                wid=slot_wid)
            seg = seg + steps.sum()

        with jax.named_scope("relay.merge"):
            fr_ok = occupied & (frontier[:, 0] >= 0)
            # census: an occupied slot whose frontier is exhausted finished
            # its walk HERE — mark its wid.  De-duping by wid (a bitmap, not
            # a counter) is what makes chaos duplicates unable to mask a
            # dropped walker: the same wid finishing twice sets one bit.
            term = occupied & (frontier[:, 0] < 0)
            fin = fin.at[jnp.where(term, slot_wid - wid_base, W)].set(
                True, mode="drop")
            new_fr = jnp.where(
                fr_ok[:, None],
                jnp.stack([frontier[:, 0], frontier[:, 1], slot_wid], -1), -1)

            if overlap:
                # -- buffer swap: fresh frontier exits + walker-channel
                # spills become the NEXT round's in-flight outbox; walker
                # arrivals join the waiting queue only now, after the
                # segment's inputs were fixed (the landing buffer).
                outbox = _compact_rows(
                    _dedup_wid(jnp.concatenate([spill_w, new_fr], axis=0)), W)
                waiting = _compact_rows(_dedup_wid(
                    jnp.concatenate([waiting, arrived], axis=0)), W)

                # -- fresh path rows: home-local columns scatter straight
                # into the home block; remote ones pin to the slot that
                # walked them and ride NEXT round's exchange.
                frow_path = jnp.where(occupied[:, None],
                                      jnp.where(paths >= 0, paths + lo, -1),
                                      -1)
                frow_wid = jnp.where(occupied, slot_wid, -1)
                has_frow = frow_wid >= 0
                fhome = jnp.where(has_frow, (frow_wid - wid_base) // Wb, -1)
                flocal = has_frow & (fhome == sidx)
                lrow = jnp.where(flocal, (frow_wid - wid_base) - sidx * Wb,
                                 Wb)
                acc = acc.at[lrow].max(
                    jnp.where(flocal[:, None], frow_path, -1), mode="drop")
                g_ok = got[:, 0] >= 0
                grow = jnp.where(g_ok, (got[:, 1] - wid_base) - sidx * Wb,
                                 Wb)
                acc = acc.at[grow].max(
                    jnp.where(g_ok[:, None], got[:, 3:], -1), mode="drop")
                # spilled in-flight rows re-pin to their slot; fresh remote
                # rows pin to theirs.  The two slot sets are disjoint by
                # construction: segment targets were free at round start,
                # spilled rows' slots were pinned.
                s_ok = spill_p[:, 0] >= 0
                s_slot = jnp.where(s_ok, spill_p[:, 2], Wl)
                pend_path = jnp.full((Wl, L + 1), -1, jnp.int32) \
                    .at[s_slot].set(spill_p[:, 3:], mode="drop")
                pend_wid = jnp.full((Wl,), -1, jnp.int32) \
                    .at[s_slot].set(spill_p[:, 1], mode="drop")
                fremote = has_frow & (fhome != sidx)
                rm_slot = jnp.where(fremote, slot_ids, Wl)
                pend_path = pend_path.at[rm_slot].set(
                    jnp.where(fremote[:, None], frow_path, -1), mode="drop")
                pend_wid = pend_wid.at[rm_slot].set(
                    jnp.where(fremote, frow_wid, -1), mode="drop")
                faults = faults + f_w + f_p
            else:
                # -- route walkers (bulk): fresh frontier exits + outbox
                # leftovers ride one all_to_all as (vertex, step, wid)
                # records; arrivals queue at the receiver (placement happens
                # next round), spills return to the sender's outbox.
                pay_w = jnp.concatenate([outbox, new_fr], axis=0)
                with jax.named_scope("relay.exchange"):
                    arrived, spill_w, n_spill_w, f_w = exchange_fn(
                        pay_w, cap=mailbox_cap, r=r, channel=0)
                dlv = dlv + arrivals(arrived)
                outbox = _compact_rows(_dedup_wid(spill_w), W)
                waiting = _compact_rows(_dedup_wid(
                    jnp.concatenate([waiting, arrived], axis=0)), W)

                # -- route paths (bulk): every slot that walked this round
                # emits its path columns (translated to global ids) toward
                # the walker's home shard; pinned rows from earlier rounds
                # retry alongside.
                row_path = jnp.where(occupied[:, None],
                                     jnp.where(paths >= 0, paths + lo, -1),
                                     pend_path)
                row_wid = jnp.where(occupied, slot_wid, pend_wid)
                has_row = row_wid >= 0
                home = jnp.where(has_row, (row_wid - wid_base) // Wb, -1)
                local = has_row & (home == sidx)
                lrow = jnp.where(local, (row_wid - wid_base) - sidx * Wb, Wb)
                acc = acc.at[lrow].max(
                    jnp.where(local[:, None], row_path, -1), mode="drop")
                remote = has_row & (home != sidx)
                pay_p = jnp.concatenate(
                    [jnp.where(remote, route_tag(home, shard_size),
                               -1)[:, None],
                     jnp.where(remote, row_wid, -1)[:, None],
                     jnp.where(remote, slot_ids, -1)[:, None],
                     jnp.where(remote[:, None], row_path, -1)], axis=1)
                with jax.named_scope("relay.exchange"):
                    got, spill_p, n_spill_p, f_p = exchange_fn(
                        pay_p, cap=path_cap, r=r, channel=1)
                faults = faults + f_w + f_p
                g_ok = got[:, 0] >= 0
                grow = jnp.where(g_ok, (got[:, 1] - wid_base) - sidx * Wb,
                                 Wb)
                acc = acc.at[grow].max(
                    jnp.where(g_ok[:, None], got[:, 3:], -1), mode="drop")
                # spilled rows stay pinned to their slot (re-keyed by the
                # slot field — exchange returns them in sort order);
                # delivered and home-local rows free theirs.
                s_ok = spill_p[:, 0] >= 0
                s_slot = jnp.where(s_ok, spill_p[:, 2], Wl)
                pend_path = jnp.full((Wl, L + 1), -1, jnp.int32) \
                    .at[s_slot].set(spill_p[:, 3:], mode="drop")
                pend_wid = jnp.full((Wl,), -1, jnp.int32) \
                    .at[s_slot].set(spill_p[:, 1], mode="drop")

            pending = jax.lax.psum(
                (waiting[:, 0] >= 0).sum(dtype=jnp.int32)
                + (outbox[:, 0] >= 0).sum(dtype=jnp.int32)
                + (pend_wid >= 0).sum(dtype=jnp.int32), axis_name=sync_axes)
            ovf = ovf + jax.lax.psum(n_spill_w + n_spill_p,
                                 axis_name=sync_axes)
        return (r + 1, pend_path, pend_wid, waiting, outbox, acc, ovf,
                peak, fin, faults, dlv, seg, pending)

    (rounds, _, _, _, _, acc, ovf, peak, fin, faults, dlv, seg,
     pending_final) = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), pend_path0, pend_wid0, waiting0, outbox0, acc0,
         jnp.int32(0), jnp.int32(0), fin0, faults0, jnp.int32(0),
         jnp.int32(0), pending0))

    # acc IS this shard's home block: walker wid's row landed here iff
    # (wid - wid_base) // Wb == sidx, so the P(walker+vertex axes)-
    # concatenated output is the coherent (W, L+1) array with no
    # cross-shard stitch collective.
    outs = [acc, rounds, ovf]
    if diagnostics:
        outs.append(jax.lax.pmax(peak, axis_name=sync_axes))
    if census:
        # Collectives run ONCE at exit, not per round: a wid finished iff
        # any vertex shard's bitmap has its bit (walkers that started as
        # -1 free slots never set a bit and are excluded by
        # construction); group counts — disjoint wid ranges — sum over
        # the walker axes.
        fin_any = jax.lax.psum(fin.astype(jnp.int32), axis_name=axis) > 0
        n_fin = jnp.sum(fin_any.astype(jnp.int32))
        if group_axes:
            n_fin = jax.lax.psum(n_fin, axis_name=group_axes)
        outs.append(n_fin)
        outs.append(pending_final)
        outs.append(jax.lax.psum(faults, axis_name=sync_axes))
    if counters:
        outs.append(jax.lax.all_gather(dlv, axis_name=sync_axes))
        outs.append(jax.lax.psum(seg, axis_name=sync_axes))
    if with_pending:
        outs.append(pending_final)
    return tuple(outs)


def make_relay(bk, cfg, params, mesh, *, mailbox_cap: int | None = None,
               max_rounds: int | None = None,
               slot_slack: int | None = None,
               path_cap: int | None = None,
               diagnostics: bool = False,
               exchange_fn=None, census: bool = False,
               overlap: bool = False, walker_axes=(),
               strict: bool = False, counters: bool = False):
    """Build the shard_mapped relay: the one wrapper every layer shares.

    Vertex-shards ``cfg.num_vertices`` over ``mesh``'s axes MINUS
    ``walker_axes`` and returns ``run(state, walkers, seed, u=None) ->
    (paths (W, L+1), rounds, overflow)`` — ``state`` a vertex-sharded
    (or logically shardable) ``BingoState``, ``walkers`` (W,) int32
    global start vertices (-1 = free slot; W must divide over the
    walker groups × vertex shards), ``seed`` (1,) int32
    (``ops.seed_from_key``), ``u`` optional (L, W, 6) fed uniforms.

    ``walker_axes`` names the mesh axes that replicate the graph and
    partition the walkers instead (DESIGN.md §13): an (S_v × S_w) mesh
    runs S_w independent walker groups of W/S_w slots each, each group
    relaying over its own S_v vertex shards, with frontier/path
    exchanges confined to the vertex axes and one global psum keeping
    the round loops in lockstep.  ``()`` (default) is the 1D relay
    over all axes.  ``overlap=True`` selects the overlapped round
    schedule (module docstring) — identical results, exchanges off the
    critical path.

    ``slot_slack`` sizes the compacted per-shard slot arrays
    (``slot_count``); ``diagnostics=True`` appends the peak per-shard
    slot occupancy as a fourth output.  ``strict=True`` raises
    ``RelayIntegrityError`` (with the pending census) when the relay
    exits against ``max_rounds`` with work outstanding — the check
    needs concrete outputs, so it fires on eager calls and is skipped
    under an enclosing jit (jitted callers read the census outputs
    instead).  ``counters=True`` appends the walker records delivered
    across shards, one count per shard (``(num_groups * num_shards,)``
    int32, in the order of the mesh's devices), and the segment
    kernel's step-loop iterations over every tile, round and shard.
    ``exchange_fn``/``census`` thread to ``relay_local`` —
    the chaos harness (``distributed/chaos.py``) swaps the mailbox
    all_to_all and reads the (distinct-finished, pending-at-exit,
    faults) census outputs it appends.  Used by the sharded
    ``DynamicWalkEngine`` (``serve/dynwalk.py``), the chaos harness and
    tests, so the divisibility validation and spec plumbing live in
    exactly one place.
    """

    axes = tuple(mesh.axis_names)
    waxes = _astuple(walker_axes)
    for a in waxes:
        if a not in axes:
            raise ValueError(f"walker axis {a!r} not in mesh axes {axes}")
    vaxes = tuple(a for a in axes if a not in waxes)
    if not vaxes:
        raise ValueError(
            "at least one mesh axis must remain a vertex axis "
            f"(walker_axes={waxes} covers all of {axes})")
    num_shards = 1
    for a in vaxes:
        num_shards *= mesh.shape[a]
    num_groups = 1
    for a in waxes:
        num_groups *= mesh.shape[a]
    if cfg.num_vertices % num_shards:
        raise ValueError(
            f"num_vertices {cfg.num_vertices} must divide over "
            f"{num_shards} shards (pad the vertex space)")
    shard_size = cfg.num_vertices // num_shards
    lcfg = dataclasses.replace(cfg, num_vertices=shard_size)
    with_pending = bool(strict)

    def local(state, walkers, seed, *rest):
        Wg = walkers.shape[0]
        return relay_local(
            bk, lcfg, params, state, walkers, seed,
            rest[0] if rest else None, sidx=shard_index(mesh, vaxes),
            num_shards=num_shards, shard_size=shard_size, axis=vaxes,
            mailbox_cap=mailbox_cap, max_rounds=max_rounds,
            slot_slack=slot_slack, path_cap=path_cap,
            diagnostics=diagnostics, exchange_fn=exchange_fn,
            census=census, overlap=overlap,
            wid_base=shard_index(mesh, waxes) * Wg, sync_axes=axes,
            with_pending=with_pending, counters=counters)

    def run(state, walkers, seed, u=None):
        W = walkers.shape[0]
        if W % num_groups:
            raise ValueError(
                f"walker count {W} must divide over {num_groups} walker "
                f"group(s) (axes {waxes})")
        sspec = jax.tree.map(lambda _: P(vaxes), state)
        wspec = P(waxes) if waxes else P()
        in_specs = (sspec, wspec, P()) + (() if u is None else (P(),))
        out_specs = (P(waxes + vaxes), P(), P()) \
            + ((P(),) if diagnostics else ()) \
            + ((P(), P(), P()) if census else ()) \
            + ((P(), P()) if counters else ()) \
            + ((P(),) if with_pending else ())
        f = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
        args = (state, walkers, seed) + (() if u is None else (u,))
        out = f(*args)
        if with_pending:
            out, pend = tuple(out[:-1]), out[-1]
            if not isinstance(pend, jax.core.Tracer) and int(pend) > 0:
                bound = max_rounds if max_rounds is not None else \
                    round_bound(W // num_groups, params.length,
                                num_shards, slot_slack=slot_slack,
                                mailbox_cap=mailbox_cap,
                                path_cap=path_cap, overlap=overlap)
                raise RelayIntegrityError(RelayPendingCensus(
                    rounds=int(out[1]), pending_at_exit=int(pend),
                    max_rounds=bound))
        return out

    return run
