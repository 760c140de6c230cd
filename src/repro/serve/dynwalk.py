"""Streaming dynamic-walk serving: interleave update rounds with walks.

The paper's principle (i) asks for "low-latency streaming updates AND
high-throughput batched updates" feeding the same walk engine; systems
like Wharf and FlexiWalker show that *update ingestion*, not sampling,
decides whether a dynamic-walk engine is usable online.  This module is
the serving loop for that regime: a ``DynamicWalkEngine`` owns one
device-resident ``BingoState`` and threads it — donated, never copied —
through alternating batched-update rounds and whole-walk batches, both
dispatched through the configured ``EngineBackend`` (DESIGN.md §9):

  * **updates** go through ``core/updates.py:make_updater`` — one jitted
    ``apply_updates`` closure with ``donate_argnums=0``; on the pallas
    backend every coalesced round is ONE update-megakernel launch
    (``kernels/update_fused.py``) that mutates the HBM-resident tables
    in place;
  * **walks**   go through ``core/walks.py:make_walker`` — the same
    donation contract; on the pallas backend deepwalk/ppr/simple are ONE
    whole-walk megakernel launch each (``kernels/walk_fused.py``);
  * **streams** arrive via ``graph/streams.py:rounds_on_device``, which
    prefetches the numpy rounds onto the device ahead of use and can
    coalesce several low-latency rounds into one §5.2 batched round —
    the latency/throughput lever.

This replaces the per-callsite ``jax.jit(batched_update)`` wrappers the
launch/ layer used to carry: "mutate graph, then walk" is one engine
object, and the state buffers are aliased across the whole session.

**Sharded mode** (DESIGN.md §10): pass ``mesh=`` and the engine serves
the same surface off a vertex-partitioned state (§9.1).  Updates are
routed to owner shards by an ownership mask and applied shard-locally
(one update-megakernel launch per shard); walks run the bulk-
synchronous ``walk_relay`` super-steps — resumable megakernel segments
over slot-compacted O(W/S) resident arrays, walker and path-record
all_to_all mailboxes — so served paths are *bit-identical* to the
single-device engine for the same key, at any shard count, with
per-shard walk state sized to active residents rather than the global
walker count.  The serving API is unchanged by the compaction.  The
donated-state discipline is unchanged too: one sharded ``BingoState``
threads through every ingest and walk.  Each relay walk also adds its
rounds, mailbox-overflow re-enqueues, segment-kernel loop steps and
cross-shard walker deliveries to device-side totals that
``relay_stats()`` reads on request.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dyngraph import (BingoConfig, BingoState, from_edges,
                                 regrow_state)
from repro.core.updates import NUM_REASONS, R_OK, UpdateStats, make_updater
from repro.core.walks import WalkParams, make_walker
from repro.graph.streams import UpdateStream, rounds_on_device
from repro.serve import spans
from repro.serve.guard import GuardPolicy, IngestGuard

__all__ = ["DynamicWalkEngine", "sharded_from_edges"]


def sharded_from_edges(cfg: BingoConfig, src, dst, bias, mesh,
                       walker_axes=()) -> BingoState:
    """``from_edges`` built in place across a mesh (the ``mesh=`` engine's
    state), never whole on one device.

    Every vertex shard takes the full edge list, keeps the edges whose
    source it owns (re-based to its local ids; the rest go to the
    out-of-range row ``shard_size``, which the scatters drop) and builds
    its rows with ``from_edges`` at the shard-local size.  A stable sort
    keeps each row in edge-list order, so shard ``s`` holds exactly rows
    ``[s·shard_size, (s+1)·shard_size)`` of the single-device build.
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed.relay import shard_index
    waxes = (walker_axes,) if isinstance(walker_axes, str) \
        else tuple(walker_axes)
    vaxes = tuple(a for a in mesh.axis_names if a not in waxes)
    S = 1
    for a in vaxes:
        S *= mesh.shape[a]
    if cfg.num_vertices % S:
        raise ValueError(f"V={cfg.num_vertices} does not divide over "
                         f"{S} vertex shards")
    size = cfg.num_vertices // S
    lcfg = dataclasses.replace(cfg, num_vertices=size)

    def build(s, d, w):
        lo = shard_index(mesh, vaxes) * size
        own = (s >= lo) & (s < lo + size)
        return from_edges(lcfg, jnp.where(own, s - lo, size), d, w)

    spec = jax.tree.map(lambda leaf: P(vaxes, *([None] * (leaf.ndim - 1))),
                        jax.eval_shape(lambda: from_edges(
                            lcfg, src[:1], dst[:1], bias[:1])))
    fn = jax.shard_map(build, mesh=mesh, in_specs=(P(), P(), P()),
                       out_specs=spec, check_vma=False)
    return jax.jit(fn)(jnp.asarray(src, jnp.int32),
                       jnp.asarray(dst, jnp.int32), jnp.asarray(bias))


class DynamicWalkEngine:
    """One device-resident dynamic graph serving updates and walks.

    The engine owns ``state``: both closures donate their state argument,
    so after construction the caller must not hold (or re-use) the
    original buffers — read ``engine.state`` instead.  ``ingest`` and
    ``walk`` may be interleaved freely; each is one jitted call (one
    megakernel launch each on the pallas backend — per shard, in
    ``mesh=`` mode, where walks run the exact cross-shard relay).

    In ``mesh=`` mode the relay's own counts ride along on the device:
    each walk adds its rounds, mailbox-overflow re-enqueues, segment-
    kernel loop steps and the walker records delivered across shards
    (per shard) to totals held in the walk program's carry, and
    ``relay_stats()`` reads them in one device-to-host read — operator
    gauges, like ``walk_pad_lanes``; the walk path itself never reads
    them.
    """

    def __init__(self, state: BingoState, cfg: BingoConfig,
                 params: WalkParams = WalkParams(), *,
                 backend: Optional[str] = None,
                 whole_walk: Optional[bool] = None, seed: int = 0,
                 mesh=None, mailbox_cap: Optional[int] = None,
                 guard=None, walk_buckets=None, defer_guard: bool = False,
                 walker_axes=(), relay_overlap: bool = True):
        self.cfg = cfg
        self.params = params
        self._state = state
        self._backend = backend
        self._whole_walk = whole_walk
        self._mesh = mesh
        self._mailbox_cap = mailbox_cap
        self._relay_overlap = relay_overlap
        self._waxes = (walker_axes,) if isinstance(walker_axes, str) \
            else tuple(walker_axes)
        self.num_shards = 1
        self._vaxes = ()
        self._num_vshards = 1
        # Capacity-ladder bookkeeping (DESIGN.md §14): serving closures
        # are cached per ladder tier, so an engine compiles at most
        # len(cfg.ladder) update/walk program sets over its lifetime
        # and re-entering a tier re-uses its programs.
        self.regrow_counts = [0] * len(cfg.ladder)
        self._tier_progs: dict = {}
        self._regrow_progs: dict = {}
        if mesh is not None:
            for a in mesh.axis_names:
                self.num_shards *= mesh.shape[a]
            self._vaxes = tuple(a for a in mesh.axis_names
                                if a not in self._waxes)
            for a in self._vaxes:
                self._num_vshards *= mesh.shape[a]
            self._state = self._shard_state(state, mesh, self._vaxes)
        self._update, self._walk = self._tier_programs(cfg.tier)
        # Fixed-lane walk cohorts (DESIGN.md §12): every walk batch is
        # padded up to the smallest bucket >= its request count, so a
        # request-size-jittered stream only ever compiles |buckets|
        # walk programs.  In sharded mode the relay requires each
        # bucket to divide over the shard count.
        self.walk_buckets = None
        if walk_buckets:
            self.walk_buckets = tuple(sorted(int(b) for b in walk_buckets))
            for b in self.walk_buckets:
                if b < 1 or b % self.num_shards:
                    raise ValueError(
                        f"walk bucket {b} must be a positive multiple of "
                        f"the shard count ({self.num_shards})")
        # guard=True -> default policy; guard=GuardPolicy(...) -> custom.
        # The classifier checks endpoints against the GLOBAL cfg — in
        # sharded mode it runs over the partitioned state as plain jnp.
        self.guard: Optional[IngestGuard] = None
        if guard:
            policy = guard if isinstance(guard, GuardPolicy) \
                else GuardPolicy()
            self.guard = IngestGuard(cfg, policy)
        # defer_guard=True moves quarantine/retry accounting off the
        # ingest hot path: rounds park their device-side reason vectors
        # in a backlog and ``drain_guard()`` settles them in one host
        # sync per coalescing window (DESIGN.md §12).
        self.defer_guard = bool(defer_guard)
        self._guard_backlog: list = []
        self._key = jax.random.key(seed)
        self.rounds_ingested = 0
        self.updates_applied = 0
        self.walks_served = 0
        # update lanes at or past ``n_valid``, and walk lanes of bucket
        # padding (device-to-host reads are ``engine.read`` spans)
        self.pad_lanes = 0
        self.walk_pad_lanes = 0
        # relay totals (mesh mode), kept on the device: columns rounds,
        # overflow, segment steps, then delivered per shard; rows their
        # high and low 30-bit limbs, so that no count wraps in a
        # long-running engine
        self._relay_batches = 0
        self._relay_tally = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._relay_tally = jax.device_put(
                jnp.zeros((2, 3 + self.num_shards), jnp.int32),
                NamedSharding(mesh, P()))

    @staticmethod
    def _shard_state(state, mesh, vaxes):
        """Vertex-partition a state over the mesh's vertex axes
        (replicated across walker axes)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        sspec = jax.tree.map(
            lambda leaf: P(vaxes, *([None] * (leaf.ndim - 1))), state)
        return jax.device_put(
            state, jax.tree.map(lambda s: NamedSharding(mesh, s), sspec,
                                is_leaf=lambda s: isinstance(s, P)))

    def _sspec(self):
        """Partition specs of the live state (shape-independent: the
        same specs describe every ladder tier, since regrowth only
        widens trailing dims)."""
        from jax.sharding import PartitionSpec as P
        vaxes = self._vaxes
        return jax.tree.map(
            lambda leaf: P(vaxes, *([None] * (leaf.ndim - 1))),
            self._state)

    def _tier_programs(self, t: int):
        """Compiled ``(update, walk)`` closures for ladder tier ``t`` —
        built once per tier and cached (the §14 program-count bound:
        at most ``len(cfg.ladder)`` update programs and
        ``len(cfg.ladder) * |walk_buckets|`` walk programs ever
        compile).  ``self._state`` must already be at tier ``t``."""
        if t not in self._tier_progs:
            tcfg = self.cfg.tier_config(t)
            if self._mesh is None:
                update = make_updater(tcfg, backend=self._backend,
                                      with_active=True)
                walk = make_walker(self._state, tcfg, self.params,
                                   backend=self._backend,
                                   whole_walk=self._whole_walk)
            else:
                update, walk = self._sharded_programs(tcfg)
            self._tier_progs[t] = (update, walk)
        return self._tier_progs[t]

    def _sharded_programs(self, cfg):
        """Vertex-partitioned serving closures (DESIGN.md §10/§13).

        The state's vertex dim shards over the mesh's *vertex* axes
        (every axis not named in ``walker_axes``) and is replicated
        across the walker axes; update batches and walk starts stay
        replicated / walker-partitioned (global ids).  Ingest = owner-
        masked ``apply_updates`` per shard (psum'd stats — every walker
        replica applies the same owned lanes, keeping the replicas in
        lockstep, so stats sum over vertex axes only); walk = the
        super-step relay — overlapped rounds by default, the production
        schedule — whose stitched (W, L+1) paths are bit-equal to the
        single-device whole walk for the same key, and whose rounds,
        overflow, segment steps and per-shard deliveries add into the
        carried relay totals (``relay_stats``).
        """
        from jax.sharding import PartitionSpec as P
        from repro.core.backend import get_backend
        from repro.distributed.relay import make_relay, shard_index
        from repro.kernels.ops import seed_from_key

        mesh, waxes, vaxes = self._mesh, self._waxes, self._vaxes
        bk = get_backend(cfg.backend if self._backend is None
                         else self._backend)
        relay = make_relay(bk, cfg, self.params, mesh,
                           mailbox_cap=self._mailbox_cap,
                           overlap=self._relay_overlap,
                           walker_axes=waxes,         # validates V % S_v
                           counters=True)
        shard_size = cfg.num_vertices // self._num_vshards
        lcfg = dataclasses.replace(cfg, num_vertices=shard_size)
        sspec = self._sspec()

        def update_local(st, is_insert, uu, vv, ww, active):
            lo = shard_index(mesh, vaxes) * shard_size
            owned = (uu >= lo) & (uu < lo + shard_size) & active
            lu = jnp.where(owned, uu - lo, 0)
            st, stats = bk.apply_updates(st, lcfg, is_insert, lu, vv, ww,
                                         active=owned)
            return st, jax.tree.map(
                lambda t: jax.lax.psum(t, axis_name=vaxes), stats)

        smap_upd = jax.shard_map(update_local, mesh=mesh,
                                 in_specs=(sspec, P(), P(), P(), P(), P()),
                                 out_specs=(sspec, P()), check_vma=False)

        # the same program names as on one device (``make_updater``,
        # ``make_walker``), so a trace reads alike on any mesh
        @functools.partial(jax.jit, donate_argnums=0)
        def engine_update(st, is_insert, u, v, w, active):
            return smap_upd(st, is_insert, u, v, w, active)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def engine_walk(st, tally, starts, key):
            paths, rounds, ovf, dlv, seg = relay(st, starts,
                                                 seed_from_key(key))
            counts = jnp.concatenate([jnp.stack([rounds, ovf, seg]), dlv])
            lo = tally[1] + counts
            tally = jnp.stack([tally[0] + (lo >> 30), lo & ((1 << 30) - 1)])
            return st, tally, paths

        return engine_update, engine_walk

    def _regrow_program(self, t: int):
        """Jitted donated-state migration tier ``t`` -> ``t + 1``.

        Single device: one jit of ``regrow_state``.  Sharded: a
        shard_map of the same pure-jnp migration over shard-local
        configs — every shard (and walker replica) re-lays its
        partition in the same program, so the mesh switches tiers in
        lockstep or not at all.
        """
        if t not in self._regrow_progs:
            tcfg = self.cfg.tier_config(t)
            ncfg = self.cfg.tier_config(t + 1)
            if self._mesh is None:
                self._regrow_progs[t] = jax.jit(
                    lambda st: regrow_state(st, tcfg, ncfg),
                    donate_argnums=0)
            else:
                shard_size = tcfg.num_vertices // self._num_vshards
                lcfg = dataclasses.replace(tcfg, num_vertices=shard_size)
                lncfg = dataclasses.replace(ncfg, num_vertices=shard_size)
                sspec = self._sspec()
                fn = jax.shard_map(lambda st: regrow_state(st, lcfg, lncfg),
                                   mesh=self._mesh, in_specs=(sspec,),
                                   out_specs=sspec, check_vma=False)
                self._regrow_progs[t] = jax.jit(fn, donate_argnums=0)
        return self._regrow_progs[t]

    # -- state ownership -----------------------------------------------------
    @property
    def state(self) -> BingoState:
        """The current sampling space (donated through every call)."""
        return self._state

    # -- serving surface -----------------------------------------------------
    def ingest(self, is_insert, u, v, w, *,
               n_valid: Optional[int] = None) -> UpdateStats:
        """Apply one batched update round; returns its ``UpdateStats``.

        Unguarded, every lane goes straight to the update pipeline
        (which still rejects-and-counts unapplyable lanes — DESIGN.md
        §11).  With ``guard=`` the device-side pre-pass classifies the
        round first: only OK lanes are applied, rejects land in the
        quarantine buffer / pending-overflow queue, and the returned
        ``rejected`` counters carry the guard's reason tally (the
        engine-level tally is zero by construction after the guard).
        Pending capacity overflows are retried — one bounded batch —
        after any round whose deletes may have freed slots.

        ``n_valid`` marks lanes ``>= n_valid`` as *padding*: the
        scheduler pads coalescing windows to one compiled round shape
        (DESIGN.md §12), and pad lanes are never applied, never
        classified, and never accounted.

        With ``defer_guard=True`` the guard's host-side bookkeeping is
        postponed: the round's device reason vector is parked in a
        backlog (the returned stats still carry a device-computed
        reason tally — no host sync) and ``drain_guard()`` settles
        quarantine/retry accounting for the whole window at once.
        """
        with spans.span("engine.ingest"):
            return self._ingest(is_insert, u, v, w, n_valid)

    def _ingest(self, is_insert, u, v, w, n_valid) -> UpdateStats:
        B = int(u.shape[0])
        nv = B if n_valid is None else int(n_valid)
        if not 0 <= nv <= B:
            raise ValueError(f"n_valid {nv} outside round of {B} lanes")
        lanes = jnp.ones((B,), bool) if nv == B else \
            jnp.arange(B, dtype=jnp.int32) < nv
        if self.guard is None:
            with spans.span("engine.update"):
                self._state, stats = self._update(
                    self._state, is_insert, u, v, w, lanes)
            self._count_round(B, nv)
            return stats._replace(max_fill=self._fill())

        g = self.guard
        rnd = self.rounds_ingested
        with spans.span("engine.classify"):
            reasons = g.classify(self._state, is_insert, u, v, w)
        with spans.span("engine.update"):
            self._state, stats = self._update(
                self._state, is_insert, u, v, w, lanes & (reasons == R_OK))
        if self.defer_guard:
            # Device-side reason tally (pad lanes masked to R_OK so
            # they never count): dispatches async, the host never
            # blocks — quarantine records wait in the backlog.
            with spans.span("engine.tally"):
                tally = jnp.bincount(
                    jnp.where(lanes, reasons, R_OK), length=NUM_REASONS
                ).at[R_OK].set(0)
                stats = stats._replace(
                    rejected=stats.rejected + tally.astype(jnp.int32),
                    max_fill=self._fill())
            self._guard_backlog.append(
                (rnd, is_insert, u, v, w, reasons, stats.del_applied, nv))
            self._count_round(B, nv)
            return stats
        with spans.span("guard.account"):
            counts = g.account(rnd, self._host(is_insert)[:nv],
                               self._host(u)[:nv], self._host(v)[:nv],
                               self._host(w)[:nv],
                               self._host(reasons)[:nv])
            g.deletes_since_retry += int(self._host(stats.del_applied))
        stats = stats._replace(
            rejected=stats.rejected + jnp.asarray(counts, jnp.int32))
        rstats = self._run_guard_retry(rnd)
        if rstats is not None:
            stats = stats._replace(
                ins_applied=stats.ins_applied + rstats.ins_applied,
                transitions=stats.transitions + rstats.transitions)
        self._count_round(B, nv)
        return stats._replace(max_fill=self._fill())

    def _count_round(self, lanes: int, n_valid: int) -> None:
        self.rounds_ingested += 1
        self.updates_applied += n_valid
        self.pad_lanes += lanes - n_valid

    @staticmethod
    def _host(x) -> np.ndarray:
        """``x`` on the host; a device array's read is one
        ``engine.read`` span, so the span count is the engine's host
        syncs and its time their wait."""
        if not isinstance(x, jax.Array):
            return np.asarray(x)
        with spans.span("engine.read"):
            return np.asarray(x)

    def _fill(self):
        """Device-scalar fill watermark ``max(deg) / capacity`` — never
        a host sync; on the sharded state the max over the partitioned
        ``deg`` is a GSPMD all-reduce, so every shard computes the same
        value (the §14 lockstep-trigger input)."""
        return jnp.max(self._state.deg) / self.cfg.capacity

    def _run_guard_retry(self, rnd) -> Optional[UpdateStats]:
        """One bounded pending-overflow retry batch, if deletes (or a
        regrow) since the last retry may have made capacity.  Returns
        the retry round's stats when lanes applied, else None."""
        g = self.guard
        if not g.want_retry():
            return None
        return self._retry_batch(rnd)

    def _retry_batch(self, rnd) -> Optional[UpdateStats]:
        """One unconditional fixed-shape retry round of pending inserts."""
        g = self.guard
        entries, ru, rv, rw = g.take_retry()
        r_ins = jnp.ones((g.policy.retry_batch,), bool)
        ru, rv, rw = jnp.asarray(ru), jnp.asarray(rv), jnp.asarray(rw)
        r_reasons = g.classify(self._state, r_ins, ru, rv, rw)
        self._state, rstats = self._update(
            self._state, r_ins, ru, rv, rw, r_reasons == R_OK)
        applied = g.settle_retry(rnd, entries, self._host(r_reasons))
        return rstats if applied else None

    @property
    def guard_backlog(self) -> int:
        """Rounds whose guard accounting awaits ``drain_guard()``."""
        return len(self._guard_backlog)

    def drain_guard(self) -> int:
        """Settle deferred guard accounting — ONE host sync per window.

        Converts every backlogged reason vector to host, routes rejects
        to quarantine / the pending queue (``IngestGuard.account``),
        then runs at most one bounded capacity-retry batch against the
        *current* state (the deferred contract: retries happen at drain
        points, not mid-window).  Returns the number of rounds settled.
        No-op without a guard or with an empty backlog; after it,
        ``guard.check_conservation()`` holds.
        """
        g = self.guard
        if g is None or not self._guard_backlog:
            return 0
        with spans.span("engine.drain_guard"):
            backlog, self._guard_backlog = self._guard_backlog, []
            with spans.span("guard.account"):
                for rnd, ins, u, v, w, reasons, dels, nv in backlog:
                    g.account(rnd, self._host(ins)[:nv],
                              self._host(u)[:nv], self._host(v)[:nv],
                              self._host(w)[:nv], self._host(reasons)[:nv])
                    g.deletes_since_retry += int(self._host(dels))
            self._run_guard_retry(self.rounds_ingested)
        return len(backlog)

    def audit(self, *, pressure: bool = False) -> dict:
        """Device-side invariant sweep of the live state (DESIGN.md §11).

        Returns ``{rule: violating-vertex count}`` over the cheap
        jit-able subset (``core/invariants.check_state_device``) —
        all-zero for a healthy state.  Works on the sharded state too
        (plain jnp; GSPMD partitions the row scans).

        ``pressure=True`` additionally feeds the guard's pending-insert
        depth to the ``at_capacity`` rule (rows full at ``deg == C``
        while inserts wait — loss-imminent without a regrow, DESIGN.md
        §14) and appends the capacity-pressure gauges from
        ``pressure()`` under non-rule keys.
        """
        from repro.core.invariants import DEVICE_RULES, check_state_device
        pend = len(self.guard.pending) \
            if (pressure and self.guard is not None) else 0
        counts = self._host(check_state_device(self._state, self.cfg,
                                               pend))
        out = dict(zip(DEVICE_RULES, counts.tolist()))
        if pressure:
            out.update(self.pressure())
        return out

    # -- capacity regrowth (DESIGN.md §14) -----------------------------------
    @property
    def tier(self) -> int:
        """Current rung of the capacity ladder."""
        return self.cfg.tier

    def max_fill(self) -> float:
        """Host-synced fill watermark ``max(deg) / capacity``."""
        return float(self._host(self._fill()))

    def pressure(self) -> dict:
        """Capacity-pressure gauges: fill watermark, ladder position,
        per-tier regrow counts, pending-insert queue depth."""
        return {
            "max_fill": self.max_fill(),
            "tier": self.tier,
            "capacity": self.cfg.capacity,
            "pending_depth": len(self.guard.pending)
            if self.guard is not None else 0,
            "regrow_counts": list(self.regrow_counts),
        }

    def want_regrow(self, watermark: float = 0.95) -> bool:
        """Should the engine escalate to the next ladder tier?

        True when a next tier exists and either the fill watermark
        crossed ``watermark`` or capacity overflows are already queued
        (pending inserts — loss-imminent).  One host sync; schedulers
        call this at drain points only.  The watermark max runs over
        the sharded ``deg`` as a GSPMD all-reduce, so in mesh mode the
        decision is identical on every shard and walker replica — the
        whole mesh switches tiers in lockstep or not at all.
        """
        if self.tier + 1 >= len(self.cfg.ladder):
            return False
        if self.guard is not None and self.guard.pending:
            return True
        return self.max_fill() >= watermark

    def regrow(self) -> BingoConfig:
        """Escalate the live state to the next capacity tier.

        Order matters for crash-exactness and replay bit-identity
        (DESIGN.md §14): (1) settle any deferred guard accounting at
        the old tier (the backlog's reason vectors were classified
        against it); (2) run the donated-state migration — pinned
        rebuild-equivalent to ``from_edges`` at the new capacity, so
        every future walk is bit-identical to an engine built there;
        (3) re-target the guard's classifier and restore pending retry
        budgets; (4) drain the pending queue against the grown state
        until it empties or stops making progress (entries still over
        the new capacity wait for the next tier or deletes — never
        quarantined by budget exhaustion at a stale tier).

        Raises ``ValueError`` at the top of the ladder — callers gate
        on ``want_regrow()``.
        """
        t = self.tier
        if t + 1 >= len(self.cfg.ladder):
            raise ValueError(
                f"already at the top tier of capacity ladder "
                f"{self.cfg.ladder}")
        with spans.span("engine.regrow"):
            return self._regrow(t)

    def _regrow(self, t: int) -> BingoConfig:
        if self.defer_guard:
            self.drain_guard()
        mig = self._regrow_program(t)
        self._state = mig(self._state)
        self.cfg = self.cfg.tier_config(t + 1)
        self.regrow_counts[t + 1] += 1
        self._update, self._walk = self._tier_programs(t + 1)
        g = self.guard
        if g is not None:
            g.regrow(self.cfg)
            while g.pending:
                before = len(g.pending)
                self._retry_batch(self.rounds_ingested)
                if len(g.pending) >= before:
                    break   # survivors exceed even C' — wait for the
                            # next tier (or deletes); never quarantine
        return self.cfg

    def _bucket_for(self, n: int) -> int:
        for b in self.walk_buckets:
            if b >= n:
                return b
        raise ValueError(
            f"walk batch of {n} requests exceeds the largest lane bucket "
            f"{self.walk_buckets[-1]} — split the batch or widen "
            f"walk_buckets")

    def walk(self, starts, key=None):
        """Serve one whole-walk batch; returns ``(B, length+1)`` paths.

        With ``walk_buckets=`` the batch is padded up to the smallest
        bucket ``>= B`` before dispatch and the result sliced back to
        the real rows, so a stream of jittered request sizes hits a
        fixed set of compiled walk programs (the §12 zero-recompilation
        pin; ``walk_cache_size()`` exposes the count).  On the counter-
        PRNG whole-walk paths (pallas megakernel, sharded relay) draws
        are per (seed, lane, t), so real lanes' paths are bit-identical
        to an unpadded call — pad lanes burn their own streams and are
        dropped.  On the reference per-step scan the batch shape is
        part of the key-split stream, so the bucket shape (not the
        request count) determines the draws — still deterministic,
        which is all the §12 replay contract needs.  ``walks_served``
        counts real (unpadded) requests only.
        """
        with spans.span("engine.walk"):
            starts = jnp.asarray(starts, jnp.int32)
            n = int(starts.shape[0])
            if key is None:
                with spans.span("engine.key"):
                    self._key, key = jax.random.split(self._key)
            if self.walk_buckets is None:
                paths = self._dispatch_walk(starts, key)
                self.walks_served += n
                return paths
            B = self._bucket_for(n)
            if B != n:
                # pad lanes: dead (-1) slots in relay mode (free slots,
                # zero resident cost); vertex 0 single-device (the
                # megakernel indexes rows by start, so starts must be
                # in range there).
                fill = -1 if self.num_shards > 1 else 0
                starts = jnp.concatenate(
                    [starts, jnp.full((B - n,), fill, jnp.int32)])
            paths = self._dispatch_walk(starts, key)
            self.walks_served += n
            self.walk_pad_lanes += B - n
            return paths[:n] if B != n else paths

    def _dispatch_walk(self, starts, key):
        if self._mesh is None:
            self._state, paths = self._walk(self._state, starts, key)
            return paths
        self._state, self._relay_tally, paths = self._walk(
            self._state, self._relay_tally, starts, key)
        self._relay_batches += 1
        return paths

    def relay_stats(self) -> dict:
        """The relay's totals over every walk batch this engine ran on
        its mesh: ``batches``, ``rounds`` (relay super-steps, one
        segment-kernel launch per shard each), ``overflow`` (mailbox
        re-enqueues), ``segment_steps`` (the segment kernel's step-loop
        iterations over every tile, round and shard: its share of
        ``rounds × shards × tiles × L`` is the part of a fixed L-step
        loop that still runs), ``delivered`` (walker records that
        arrived at a shard from another, i.e. hops that left a shard)
        and ``delivered_per_shard``.  One device-to-host read (an
        ``engine.read`` span); raises without a mesh."""
        if self._mesh is None:
            raise ValueError("relay_stats() needs an engine built with "
                             "mesh=")
        hi, lo = self._host(self._relay_tally).astype(np.int64)
        tot = (hi << 30) + lo
        return {"batches": self._relay_batches, "rounds": int(tot[0]),
                "overflow": int(tot[1]), "segment_steps": int(tot[2]),
                "delivered": int(tot[3:].sum()),
                "delivered_per_shard": [int(x) for x in tot[3:]]}

    def walk_cache_size(self) -> int:
        """Compiled-program count across every tier's walk closure (the
        §12 zero-recompilation pin and the §14 ladder bound
        ``<= len(cfg.ladder) * |walk_buckets|`` read this; -1 if the
        runtime does not expose it)."""
        try:
            return sum(int(walk._cache_size())
                       for _, walk in self._tier_progs.values())
        except Exception:
            return -1

    def update_cache_size(self) -> int:
        """Compiled-program count across every tier's update closure
        (the §14 ladder bound: ``<= len(cfg.ladder)`` programs for a
        fixed round shape; -1 if the runtime does not expose it)."""
        try:
            return sum(int(upd._cache_size())
                       for upd, _ in self._tier_progs.values())
        except Exception:
            return -1

    def run_stream(self, stream: UpdateStream, starts, *,
                   coalesce: int = 1, prefetch: int = 2,
                   walks_per_round: int = 1) -> Iterable:
        """Drive a full update stream, walking between rounds.

        Yields ``(round_index, UpdateStats, paths)`` per coalesced round
        — ``paths`` stacks ``walks_per_round`` whole-walk batches from
        ``starts``.  Rounds are uploaded ahead of use
        (``rounds_on_device``), so ingestion overlaps the walks' device
        time: the synchronous "integrate all updates before each walk"
        contract of the paper's evaluation loop, without host stalls.
        """
        if walks_per_round < 1:
            raise ValueError(   # ingest-only loops should call ingest()
                f"walks_per_round must be >= 1; got {walks_per_round}")
        starts = jnp.asarray(starts, jnp.int32)
        for r, (ins, u, v, w) in enumerate(rounds_on_device(
                stream, prefetch=prefetch, coalesce=coalesce)):
            stats = self.ingest(ins, u, v, w)
            paths = [self.walk(starts) for _ in range(walks_per_round)]
            yield r, stats, jnp.stack(paths) if walks_per_round > 1 \
                else paths[0]
