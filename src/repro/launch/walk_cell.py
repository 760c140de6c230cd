"""The paper's own workload as a dry-run cell: one distributed walk step,
one whole-walk batch, plus one batched-update step on the production mesh.

Distribution = paper §9.1: the whole BINGO sampling space is 1-D
vertex-partitioned over data(×pod); the walk step samples locally with the
fused hierarchical sampler and the batched-update step runs the §5.2
insert→delete→rebuild pipeline on a 100K-update batch.  Walker routing
(where next hops leave the shard) is the gather/all-to-all traffic the
roofline's collective term captures.

Shapes: ``walk_step``  — one synchronous step of all walkers (sample +
        all_to_all exchange per step: the paper's synchronous engine);
        ``walk_whole`` — the whole-walk entry (DESIGN.md §8): every shard
        runs its resident walkers' full L-step walks locally through
        ``backend.sample_walk`` — one persistent megakernel launch on
        TPU — with no per-step exchange (the asynchronous-engine mode:
        walks stay shard-local, paths are gathered once at the end);
        ``walk_relay`` — the exact sharded whole walk (DESIGN.md §10):
        bulk-synchronous super-steps of the *resumable* megakernel over
        slot-compacted (W/S + slack) resident arrays — each round every
        shard walks its residents as one segment, walkers whose hop
        leaves the shard ride a (vertex, step, wid) all_to_all mailbox
        to their new owner and resume there, path columns route to the
        walker's home shard block, and the concatenated home blocks are
        bit-identical to the single-shard walk (the fix for
        walk_whole's boundary truncation, at O(W/S) resident state) —
        now with the overlapped round schedule (DESIGN.md §10: round
        g's exchanges fly while round g+1's segment runs);
        ``walk_relay_2d`` — the same relay on the chips re-meshed as
        (S_v vertex shards × S_w walker replicas) (DESIGN.md §13):
        graph tables replicated across the walker axis, walker slots
        and home path blocks partitioned across it, frontier exchange
        only along the vertex axis — walk throughput scales in S_w
        without re-sharding the graph, at S_w × table replication
        (which is why FULL needs the 64 × 4 factorization, not 16 × 16);
        ``update_step`` — one batched graph update (100K updates) through
        ``backend.apply_updates`` (DESIGN.md §9);
        ``update_walk`` — the streaming-serving round (DESIGN.md §9):
        updates are routed to their owner shards (replicated batch +
        ownership mask — each shard applies exactly the edges whose
        source vertex it owns), then every shard immediately runs a
        whole-walk batch on its freshly-updated rows.  "Mutate graph,
        then walk" as one cell — on TPU, one update-megakernel launch
        plus one walk-megakernel launch per shard.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import bingo_walk
from repro.core.backend import get_backend
from repro.core.dyngraph import BingoConfig, BingoState
from repro.core.alias import AliasTable
from repro.launch.specs import CellSpec

__all__ = ["build_walk_cell"]


class _WalkCfgShim:
    """roofline.analyze duck-type: 'active params' = resident sampling-space
    int32/float32 words (so useful_ratio reads as touched/resident)."""

    def __init__(self, wcfg, bcfg):
        self._n = (wcfg.num_vertices * wcfg.capacity * 2        # nbr+bias
                   + wcfg.num_vertices * bcfg.num_radix * 2     # counters
                   + wcfg.num_vertices * bcfg.num_inter * 2)    # alias rows

    def active_param_count(self):
        return self._n


def _state_sds(bcfg: BingoConfig) -> BingoState:
    from repro.core.dyngraph import empty_state
    return jax.eval_shape(functools.partial(empty_state, bcfg))


def _state_specs(bcfg: BingoConfig, mesh) -> BingoState:
    """Every (V, ...) tensor shards its vertex dim over the FULL device
    grid — the walk engine has no tensor-parallel work, so the 1-D vertex
    partition (paper §9.1) uses every chip."""
    vaxes = tuple(mesh.axis_names)

    def spec(leaf):
        return P(vaxes, *([None] * (leaf.ndim - 1)))

    sds = _state_sds(bcfg)
    return jax.tree.map(spec, sds)


def build_walk_cell(shape_name: str, mesh, overrides: dict) -> CellSpec:
    wcfg = bingo_walk.FULL
    # Capacity-ladder tier sizing (DESIGN.md §14): capacity_mult=2**t
    # compiles the SAME cell at rung t's C' — the dry-run proves a
    # ladder's top tier still fits per device before it is declared in
    # production (report.py's mem_deltas gates the tagged JSON).
    cmult = int(overrides.get("capacity_mult", 1))
    bcfg = BingoConfig(num_vertices=wcfg.num_vertices,
                       capacity=wcfg.capacity * cmult,
                       bias_bits=wcfg.bias_bits,
                       adaptive=overrides.get("adaptive", True),
                       backend=overrides.get("backend", "auto"),
                       # production default K=2: hides the row-gather DMA
                       # behind the other cohort's sample (DESIGN.md §8)
                       cohorts=overrides.get("cohorts", 2))
    state_sds = _state_sds(bcfg)
    sspecs = _state_specs(bcfg, mesh)
    chips = 1
    for n in mesh.shape.values():
        chips *= n
    dp = tuple(mesh.axis_names)

    if shape_name == "walk_step":
        W = wcfg.walkers
        walkers_sds = jax.ShapeDtypeStruct((W,), jnp.int32)
        key_sds = jax.ShapeDtypeStruct((), jnp.int32)
        num_shards = 1
        for a in dp:
            num_shards *= mesh.shape[a]
        shard_size = wcfg.num_vertices // num_shards

        # Paper §9.1 realized with shard_map: each vertex shard samples its
        # resident walkers locally (global ids -> local rows) through the
        # configured SamplerBackend (production: the fused Pallas step),
        # then one all_to_all ships walkers to their next vertex's owner.
        # Walkers move; sampling structures never do.
        sampler = get_backend(bcfg.backend)

        def walk_step_local(state, walkers, seed):
            from repro.distributed.walker_exchange import exchange_walkers
            sidx = jax.lax.axis_index(dp[0])
            for a in dp[1:]:
                sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
            key = jax.random.fold_in(jax.random.key(seed[0]), sidx)
            local = jnp.where(walkers >= 0,
                              walkers - sidx * shard_size, 0)
            nxt, _ = sampler.sample_step(
                state, bcfg, jnp.clip(local, 0, shard_size - 1), key)
            alive = (walkers >= 0) & (nxt >= 0)
            nxt = jnp.where(alive, nxt, -1)
            arrived, _leftover, _overflow = exchange_walkers(
                nxt, shard_size, num_shards, axis=dp)
            return arrived
        walk_step = jax.shard_map(
            walk_step_local, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(dp), sspecs,
                                   is_leaf=lambda s: isinstance(s, P)),
                      P(dp), P()),
            out_specs=P(dp), check_vma=False)

        return CellSpec(
            arch="bingo-walk", shape_name=shape_name, kind="prefill",
            fn=walk_step,
            args_sds=(state_sds, walkers_sds,
                      jax.ShapeDtypeStruct((1,), jnp.int32)),
            in_shardings=(jax.tree.map(lambda s: NamedSharding(mesh, s),
                                       sspecs,
                                       is_leaf=lambda s: isinstance(s, P)),
                          NamedSharding(mesh, P(dp)),
                          NamedSharding(mesh, P())),
            out_shardings=NamedSharding(mesh, P(dp)),
            donate_argnums=(),
            meta={"tokens": W, "cfg_obj": _WalkCfgShim(wcfg, bcfg)},
        )

    if shape_name == "walk_whole":
        from repro.core.walks import WalkParams
        W = wcfg.walkers
        L = wcfg.walk_length
        num_shards = 1
        for a in dp:
            num_shards *= mesh.shape[a]
        shard_size = wcfg.num_vertices // num_shards
        sampler = get_backend(bcfg.backend)
        wparams = WalkParams(kind="deepwalk", length=L)

        # Whole-walk entry (DESIGN.md §8): each shard walks its resident
        # walkers for the full L steps locally — on TPU this is ONE
        # megakernel launch per shard instead of L launches + L
        # all_to_alls.  The adjacency stores *global* neighbor ids, so
        # the shard first rewrites its nbr table into shard-local rows,
        # truncating out-of-shard neighbors to -1: a walker whose next
        # hop leaves the shard terminates there (the asynchronous-engine
        # trade — no exchange traffic, shard-local sub-walks; the
        # walk_relay shape below re-enqueues walkers with their new
        # owner instead and is exact, DESIGN.md §10).  Paths are emitted
        # in one (W/shards, L+1) write.
        def walk_whole_local(state, walkers, seed):
            sidx = jax.lax.axis_index(dp[0])
            for a in dp[1:]:
                sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
            key = jax.random.fold_in(jax.random.key(seed[0]), sidx)
            lo = sidx * shard_size
            owned = (state.nbr >= lo) & (state.nbr < lo + shard_size)
            state = state._replace(
                nbr=jnp.where(owned, state.nbr - lo, -1))
            local = jnp.where(walkers >= 0,
                              walkers - lo, 0)
            return sampler.sample_walk(
                state, bcfg, jnp.clip(local, 0, shard_size - 1), key,
                wparams)
        walk_whole = jax.shard_map(
            walk_whole_local, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(dp), sspecs,
                                   is_leaf=lambda s: isinstance(s, P)),
                      P(dp), P()),
            out_specs=P(dp), check_vma=False)

        return CellSpec(
            arch="bingo-walk", shape_name=shape_name, kind="prefill",
            fn=walk_whole,
            args_sds=(state_sds, jax.ShapeDtypeStruct((W,), jnp.int32),
                      jax.ShapeDtypeStruct((1,), jnp.int32)),
            in_shardings=(jax.tree.map(lambda s: NamedSharding(mesh, s),
                                       sspecs,
                                       is_leaf=lambda s: isinstance(s, P)),
                          NamedSharding(mesh, P(dp)),
                          NamedSharding(mesh, P())),
            out_shardings=NamedSharding(mesh, P(dp)),
            donate_argnums=(),
            meta={"tokens": W * L, "cfg_obj": _WalkCfgShim(wcfg, bcfg)},
        )

    if shape_name == "walk_relay":
        from repro.core.walks import WalkParams
        from repro.distributed.relay import make_relay
        W = wcfg.walkers
        L = wcfg.walk_length
        engine = get_backend(bcfg.backend)
        wparams = WalkParams(kind="deepwalk", length=L)

        # The slot-compacted super-step relay (DESIGN.md §10): per
        # round, every shard runs ONE resumable megakernel segment over
        # its Wl = W/S + slack compacted slots (free-list placement;
        # the slot→wid map keys the PRNG), exiting walkers ride a
        # (vertex, step, wid) all_to_all mailbox to their next owner,
        # finished segments' path columns ride a (home-tag, wid, slot,
        # path) mailbox to the walker's home shard's (W/S, L+1) block,
        # and overflow of either is re-enqueued — looping until no
        # walker is live anywhere.  Unlike walk_whole nothing
        # truncates: the home blocks concatenate to (W, L+1) paths
        # bit-identical to the single-shard walk at any shard count —
        # and unlike the wid-indexed PR-4 layout (~62 GiB/dev at FULL,
        # unfit) the resident state is O(W/S), so FULL must now FIT
        # (CI gates hbm_fit on this cell's dry-run).  overlap=True runs
        # the production schedule: round g's frontier/path exchanges fly
        # while round g+1's segment walks the stay-locals — bit-exact
        # either way, the PRNG is schedule-invariant (DESIGN.md §10).
        walk_relay = make_relay(engine, bcfg, wparams, mesh,
                                overlap=overrides.get("overlap", True))

        rep = NamedSharding(mesh, P())
        return CellSpec(
            arch="bingo-walk", shape_name=shape_name, kind="prefill",
            fn=walk_relay,
            args_sds=(state_sds, jax.ShapeDtypeStruct((W,), jnp.int32),
                      jax.ShapeDtypeStruct((1,), jnp.int32)),
            in_shardings=(jax.tree.map(lambda s: NamedSharding(mesh, s),
                                       sspecs,
                                       is_leaf=lambda s: isinstance(s, P)),
                          rep, rep),
            out_shardings=(NamedSharding(mesh, P(dp)), None, None),
            donate_argnums=(),
            meta={"tokens": W * L, "cfg_obj": _WalkCfgShim(wcfg, bcfg)},
        )

    if shape_name == "walk_relay_2d":
        from repro.core.walks import WalkParams
        from repro.distributed.relay import make_relay
        W = wcfg.walkers
        L = wcfg.walk_length
        engine = get_backend(bcfg.backend)
        wparams = WalkParams(kind="deepwalk", length=L)

        # The 2D vertex × walker factorization (DESIGN.md §13): the same
        # chips re-meshed as (S_v vertex shards × S_w walker replicas).
        # Graph tables shard their vertex dim over "data" ONLY — each of
        # the S_w walker groups holds a full replica of its vertex
        # shard's tables — while walker slots and home path blocks
        # partition over "walker", so each group relays W/S_w walkers
        # over its private vertex-axis transport.  Walk throughput
        # scales in S_w without re-sharding the graph; the price is
        # S_w × table replication, which the hbm_fit gate re-costs: at
        # FULL, 16 × 16 does NOT fit (the 41 M-vertex tables need
        # S_v ≥ ~21), 64 × 4 does — that asymmetry is the §13 table.
        S_w = overrides.get("walker_replicas", 4)
        if chips % S_w or W % S_w:
            raise ValueError(
                f"walker_replicas={S_w} must divide chips={chips} "
                f"and walkers={W}")
        S_v = chips // S_w
        mesh2 = jax.sharding.Mesh(mesh.devices.reshape(S_v, S_w),
                                  ("data", "walker"))

        def vspec(leaf):
            return P("data", *([None] * (leaf.ndim - 1)))

        sspecs2 = jax.tree.map(vspec, state_sds)
        walk_relay = make_relay(engine, bcfg, wparams, mesh2,
                                overlap=overrides.get("overlap", True),
                                walker_axes=("walker",))

        rep = NamedSharding(mesh2, P())
        return CellSpec(
            arch="bingo-walk", shape_name=shape_name, kind="prefill",
            fn=walk_relay,
            args_sds=(state_sds, jax.ShapeDtypeStruct((W,), jnp.int32),
                      jax.ShapeDtypeStruct((1,), jnp.int32)),
            in_shardings=(jax.tree.map(lambda s: NamedSharding(mesh2, s),
                                       sspecs2,
                                       is_leaf=lambda s: isinstance(s, P)),
                          NamedSharding(mesh2, P("walker")), rep),
            out_shardings=(NamedSharding(mesh2, P(("walker", "data"))),
                           None, None),
            donate_argnums=(),
            meta={"tokens": W * L, "cfg_obj": _WalkCfgShim(wcfg, bcfg),
                  "mesh_sv": S_v, "mesh_sw": S_w},
        )

    if shape_name == "update_step":
        Bu = wcfg.update_batch
        engine = get_backend(bcfg.backend)

        def update_step(state, is_insert, u, v, w):
            # One batched §5.2 round through the EngineBackend — GSPMD
            # partitions the reference path's whole-table scatters over
            # the vertex shards; the pallas path is one megakernel.
            return engine.apply_updates(state, bcfg, is_insert, u, v, w)

        upd_sds = (jax.ShapeDtypeStruct((Bu,), jnp.bool_),
                   jax.ShapeDtypeStruct((Bu,), jnp.int32),
                   jax.ShapeDtypeStruct((Bu,), jnp.int32),
                   jax.ShapeDtypeStruct((Bu,), jnp.int32))
        state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                                is_leaf=lambda s: isinstance(s, P))
        rep = NamedSharding(mesh, P())
        return CellSpec(
            arch="bingo-walk", shape_name=shape_name, kind="prefill",
            fn=update_step,
            args_sds=(state_sds,) + upd_sds,
            in_shardings=(state_sh, rep, rep, rep, rep),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
            meta={"tokens": Bu, "cfg_obj": _WalkCfgShim(wcfg, bcfg)},
        )

    if shape_name == "update_walk":
        from repro.core.walks import WalkParams
        Bu = wcfg.update_batch
        W = wcfg.walkers
        L = wcfg.walk_length
        num_shards = 1
        for a in dp:
            num_shards *= mesh.shape[a]
        shard_size = wcfg.num_vertices // num_shards
        lcfg = dataclasses.replace(bcfg, num_vertices=shard_size)
        engine = get_backend(bcfg.backend)
        wparams = WalkParams(kind="deepwalk", length=L)

        # The streaming serving round (serve/dynwalk.py, distributed):
        # the replicated update batch is routed to owner shards — each
        # shard's active mask selects exactly the edges whose source
        # vertex it owns (vertex-partitioned §9.1: updates move to the
        # data, sampling structures never move) — applied through
        # engine.apply_updates on the shard-local rows, then the shard
        # walks its resident walkers through the fresh tables
        # (walk_whole's shard-local adjacency view).  Per-shard
        # UpdateStats are psum'd so the cell reports global counts.
        from repro.serve.guard import valid_lanes

        def update_walk_local(state, is_insert, u, v, w, walkers, seed):
            sidx = jax.lax.axis_index(dp[0])
            for a in dp[1:]:
                sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
            lo = sidx * shard_size
            # valid_lanes checks endpoints against the GLOBAL vertex
            # count — the one range check the shard-local pipeline
            # cannot do itself (its cfg.num_vertices is the shard size
            # while v stays a global id), so a v >= V lane would
            # otherwise be applied by its owner (DESIGN.md §11).
            owned_u = valid_lanes(bcfg, u, v) \
                & (u >= lo) & (u < lo + shard_size)
            lu = jnp.where(owned_u, u - lo, 0)
            st, stats = engine.apply_updates(state, lcfg, is_insert, lu,
                                             v, w, active=owned_u)
            stats = jax.tree.map(
                lambda t: jax.lax.psum(t, axis_name=dp), stats)
            key = jax.random.fold_in(jax.random.key(seed[0]), sidx)
            owned_n = (st.nbr >= lo) & (st.nbr < lo + shard_size)
            view = st._replace(nbr=jnp.where(owned_n, st.nbr - lo, -1))
            # Only live walkers resident on this shard walk; dead (-1)
            # or foreign slots emit all -1 rather than a fabricated walk
            # from a clamped vertex.  Paths are translated back to
            # GLOBAL vertex ids so the P(dp)-concatenated output is
            # directly consumable (walk_whole predates this and stays
            # shard-local; the serving round's paths leave the cell).
            resident = (walkers >= lo) & (walkers < lo + shard_size)
            local = jnp.where(resident, walkers - lo, 0)
            paths = engine.sample_walk(
                view, lcfg, jnp.clip(local, 0, shard_size - 1), key,
                wparams)
            paths = jnp.where(resident[:, None] & (paths >= 0),
                              paths + lo, -1)
            return st, paths, stats
        update_walk = jax.shard_map(
            update_walk_local, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(dp), sspecs,
                                   is_leaf=lambda s: isinstance(s, P)),
                      P(), P(), P(), P(), P(dp), P()),
            out_specs=(jax.tree.map(lambda _: P(dp), sspecs,
                                    is_leaf=lambda s: isinstance(s, P)),
                       P(dp), P()),
            check_vma=False)

        upd_sds = (jax.ShapeDtypeStruct((Bu,), jnp.bool_),
                   jax.ShapeDtypeStruct((Bu,), jnp.int32),
                   jax.ShapeDtypeStruct((Bu,), jnp.int32),
                   jax.ShapeDtypeStruct((Bu,), jnp.int32))
        state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                                is_leaf=lambda s: isinstance(s, P))
        rep = NamedSharding(mesh, P())
        return CellSpec(
            arch="bingo-walk", shape_name=shape_name, kind="prefill",
            fn=update_walk,
            args_sds=(state_sds,) + upd_sds + (
                jax.ShapeDtypeStruct((W,), jnp.int32),
                jax.ShapeDtypeStruct((1,), jnp.int32)),
            in_shardings=(state_sh, rep, rep, rep, rep,
                          NamedSharding(mesh, P(dp)), rep),
            out_shardings=(state_sh, NamedSharding(mesh, P(dp)), None),
            donate_argnums=(0,),
            meta={"tokens": Bu + W * L,
                  "cfg_obj": _WalkCfgShim(wcfg, bcfg)},
        )

    if shape_name == "serve_round":
        from repro.core.walks import WalkParams
        from repro.distributed.relay import make_relay
        Bu = wcfg.update_batch
        Bw = 65536                      # one walk-cohort bucket (div by S)
        L = wcfg.walk_length
        engine = get_backend(bcfg.backend)
        wparams = WalkParams(kind="deepwalk", length=L)

        # One overlapped serving round of the continuous scheduler
        # (DESIGN.md §12): a fixed-lane walk cohort samples generation g
        # through the exact relay (padded lanes are -1 = free slots,
        # zero resident cost) while the padded update coalescing window
        # builds g+1 on the donated state — ``lanes`` masks the window's
        # padding so every round compiles to ONE shape regardless of how
        # many updates the deadline flushed.  Inside one XLA program the
        # scheduler's staleness contract is structural: the walk reads
        # the pre-update tables (its gathers order before the in-place
        # donated-buffer writes), exactly the "walks against g overlap
        # the megakernel building g+1" picture, with no host round-trip
        # between them.
        walk_relay = make_relay(engine, bcfg, wparams, mesh)

        def serve_round(state, is_insert, u, v, w, lanes, starts, seed):
            paths, _rounds, _overflow = walk_relay(state, starts, seed)
            st2, stats = engine.apply_updates(state, bcfg, is_insert, u,
                                              v, w, active=lanes)
            return st2, paths, stats

        upd_sds = (jax.ShapeDtypeStruct((Bu,), jnp.bool_),
                   jax.ShapeDtypeStruct((Bu,), jnp.int32),
                   jax.ShapeDtypeStruct((Bu,), jnp.int32),
                   jax.ShapeDtypeStruct((Bu,), jnp.int32),
                   jax.ShapeDtypeStruct((Bu,), jnp.bool_))
        state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                                is_leaf=lambda s: isinstance(s, P))
        rep = NamedSharding(mesh, P())
        return CellSpec(
            arch="bingo-walk", shape_name=shape_name, kind="prefill",
            fn=serve_round,
            args_sds=(state_sds,) + upd_sds + (
                jax.ShapeDtypeStruct((Bw,), jnp.int32),
                jax.ShapeDtypeStruct((1,), jnp.int32)),
            in_shardings=(state_sh, rep, rep, rep, rep, rep, rep, rep),
            out_shardings=(state_sh, NamedSharding(mesh, P(dp)), None),
            donate_argnums=(0,),
            meta={"tokens": Bu + Bw * L,
                  "cfg_obj": _WalkCfgShim(wcfg, bcfg)},
        )

    raise ValueError(shape_name)
