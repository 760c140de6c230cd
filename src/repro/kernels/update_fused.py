"""Persistent batched-update megakernel: §5.2 insert→delete→rebuild in VMEM.

The reference ``core/updates.py:batched_update`` realizes the paper's
high-throughput batched pipeline as whole-table jnp: every stage scatters
into / gathers out of the full ``(V, C)`` adjacency tensors in HBM, and
the rebuild re-materializes ``(U, C, K)`` digit intermediates.  This
kernel is the update-side sibling of ``kernels/walk_fused.py``: ONE
``pallas_call`` owns the whole batched round, the ``BingoState`` tables
stay HBM-resident (``memory_space=ANY`` operands, aliased input→output so
untouched vertices are never copied), and per grid step only the
*affected* vertices' rows are DMA'd into double-buffered VMEM scratch.

Staging per affected-vertex tile of Rt rows (paper Fig. 10(a)):

  * **host-order prepass (jnp, outside the kernel)** — the paper's
    "CPU-side ordering becomes an on-device sort": one stable sort of
    the lanes by (vertex, deleted value) puts each vertex's inserts in
    lane order ahead of its deletes; the runs of that sort give every
    lane its affected row, its insert rank or delete lane and its
    duplicate rank, and the lanes are scattered into dense
    per-affected-row *patches* (values at their target slots).
    Ordering only — no ``BingoState`` tensor is touched outside the
    kernel;
  * **inserts** — conflict-free append: one lane select places the patch
    values on the lanes ``[deg, deg + inserted)`` (the scatter the
    reference does in HBM happens on the VMEM-resident row);
  * **deletes** — in-kernel locate (the (rank+1)-th occurrence of each
    doomed value, a masked lane prefix count per patch lane — deletes
    must see the rows *after* this round's inserts) followed by the
    paper's **two-phase delete-and-swap**: phase 1 kills doomed tail
    slots in place, phase 2 moves the surviving tail slots into the
    front holes (a one-hot move per hole index — gather-free,
    bit-identical to ``updates.two_phase_delete``).  Both loops run as
    many passes as the tile's busiest row has delete lanes;
  * **rebuild** — group membership, sizes, digit sums, Eq. 9 types and
    the compacted ``gmem`` rows are recomputed from the final bias row
    one radix group at a time, exactly like
    ``dyngraph.build_vertex_groups``; a group's member slots reach the
    front of its ``gmem`` row in log2(C) power-of-two lane hops.

Rows travel HBM→VMEM→HBM once each; the gathers for tile i+1 are issued
while tile i computes (same double-buffered ``make_async_copy``
discipline as the walk megakernel).  Per-row results that are O(K)-sized
(deg, gsize, digitsum, wdec, gtype) come back as dense blocked outputs
and are scattered outside the kernel, where the inter-group alias rows
are built from them by the reference's own ``build_itable_rows`` — so
both paths divide and sum with the same XLA ops on any backend.

Mosaic constraints shape the layout (DESIGN.md §9): prefix counts are
``core/lanes.py`` log-step rotations (no ``cumsum``/``argmax`` lowering),
the row tables enter as ``(V, 1, C)`` so one vertex row is one
tile-aligned DMA, and the per-tile ids ride a 128-lane row DMA'd into
SMEM.

Static bound: each affected vertex carries at most ``block_dels`` delete
*patch* lanes per round (default ``min(B, 2·C)``).  When ``B <= 2·C``
— every test, the bench rounds, and any sanely-coalesced serving round
— every delete in the batch gets a lane, so the bound is vacuous and
the path is exact unconditionally.  Beyond that, a single vertex
receiving more than ``del_lanes`` delete lanes in one round (possible
only for batches much larger than capacity where most of those lanes
are *misses* — at most C can ever succeed) would have its
lexsort-latest lanes dropped; raise ``block_dels`` for such workloads
or split the round.

The oracle is ``core/updates.py:batched_update`` itself (DESIGN.md §9):
``tests/test_update_fused.py`` pins the full ``BingoState`` bit-exactly
across group types, fp-bias, bases 2/4 and insert/delete/mixed rounds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import radix
from repro.core.alias import AliasTable
from repro.core.dyngraph import (DENSE, BingoConfig, BingoState,
                                 build_itable_rows, classify)
from repro.core.lanes import lane_cumsum, lane_total
from repro.core.updates import (NUM_REASONS, R_ABSENT, R_CAPACITY, R_VERTEX,
                                UpdateStats)

__all__ = ["update_fused_pallas"]


# Per-tile scalars ride one 128-lane int32 row per tile (a (nt, 1, 128)
# table, tile-aligned for the DMA into SMEM): the tile's affected vertex
# ids in lanes [0, Rt) and the delete-loop bound in the last lane.
_IDS_W = 128
_NDEL_LANE = _IDS_W - 1


def _kernel(cfg: BingoConfig, Rt, Dp, *refs):
    V = cfg.num_vertices
    fp = cfg.fp_bias
    has_ginv = not cfg.adaptive
    refs = list(refs)
    ids_any = refs.pop(0)                      # (nt, 1, 128) ANY
    deg0_ref, deg1_ref = refs.pop(0), refs.pop(0)   # (Rt, 1) pre/post ins
    insn_ref, insb_ref = refs.pop(0), refs.pop(0)   # (Rt, C) patches
    insf_ref = refs.pop(0) if fp else None
    delv_ref, delr_ref = refs.pop(0), refs.pop(0)   # (Rt, Dp) patches
    row_any = [refs.pop(0) for _ in range(3 if fp else 2)]  # (V, 1, C)
    refs = refs[2 if has_ginv else 1:]          # gmem/ginv: written only
    # outputs: aliased ANY row tables, then dense per-row blocks
    row_o = [refs.pop(0) for _ in range(3 if fp else 2)]
    gmem_o = refs.pop(0)
    ginv_o = refs.pop(0) if has_ginv else None
    dego_ref, gsz_ref, dsum_ref = refs.pop(0), refs.pop(0), refs.pop(0)
    wdec_ref, gt_ref, ndel_ref = refs.pop(0), refs.pop(0), refs.pop(0)
    # scratch
    row_b = [refs.pop(0) for _ in range(3 if fp else 2)]   # (2, Rt, 1, C)
    row_out = [refs.pop(0) for _ in range(3 if fp else 2)]  # (Rt, 1, C)
    out_gmem = refs.pop(0)
    out_ginv = refs.pop(0) if has_ginv else None
    ids_sm, gsem, osem, isem = refs            # SMEM (2, 1, 128), DMA sems

    i = pl.program_id(0)
    nt = pl.num_programs(0)
    slot = jax.lax.rem(i, 2)

    def load_ids(s, tile):
        cp = pltpu.make_async_copy(ids_any.at[tile], ids_sm.at[s], isem)
        cp.start()
        cp.wait()

    def gather(s, action):
        """Start/wait the row DMAs of every real (non-sentinel) row.

        The predicate is stable between the paired start/wait loops:
        ``ids_sm[s]`` is only rewritten when slot ``s`` is reloaded for a
        later tile, after this tile's wait."""
        def body(r, _):
            @pl.when(ids_sm[s, 0, r] < V)
            def _():
                vtx = ids_sm[s, 0, r]
                for tab, buf in zip(row_any, row_b):
                    getattr(pltpu.make_async_copy(
                        tab.at[vtx], buf.at[s, r], gsem.at[s]), action)()
            return 0
        jax.lax.fori_loop(0, Rt, body, 0)

    @pl.when(i == 0)
    def _():
        load_ids(0, 0)
        gather(0, "start")

    gather(slot, "wait")

    # double buffering: tile i+1's row gathers run under tile i's compute
    @pl.when(i + 1 < nt)
    def _():
        nslot = jax.lax.rem(i + 1, 2)
        load_ids(nslot, i + 1)
        gather(nslot, "start")

    # Affected ids are sorted with the sentinel V last, so a tile whose
    # first row is the sentinel has no real row: skip its compute.  Its
    # dense outputs are dropped by the caller's scatter, except the
    # delete count, which is summed — zero it here.
    ndel_ref[...] = jnp.zeros((Rt, 1), jnp.int32)

    @pl.when(ids_sm[slot, 0, 0] < V)
    def _():
        _rebuild_tile(cfg, Rt, Dp, slot, ids_sm[slot, 0, _NDEL_LANE],
                      deg0_ref, deg1_ref, insn_ref, insb_ref, insf_ref,
                      delv_ref, delr_ref, row_b, row_out, out_gmem,
                      out_ginv, dego_ref, gsz_ref, dsum_ref, wdec_ref,
                      gt_ref, ndel_ref)

        def put(action):
            def body(r, _):
                @pl.when(ids_sm[slot, 0, r] < V)
                def _():
                    vtx = ids_sm[slot, 0, r]
                    pairs = list(zip(row_out, row_o)) + [(out_gmem, gmem_o)]
                    if has_ginv:
                        pairs.append((out_ginv, ginv_o))
                    for src, dst in pairs:
                        getattr(pltpu.make_async_copy(
                            src.at[r], dst.at[vtx], osem), action)()
                return 0
            jax.lax.fori_loop(0, Rt, body, 0)

        put("start")
        put("wait")


def _rebuild_tile(cfg, Rt, Dp, slot, nd, deg0_ref, deg1_ref, insn_ref,
                  insb_ref, insf_ref, delv_ref, delr_ref, row_b, row_out,
                  out_gmem, out_ginv, dego_ref, gsz_ref, dsum_ref, wdec_ref,
                  gt_ref, ndel_ref):
    """Insert → locate → two-phase delete → group rebuild for one tile.

    Every lane op here is one Mosaic lowers: prefix counts are
    ``lanes.lane_cumsum`` over ``pltpu.roll``, reductions are lane sums,
    and bool vectors never cross a loop carry (int32 0/1 instead)."""
    C, K, Cg = cfg.capacity, cfg.num_radix, cfg.group_capacity
    fp = cfg.fp_bias
    bl = cfg.base_log2
    roll = pltpu.roll

    def cum(x):
        return lane_cumsum(x.astype(jnp.int32), roll)

    rows = [b[slot].reshape(Rt, C) for b in row_b]

    # ---- stage 1: conflict-free inserts: lanes [deg0, deg1) take the
    # patch values (the prepass placed each at deg0 + its rank) ----
    d0, d = deg0_ref[...], deg1_ref[...]            # (Rt, 1)
    colC = jax.lax.broadcasted_iota(jnp.int32, (Rt, C), 1)
    insm = (colC >= d0) & (colC < d)
    nbr1 = jnp.where(insm, insn_ref[...], rows[0])
    bias1 = jnp.where(insm, insb_ref[...], rows[1])
    frac1 = jnp.where(insm, insf_ref[...], rows[2]) if fp else None
    in_row = colC < d

    # ---- stage 2a: locate — (rank+1)-th match of each doomed value.
    # ``nd`` bounds the tile's delete lanes, so the loop runs only as
    # many passes as the busiest row needs ----
    delv, delr = delv_ref[...], delr_ref[...]
    colD = jax.lax.broadcasted_iota(jnp.int32, (Rt, Dp), 1)

    def locate(j, dmask):
        at_j = colD == j
        dvj = jnp.sum(jnp.where(at_j, delv, 0), -1, keepdims=True)
        rkj = jnp.sum(jnp.where(at_j, delr, 0), -1, keepdims=True)
        m = (nbr1 == dvj) & in_row & (dvj >= 0)     # off lanes carry -1
        hit = m & (cum(m) == rkj + 1)
        return jnp.maximum(dmask, hit.astype(jnp.int32))

    dmask = jax.lax.fori_loop(0, nd, locate,
                              jnp.zeros((Rt, C), jnp.int32)) != 0

    # ---- stage 2b: two-phase delete-and-swap (paper Fig. 10(b)) ----
    n = jnp.sum(dmask.astype(jnp.int32), -1, keepdims=True)
    ndel_ref[...] = n
    front = d - n
    surv_tail = (colC >= front) & in_row & ~dmask
    hole = dmask & (colC < front)
    r_surv = cum(surv_tail) - 1
    r_hole = cum(hole) - 1

    def mv(j, vals):
        # phase 2, hole j: the j-th surviving tail slot fills the j-th
        # front hole (a one-hot read + one-hot write — no gathers).  A
        # row has at most n <= nd holes.
        sel_h = hole & (r_hole == j)
        sel_s = surv_tail & (r_surv == j)
        put = sel_h & (jnp.sum(sel_s.astype(jnp.int32), -1,
                               keepdims=True) > 0)
        out = []
        for val, old in zip(vals, (nbr1, bias1, frac1)):
            zero = jnp.zeros((), old.dtype)
            got = jnp.sum(jnp.where(sel_s, old, zero), -1, keepdims=True)
            out.append(jnp.where(put, got, val))
        return tuple(out)

    moved = jax.lax.fori_loop(0, nd, mv, (nbr1, bias1, frac1)
                              if fp else (nbr1, bias1))
    keep = colC < front
    nbr3 = jnp.where(keep, moved[0], -1)
    bias3 = jnp.where(keep, moved[1], 0)
    row_out[0][...] = nbr3.reshape(Rt, 1, C)
    row_out[1][...] = bias3.reshape(Rt, 1, C)
    if fp:
        frac3 = jnp.where(keep, moved[2], 0.0)
        row_out[2][...] = frac3.reshape(Rt, 1, C)
        wdec = lane_total(frac3, roll)
    else:
        wdec = jnp.zeros((Rt, 1), jnp.float32)

    # ---- stage 3: rebuild, one radix group at a time on (Rt, C) lanes
    # (dyngraph.build_vertex_groups).  Group k's gmem row compacts its
    # member slots to the front: each kept slot moves left by the count
    # of non-kept slots before it, in log2(C) power-of-two hops (shifts
    # are non-decreasing along the row, so no two slots ever collide) ----
    colK = jax.lax.broadcasted_iota(jnp.int32, (Rt, K), 1)
    gsize = jnp.zeros((Rt, K), jnp.int32)
    digitsum = jnp.zeros((Rt, K), jnp.int32)
    gtype = jnp.zeros((Rt, K), jnp.int32)
    for k in range(K):
        dig = jnp.where(keep, (bias3 >> (k * bl)) & ((1 << bl) - 1), 0)
        member = dig != 0
        pos = cum(member) - 1
        gs = jnp.sum(member.astype(jnp.int32), -1, keepdims=True)
        gt = classify(gs, front[:, 0], cfg, jnp.int32)             # (Rt, 1)
        gsize = jnp.where(colK == k, gs, gsize)
        digitsum = jnp.where(colK == k, jnp.sum(dig, -1, keepdims=True),
                             digitsum)
        gtype = jnp.where(colK == k, gt, gtype)
        if out_ginv is not None:
            out_ginv[:, k, :] = jnp.where(member, pos, -1)
        kept = member & (pos < Cg)
        if cfg.adaptive:
            kept = kept & (gt != DENSE)
        val = jnp.where(kept, colC, -1)
        shift = jnp.where(kept, colC - pos, 0)
        s = 1
        while s < C:
            go = (shift & s) != 0
            land = roll(go.astype(jnp.int32), C - s, 1) != 0
            val = jnp.where(land, roll(val, C - s, 1),
                            jnp.where(go, -1, val))
            shift = jnp.where(land, roll(shift, C - s, 1),
                              jnp.where(go, 0, shift))
            s *= 2
        out_gmem[:, k, :] = val[:, :Cg]

    dego_ref[...] = front
    gsz_ref[...] = gsize
    dsum_ref[...] = digitsum
    wdec_ref[...] = wdec
    gt_ref[...] = gtype


@functools.partial(jax.jit,
                   static_argnames=("cfg", "block_rows", "block_dels",
                                    "interpret"))
def update_fused_pallas(state: BingoState, cfg: BingoConfig, is_insert,
                        u, v, w, active=None, *, block_rows: int = 8,
                        block_dels: int = 0, interpret: bool = False):
    """Batched §5.2 update round in ONE ``pallas_call``.

    Same contract as ``core/updates.py:batched_update`` (bit-identical
    output — the jnp path is the oracle): apply ``is_insert[b] ?
    insert(u, v, w) : delete(u, v)`` for every active lane, inserts
    before deletes, earliest-version-first duplicate deletion, one
    group/alias rebuild per affected vertex.  Returns
    ``(new_state, UpdateStats)``.

    ``block_dels`` caps the per-vertex delete patch lanes (the module
    docstring's static bound); 0 picks ``min(B, 2·C)``, which is exact
    for every batch when ``B <= 2·C`` and leaves headroom for skewed
    larger ones.
    """
    V, C, K = cfg.num_vertices, cfg.capacity, cfg.num_radix
    Cg = cfg.group_capacity
    fp = cfg.fp_bias
    B = u.shape[0]
    with jax.named_scope("update.prepass"):
        u = jnp.asarray(u, jnp.int32)
        v = jnp.asarray(v, jnp.int32)
        if active is None:
            active = jnp.ones((B,), bool)
        # Same lane-validity contract as the reference (reject-and-count:
        # a negative u would wrap in the prepass scatters): see
        # ``batched_update``'s robustness note.
        lane_ok = (u >= 0) & (u < V) & (v >= 0)
        ins = is_insert & active & lane_ok
        dele = (~is_insert) & active & lane_ok
        if fp:
            w_int, w_frac = radix.decompose_fp(w, cfg.lam)
        else:
            w_int = jnp.asarray(w, jnp.int32)
            w_frac = jnp.zeros((B,), jnp.float32)

        # -- ordering prepass: ONE stable sort of the lanes by (vertex,
        # delete value).  Inserts key -1, so they lead their vertex's run
        # in lane order; its deletes follow, ordered by v; unused lanes
        # key V and sort last.  A lane's row in U is the number of vertex
        # runs before it, and each rank is the lane's offset from the
        # first lane of its run: no search of U --
        uk = jnp.where(ins | dele, u, V)
        vk = jnp.where(dele, v, -1)
        srt = jax.lax.sort((uk, vk, v, w_int) + ((w_frac,) if fp else ()),
                           num_keys=2)
        u_s, vk_s, v_s, wi_s = srt[:4]
        wf_s = srt[4] if fp else None
        idx = jnp.arange(B, dtype=jnp.int32)

        def starts_run(x):
            return jnp.concatenate([jnp.ones((1,), bool), x[1:] != x[:-1]])

        def run_rank(first):
            return idx - jax.lax.cummax(jnp.where(first, idx, -1), axis=0)

        first_u = starts_run(u_s)
        is_del = vk_s >= 0
        first_k = first_u | starts_run(is_del)
        rank = run_rank(first_k)     # insert rank, or delete lane, in a row
        rankD = run_rank(first_k | starts_run(vk_s))  # duplicate (u, v) rank
        row = jnp.cumsum(first_u, dtype=jnp.int32) - 1
        # the affected vertices, sorted and padded with V
        U = jnp.sort(jnp.where(first_u, u_s, V))                     # (B,)
        Uc = jnp.minimum(U, V - 1)

        off = state.deg[jnp.minimum(u_s, V - 1)] + rank
        okA = (u_s < V) & ~is_del & (off < C)
        n_ins = jnp.sum(okA, dtype=jnp.int32)
        rowA = jnp.where(okA, row, B)
        offA = jnp.where(okA, off, 0)
        ins_nbr = jnp.zeros((B, C), jnp.int32).at[rowA, offA].set(
            v_s, mode="drop")
        ins_bias = jnp.zeros((B, C), jnp.int32).at[rowA, offA].set(
            wi_s, mode="drop")
        ins_cnt = jnp.zeros((B,), jnp.int32).at[rowA].add(1, mode="drop")
        deg0 = state.deg[Uc]
        deg_ins = deg0 + ins_cnt

        Dp = block_dels if block_dels > 0 else min(B, 2 * C)
        rowD = jnp.where(is_del & (rank < Dp), row, B)
        laneD = jnp.minimum(rank, Dp - 1)
        # v >= 0 on every valid delete lane, so -1 marks an unused lane
        del_v = jnp.full((B, Dp), -1, jnp.int32).at[rowD, laneD].set(
            vk_s, mode="drop")
        del_rank = jnp.zeros((B, Dp), jnp.int32).at[rowD, laneD].set(
            rankD, mode="drop")
        del_cnt = jnp.zeros((B,), jnp.int32).at[rowD].add(1, mode="drop")

        # ---- pad the affected-row axis to the tile size ----
        Rt = max(1, min(block_rows, B))
        if Rt >= _NDEL_LANE:
            raise ValueError(f"block_rows {Rt} does not fit a {_IDS_W}-lane "
                             "id row")
        nt = -(-B // Rt)
        pad = nt * Rt - B
        Bp = nt * Rt

        def padr(x, fill):
            return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                           constant_values=fill)

        Up = padr(U, V)
        tile_ndel = jnp.max(padr(del_cnt, 0).reshape(nt, Rt), axis=1)
        ids = jnp.concatenate(
            [Up.reshape(nt, Rt), jnp.zeros((nt, _NDEL_LANE - Rt), jnp.int32),
             tile_ndel[:, None]], axis=1).reshape(nt, 1, _IDS_W)
        patches = ([ids, padr(deg0[:, None], 0), padr(deg_ins[:, None], 0),
                    padr(ins_nbr, 0), padr(ins_bias, 0)]
                   + ([padr(jnp.zeros((B, C), jnp.float32).at[rowA, offA]
                             .set(wf_s, mode="drop"), 0)] if fp else [])
                   + [padr(del_v, -1), padr(del_rank, 0)])

    has_ginv = state.ginv is not None
    # Row tables go in as (V, 1, C) so each vertex row is one tile-aligned
    # DMA (see walk_fused.META_W); frac only in fp mode — an integer-bias
    # state keeps it all zero, and so does every round.
    tabs = [state.nbr, state.bias] + ([state.frac] if fp else [])
    with jax.named_scope("update.relayout"):
        tabs = [t.reshape(V, 1, C) for t in tabs]
    ntab = len(tabs) + 1 + has_ginv

    def row_spec(lane):
        return pl.BlockSpec((Rt, lane), lambda i: (i, 0))

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    args = (patches + tabs + [state.gmem]
            + ([state.ginv] if has_ginv else []))
    first_tab = len(args) - ntab
    in_specs = ([any_spec] + [row_spec(1)] * 2 + [row_spec(C)] * (3 if fp
                                                                  else 2)
                + [row_spec(Dp)] * 2 + [any_spec] * ntab)

    sds = jax.ShapeDtypeStruct
    out_specs = [any_spec] * ntab + [
        row_spec(1), row_spec(K), row_spec(K), row_spec(1), row_spec(K),
        row_spec(1)]
    out_shape = [sds(t.shape, t.dtype) for t in args[first_tab:]]
    out_shape += [sds((Bp, 1), jnp.int32), sds((Bp, K), jnp.int32),
                  sds((Bp, K), jnp.int32), sds((Bp, 1), jnp.float32),
                  sds((Bp, K), jnp.int32), sds((Bp, 1), jnp.int32)]
    # aliased in-place tables: untouched vertices are never copied
    aliases = {first_tab + t: t for t in range(ntab)}

    scratch = ([pltpu.VMEM((2, Rt, 1, C), t.dtype) for t in tabs]
               + [pltpu.VMEM((Rt, 1, C), t.dtype) for t in tabs]
               + [pltpu.VMEM((Rt, K, Cg), jnp.int32)])
    if has_ginv:
        scratch.append(pltpu.VMEM((Rt, K, C), jnp.int32))
    scratch += [
        pltpu.SMEM((2, 1, _IDS_W), jnp.int32),  # tile ids (DMA scalars)
        pltpu.SemaphoreType.DMA((2,)),          # row gathers, per slot
        pltpu.SemaphoreType.DMA(()),            # row write-backs
        pltpu.SemaphoreType.DMA(()),            # id row
    ]

    with jax.named_scope("update.kernel"):
        outs = list(pl.pallas_call(
            functools.partial(_kernel, cfg, Rt, Dp),
            grid=(nt,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            input_output_aliases=aliases,
            interpret=interpret,
            name="update_round",
        )(*args))
    with jax.named_scope("update.relayout"):
        rows_n = [t.reshape(V, C) for t in outs[:len(tabs)]]
    gmem_n = outs[len(tabs)]
    ginv_n = outs[len(tabs) + 1] if has_ginv else None
    dego, gsz, dsum, wdec, gt, ndel = outs[ntab:]
    with jax.named_scope("update.epilogue"):
        # The inter-group alias rows are built outside the kernel by the
        # very function the reference uses, so both paths divide and sum
        # with the same XLA ops on any backend.
        with jax.named_scope("alias_rows"):
            itab = build_itable_rows(cfg, dsum, wdec[:, 0])
        with jax.named_scope("writeback"):
            def at(table, values):        # pad rows (>= V) dropped
                return table.at[Up].set(values, mode="drop")

            st = state._replace(
                nbr=rows_n[0], bias=rows_n[1],
                frac=rows_n[2] if fp else state.frac,
                gmem=gmem_n, ginv=ginv_n,
                deg=at(state.deg, dego[:, 0]),
                gsize=at(state.gsize, gsz),
                digitsum=at(state.digitsum, dsum),
                wdec=at(state.wdec, wdec[:, 0]),
                gtype=at(state.gtype, gt.astype(jnp.int8)),
                itable=AliasTable(prob=at(state.itable.prob, itab.prob),
                                  alias=at(state.itable.alias, itab.alias)))

            n_del = jnp.sum(jnp.where(Up < V, ndel[:, 0], 0),
                            dtype=jnp.int32)
            old_gtype = state.gtype[Uc]
            new_gtype = gt[:B].astype(jnp.int8)
            valid_row = (U < V)[:, None]
            pair = (old_gtype.astype(jnp.int32) * 5
                    + new_gtype.astype(jnp.int32))
            changed = (old_gtype != new_gtype) & valid_row
            trans = jnp.zeros((25,), jnp.int32).at[
                jnp.where(changed, pair, 25)].add(1, mode="drop").reshape(5, 5)
            rejected = (
                jnp.zeros((NUM_REASONS,), jnp.int32)
                .at[R_VERTEX].set(jnp.sum(active & ~lane_ok, dtype=jnp.int32))
                .at[R_CAPACITY].set(jnp.sum(ins, dtype=jnp.int32) - n_ins)
                .at[R_ABSENT].set(jnp.sum(dele, dtype=jnp.int32) - n_del))
    return st, UpdateStats(n_ins, n_del, trans, rejected)
