"""Persistent whole-walk megakernel: the L-step loop lives in VMEM.

The per-step production path (``kernels/walk_sample.py``) still pays
per-step overhead the kernel cannot see: every step of the
``random_walk`` scan materializes five gathered (B, C)/(B, K) row arrays
in HBM, launches one ``pallas_call``, and round-trips walker state
through XLA — an 80-step DeepWalk is 80 launches and ~80×5 HBM-resident
gathers for work that is per-walker *sequential*.  This kernel is the
jax_pallas analogue of ThunderRW's step interleaving and FlexiWalker's
fused dynamic-walk kernels: one resident ``pallas_call`` per walk batch
that owns the whole step loop (DESIGN.md §8).

Structure per walker tile of Bt:

  * the full BINGO tables (itable prob/alias, bias, nbr, frac, deg) stay
    HBM-resident operands (``memory_space=ANY``) — nothing (B, C)-shaped
    ever materializes in HBM;
  * per step, only the *current* walkers' rows are DMA'd into VMEM
    scratch via ``pltpu.make_async_copy``.  With ``cohorts=1`` the
    scratch is double-buffered over two slots so the step-(t+1) gather
    (issued the moment step t's sample lands) overlaps step t's path
    write, alive bookkeeping, and uniform draw — but the *sample* of
    step t+1 still waits on its own DMA with nothing upstream to hide
    under (the next vertex is data-dependent);
  * **cohort interleaving** (``cohorts=K`` ∈ {2, 4}, ThunderRW's core
    technique): the walker tile is split into K cohorts of Bt/K lanes,
    and the step loop is software-pipelined over K *phases* per step —
    cohort c's step-(t+1) row DMA is issued at the end of its phase and
    waited K−1 phases later, so it runs under the full ``sample_rows``
    compute of the other K−1 cohorts instead of under bookkeeping only.
    The 2-slot ping-pong becomes a rotated schedule of K per-cohort
    VMEM slots (slot c is only rewritten after cohort c's sample
    consumed it, so one slot per cohort suffices — total row scratch
    *shrinks* from 2·Bt to Bt rows); per-cohort alive flags live in the
    same SMEM mirror, which every phase rewrites whole without moving
    another cohort's DMA predicates (``sync_state``).  Cohort assignment
    provably cannot change any walker's stream: uniforms are keyed by
    ``(seed, wid, t)`` (below), never by lane, phase, or slot — so any
    K produces bit-identical paths (pinned by ``tests/test_kernels.py``
    against K=1 and the jnp oracle);
  * walker state (cur | alive) lives in VMEM scratch, mirrored to SMEM
    after each phase (one (Bt, 2) DMA) because DMA descriptors need
    scalar indices; dead walkers (PPR termination, dead ends) skip their
    row gathers entirely via ``pl.when`` on the SMEM alive flag;
  * the tables arrive as (V, 1, W) row tables (``META_W``), so every
    row gather is one tile-aligned DMA;
  * the sample itself is the exact in-register two-stage pass shared
    with the per-step kernel (``walk_sample.sample_rows``): stage (i)
    alias one-hot, stage (ii) masked lane prefix count, including the fp
    decimal group and base > 2 digit-acceptance lanes — or the
    degree-based ``uniform_pick`` for the ``simple`` kind;
  * uniforms are counter-based (``uniforms_at``): step-t uniforms are a
    pure hash of ``(seed, walker row, t)``, so a walker draws the same
    stream wherever (and whenever) step t executes — the resume
    contract of the super-step relay (DESIGN.md §10).  Feeding ``u``
    (L, B, 6) overrides the hash when a test wants to pin an exact
    stream;
  * the (Bt, L+1) path tile is written to HBM once, column by column.

**Segment entry** (``segment=True``, DESIGN.md §10): each walker carries
a start step ``t0`` — it idles until loop step ``t0``, writes its start
vertex at path column ``t0`` (earlier columns stay -1 and are merged by
the caller), and walks the remaining ``L - t0`` steps.  Adjacency rows
may encode *remote* neighbors as ``-(global_id + 2)``: a walker that
samples one exits with a ``(vertex, step)`` frontier record instead of
dying, which is what the relay routes to the vertex's owner shard.
Slots with ``starts < 0`` are free and emit all -1.  Because the relay
packs walkers into *compacted* slots (slot index != walker id), the
segment entry also takes a slot→wid map ``wid`` (B,) int32: the hash
PRNG draws with the *mapped* global walker id, so a walker keeps its
stream no matter which lane of which shard it currently occupies
(default ``wid = arange(B)``, the whole-walk identity layout).

Uniform column layout (hashed or fed, 6 lanes per walker per step):
``u0`` alias bucket, ``u1`` alias coin, ``u2`` member pick, ``u3``
acceptance coin, ``u4`` ITS position, ``u5`` PPR stop coin.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.walk_sample import sample_rows, uniform_pick

__all__ = ["walk_fused_pallas", "uniforms_at", "NUM_UNIFORMS"]

NUM_UNIFORMS = 6

# Row tables reach the kernel as (V, 1, W): XLA lays such an array out in
# (1, 128) tiles, so one vertex's row is one contiguous tile-aligned DMA
# (a row of a (V, W) array sits inside an (8, 128) tile, which Mosaic
# refuses to slice).  The K-wide alias/prob rows and ``deg`` ride in
# 128-lane "meta" rows: alias (or prob) in lanes [0, Kin), deg in the
# last lane of the int32 one.
META_W = 128
DEG_LANE = META_W - 1

# murmur3 finalizer constants + distinct odd counter multipliers, as
# wrapped int32 (XLA integer multiply wraps; shifts below are logical).
_M1 = np.int32(np.uint32(0x85EBCA6B).astype(np.int32))
_M2 = np.int32(np.uint32(0xC2B2AE35).astype(np.int32))
_P_WID = np.int32(np.uint32(0x9E3779B1).astype(np.int32))
_P_T = np.int32(np.uint32(0x7FEB352D).astype(np.int32))
_P_COL = np.int32(np.uint32(0x846CA68B).astype(np.int32))


def _fmix32(x):
    """murmur3 32-bit finalizer on int32 (logical shifts, wrapping mul)."""
    x = x ^ jax.lax.shift_right_logical(x, 16)
    x = x * _M1
    x = x ^ jax.lax.shift_right_logical(x, 13)
    x = x * _M2
    x = x ^ jax.lax.shift_right_logical(x, 16)
    return x


def uniforms_at(seed, wid, t, ncols: int = NUM_UNIFORMS):
    """Counter-based per-(walker, step) uniforms — the relay PRNG contract.

    ``seed`` scalar int32; ``wid``/``t`` broadcastable int32 arrays whose
    broadcast ends in a length-1 trailing axis.  Returns float32 uniforms
    in [0, 1) of that broadcast shape with the trailing axis widened to
    ``ncols``.  A pure function of ``(seed, wid, t, column)`` built from
    chained murmur3 finalizers: the same walker id draws the same step-t
    stream on every shard, round, backend, and loop position — which is
    what makes a relay-resumed walk bit-identical to the single-shard
    walk (DESIGN.md §10).  Plain int32 jnp ops, so the kernel body and
    the jnp oracle share this code path exactly.
    """
    h = _fmix32(seed ^ (wid * _P_WID))
    h = _fmix32(h ^ (t * _P_T))
    out_shape = h.shape[:-1] + (ncols,)
    col = jax.lax.broadcasted_iota(jnp.int32, out_shape, len(out_shape) - 1)
    h = _fmix32(h ^ (col * _P_COL))
    top24 = jax.lax.shift_right_logical(h, 8)
    return top24.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _kernel(length, base_log2, stop_prob, uniform, has_frac, has_u,
            segment, block_b, num_verts, cohorts, num_inter, *refs):
    Bt = block_b
    K = cohorts
    Bc = Bt // K                               # cohort lane count
    # --- unpack refs: inputs, outputs, scratch (order fixed by pallas_call)
    refs = list(refs)
    seed_ref = refs.pop(0)                     # (1,) SMEM
    starts_ref = refs.pop(0)                   # (Bt, 1) VMEM
    t0_ref = refs.pop(0) if segment else None  # (Bt, 1) VMEM
    wid_ref = refs.pop(0) if segment else None  # (Bt, 1) VMEM slot→wid
    u_ref = refs.pop(0) if has_u else None     # (L, Bt, 6) VMEM
    # HBM (V, 1, W) row tables: uniform = (meta, nbr); otherwise
    # (meta, prob, bias, nbr[, frac]) — see META_W.
    ntab = 2 if uniform else 4 + has_frac
    tabs = tuple(refs.pop(0) for _ in range(ntab))
    out_ref = refs.pop(0)                      # (Bt, L+1) VMEM
    fr_ref = refs.pop(0) if segment else None  # (Bt, 2) VMEM
    bufs = tuple(refs.pop(0) for _ in tabs)    # (nslots, rows, 1, W) VMEM
    state_v, state_s, gsem, ssem = refs        # VMEM/SMEM (Bt,2), DMA sems

    # Walker identity for the counter-based PRNG, hoisted out of the
    # step loop (``pl.program_id`` must sit at kernel top level).
    # Whole walks use the global batch row; segments read the slot→wid
    # map instead — the relay packs walkers into compacted slots, so
    # the cross-shard-stable id the resume contract needs is NOT the
    # lane index.  Keyed by wid and t only: cohort geometry cannot
    # change any walker's stream.
    if segment:
        wid_all = None                  # read from wid_ref per phase
    else:
        wid_all = (pl.program_id(0) * Bt
                   + jax.lax.broadcasted_iota(jnp.int32, (Bt, 1), 0))

    def gather(slot, lane0, action):
        """Start/wait the row DMAs for every *alive* walker in lanes
        ``[lane0, lane0 + Bc)`` (one cohort; the whole tile at K=1).

        ``pl.when`` on the SMEM alive flag is the PPR early-termination
        win: dead walkers stop gathering (and must skip the wait too —
        the predicate is stable between the paired loops because a
        cohort's ``state_s`` lanes are only rewritten by its own phase,
        after the previous ``wait`` and before the next ``start``)."""
        def body(b, _):
            @pl.when(state_s[lane0 + b, 1] != 0)
            def _():
                v = jnp.clip(state_s[lane0 + b, 0], 0, num_verts - 1)
                for tab, buf in zip(tabs, bufs):
                    dma = pltpu.make_async_copy(tab.at[v], buf.at[slot, b],
                                                gsem.at[slot])
                    getattr(dma, action)()
            return 0
        jax.lax.fori_loop(0, Bc, body, 0)

    def sync_state():
        """Mirror (cur | alive) to SMEM — DMA indices must be scalars.

        The whole (Bt, 2) tile goes at once: Mosaic refuses a row slice
        of a 2-lane VMEM buffer (lane slices must be 128-aligned).  This
        never perturbs another cohort's DMA predicates: a cohort's
        ``state_v`` lanes change only in its own phase, which mirrors
        them before it ends, so the other lanes are rewritten with the
        values their SMEM copies already hold."""
        cp = pltpu.make_async_copy(state_v, state_s, ssem)
        cp.start()
        cp.wait()

    # --- prologue: start vertex at col t0 (col 0 when not a segment),
    # everything else -1, stage the step-0 rows of the t0 == 0 walkers.
    starts = starts_ref[...]
    colL = jax.lax.broadcasted_iota(jnp.int32, (Bc, length + 1), 1)
    if segment:
        t0 = t0_ref[...]
        occupied = (starts >= 0) & (t0 <= length)
        colT = jax.lax.broadcasted_iota(jnp.int32, (Bt, length + 1), 1)
        out_ref[...] = jnp.where((colT == t0) & occupied, starts, -1)
        fr_ref[...] = jnp.full((Bt, 2), -1, jnp.int32)
        alive0 = occupied & (t0 == 0)
    else:
        t0 = jnp.zeros((Bt, 1), jnp.int32)
        colT = jax.lax.broadcasted_iota(jnp.int32, (Bt, length + 1), 1)
        out_ref[...] = jnp.where(colT == 0, starts, -1)
        alive0 = jnp.ones((Bt, 1), jnp.bool_)
    state_v[:, 0:1] = jnp.maximum(starts, 0)
    state_v[:, 1:2] = alive0.astype(jnp.int32)
    sync_state()
    if K == 1:
        gather(0, 0, "start")
    else:
        for c in range(K):
            gather(c, c * Bc, "start")

    def phase(t, c, slot, next_slot):
        """One cohort's step-t phase: wait its rows, sample in-register,
        advance walker state, write path column t+1, and issue its
        step-(t+1) gather into ``next_slot``.  At K >= 2 that gather is
        in flight for the K-1 following phases (the other cohorts'
        samples at step t) before cohort c waits on it — the ThunderRW
        interleaving; at K=1 it only overlaps the loop epilogue."""
        lane0 = c * Bc
        sl = slice(lane0, lane0 + Bc)
        gather(slot, lane0, "wait")
        rows = [b[slot].reshape(Bc, b.shape[-1]) for b in bufs]
        meta = rows[0]
        colM = jax.lax.broadcasted_iota(jnp.int32, meta.shape, 1)
        deg = jnp.sum(jnp.where(colM == DEG_LANE, meta, 0), -1,
                      keepdims=True)                         # (Bc, 1)
        cur = state_v[sl, 0:1]
        alive = state_v[sl, 1:2] != 0
        wid = wid_ref[sl] if segment else wid_all[sl]        # (Bc, 1)
        if has_u:
            u = u_ref[t][sl]                                 # (Bc, 6)
        else:
            u = uniforms_at(seed_ref[0], wid, t)
        if uniform:
            nxt, _slt, ok = uniform_pick(rows[1], deg, u[:, 2:3])
        else:
            frac = rows[4] if has_frac else None
            nxt, _slt, ok = sample_rows(
                rows[1], meta, rows[2], rows[3], deg, u, frac,
                base_log2=base_log2, num_inter=num_inter)
        # scan-step parity (core/walks.py): the deg check covers both this
        # step's deg[cur] > 0 and the previous step's deg[nxt] > 0.
        alive = alive & (deg > 0)
        if stop_prob > 0.0:
            alive = alive & (u[:, 5:6] >= jnp.float32(stop_prob))
        # nxt >= 0 matches the scan reference's nxt_alive; rows may also
        # mark hops unusable on purpose: -1 truncates (walk_whole's
        # shard-local view), and in segment mode -(g+2) encodes a REMOTE
        # neighbor — the walker exits with a frontier record instead.
        emit = alive & (nxt >= 0)
        # column t+1 of the path tile via a lane-mask select — a dynamic
        # lane-dim store is the one construct Mosaic may refuse; the
        # (Bc, L+1) read-modify-write is a single VPU pass over the
        # cohort's rows.  Lanes only write columns inside their own
        # [t0, L] window so a later-starting walker's prologue column
        # survives.
        t0c = t0[sl]
        wmask = (colL == t + 1) & (t0c <= t)
        out_ref[sl, :] = jnp.where(wmask, jnp.where(emit, nxt, -1),
                                   out_ref[sl, :])
        if segment:
            remote = alive & (nxt <= -2)
            fr_ref[sl, :] = jnp.where(
                remote,
                jnp.concatenate([-nxt - 2, jnp.full_like(nxt, t + 1)], -1),
                fr_ref[sl, :])
        new_alive = alive & ok & (nxt >= 0)
        cur2 = jnp.where(new_alive, nxt, cur)
        if segment:
            # wake the walkers whose segment window opens at step t+1
            startc = starts[sl]
            activate = (startc >= 0) & (t0c == t + 1) & (t + 1 < length)
            cur2 = jnp.where(activate, startc, cur2)
            new_alive = new_alive | activate
        state_v[sl, 0:1] = cur2
        state_v[sl, 1:2] = new_alive.astype(jnp.int32)

        # kick off this cohort's step-t+1 gathers immediately — they
        # overlap nothing upstream (the next vertex is data-dependent)
        # but everything downstream: at K=1 the loop epilogue, next
        # wait setup, and (hash-PRNG mode) the next uniform draw; at
        # K >= 2 additionally the other K-1 cohorts' full step-t
        # samples, which is where the DMA latency actually hides.
        @pl.when(t + 1 < length)
        def _():
            sync_state()
            gather(next_slot, lane0, "start")

    def step(t, _):
        if K == 1:
            # 2-slot ping-pong: the whole tile is one cohort, rows for
            # step t in slot t%2 while slot (t+1)%2 receives the next.
            phase(t, 0, jax.lax.rem(t, 2), jax.lax.rem(t + 1, 2))
        else:
            # rotated schedule: cohort c owns slot c outright — it is
            # only rewritten (phase end) after its sample consumed it
            # (phase start), so K slots of Bc rows replace 2 of Bt.
            for c in range(K):
                phase(t, c, c, c)
        return 0

    jax.lax.fori_loop(0, length, step, 0)


@functools.partial(
    jax.jit,
    static_argnames=("length", "base_log2", "stop_prob", "uniform",
                     "segment", "block_b", "interpret", "cohorts"))
def walk_fused_pallas(prob, alias, bias, nbr, deg, frac, starts, seed,
                      u=None, t0=None, wid=None, *, length: int,
                      base_log2: int = 1,
                      stop_prob: float = 0.0, uniform: bool = False,
                      segment: bool = False, block_b: int = 256,
                      interpret: bool = False, cohorts: int = 1):
    """Whole-walk fused BINGO walk: one ``pallas_call`` for all L steps.

    ``prob``/``alias`` (V, Kin), ``bias``/``nbr`` (V, C) int32, ``deg``
    (V,) int32 and optionally ``frac`` (V, C) float32 are the *full*
    ``BingoState`` tables, kept HBM-resident; ``starts`` (B,) int32;
    ``seed`` (1,) int32 keys the counter-based per-(walker, step) PRNG
    (``uniforms_at`` — same seed, same walk, on any shard).  Passing
    ``u`` (L, B, 6) float32 overrides the hash with fed uniforms (how
    tests pin exact streams against ``ref.walk_fused_ref``).
    ``uniform=True`` runs the degree-based unbiased pick (the ``simple``
    kind) and ignores prob/alias/bias/frac entirely.

    ``segment=True`` is the resumable entry (DESIGN.md §10): ``t0``
    (B,) int32 gives each walker's start step, ``starts < 0`` marks free
    slots, adjacency values ``<= -2`` are remote neighbors encoded as
    ``-(global_id + 2)``, and the return becomes ``(path, frontier)``
    with ``frontier`` (B, 2) int32 ``[vertex, step]`` exit records
    (-1 where the walker finished locally).  ``wid`` (B,) int32 is the
    slot→wid map of the compacted relay: the hash PRNG is keyed by
    ``wid[b]``, not by the lane index ``b`` (default ``arange(B)`` —
    identity, i.e. the uncompacted layout).

    Returns the (B, length+1) int32 path; column ``t0`` (0 for whole
    walks) is the start vertex, columns outside a walker's segment
    window and terminated walkers pad with -1 (the
    ``core/walks.py:random_walk`` contract).

    ``cohorts=K`` (K ∈ {1, 2, 4, ...}) turns on cohort interleaving:
    the per-tile batch is split into K cohorts whose gather DMAs and
    sample compute are software-pipelined (module docstring).  The
    output is **bit-identical for every K** — the PRNG keys by
    (seed, wid, t) only, and every sample is lane-local — so ``ref``
    oracles (which have no cohort notion) pin all values of K.
    """
    if cohorts < 1:
        raise ValueError(f"cohorts must be >= 1; got {cohorts}")
    if u is not None and u.shape[-1] < NUM_UNIFORMS:
        # Strict: the stop coin lives in column 5, and JAX's clamped
        # out-of-bounds gather would otherwise silently alias it onto
        # the ITS column for narrower arrays.
        raise ValueError(
            f"fed uniforms must be (L, B, {NUM_UNIFORMS}); got {u.shape}")
    B = starts.shape[0]
    V = nbr.shape[0]
    has_frac = frac is not None and not uniform
    has_u = u is not None
    block_b = min(block_b, B)
    # The tile must split evenly into cohorts; round up — ragged tails
    # are already handled (Pallas pads out-of-bounds tile lanes; their
    # gathers clip to vertex 0 and their output rows are discarded), so
    # a ragged B simply rides the same padding at any K.
    block_b = -(-block_b // cohorts) * cohorts
    nslots = 2 if cohorts == 1 else cohorts
    rows = block_b // (1 if cohorts == 1 else cohorts)
    grid = (pl.cdiv(B, block_b),)
    if segment and t0 is None:
        t0 = jnp.zeros((B,), jnp.int32)
    if segment and wid is None:
        wid = jnp.arange(B, dtype=jnp.int32)

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),              # seed
        pl.BlockSpec((block_b, 1), lambda i: (i, 0)),       # starts
    ]
    args = [seed, starts[:, None]]
    if segment:
        in_specs.append(pl.BlockSpec((block_b, 1), lambda i: (i, 0)))
        args.append(t0[:, None])
        in_specs.append(pl.BlockSpec((block_b, 1), lambda i: (i, 0)))
        args.append(wid[:, None])
    if has_u:
        in_specs.append(
            pl.BlockSpec((length, block_b, NUM_UNIFORMS),
                         lambda i: (0, i, 0)))
        args.append(u)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    Kin = 0 if uniform else prob.shape[-1]
    if Kin >= DEG_LANE:
        raise ValueError(f"{Kin} inter-group entries do not fit a "
                         f"{META_W}-lane meta row")
    with jax.named_scope("walk.relayout"):
        pad = jnp.zeros((V, DEG_LANE - Kin), jnp.int32)
        meta = jnp.concatenate(
            ([pad] if uniform else [alias.astype(jnp.int32), pad])
            + [deg[:, None]], axis=-1)
        tab_args = [meta] if uniform else [
            meta, jnp.pad(prob, ((0, 0), (0, META_W - Kin)))]
        tab_args += [nbr] if uniform else [bias, nbr]
        if has_frac:
            tab_args.append(frac)
        tab_args = [t.reshape(V, 1, t.shape[-1]) for t in tab_args]
    # Per-slot scratch rows: the K=1 ping-pong needs 2 full-tile slots;
    # K >= 2 needs K cohort-sized slots — K·(Bt/K) = Bt rows total, a
    # 2x shrink of gather scratch vs. the ping-pong (DESIGN.md §8).
    in_specs += [any_spec] * len(tab_args)
    args += tab_args

    out_specs = [pl.BlockSpec((block_b, length + 1), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, length + 1), jnp.int32)]
    if segment:
        out_specs.append(pl.BlockSpec((block_b, 2), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, 2), jnp.int32))

    scratch = [pltpu.VMEM((nslots, rows, 1, t.shape[-1]), t.dtype)
               for t in tab_args]
    scratch += [
        pltpu.VMEM((block_b, 2), jnp.int32),        # state_v: cur | alive
        pltpu.SMEM((block_b, 2), jnp.int32),        # state_s: DMA indices
        pltpu.SemaphoreType.DMA((nslots,)),         # row gathers, per slot
        pltpu.SemaphoreType.DMA(()),                # state mirror copy
    ]
    kern = functools.partial(_kernel, length, base_log2, float(stop_prob),
                             uniform, has_frac, has_u, segment, block_b, V,
                             cohorts, Kin)
    with jax.named_scope("walk.kernel"):
        out = pl.pallas_call(
            kern,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
            name="walk_segment" if segment else "walk_whole",
        )(*args)
    return (out[0], out[1]) if segment else out[0]
