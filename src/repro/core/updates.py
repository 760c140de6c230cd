"""Dynamic graph updates — paper §4.2 (streaming) and §5.2 (batched).

Streaming path (low-latency, one update at a time — paper principle (i)):
  * ``insert_edge``: append into the adjacency row, push the new slot into
    every radix group whose digit is set (O(K) scatters), rebuild only the
    K-entry inter-group alias row.
  * ``delete_edge``: locate the edge in each group (inverted index in
    baseline mode / one vectorized row scan in adaptive mode — DESIGN.md §2),
    swap-with-tail inside each group, swap-with-tail on the adjacency row,
    relabel group references of the moved slot, rebuild the alias row.
  * Group-type transitions (Eq. 9 reclassification after every update) are
    handled with a rare `lax.cond` full-row rebuild — the paper's Table 4
    measures transition rates < 0.5%, and our stats reproduce that.

Batched path (high-throughput — paper principle (i), §5.2):
  insert -> delete -> rebuild, exactly the paper's staging:
  * parallel conflict-free inserts (sort by vertex + segmented ranks — the
    TPU replacement for GPU atomics);
  * parallel deletion via the paper's **two-phase delete-and-swap**
    (phase 1 deletes doomed tail elements; phase 2 fills front holes with
    tail elements that are now guaranteed to survive), vectorized per row;
  * one group/alias rebuild per affected vertex (the paper rebuilds
    per-transition; batched mode amortizes a single vectorized rebuild —
    DESIGN.md §2).

``batched_update`` here is the whole-table jnp pipeline — the reference
half of the update stack and the bit-exact oracle for the pallas
update megakernel (``kernels/update_fused.py``, DESIGN.md §9).  Callers
reach whichever is configured through ``EngineBackend.apply_updates``
(``core/backend.py``) or the donated ``make_updater`` closure below;
streaming *singles* stay on this jnp path on every backend — an O(K)
touch per update cannot amortize a kernel launch (DESIGN.md §9).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import radix
from repro.core.alias import AliasTable
from repro.core.dyngraph import (DENSE, EMPTY, BingoConfig, BingoState,
                                 build_itable_rows, build_vertex_groups,
                                 classify, refresh_vertices)

__all__ = ["insert_edge", "delete_edge", "stream_updates", "batched_update",
           "UpdateStats", "two_phase_delete", "make_updater",
           "R_OK", "R_VERTEX", "R_DUP", "R_ABSENT", "R_CAPACITY", "R_WEIGHT",
           "NUM_REASONS", "REASON_NAMES"]

# Reject-reason taxonomy (DESIGN.md §11).  The engine-level pipelines below
# classify and count R_VERTEX / R_CAPACITY / R_ABSENT themselves; R_DUP and
# R_WEIGHT are policy decisions owned by the serving guard
# (``serve/guard.py``), which reuses these codes so quarantine records and
# ``UpdateStats.rejected`` speak one language.
R_OK = 0          # applied
R_VERTEX = 1      # endpoint out of range: u outside [0, V) or v < 0
R_DUP = 2         # duplicate insert of a live edge (guard policy)
R_ABSENT = 3      # delete of an edge that is not present
R_CAPACITY = 4    # insert into a full adjacency row (deg == C)
R_WEIGHT = 5      # non-finite / non-positive bias (guard / stream layer)
NUM_REASONS = 6
REASON_NAMES = ("ok", "vertex", "dup", "absent", "capacity", "weight")


class UpdateStats(NamedTuple):
    ins_applied: jax.Array    # () int32
    del_applied: jax.Array    # () int32
    transitions: jax.Array    # (5, 5) int32 group-type transition counts
    rejected: jax.Array       # (NUM_REASONS,) int32 per-reason reject counts
    # Capacity-pressure watermark max(deg)/capacity after the round
    # (DESIGN.md §14) — attached by the serving engine as a device
    # scalar (never a host sync); None on the raw kernel paths, and as
    # None it is not a pytree leaf, so stats trees stay comparable.
    max_fill: Optional[jax.Array] = None


def _locate(state: BingoState, cfg: BingoConfig, u, slot):
    """Position of adjacency slot ``slot`` in each of u's groups, -1 if absent.

    Baseline: O(1) inverted-index lookup (paper §4.2 design change #2).
    Adaptive: one vectorized compare over the (K, Cg) group rows — the TPU
    locate that lets GA mode drop the inverted index entirely.
    """
    if state.ginv is not None:
        return state.ginv[u, :, slot]
    eq = state.gmem[u] == slot                      # (K, Cg)
    pos = jnp.argmax(eq, axis=-1).astype(jnp.int32)
    return jnp.where(jnp.any(eq, axis=-1), pos, -1)


def _rebuild_vertex(state: BingoState, cfg: BingoConfig, u) -> BingoState:
    """Exact group rebuild for one vertex (transition path, rare)."""
    gmem, ginv, gsize, digitsum, gtype, wdec = build_vertex_groups(
        cfg, state.bias[u], state.frac[u], state.deg[u])
    st = state._replace(
        gmem=state.gmem.at[u].set(gmem),
        gsize=state.gsize.at[u].set(gsize),
        digitsum=state.digitsum.at[u].set(digitsum),
        gtype=state.gtype.at[u].set(gtype),
        wdec=state.wdec.at[u].set(wdec),
    )
    if state.ginv is not None:
        st = st._replace(ginv=state.ginv.at[u].set(ginv))
    return st


def _set_itable_row(state: BingoState, cfg: BingoConfig, u) -> BingoState:
    row = build_itable_rows(cfg, state.digitsum[u][None], state.wdec[u][None])
    return state._replace(itable=AliasTable(
        prob=state.itable.prob.at[u].set(row.prob[0]),
        alias=state.itable.alias.at[u].set(row.alias[0]),
    ))


def insert_edge(state: BingoState, cfg: BingoConfig, u, v, w,
                ) -> Tuple[BingoState, jax.Array]:
    """Streaming insertion (paper Fig. 5).  Returns ``(state, ok)``.

    O(K) group appends + O(K) alias rebuild; a full-row rebuild fires only
    on a DENSE -> materialized type transition (rare, Table 4).

    ``ok`` is False (and the state untouched) for a full row *or* an
    out-of-range endpoint — u outside [0, V), v < 0.  v's upper bound is
    deliberately unchecked here: sharded callers store GLOBAL neighbor ids
    against a local ``cfg.num_vertices`` (DESIGN.md §10); the serving guard
    checks v < V against the global config.
    """
    K, C, Cg = cfg.num_radix, cfg.capacity, cfg.group_capacity
    V = cfg.num_vertices
    u = jnp.asarray(u, jnp.int32)
    v = jnp.asarray(v, jnp.int32)
    if cfg.fp_bias:
        w_int, w_frac = radix.decompose_fp(w, cfg.lam)
    else:
        w_int = jnp.asarray(w, jnp.int32)
        w_frac = jnp.float32(0.0)

    valid = (u >= 0) & (u < V) & (v >= 0)
    u = jnp.where(valid, u, 0)          # clamp so even gathers cannot wrap
    ok = valid & (state.deg[u] < C)
    slot = state.deg[u]
    slot_idx = jnp.where(ok, slot, C)                     # OOB -> dropped
    nbr = state.nbr.at[u, slot_idx].set(v, mode="drop")
    bias = state.bias.at[u, slot_idx].set(w_int, mode="drop")
    frac = state.frac.at[u, slot_idx].set(w_frac, mode="drop")
    deg = state.deg.at[u].add(ok.astype(jnp.int32))

    ks = jnp.arange(K, dtype=jnp.int32)
    digs = radix.digit_at(w_int, ks, cfg.base_log2)       # (K,)
    member = (digs != 0) & ok
    old_size = state.gsize[u]
    old_type = state.gtype[u]
    gsize = state.gsize.at[u].add(member.astype(jnp.int32))
    digitsum = state.digitsum.at[u].add(jnp.where(ok, digs, 0))
    wdec = state.wdec.at[u].add(jnp.where(ok, w_frac, 0.0))
    new_type = classify(gsize[u], deg[u], cfg)

    # Intra-group appends (stage (i) of Fig. 5) — one masked scatter over K.
    append = member & (old_type != DENSE) & (new_type != DENSE)
    pos = jnp.where(append & (old_size < Cg), old_size, Cg)
    gmem = state.gmem.at[u, ks, pos].set(slot, mode="drop")
    st = state._replace(nbr=nbr, bias=bias, frac=frac, deg=deg, gmem=gmem,
                        gsize=gsize, digitsum=digitsum, wdec=wdec,
                        gtype=state.gtype.at[u].set(new_type))
    if state.ginv is not None:
        st = st._replace(ginv=state.ginv.at[
            u, ks, jnp.where(append, slot, C)].set(old_size, mode="drop"))

    need_rebuild = (old_type == DENSE) & (new_type != DENSE) & (new_type != EMPTY)
    st = jax.lax.cond(jnp.any(need_rebuild),
                      lambda s: _rebuild_vertex(s, cfg, u), lambda s: s, st)
    # Stage (ii) of Fig. 5: rebuild the K-entry inter-group alias row.
    return _set_itable_row(st, cfg, u), ok


def delete_edge(state: BingoState, cfg: BingoConfig, u, v,
                ) -> Tuple[BingoState, jax.Array]:
    """Streaming deletion (paper Fig. 6) — near-constant O(K) work.

    Steps (i)-(iv) of the paper: identify contributing groups, locate via
    inverted index / row scan, delete-and-swap in each group, swap-with-tail
    on the adjacency row (relabeling group references of the moved slot),
    rebuild the inter-group alias row.

    ``ok`` is False for an absent edge *or* an out-of-range u (negative u
    would otherwise wrap into another vertex's row).
    """
    K, C, Cg = cfg.num_radix, cfg.capacity, cfg.group_capacity
    V = cfg.num_vertices
    u = jnp.asarray(u, jnp.int32)
    valid_u = (u >= 0) & (u < V)
    u = jnp.where(valid_u, u, 0)
    ks = jnp.arange(K, dtype=jnp.int32)
    valid = jnp.arange(C, dtype=jnp.int32) < state.deg[u]
    matches = (state.nbr[u] == v) & valid
    ok = jnp.any(matches) & valid_u
    slot = jnp.argmax(matches).astype(jnp.int32)          # earliest version
    last = state.deg[u] - 1

    w_s = jnp.where(ok, state.bias[u, slot], 0)
    f_s = jnp.where(ok, state.frac[u, slot], 0.0)
    digs_s = radix.digit_at(w_s, ks, cfg.base_log2)
    member_s = (digs_s != 0) & ok
    old_size = state.gsize[u]
    old_type = state.gtype[u]

    gsize = state.gsize.at[u].add(-member_s.astype(jnp.int32))
    digitsum = state.digitsum.at[u].add(-digs_s)
    wdec = state.wdec.at[u].add(-f_s)
    deg = state.deg.at[u].add(-ok.astype(jnp.int32))

    # (i)+(ii)+(iii): per-group delete-and-swap for materialized groups.
    mat_s = member_s & (old_type != DENSE)
    pos = _locate(state, cfg, u, slot)                    # (K,)
    tail = old_size - 1
    tail_c = jnp.clip(tail, 0, Cg - 1)
    moved = state.gmem[u, ks, tail_c]                     # group-tail entries
    gmem = state.gmem.at[u, ks, jnp.where(mat_s, pos, Cg)].set(
        moved, mode="drop")
    gmem = gmem.at[u, ks, jnp.where(mat_s, tail, Cg)].set(-1, mode="drop")
    ginv = state.ginv
    if ginv is not None:
        ginv = ginv.at[u, ks, jnp.where(mat_s, moved, C)].set(pos, mode="drop")
        ginv = ginv.at[u, ks, jnp.where(mat_s, slot, C)].set(-1, mode="drop")
    st = state._replace(gmem=gmem, ginv=ginv, gsize=gsize,
                        digitsum=digitsum, wdec=wdec, deg=deg)

    # Adjacency swap-with-tail: move slot ``last`` into the hole at ``slot``
    # and relabel its group references (the paper's design change #1 — we
    # store slot *indices* in groups precisely to make this O(1) per group).
    do_swap = ok & (slot != last)
    last_c = jnp.clip(last, 0, C - 1)
    w_l = st.bias[u, last_c]
    nbr = st.nbr.at[u, jnp.where(do_swap, slot, C)].set(
        st.nbr[u, last_c], mode="drop")
    bias = st.bias.at[u, jnp.where(do_swap, slot, C)].set(w_l, mode="drop")
    frc = st.frac.at[u, jnp.where(do_swap, slot, C)].set(
        st.frac[u, last_c], mode="drop")
    nbr = nbr.at[u, jnp.where(ok, last, C)].set(-1, mode="drop")
    bias = bias.at[u, jnp.where(ok, last, C)].set(0, mode="drop")
    frc = frc.at[u, jnp.where(ok, last, C)].set(0.0, mode="drop")

    digs_l = radix.digit_at(w_l, ks, cfg.base_log2)
    mat_l = (digs_l != 0) & do_swap & (old_type != DENSE)
    pos2 = _locate(st, cfg, u, last)                      # after group-delete
    gmem = st.gmem.at[u, ks, jnp.where(mat_l, pos2, Cg)].set(
        slot, mode="drop")
    st = st._replace(nbr=nbr, bias=bias, frac=frc, gmem=gmem)
    if ginv is not None:
        ginv = st.ginv.at[u, ks, jnp.where(mat_l, slot, C)].set(
            pos2, mode="drop")
        ginv = ginv.at[u, ks, jnp.where(ok & (slot != last), last, C)
                       ].set(-1, mode="drop")
        st = st._replace(ginv=ginv)

    new_type = classify(gsize[u], deg[u], cfg)
    st = st._replace(gtype=st.gtype.at[u].set(new_type))
    need_rebuild = (old_type == DENSE) & (new_type != DENSE) & (new_type != EMPTY)
    st = jax.lax.cond(jnp.any(need_rebuild),
                      lambda s: _rebuild_vertex(s, cfg, u), lambda s: s, st)
    return _set_itable_row(st, cfg, u), ok


def stream_updates(state: BingoState, cfg: BingoConfig, is_insert, u, v, w,
                   ) -> Tuple[BingoState, jax.Array]:
    """Apply a sequence of updates one-at-a-time (streaming semantics)."""
    if not cfg.fp_bias:
        w = jnp.asarray(w, jnp.int32)

    def body(st, upd):
        ins, uu, vv, ww = upd
        st, ok = jax.lax.cond(
            ins,
            lambda s: insert_edge(s, cfg, uu, vv, ww),
            lambda s: delete_edge(s, cfg, uu, vv),
            st)
        return st, ok

    return jax.lax.scan(body, state, (is_insert, u, v, w))


# ---------------------------------------------------------------------------
# Batched updates (§5.2)
# ---------------------------------------------------------------------------

def two_phase_delete(vals_tuple, del_mask, d):
    """Paper Fig. 10(b): two-phase parallel delete-and-swap on one row.

    Phase 1 marks the n tail slots; tail slots that are themselves deleted
    (γ of them) die in place.  Phase 2 moves the n-γ *surviving* tail slots
    — which are now guaranteed not to be deleted — into the n-γ front holes.
    Returns ``(new_vals_tuple, new_len, remap)`` where ``remap[i]`` is the
    new position of old slot i (-1 if deleted).
    """
    C = del_mask.shape[0]
    ar = jnp.arange(C, dtype=jnp.int32)
    in_row = ar < d
    del_mask = del_mask & in_row
    n = jnp.sum(del_mask, dtype=jnp.int32)
    front = d - n
    is_tail = (ar >= front) & in_row
    surv_tail = is_tail & ~del_mask
    hole = del_mask & (ar < front)
    r_surv = jnp.cumsum(surv_tail, dtype=jnp.int32) - 1
    r_hole = jnp.cumsum(hole, dtype=jnp.int32) - 1
    hole_pos = jnp.full((C,), C, jnp.int32).at[
        jnp.where(hole, r_hole, C)].set(ar, mode="drop")
    tgt = jnp.where(surv_tail, hole_pos[jnp.clip(r_surv, 0, C - 1)], C)

    new_vals = []
    for vals, fill in vals_tuple:
        nv = vals.at[tgt].set(vals, mode="drop")
        nv = jnp.where(ar < front, nv, fill)
        new_vals.append(nv)
    remap = jnp.where(del_mask, -1, jnp.where(surv_tail, tgt, ar))
    remap = jnp.where(in_row, remap, -1)
    return tuple(new_vals), front, remap


def _padded_unique(x, sentinel):
    """Sorted unique values of ``x`` padded with ``sentinel`` (static shape)."""
    s = jnp.sort(x)
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    return jnp.sort(jnp.where(first, s, sentinel))


def batched_update(state: BingoState, cfg: BingoConfig, is_insert, u, v, w,
                   active=None) -> Tuple[BingoState, UpdateStats]:
    """High-throughput batched update (paper §5.2 / Fig. 10(a)).

    Stages: CPU-side ordering becomes an on-device sort; then per vertex —
    insert, delete (two-phase delete-and-swap), and a single rebuild of the
    group structures + inter-group alias tables of affected vertices.

    Robustness contract (DESIGN.md §11): no lane can corrupt the table.
    Out-of-range endpoints (u outside [0, V), v < 0 — a negative u would
    otherwise *wrap* in the scatters and write another vertex's row),
    inserts into a full row, and deletes of absent edges are all dropped
    and counted per-reason in ``UpdateStats.rejected``.  v's upper bound is
    deliberately unchecked: sharded callers store GLOBAL neighbor ids
    against a local ``cfg.num_vertices`` (DESIGN.md §10); the serving
    guard (``serve/guard.py``) checks v < V against the global config.
    """
    V, C = cfg.num_vertices, cfg.capacity
    B = u.shape[0]
    u = jnp.asarray(u, jnp.int32)
    v = jnp.asarray(v, jnp.int32)
    if active is None:
        active = jnp.ones((B,), bool)
    lane_ok = (u >= 0) & (u < V) & (v >= 0)
    ins = is_insert & active & lane_ok
    dele = (~is_insert) & active & lane_ok
    if cfg.fp_bias:
        w_int, w_frac = radix.decompose_fp(w, cfg.lam)
    else:
        w_int = jnp.asarray(w, jnp.int32)
        w_frac = jnp.zeros((B,), jnp.float32)

    old_gtype_all = state.gtype

    # ---- stage 1: parallel inserts (sort by vertex + segmented ranks) ----
    su = jnp.where(ins, u, V)
    order = jnp.argsort(su)
    su_s, v_s = su[order], v[order]
    wi_s, wf_s = w_int[order], w_frac[order]
    idx = jnp.arange(B, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), su_s[1:] != su_s[:-1]])
    rank = idx - jax.lax.cummax(jnp.where(first, idx, -1), axis=0)
    off = state.deg[jnp.minimum(su_s, V - 1)] + rank
    okA = (su_s < V) & (off < C)
    tgt = jnp.where(okA, off, C)
    nbr = state.nbr.at[su_s, tgt].set(v_s, mode="drop")
    bias = state.bias.at[su_s, tgt].set(wi_s, mode="drop")
    frac = state.frac.at[su_s, tgt].set(wf_s, mode="drop")
    deg = state.deg.at[jnp.where(okA, su_s, V)].add(1, mode="drop")
    n_ins = jnp.sum(okA, dtype=jnp.int32)

    # ---- stage 2: parallel deletes ----
    du = jnp.where(dele, u, V)
    dv = jnp.where(dele, v, -1)
    ordD = jnp.lexsort((dv, du))
    du_s, dv_s = du[ordD], dv[ordD]
    firstD = jnp.concatenate(
        [jnp.ones((1,), bool), (du_s[1:] != du_s[:-1]) | (dv_s[1:] != dv_s[:-1])])
    rankD = idx - jax.lax.cummax(jnp.where(firstD, idx, -1), axis=0)
    rows = nbr[jnp.minimum(du_s, V - 1)]                   # (B, C)
    validD = (jnp.arange(C, dtype=jnp.int32)[None, :]
              < deg[jnp.minimum(du_s, V - 1)][:, None])
    m = (rows == dv_s[:, None]) & validD & (du_s < V)[:, None]
    cnt = jnp.cumsum(m, axis=-1)
    # rankD-th duplicate deletes the (rankD+1)-th (earliest-first) match
    hit = m & (cnt == (rankD + 1)[:, None])
    okD = jnp.any(hit, axis=-1)
    slotD = jnp.argmax(hit, axis=-1).astype(jnp.int32)
    n_del = jnp.sum(okD, dtype=jnp.int32)

    # affected vertices (inserts ∪ deletes), padded with sentinel V
    U = _padded_unique(jnp.where(ins | dele, u, V), V)     # (B,)
    rowid = jnp.searchsorted(U, du_s)                      # delete -> row in U
    rowid = jnp.where(okD, rowid, B)
    del_mask = jnp.zeros((B, C), bool).at[rowid, slotD].set(True, mode="drop")

    Uc = jnp.minimum(U, V - 1)
    (new_nbr, new_bias, new_frac), new_len, _ = jax.vmap(
        lambda nb, bi, fr, dm, dd: two_phase_delete(
            ((nb, -1), (bi, 0), (fr, 0.0)), dm, dd)
    )(nbr[Uc], bias[Uc], frac[Uc], del_mask, deg[Uc])

    st = state._replace(
        nbr=nbr.at[U].set(new_nbr, mode="drop"),
        bias=bias.at[U].set(new_bias, mode="drop"),
        frac=frac.at[U].set(new_frac, mode="drop"),
        deg=deg.at[U].set(new_len, mode="drop"),
    )

    # ---- stage 3: single rebuild per affected vertex (groups + alias) ----
    st = refresh_vertices(st, cfg, U)

    new_gtype = st.gtype[Uc]
    old_gtype = old_gtype_all[Uc]
    valid_row = (U < V)[:, None]
    pair = old_gtype.astype(jnp.int32) * 5 + new_gtype.astype(jnp.int32)
    changed = (old_gtype != new_gtype) & valid_row
    trans = jnp.zeros((25,), jnp.int32).at[
        jnp.where(changed, pair, 25)].add(1, mode="drop").reshape(5, 5)
    rejected = (
        jnp.zeros((NUM_REASONS,), jnp.int32)
        .at[R_VERTEX].set(jnp.sum(active & ~lane_ok, dtype=jnp.int32))
        .at[R_CAPACITY].set(jnp.sum(ins, dtype=jnp.int32) - n_ins)
        .at[R_ABSENT].set(jnp.sum(dele, dtype=jnp.int32) - n_del))
    return st, UpdateStats(n_ins, n_del, trans, rejected)


def make_updater(cfg: BingoConfig, backend: Optional[str] = None,
                 with_active: bool = False):
    """Jitted batched-update closure (cfg/backend static), donated state.

    Mirrors ``core/walks.py:make_walker``: returns ``run(st, is_insert,
    u, v, w) -> (st, UpdateStats)`` with the state donated
    (``donate_argnums=0``) and threaded through, so XLA aliases the full
    ``BingoState`` buffers input→output and repeated update rounds never
    copy the tables — callers rebind ``st, stats = run(st, ...)``
    (``serve/dynwalk.py``).  The round
    is applied through the ``EngineBackend`` named by ``backend``
    (default ``cfg.backend``): the jnp pipeline on the reference
    backend, one update-megakernel launch on pallas.

    With ``with_active=True`` the closure takes a sixth ``active (B,)``
    bool argument — the serving guard (``serve/guard.py``) uses it to
    apply only the lanes its device-side pre-pass accepted while keeping
    the round's shape (and hence the compiled program) fixed.
    """
    from repro.core.backend import get_backend
    bk = get_backend(cfg.backend if backend is None else backend)

    # The program is named ``engine_update`` in traces, on one device
    # and on a mesh (``serve/dynwalk.py``) alike.
    if with_active:
        @functools.partial(jax.jit, donate_argnums=0)
        def engine_update(st, is_insert, u, v, w, active):
            return bk.apply_updates(st, cfg, is_insert, u, v, w,
                                    active=active)
    else:
        @functools.partial(jax.jit, donate_argnums=0)
        def engine_update(st, is_insert, u, v, w):
            return bk.apply_updates(st, cfg, is_insert, u, v, w)
    return engine_update
