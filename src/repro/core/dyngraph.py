"""BINGO dynamic-graph + sampling-space state (paper §3–§5), TPU-adapted.

The paper's CUDA implementation builds on Hornet dynamic arrays.  XLA needs
static shapes, so the Hornet block pools become *fixed-capacity padded
tensors* (DESIGN.md §2):

  adjacency          nbr/bias/frac : (V, C)      slot-compact rows, ``deg`` counts
  intra-group lists  gmem          : (V, K, Cg)  neighbor *slot indices* (§4.2)
  inverted index     ginv          : (V, K, C)   slot -> position-in-group
                                                 (baseline mode only — in the
                                                 group-adaptive mode locate is
                                                 a single vectorized row scan,
                                                 see DESIGN.md §2)
  counters           gsize, digitsum : (V, K)    |G_k| and Σ digit_k(w_i)
  decimal group      wdec          : (V,)        Σ frac (fp-bias mode, §4.3)
  group types        gtype         : (V, K)      Eq. 9 classification (§5.1)
  inter-group space  itable        : alias table over K (+1 decimal) groups

Group-type invariant: every non-DENSE, non-EMPTY group row is *materialized*
(its ``gmem`` prefix lists exactly the member slots).  DENSE groups store
nothing and sample by rejection on the raw adjacency row (paper §5.1).
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import radix
from repro.core.alias import AliasTable, build_alias
from repro.core.lanes import lane_total

__all__ = [
    "EMPTY", "DENSE", "ONE", "SPARSE", "REGULAR",
    "BingoConfig", "BingoState",
    "classify", "build_vertex_groups", "build_itable_rows",
    "empty_state", "from_edges", "refresh_vertices", "regrow_state",
]

# Group type codes (Eq. 9).  Precedence follows the paper's listing:
# dense > one-element > sparse > regular.
EMPTY, DENSE, ONE, SPARSE, REGULAR = 0, 1, 2, 3, 4


@dataclasses.dataclass(frozen=True)
class BingoConfig:
    """Static configuration (hashable — safe as a jit static argument)."""

    num_vertices: int
    capacity: int                 # C — max neighbors per vertex
    bias_bits: int = 16           # max integer-bias width
    base_log2: int = 1            # radix base = 2**base_log2 (paper: base 2)
    adaptive: bool = True         # §5.1 group-adaptive (GA) vs baseline (BS)
    alpha: float = 0.40           # dense threshold  (|G|/d > alpha)
    beta: float = 0.10            # sparse threshold (|G|/d < beta)
    fp_bias: bool = False         # §4.3 floating-point biases
    lam: float = 16.0             # λ amortization factor (fp mode)
    backend: str = "auto"         # sampler backend (core/backend.py):
                                  # reference | pallas | auto (= pallas on
                                  # TPU, reference elsewhere)
    cohorts: int = 1              # walk-megakernel cohort interleaving
                                  # factor K (DESIGN.md §8) — bit-exact
                                  # for every K; purely a perf knob
    capacity_ladder: tuple = ()   # pre-declared capacity tiers (C, 2C, …)
                                  # for live regrowth (DESIGN.md §14);
                                  # () = fixed capacity, no escalation

    def __post_init__(self):
        if not isinstance(self.capacity_ladder, tuple):
            object.__setattr__(self, "capacity_ladder",
                               tuple(int(c) for c in self.capacity_ladder))
        lad = self.capacity_ladder
        if lad:
            if any(b <= a for a, b in zip(lad, lad[1:])):
                raise ValueError(
                    f"capacity_ladder must be strictly increasing: {lad}")
            if self.capacity not in lad:
                raise ValueError(
                    f"capacity {self.capacity} is not a rung of "
                    f"capacity_ladder {lad} — the ladder must be declared "
                    "up front so every tier's programs are known")

    @property
    def ladder(self) -> tuple:
        """The capacity tiers, always non-empty (``(capacity,)`` when no
        ladder was declared)."""
        return self.capacity_ladder or (self.capacity,)

    @property
    def tier(self) -> int:
        """Index of the current capacity in the ladder."""
        return self.ladder.index(self.capacity)

    def tier_config(self, t: int) -> "BingoConfig":
        """The config at ladder rung ``t`` — identical in every field but
        ``capacity`` (the ladder itself is carried unchanged, so tier
        configs of one engine share one ladder)."""
        return dataclasses.replace(self, capacity=self.ladder[t])

    @property
    def num_radix(self) -> int:
        """K — number of radix groups."""
        return radix.num_groups(self.bias_bits, self.base_log2)

    @property
    def group_capacity(self) -> int:
        """Cg — per-group slot capacity.

        Adaptive mode: any group larger than ``alpha * deg`` is DENSE and
        unmaterialized, so materialized groups never exceed
        ``ceil(alpha * C) + 1`` slots (DESIGN.md §2) — a real >2x saving on
        the dominant intra-group storage, mirroring paper Fig. 11.  The
        bound is rounded up to a whole 128-lane tile: the TPU stores a
        (K, Cg) row in such tiles anyway, and the update kernel's row
        DMA must cover whole tiles.
        """
        if self.adaptive:
            need = int(math.ceil(self.alpha * self.capacity)) + 1
            return min(self.capacity, -(-need // 128) * 128)
        return self.capacity

    @property
    def num_inter(self) -> int:
        """Entries in the inter-group alias table (K + decimal group)."""
        return self.num_radix + (1 if self.fp_bias else 0)

    @property
    def base(self) -> int:
        return 1 << self.base_log2


class BingoState(NamedTuple):
    nbr: jax.Array               # (V, C) int32, -1 padded
    bias: jax.Array              # (V, C) int32 integer (λ-scaled) biases
    frac: jax.Array              # (V, C) float32 decimal parts (fp mode)
    deg: jax.Array               # (V,) int32
    gmem: jax.Array              # (V, K, Cg) int32 slot indices, -1 padded
    ginv: Optional[jax.Array]    # (V, K, C) int32 or None (adaptive mode)
    gsize: jax.Array             # (V, K) int32
    digitsum: jax.Array          # (V, K) int32  Σ digit_k  (W(p_k)/B^k)
    wdec: jax.Array              # (V,) float32  W_D — decimal group weight
    gtype: jax.Array             # (V, K) int8   Eq. 9 classes
    itable: AliasTable           # prob/alias (V, num_inter)

    @property
    def num_vertices(self) -> int:
        return self.nbr.shape[0]


def classify(gsize, deg, cfg: BingoConfig, dtype=jnp.int8):
    """Eq. 9 group classification, vectorized over ``(..., K)`` sizes.

    ``dtype`` is the code type: int8 in the state; the update kernel asks
    for int32, since Mosaic cannot narrow vectors to 8 bits."""
    deg = deg[..., None].astype(jnp.float32)
    g = gsize.astype(jnp.float32)
    if not cfg.adaptive:
        return jnp.where(gsize > 0, REGULAR, EMPTY).astype(dtype)
    t = jnp.where(
        g > cfg.alpha * deg,  # |G|/d > alpha (paper: alpha% = 40%)
        DENSE,
        jnp.where(
            gsize == 1,
            ONE,
            jnp.where(g < cfg.beta * deg, SPARSE, REGULAR),
        ),
    )
    return jnp.where(gsize == 0, EMPTY, t).astype(dtype)


def build_vertex_groups(cfg: BingoConfig, bias_row, frac_row, deg):
    """Full sampling-space (re)build for one vertex from its bias row.

    Vectorized over C lanes; used at construction, after batched updates,
    and on (rare, Table 4) group-type transitions.  Returns
    ``(gmem (K,Cg), ginv (K,C)|None, gsize (K,), digitsum (K,), gtype (K,),
    wdec ())``.
    """
    K, C, Cg = cfg.num_radix, cfg.capacity, cfg.group_capacity
    valid = jnp.arange(C, dtype=jnp.int32) < deg
    digs = radix.digits(bias_row, K, cfg.base_log2)          # (C, K)
    digs = jnp.where(valid[:, None], digs, 0)
    member = digs != 0                                        # (C, K)
    gsize = member.sum(0, dtype=jnp.int32)                    # (K,)
    digitsum = digs.sum(0, dtype=jnp.int32)                   # (K,)
    gtype = classify(gsize, deg, cfg)                         # (K,)

    # Compact member slots into gmem rows with one masked scatter.
    pos = jnp.cumsum(member, axis=0, dtype=jnp.int32) - 1     # (C, K)
    slot = jnp.broadcast_to(
        jnp.arange(C, dtype=jnp.int32)[:, None], (C, K))
    keep = member & (pos < Cg)
    if cfg.adaptive:                                          # DENSE rows stay empty
        keep = keep & (gtype[None, :] != DENSE)
    flat_idx = jnp.where(keep, pos * K + jnp.arange(K)[None, :], K * Cg)
    gmem = jnp.full((K * Cg + 1,), -1, jnp.int32)
    gmem = gmem.at[flat_idx.reshape(-1)].set(slot.reshape(-1), mode="drop")
    gmem = gmem[: K * Cg].reshape(Cg, K).T                    # (K, Cg)

    if cfg.adaptive:
        ginv = None
    else:
        ginv = jnp.where(member, pos, -1).T.astype(jnp.int32)  # (K, C)

    # Decimal-group weight: ``lanes.lane_total``, the form the update
    # kernel takes, so the two match bit for bit.  Integer-bias states
    # keep every frac at 0.
    if cfg.fp_bias:
        wdec = lane_total(jnp.where(valid, frac_row, 0.0), jnp.roll)[0]
    else:
        wdec = jnp.zeros((), jnp.float32)
    return gmem, ginv, gsize, digitsum, gtype, wdec


def build_itable_rows(cfg: BingoConfig, digitsum, wdec) -> AliasTable:
    """Inter-group alias tables (stage-(i) sampling space) from counters."""
    w = radix.group_weights(digitsum, cfg.base_log2)          # (..., K)
    if cfg.fp_bias:
        w = jnp.concatenate([w, wdec[..., None]], axis=-1)    # decimal group
    return build_alias(w)


def empty_state(cfg: BingoConfig) -> BingoState:
    V, C, K, Cg = cfg.num_vertices, cfg.capacity, cfg.num_radix, cfg.group_capacity
    return BingoState(
        nbr=jnp.full((V, C), -1, jnp.int32),
        bias=jnp.zeros((V, C), jnp.int32),
        frac=jnp.zeros((V, C), jnp.float32),
        deg=jnp.zeros((V,), jnp.int32),
        gmem=jnp.full((V, K, Cg), -1, jnp.int32),
        ginv=None if cfg.adaptive else jnp.full((V, K, C), -1, jnp.int32),
        gsize=jnp.zeros((V, K), jnp.int32),
        digitsum=jnp.zeros((V, K), jnp.int32),
        wdec=jnp.zeros((V,), jnp.float32),
        gtype=jnp.zeros((V, K), jnp.int8),
        itable=AliasTable(
            prob=jnp.ones((V, cfg.num_inter), jnp.float32),
            alias=jnp.broadcast_to(
                jnp.arange(cfg.num_inter, dtype=jnp.int32), (V, cfg.num_inter)
            ),
        ),
    )


def _scatter_adjacency(cfg: BingoConfig, src, dst, w_int, w_frac):
    """Slot-compact adjacency tensors from an edge list (vectorized)."""
    V, C = cfg.num_vertices, cfg.capacity
    order = jnp.argsort(src, stable=True)
    s, d = src[order], dst[order]
    wi, wf = w_int[order], w_frac[order]
    # rank of each edge within its source segment
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    idx = jnp.arange(s.shape[0], dtype=jnp.int32)
    seg_start = jax.lax.cummax(jnp.where(first, idx, -1), axis=0)
    rank = idx - seg_start
    ok = rank < C
    nbr = jnp.full((V, C), -1, jnp.int32).at[s, rank].set(
        jnp.where(ok, d, -1), mode="drop")
    bias = jnp.zeros((V, C), jnp.int32).at[s, rank].set(
        jnp.where(ok, wi, 0), mode="drop")
    frac = jnp.zeros((V, C), jnp.float32).at[s, rank].set(
        jnp.where(ok, wf, 0.0), mode="drop")
    deg = jnp.zeros((V,), jnp.int32).at[s].add(ok.astype(jnp.int32), mode="drop")
    return nbr, bias, frac, deg


def _group_rows(cfg: BingoConfig, bias, frac, deg, chunk: int):
    """``build_vertex_groups`` over every row of ``(N, C)`` tables.

    Above ``chunk`` rows the build runs as a ``lax.map`` over
    ``chunk``-row tiles (the row axis zero-padded to a whole number of
    tiles), so the ``(N, C, K)`` digit intermediates never exist for more
    than one tile at a time.  Each row's output depends on that row alone,
    so the tiling never changes a bit of it.
    """
    N = deg.shape[0]

    def build(args):
        return jax.vmap(lambda b, f, d: build_vertex_groups(cfg, b, f, d)
                        )(*args)

    if N <= chunk:
        return build((bias, frac, deg))
    n = -(-N // chunk)
    pad = n * chunk - N

    def tiles(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((n, chunk) + x.shape[1:])

    outs = jax.lax.map(build, (tiles(bias), tiles(frac), tiles(deg)))
    return jax.tree.map(lambda t: t.reshape((n * chunk,) + t.shape[2:])[:N],
                        outs)


def from_edges(cfg: BingoConfig, src, dst, bias) -> BingoState:
    """Construct the full BINGO sampling space from an edge list.

    ``bias`` is int for integer mode; float for fp mode (λ-scaled per §4.3).
    Fully vectorized — no per-edge host loop; the group rows are built in
    4,096-row tiles (``_group_rows``) so a one-chip-sized graph builds
    on the chip without materializing ``(V, C, K)`` intermediates.
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    if cfg.fp_bias:
        w_int, w_frac = radix.decompose_fp(bias, cfg.lam)
    else:
        w_int = jnp.asarray(bias, jnp.int32)
        w_frac = jnp.zeros_like(src, dtype=jnp.float32)
    nbr, b, f, deg = _scatter_adjacency(cfg, src, dst, w_int, w_frac)
    gmem, ginv, gsize, digitsum, gtype, wdec = _group_rows(cfg, b, f, deg,
                                                           4096)
    itable = build_itable_rows(cfg, digitsum, wdec)
    return BingoState(nbr, b, f, deg, gmem, ginv, gsize, digitsum, wdec,
                      gtype, itable)


def refresh_vertices(state: BingoState, cfg: BingoConfig, verts,
                     chunk: int = 4096) -> BingoState:
    """Rebuild group rows + inter-group tables for a padded vertex list.

    ``verts`` entries equal to ``V`` (sentinel) are dropped.  Used by the
    batched-update path (§5.2 'rebuild' stage) and by tests.  Large
    batches rebuild in ``chunk``-row tiles (lax.map) so the (U, C, K)
    digit intermediates never materialize at 100K-update scale.
    """
    V = cfg.num_vertices
    vv = jnp.minimum(verts, V - 1)
    U = int(verts.shape[0])

    def build_rows(idx):
        return jax.vmap(
            lambda br, fr, dg: build_vertex_groups(cfg, br, fr, dg)
        )(state.bias[idx], state.frac[idx], state.deg[idx])

    if U > chunk and U % chunk == 0:
        outs = jax.lax.map(build_rows, vv.reshape(U // chunk, chunk))
        gmem, ginv, gsize, digitsum, gtype, wdec = jax.tree.map(
            lambda t: t.reshape((U,) + t.shape[2:]), outs)
    else:
        gmem, ginv, gsize, digitsum, gtype, wdec = build_rows(vv)
    itab = build_itable_rows(cfg, digitsum, wdec)
    st = state._replace(
        gmem=state.gmem.at[verts].set(gmem, mode="drop"),
        gsize=state.gsize.at[verts].set(gsize, mode="drop"),
        digitsum=state.digitsum.at[verts].set(digitsum, mode="drop"),
        wdec=state.wdec.at[verts].set(wdec, mode="drop"),
        gtype=state.gtype.at[verts].set(gtype, mode="drop"),
        itable=AliasTable(
            prob=state.itable.prob.at[verts].set(itab.prob, mode="drop"),
            alias=state.itable.alias.at[verts].set(itab.alias, mode="drop"),
        ),
    )
    if state.ginv is not None:
        st = st._replace(ginv=state.ginv.at[verts].set(ginv, mode="drop"))
    return st


def regrow_state(state: BingoState, cfg: BingoConfig,
                 cfg_next: BingoConfig, chunk: int = 4096) -> BingoState:
    """Migrate a state from capacity ``cfg.capacity`` to the larger
    ``cfg_next.capacity`` — the ladder-escalation step (DESIGN.md §14).

    The adjacency rows are slot-compact, so growth is a pure pad:
    ``nbr/bias/frac`` extend from ``(V, C)`` to ``(V, C')`` with the
    empty-slot sentinels and ``deg`` is unchanged.  Every derived table
    (``gmem/ginv/gsize/digitsum/gtype/wdec/itable``) is a pure function
    of ``(bias_row, frac_row, deg, cfg)``, so rebuilding them at
    ``cfg_next`` yields *bit-identical* output to ``from_edges`` at
    ``C'`` over the same edges listed in row order — the
    rebuild-equivalence pin (``tests/test_regrow.py``), which makes all
    future walks bit-identical by the counter PRNG's shape-independence.

    Pure jnp (jit- and GSPMD-friendly: in sharded mode the caller runs
    it per shard with shard-local configs).  Large V rebuilds in
    ``chunk``-row tiles like ``refresh_vertices`` so the ``(V, C', K)``
    digit intermediates never materialize at scale.
    """
    C, C2 = cfg.capacity, cfg_next.capacity
    if C2 <= C:
        raise ValueError(f"regrow must grow: C'={C2} <= C={C}")
    if cfg_next.num_vertices != cfg.num_vertices or (
            cfg_next.bias_bits, cfg_next.base_log2, cfg_next.adaptive,
            cfg_next.fp_bias) != (cfg.bias_bits, cfg.base_log2,
                                  cfg.adaptive, cfg.fp_bias):
        raise ValueError("regrow may only change capacity; every other "
                         "sampling-space field must match")
    pad = ((0, 0), (0, C2 - C))
    nbr = jnp.pad(state.nbr, pad, constant_values=-1)
    bias = jnp.pad(state.bias, pad, constant_values=0)
    frac = jnp.pad(state.frac, pad, constant_values=0.0)
    deg = state.deg
    gmem, ginv, gsize, digitsum, gtype, wdec = _group_rows(
        cfg_next, bias, frac, deg, chunk)
    itable = build_itable_rows(cfg_next, digitsum, wdec)
    return BingoState(nbr, bias, frac, deg, gmem, ginv, gsize, digitsum,
                      wdec, gtype, itable)
