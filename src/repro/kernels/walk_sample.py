"""Pallas kernel: fused hierarchical BINGO sampling for a walker block.

The paper's sampling hot spot (§4.1): stage (i) alias pick over K radix
groups, stage (ii) pick inside the chosen group.  On GPU each walker is a
thread chasing pointers through the inter-group table, the intra-group
neighbor index list and the adjacency row — three dependent HBM
round-trips.

TPU adaptation (DESIGN.md §2): the per-walker rows (alias row, bias row,
neighbor row) are gathered once into VMEM, and the whole two-stage sample
happens in-register:

  stage (i)  one-hot select over the K-lane alias row (no gather unit);
  stage (ii) *exact* intra-group pick via a bit-masked lane prefix count
             over the C-lane bias row — selecting the ⌈u2·|G_k|⌉-th
             member of group k in log2(C) VPU passes.  This subsumes
             the paper's dense-group rejection AND the gmem/inverted-index
             lookup: those structures remain necessary for *updates*, but
             TPU sampling recomputes membership faster than it could
             gather it.

Beyond the base-2 integer fast path the kernel covers the full BINGO
sampling space (DESIGN.md §7):

  * radix bases > 2 (``base_log2 > 1``, supplement §9.2): the uniform
    member pick becomes a *proposal*; one digit-proportional acceptance
    coin (accept w.p. digit/(B-1)) keeps the O(1) happy path, and rejected
    walkers take an exact masked-ITS lane pass over the digit weights —
    the exact conditional of Eq. 6, so the mixture is digit-proportional
    and ``transition_probs`` equality holds with no retry loop;
  * the fp-bias decimal group (§4.3): when stage (i) lands on the decimal
    group the member pick is an exact ITS lane pass over the gathered
    ``frac`` row (mass < 1/d by construction, §4.4 — off the hot path).

Grid: walker tiles of Bt; BlockSpec stages (Bt, K) alias rows and (Bt, C)
bias/neighbor(/frac) rows.  VMEM ≈ Bt·(2K·4 + 3C·4 + 24) B; Bt=256,
C=1024, K=16 is ~3.2 MB.  All uniforms are fed as inputs so the kernel is
replayable: 3 per walker for the base-2 integer path, 5 (acceptance coin +
ITS position) for the extended paths.

This is the *per-step* kernel: one launch per walk step, rows gathered in
HBM by the caller.  Whole walks go through the persistent megakernel in
``kernels/walk_fused.py`` instead (DESIGN.md §8), which runs the L-step
loop in VMEM and reuses ``sample_rows``/``uniform_pick`` below as its
in-register sampling stage; this kernel remains the path for node2vec
proposals and the distributed per-step exchange cell.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lanes import first_lane, its_pick, lane_cumsum

__all__ = ["walk_sample_pallas", "walk_sample_uniform_pallas",
           "sample_rows", "uniform_pick"]


def sample_rows(prob, alias, bias, nbr, deg, u, frac=None, *,
                base_log2: int = 1, num_inter=None):
    """In-register two-stage BINGO sample on VMEM-resident rows.

    The shared kernel body: called on a (Bt, ·) walker tile by both the
    per-step kernel below and the whole-walk megakernel
    (``kernels/walk_fused.py``), which keeps the tile resident and feeds
    freshly DMA'd rows every step.  All arguments are *values* (already
    loaded from refs): prob/alias (Bt, Kin), bias/nbr (Bt, C) int32,
    deg (Bt, 1) int32, u (Bt, ≥3|≥5) uniforms, frac (Bt, C) float32 in
    fp mode.  ``num_inter`` is the real alias-row width when prob/alias
    arrive lane-padded (the megakernel's 128-lane meta rows); lanes past
    it are never selected.  Returns ``(nxt, slot, ok)`` each (Bt, 1);
    nxt/slot are -1 where ``ok`` is False (empty sampling space).
    """
    Bt, W = prob.shape
    Kin = W if num_inter is None else num_inter
    C = bias.shape[-1]
    has_frac = frac is not None
    u0, u1, u2 = u[:, 0:1], u[:, 1:2], u[:, 2:3]          # (Bt, 1)

    # stage (i): alias pick over the Kin-lane row, gather-free one-hot
    # selects.  Kin counts the K radix groups plus, in fp mode, the
    # decimal group appended by build_itable_rows.
    colK = jax.lax.broadcasted_iota(jnp.int32, (Bt, W), 1)
    i = jnp.minimum((u0 * Kin).astype(jnp.int32), Kin - 1)  # (Bt, 1)
    at_i = colK == i
    p_i = jnp.sum(jnp.where(at_i, prob, 0.0), -1, keepdims=True)
    a_i = jnp.sum(jnp.where(at_i, alias, 0), -1, keepdims=True)
    k = jnp.where(u1 < p_i, i, a_i)                       # (Bt, 1) group

    num_radix = Kin - 1 if has_frac else Kin
    kc = jnp.minimum(k, num_radix - 1)
    is_dec = (k == num_radix) if has_frac else None

    # stage (ii): digit row of the chosen radix group, recomputed in-register
    colC = jax.lax.broadcasted_iota(jnp.int32, (Bt, C), 1)
    valid = colC < deg
    dmask = (1 << base_log2) - 1
    dig = jnp.where(valid, (bias >> (kc * base_log2)) & dmask, 0)  # (Bt, C)
    member = dig != 0
    mi = member.astype(jnp.int32)
    gsize = mi.sum(-1, keepdims=True)

    # uniform member pick via masked lane prefix count (exact for base 2
    # — every member carries the same sub-bias 2^k, Eq. 6).  No member
    # is hit only when the group is empty, where ``ok`` masks the slot.
    target = jnp.minimum((u2 * gsize).astype(jnp.int32), gsize - 1) + 1
    cum = lane_cumsum(mi, pltpu.roll)
    slot = first_lane(member & (cum == target))                 # (Bt, 1)

    if base_log2 > 1:
        # digit-proportional acceptance (§9.2): the uniform pick is only a
        # proposal; accept w.p. digit/(B-1), else take the exact masked
        # ITS over the digit weights — the mixture is exactly Eq. 6.
        u3, u4 = u[:, 3:4], u[:, 4:5]
        dig_c = jnp.sum(jnp.where(colC == slot, dig, 0), -1, keepdims=True)
        accept = u3 * jnp.float32((1 << base_log2) - 1) < dig_c.astype(
            jnp.float32)
        slot_its = its_pick(dig.astype(jnp.float32), u4, pltpu.roll)
        slot = jnp.where(accept, slot, slot_its)
    ok = gsize > 0

    if has_frac:
        # decimal group (§4.3): exact ITS over the gathered frac row
        u4 = u[:, 4:5]
        wf = jnp.where(valid, frac, 0.0)
        slot_dec = its_pick(wf, u4, pltpu.roll)
        slot = jnp.where(is_dec, slot_dec, slot)
        # bool logic, not a select on bools (Mosaic cannot lower that)
        ok = (is_dec & (wf.sum(-1, keepdims=True) > 0)) | (~is_dec & ok)

    nxt = jnp.sum(jnp.where(colC == slot, nbr, 0), -1, keepdims=True)
    return (jnp.where(ok, nxt, -1), jnp.where(ok, slot, -1), ok)


def uniform_pick(nbr, deg, u2):
    """Degree-based unbiased pick: slot = ⌊u2·deg⌋ in one lane compare.

    ``nbr`` (Bt, C) int32, ``deg`` (Bt, 1) int32, ``u2`` (Bt, 1) in
    [0, 1).  No bias/alias rows at all — the ``simple`` walk kind and
    degree-normalized baselines sample straight off the adjacency row.
    Returns ``(nxt, slot, ok)`` each (Bt, 1); -1 where deg == 0.
    """
    Bt, C = nbr.shape
    colC = jax.lax.broadcasted_iota(jnp.int32, (Bt, C), 1)
    slot = jnp.minimum((u2 * deg.astype(jnp.float32)).astype(jnp.int32),
                       deg - 1)
    nxt = jnp.sum(jnp.where(colC == slot, nbr, 0), -1, keepdims=True)
    ok = deg > 0
    return (jnp.where(ok, nxt, -1), jnp.where(ok, slot, -1), ok)


def _kernel(base_log2, has_frac, prob_ref, alias_ref, bias_ref, nbr_ref,
            deg_ref, u_ref, *rest):
    if has_frac:
        frac_ref, nxt_ref, slot_ref = rest
        frac = frac_ref[...]
    else:
        nxt_ref, slot_ref = rest
        frac = None
    nxt, slot, _ = sample_rows(prob_ref[...], alias_ref[...], bias_ref[...],
                               nbr_ref[...], deg_ref[...], u_ref[...], frac,
                               base_log2=base_log2)
    slot_ref[...] = slot
    nxt_ref[...] = nxt


def _uniform_kernel(nbr_ref, deg_ref, u_ref, nxt_ref, slot_ref):
    nxt, slot, _ = uniform_pick(nbr_ref[...], deg_ref[...], u_ref[:, 0:1])
    slot_ref[...] = slot
    nxt_ref[...] = nxt


@functools.partial(jax.jit,
                   static_argnames=("base_log2", "block_b", "interpret"))
def walk_sample_pallas(prob, alias, bias, nbr, deg, u, frac=None, *,
                       base_log2: int = 1, block_b: int = 256,
                       interpret: bool = False):
    """Fused BINGO step on gathered rows.

    prob/alias (B, Kin) f32/i32 — Kin = K radix groups (+1 decimal group in
    fp mode, in which case ``frac`` (B, C) f32 must be passed);
    bias/nbr (B, C) i32; deg (B,) i32; u (B, 3) uniforms for the base-2
    integer path, (B, 5) when ``base_log2 > 1`` or ``frac`` is given
    (cols: alias bucket, alias coin, member pick, acceptance coin, ITS
    position).  Returns (nxt (B,) i32, slot (B,) i32); -1 on empty rows.
    """
    B, Kin = prob.shape
    C = bias.shape[-1]
    NU = u.shape[-1]
    has_frac = frac is not None
    if (base_log2 > 1 or has_frac) and NU < 5:
        raise ValueError(
            f"extended sampling paths need u (B, 5); got (B, {NU})")
    block_b = min(block_b, B)
    grid = (pl.cdiv(B, block_b),)
    in_specs = [
        pl.BlockSpec((block_b, Kin), lambda i: (i, 0)),
        pl.BlockSpec((block_b, Kin), lambda i: (i, 0)),
        pl.BlockSpec((block_b, C), lambda i: (i, 0)),
        pl.BlockSpec((block_b, C), lambda i: (i, 0)),
        pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        pl.BlockSpec((block_b, NU), lambda i: (i, 0)),
    ]
    args = [prob, alias, bias, nbr, deg[:, None], u]
    if has_frac:
        in_specs.append(pl.BlockSpec((block_b, C), lambda i: (i, 0)))
        args.append(frac)
    nxt, slot = pl.pallas_call(
        functools.partial(_kernel, base_log2, has_frac),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        interpret=interpret,
    )(*args)
    return nxt[:, 0], slot[:, 0]


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def walk_sample_uniform_pallas(nbr, deg, u, *, block_b: int = 256,
                               interpret: bool = False):
    """Fused unbiased neighbor pick on gathered adjacency rows.

    ``nbr`` (B, C) int32, ``deg`` (B,) int32, ``u`` (B, 1) uniforms.
    The degree-based pick needs no prob/alias/bias rows — stage (i) and
    the membership cumsum collapse to one lane compare against ``deg``
    (``uniform_pick``), so the ``simple`` walk kind skips 3 of the 5
    row gathers entirely.  Returns (nxt (B,) i32, slot (B,) i32).
    """
    B, C = nbr.shape
    block_b = min(block_b, B)
    grid = (pl.cdiv(B, block_b),)
    nxt, slot = pl.pallas_call(
        _uniform_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, C), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        interpret=interpret,
    )(nbr, deg[:, None], u[:, :1])
    return nxt[:, 0], slot[:, 0]
