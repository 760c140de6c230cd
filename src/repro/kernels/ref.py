"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.lanes import its_pick

__all__ = ["walk_sample_ref", "walk_sample_uniform_ref", "walk_fused_ref",
           "walk_segment_ref", "hash_uniforms_ref",
           "alias_build_ref", "radix_hist_ref", "attention_ref"]


def radix_hist_ref(bias, deg, num_k: int):
    """Eq. 4 counters: (digitsum (V, K) int32, gsize (V, K) int32).

    ``bias`` (V, C) int32, ``deg`` (V,) int32. Base-2 digits only (the
    production radix; §9.2 bases are handled by the pure-JAX path).
    """
    C = bias.shape[-1]
    valid = jnp.arange(C, dtype=jnp.int32)[None, :] < deg[:, None]
    ks = jnp.arange(num_k, dtype=jnp.int32)
    digs = (bias[..., None] >> ks) & 1                    # (V, C, K)
    digs = jnp.where(valid[..., None], digs, 0)
    return (digs.sum(1, dtype=jnp.int32), (digs != 0).sum(1, dtype=jnp.int32))


def alias_build_ref(w):
    """Vose tables for weight rows ``(V, n)`` -> (prob, alias)."""
    from repro.core.alias import build_alias
    t = build_alias(w)
    return t.prob, t.alias


def _its_pick_ref(w, x01):
    """Exact ITS lane pass — the kernel's ``lanes.its_pick`` with
    ``jnp.roll`` for the rotation, so float prefixes add in the same
    order as in the kernel (row form: ``x01`` (B,), returns (B,))."""
    return its_pick(w, x01[:, None], jnp.roll)[:, 0]


def walk_sample_ref(prob, alias, bias, nbr, deg, u0, u1, u2,
                    u3=None, u4=None, *, frac=None, base_log2: int = 1):
    """Exact fused BINGO step for gathered per-walker rows.

    Inputs (B = walkers, Kin = radix groups (+1 decimal in fp mode),
    C = capacity):
      prob/alias (B, Kin) — inter-group alias rows (stage (i));
      bias (B, C) int32, nbr (B, C) int32, deg (B,) int32 — adjacency rows;
      u0, u1, u2 (B,) — uniforms (alias bucket, alias coin, intra pick);
      u3, u4 (B,) — acceptance coin + ITS position, required when
      ``base_log2 > 1`` or ``frac`` (B, C) float32 is given (fp mode).
    Returns (nxt (B,) int32, slot (B,) int32); -1 for empty rows.

    Stage (ii) is the TPU-native *exact* intra-group pick: a masked cumsum
    over the C lanes selects the ⌈u2·|G_k|⌉-th member — one VPU pass, no
    gmem/inverted-index gather (DESIGN.md §2: those structures exist for
    *updates*; sampling recomputes membership in-register).  Bases > 2 add
    one digit-proportional acceptance coin with an exact masked-ITS
    fallback; the decimal group runs an ITS pass over ``frac``
    (DESIGN.md §7).
    """
    B, Kin = prob.shape
    C = bias.shape[-1]
    has_frac = frac is not None
    n = Kin
    i = jnp.minimum((u0 * n).astype(jnp.int32), n - 1)
    p = jnp.take_along_axis(prob, i[:, None], axis=-1)[:, 0]
    a = jnp.take_along_axis(alias, i[:, None], axis=-1)[:, 0]
    k = jnp.where(u1 < p, i, a)                            # (B,) group

    num_radix = Kin - 1 if has_frac else Kin
    kc = jnp.minimum(k, num_radix - 1)
    valid = jnp.arange(C, dtype=jnp.int32)[None, :] < deg[:, None]
    dmask = (1 << base_log2) - 1
    dig = jnp.where(valid,
                    (bias >> (kc[:, None] * base_log2)) & dmask, 0)
    member = dig != 0                                      # (B, C)
    gsize = member.sum(-1, dtype=jnp.int32)
    target = jnp.minimum((u2 * gsize).astype(jnp.int32), gsize - 1) + 1
    cum = jnp.cumsum(member, axis=-1, dtype=jnp.int32)
    hit = member & (cum == target[:, None])
    slot = jnp.argmax(hit, axis=-1).astype(jnp.int32)

    if base_log2 > 1:
        dig_c = jnp.take_along_axis(dig, slot[:, None], axis=-1)[:, 0]
        accept = u3 * jnp.float32(dmask) < dig_c.astype(jnp.float32)
        slot_its = _its_pick_ref(dig.astype(jnp.float32), u4)
        slot = jnp.where(accept, slot, slot_its)
    ok = gsize > 0

    if has_frac:
        is_dec = k == num_radix
        wf = jnp.where(valid, frac, 0.0)
        slot_dec = _its_pick_ref(wf, u4)
        slot = jnp.where(is_dec, slot_dec, slot)
        ok = jnp.where(is_dec, wf.sum(-1) > 0, ok)

    slot = jnp.where(ok, slot, -1)
    nxt = jnp.where(ok, jnp.take_along_axis(
        nbr, jnp.maximum(slot, 0)[:, None], axis=-1)[:, 0], -1)
    return nxt, slot


def walk_sample_uniform_ref(nbr, deg, u0):
    """Degree-based unbiased pick: slot = ⌊u0·deg⌋ (mirrors
    walk_sample.py:uniform_pick).  ``nbr`` (B, C) int32, ``deg`` (B,)
    int32, ``u0`` (B,) uniforms.  Returns (nxt, slot); -1 where deg == 0.
    """
    slot = jnp.minimum((u0 * deg.astype(jnp.float32)).astype(jnp.int32),
                       deg - 1)
    ok = deg > 0
    nxt = jnp.take_along_axis(nbr, jnp.maximum(slot, 0)[:, None],
                              axis=-1)[:, 0]
    return jnp.where(ok, nxt, -1), jnp.where(ok, slot, -1)


def hash_uniforms_ref(seed, length: int, B: int, wid=None):
    """Materialized (L, B, 6) counter-based uniforms — the exact stream
    the megakernel draws on the fly (``walk_fused.uniforms_at``), for
    oracles that scan over fed arrays.  ``wid`` (B,) int32 overrides the
    walker-id column (the compacted relay's slot→wid map); the default
    is the batch row — the whole-walk identity layout."""
    from repro.kernels.walk_fused import uniforms_at
    if wid is None:
        wid = jnp.arange(B, dtype=jnp.int32)
    ts = jnp.arange(length, dtype=jnp.int32)[:, None, None]
    return uniforms_at(seed[0] if seed.ndim else seed,
                       wid.astype(jnp.int32)[None, :, None], ts)


def walk_fused_ref(prob, alias, bias, nbr, deg, frac, starts, u=None, *,
                   base_log2: int = 1, stop_prob: float = 0.0,
                   uniform: bool = False, seed=None, length=None,
                   cohorts: int = 1):
    """Whole-walk oracle: the L-step scan under fed (or hashed) uniforms.

    ``cohorts`` is accepted (so ``ops.walk_fused(force_ref=True)`` takes
    the same signature) and ignored: the oracle has no DMA pipeline, and
    the kernel's output is provably K-invariant — the counter PRNG keys
    by (seed, wid, t), never by cohort/slot — so this single scan is
    the ground truth for every K.

    The pure-jnp ground truth for ``kernels/walk_fused.py`` — same
    (L, B, 6) uniform columns (alias bucket, alias coin, member pick,
    acceptance coin, ITS position, PPR stop coin), same per-step alive
    semantics as ``core/walks.py:scan_walk``, with each step's sample
    drawn by ``walk_sample_ref`` (or the degree pick for
    ``uniform=True``) on rows gathered in HBM.  When ``u`` is None the
    uniforms are the counter-based ``(seed, walker, t)`` hash stream
    (``hash_uniforms_ref``) — bit-identical to what the megakernel
    draws in hash mode, so kernel == oracle holds on both PRNG paths.
    Also the roofline/cost-analysis stand-in
    (``ops.walk_fused(force_ref=True)``) since Pallas bodies are opaque
    to HLO cost analysis.  Returns the (B, L+1) int32 path.
    """
    B = starts.shape[0]
    if u is None:
        u = hash_uniforms_ref(seed, length, B)
    if u.shape[-1] < 6:
        raise ValueError(
            f"fed uniforms must be (L, B, 6); got {u.shape}")
    V = nbr.shape[0]

    def step(carry, ut):
        cur, alive = carry
        safe = jnp.clip(cur, 0, V - 1)
        d = deg[safe]
        if uniform:
            nxt, _ = walk_sample_uniform_ref(nbr[safe], d, ut[:, 2])
        else:
            fr = frac[safe] if frac is not None else None
            nxt, _ = walk_sample_ref(prob[safe], alias[safe], bias[safe],
                                     nbr[safe], d, ut[:, 0], ut[:, 1],
                                     ut[:, 2], ut[:, 3], ut[:, 4],
                                     frac=fr, base_log2=base_log2)
        alive = alive & (d > 0)
        if stop_prob > 0.0:
            alive = alive & (ut[:, 5] >= jnp.float32(stop_prob))
        out = jnp.where(alive, nxt, -1)
        new_alive = alive & (nxt >= 0)
        return (jnp.where(new_alive, nxt, cur), new_alive), out

    (_, _), path = jax.lax.scan(
        step, (starts, jnp.ones((B,), bool)), u)
    return jnp.concatenate([starts[:, None], jnp.swapaxes(path, 0, 1)],
                           axis=1)


def walk_segment_ref(prob, alias, bias, nbr, deg, frac, starts, t0,
                     u=None, wid=None, *, length: int, base_log2: int = 1,
                     stop_prob: float = 0.0, uniform: bool = False,
                     seed=None, cohorts: int = 1):
    """Resumable-segment oracle (DESIGN.md §10): windowed L-step scan.

    ``cohorts`` is accepted and ignored, exactly as in
    ``walk_fused_ref`` — one scan pins all K.

    The pure-jnp ground truth for the megakernel's ``segment=True``
    entry.  Per walker: idle until step ``t0`` (start vertex written at
    path column ``t0``, earlier columns -1), walk with the exact
    ``walk_sample_ref`` step until the walk ends or a *remote* neighbor
    (adjacency value ``-(g + 2)``) is sampled — the walker then exits
    with a ``(g, step)`` frontier record.  ``starts < 0`` marks free
    slots.  Uniforms per step t come from ``u[t]`` when fed, else from
    the counter-based ``(seed, wid[b], t)`` hash, where ``wid`` is the
    compacted relay's slot→wid map (default: the batch row) — identical
    columns and semantics to the kernel, bit-exact in both modes.
    Returns ``(path (B, L+1), frontier (B, 2), steps (1,))``: the scan
    is one tile that runs all L steps, where the kernel's tiles report
    the steps of their own windows.
    """
    B = starts.shape[0]
    L = length
    if u is None:
        u = hash_uniforms_ref(seed, L, B, wid)
    if u.shape[-1] < 6:
        raise ValueError(
            f"fed uniforms must be (L, B, 6); got {u.shape}")
    V = nbr.shape[0]
    occupied = (starts >= 0) & (t0 <= L)
    alive0 = occupied & (t0 == 0)

    def step(carry, xs):
        t, ut = xs
        cur, alive, fv, ft = carry
        safe = jnp.clip(cur, 0, V - 1)
        d = deg[safe]
        if uniform:
            nxt, _ = walk_sample_uniform_ref(nbr[safe], d, ut[:, 2])
        else:
            fr = frac[safe] if frac is not None else None
            nxt, _ = walk_sample_ref(prob[safe], alias[safe], bias[safe],
                                     nbr[safe], d, ut[:, 0], ut[:, 1],
                                     ut[:, 2], ut[:, 3], ut[:, 4],
                                     frac=fr, base_log2=base_log2)
        alive = alive & (d > 0)
        if stop_prob > 0.0:
            alive = alive & (ut[:, 5] >= jnp.float32(stop_prob))
        emit = alive & (nxt >= 0)
        remote = alive & (nxt <= -2)
        out = jnp.where((t0 <= t) & emit, nxt, -1)
        fv = jnp.where(remote, -nxt - 2, fv)
        ft = jnp.where(remote, t + 1, ft)
        new_alive = emit
        activate = occupied & (t0 == t + 1) & (t + 1 < L)
        cur2 = jnp.where(new_alive, nxt, cur)
        cur2 = jnp.where(activate, starts, cur2)
        return (cur2, new_alive | activate, fv, ft), out

    init = (jnp.maximum(starts, 0), alive0,
            jnp.full((B,), -1, jnp.int32), jnp.full((B,), -1, jnp.int32))
    (_, _, fv, ft), cols = jax.lax.scan(
        step, init, (jnp.arange(L, dtype=jnp.int32), u))
    path = jnp.concatenate([jnp.full((B, 1), -1, jnp.int32),
                            jnp.swapaxes(cols, 0, 1)], axis=1)
    colL = jnp.arange(L + 1, dtype=jnp.int32)[None, :]
    path = jnp.where((colL == t0[:, None]) & occupied[:, None],
                     starts[:, None], path)
    return (path, jnp.stack([fv, ft], axis=-1),
            jnp.full((1,), L, jnp.int32))


def attention_ref(q, k, v, *, causal=True, window=0, scale=None,
                  q_offset=None):
    """Reference attention: (B, H, S, D) x (B, Hkv, T, D) -> (B, H, S, D).

    GQA-aware *without* materializing repeated KV (grouped einsum);
    optional sliding window (0 = off).  ``q_offset = T - S`` aligns
    causality for decode (S=1, T=cache).
    """
    B, H, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    rep = H // Hkv
    scale = (D ** -0.5) if scale is None else scale
    qg = (q * scale).reshape(B, Hkv, rep, S, D)
    logits = jnp.einsum("bkrsd,bktd->bkrst", qg, k,
                        preferred_element_type=jnp.float32)
    off = (T - S) if q_offset is None else q_offset
    qpos = jnp.arange(S)[:, None] + off
    kpos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkrst,bktd->bkrsd", p, v)
    return out.reshape(B, H, S, D).astype(q.dtype)


def attention_ref_chunked(q, k, v, *, causal=True, window=0, scale=None,
                          q_chunk=1024):
    """Query-chunked attention for long prefill: scans over q blocks so at
    most a (B, H, q_chunk, T) logits tile is live — the jnp stand-in for
    the Pallas flash kernel's memory profile (its FLOPs live in a scan
    body; specs.attn_flops_correction re-multiplies them for §Roofline).
    """
    B, H, S, D = q.shape
    qc = min(q_chunk, S)
    if S % qc:
        return attention_ref(q, k, v, causal=causal, window=window,
                             scale=scale)
    n = S // qc
    qs = q.reshape(B, H, n, qc, D).transpose(2, 0, 1, 3, 4)

    def chunk(i, qi):
        return attention_ref(qi, k, v, causal=causal, window=window,
                             scale=scale, q_offset=i * qc)

    outs = jax.lax.map(lambda iq: chunk(iq[0], iq[1]),
                       (jnp.arange(n), qs))
    return outs.transpose(1, 2, 0, 3, 4).reshape(B, H, S, D)
