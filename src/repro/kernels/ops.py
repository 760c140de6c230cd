"""Public jit'd wrappers for the Pallas kernels.

On a TPU backend the wrappers dispatch to the compiled kernels; everywhere
else (this CPU container, unit tests) they run the same kernel bodies in
interpret mode.  ``force_ref=True`` routes to the pure-jnp oracle — the
dry-run/roofline path uses it so HLO cost analysis sees real FLOPs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.alias_build import alias_build_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.radix_hist import radix_hist_pallas
from repro.kernels.update_fused import update_fused_pallas
from repro.kernels.walk_fused import walk_fused_pallas
from repro.kernels.walk_sample import (walk_sample_pallas,
                                       walk_sample_uniform_pallas)

__all__ = ["walk_sample", "walk_sample_uniform", "walk_fused",
           "walk_segment", "seed_from_key", "update_fused", "alias_build",
           "radix_hist", "flash_attention", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def radix_hist(bias, deg, *, num_k: int, force_ref: bool = False):
    if force_ref:
        return _ref.radix_hist_ref(bias, deg, num_k)
    return radix_hist_pallas(bias, deg, num_k=num_k, interpret=not on_tpu())


def alias_build(w, *, force_ref: bool = False):
    if force_ref:
        return _ref.alias_build_ref(w)
    return alias_build_pallas(w, interpret=not on_tpu())


def walk_sample(prob, alias, bias, nbr, deg, u, frac=None, *,
                base_log2: int = 1, force_ref: bool = False):
    if (base_log2 > 1 or frac is not None) and u.shape[-1] < 5:
        raise ValueError(
            f"extended sampling paths need u (B, 5); got (B, {u.shape[-1]})")
    if force_ref:
        u3 = u[:, 3] if u.shape[-1] > 3 else None
        u4 = u[:, 4] if u.shape[-1] > 4 else None
        return _ref.walk_sample_ref(prob, alias, bias, nbr, deg,
                                    u[:, 0], u[:, 1], u[:, 2], u3, u4,
                                    frac=frac, base_log2=base_log2)
    return walk_sample_pallas(prob, alias, bias, nbr, deg, u, frac,
                              base_log2=base_log2, interpret=not on_tpu())


def walk_sample_uniform(nbr, deg, u, *, force_ref: bool = False):
    """Unbiased degree pick on gathered rows — no prob/alias/bias rows."""
    if force_ref:
        return _ref.walk_sample_uniform_ref(nbr, deg, u[:, 0])
    return walk_sample_uniform_pallas(nbr, deg, u, interpret=not on_tpu())


def seed_from_key(key):
    """Derive the (1,) int32 seed of the counter-based walk PRNG from a
    JAX PRNG key.  One shared derivation so every path — megakernel,
    segment kernel, jnp oracles, the sharded relay — draws the *same*
    ``(seed, walker, t)`` uniform stream for the same key."""
    return jax.random.randint(key, (1,), 0, jnp.iinfo(jnp.int32).max,
                              dtype=jnp.int32)


def walk_fused(prob, alias, bias, nbr, deg, frac, starts, key, u=None, *,
               length: int, base_log2: int = 1, stop_prob: float = 0.0,
               uniform: bool = False, force_ref: bool = False,
               block_b: int = 256, cohorts: int = 1):
    """Whole-walk entry: one resident megakernel launch for all L steps.

    Tables are the full ``BingoState`` arrays (see
    ``kernels/walk_fused.py``).  Uniforms come from the counter-based
    ``(seed, walker, t)`` hash (``walk_fused.uniforms_at``) with the
    seed derived from ``key`` — no (L, B, 6) HBM buffer at production
    scale, the same stream on every path (compiled TPU, interpret mode,
    and the ``force_ref`` jnp oracle — where HLO cost analysis needs
    real FLOPs), and the same stream a relay-resumed segment of this
    walk would draw on another shard (DESIGN.md §10).  Pass ``u``
    (L, B, 6) to pin an explicit stream instead.  ``cohorts=K`` turns
    on the kernel's cohort interleaving (DESIGN.md §8) — output is
    bit-identical for every K, so the jnp oracle (which has no cohort
    notion) stays the ground truth and ``force_ref`` simply ignores it.
    Returns the (B, length+1) int32 path.
    """
    seed = seed_from_key(key)
    if force_ref:
        return _ref.walk_fused_ref(prob, alias, bias, nbr, deg, frac,
                                   starts, u, base_log2=base_log2,
                                   stop_prob=stop_prob, uniform=uniform,
                                   seed=seed, length=length,
                                   cohorts=cohorts)
    return walk_fused_pallas(prob, alias, bias, nbr, deg, frac, starts,
                             seed, u, length=length, base_log2=base_log2,
                             stop_prob=stop_prob, uniform=uniform,
                             block_b=block_b, cohorts=cohorts,
                             interpret=not on_tpu())


def walk_segment(prob, alias, bias, nbr, deg, frac, starts, t0, seed,
                 u=None, wid=None, *, length: int, base_log2: int = 1,
                 stop_prob: float = 0.0, uniform: bool = False,
                 force_ref: bool = False, block_b: int = 256,
                 cohorts: int = 1):
    """Resumable walk segment: the relay's per-round kernel entry.

    Same tables as ``walk_fused`` but with per-walker start steps ``t0``
    (B,) int32, free slots marked ``starts < 0``, and remote neighbors
    encoded ``-(g + 2)`` in ``nbr`` — walkers that sample one exit with
    a ``(vertex, step)`` frontier record (DESIGN.md §10).  ``seed`` is
    the raw (1,) int32 PRNG seed (``seed_from_key``), NOT a JAX key:
    the relay threads one seed through every shard and round so resumed
    walkers keep their stream.  ``wid`` (B,) int32 is the compacted
    relay's slot→wid map — the hash PRNG keys by global walker id, not
    by lane (default identity, ``arange(B)``).  Returns
    ``(path (B, length+1), frontier (B, 2), steps (tiles,))``, the
    last the steps each kernel tile ran (``walk_fused_pallas``).
    """
    if force_ref:
        return _ref.walk_segment_ref(prob, alias, bias, nbr, deg, frac,
                                     starts, t0, u, wid, length=length,
                                     base_log2=base_log2,
                                     stop_prob=stop_prob, uniform=uniform,
                                     seed=seed, cohorts=cohorts)
    return walk_fused_pallas(prob, alias, bias, nbr, deg, frac, starts,
                             seed, u, t0, wid, length=length,
                             base_log2=base_log2, stop_prob=stop_prob,
                             uniform=uniform, segment=True,
                             block_b=block_b, cohorts=cohorts,
                             interpret=not on_tpu())


def update_fused(state, cfg, is_insert, u, v, w, active=None, *,
                 block_rows: int = 8, block_dels: int = 0,
                 force_ref: bool = False):
    """Whole batched §5.2 update round: one megakernel launch.

    The oracle is ``core/updates.py:batched_update`` itself — the fused
    path must (and ``tests/test_update_fused.py`` asserts it does)
    produce a bit-identical ``BingoState`` and ``UpdateStats``.
    ``force_ref=True`` routes to it directly (dry-run/roofline cells,
    where HLO cost analysis needs real FLOPs).
    """
    if force_ref:
        from repro.core.updates import batched_update
        return batched_update(state, cfg, is_insert, u, v, w, active=active)
    return update_fused_pallas(state, cfg, is_insert, u, v, w, active,
                               block_rows=block_rows,
                               block_dels=block_dels,
                               interpret=not on_tpu())


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale=None, force_ref: bool = False):
    if force_ref:
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  scale=scale)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  scale=scale, interpret=not on_tpu())
