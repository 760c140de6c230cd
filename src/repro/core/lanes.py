"""Lane primitives that the TPU kernel compiler lowers.

Mosaic has no lowering for ``cumsum`` or ``argmax``, so the kernels build
them from ops it does lower: a log-step (Hillis–Steele) prefix sum made
of lane rotations, selects and adds, and a first-set-lane search made of
a select and a lane ``min``.  The helpers take the rotation as an
argument — ``pltpu.roll`` inside a kernel, ``jnp.roll`` in the jnp
oracles (``kernels/ref.py``, ``core/dyngraph.py``) — so a kernel and its
oracle add floats in the same order and agree bit for bit.  Integer
prefixes are exact in any order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["lane_cumsum", "lane_total", "first_lane", "its_pick"]


def lane_cumsum(x, roll):
    """Inclusive prefix sum along the last axis in ⌈log2 n⌉ doubling steps.

    Step ``s`` adds the value ``s`` lanes to the left (zero for the first
    ``s`` lanes); ``roll(x, s, axis)`` must rotate toward higher lanes,
    as ``jnp.roll`` does.
    """
    n = x.shape[-1]
    ax = x.ndim - 1
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, ax)
    zero = jnp.zeros((), x.dtype)
    s = 1
    while s < n:
        x = x + jnp.where(col >= s, roll(x, s, ax), zero)
        s *= 2
    return x


def lane_total(x, roll):
    """(…, 1) total of ``x`` along the last axis: the largest lane of its
    log-step prefix sum.

    The float prefix is not monotone — the last lane and an earlier one
    add the same values in different orders and can differ by an ulp —
    so every kernel and oracle that needs a lane total takes this one
    form, and they agree bit for bit.
    """
    return jnp.max(lane_cumsum(x, roll), axis=-1, keepdims=True)


def first_lane(mask):
    """(…, 1) index of the first set lane of ``mask``; n where none is."""
    n = mask.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, mask.shape, mask.ndim - 1)
    return jnp.min(jnp.where(mask, col, n), axis=-1, keepdims=True)


def its_pick(w, x01, roll):
    """Exact ITS lane pass: first lane i with prefix(w)[i] > x01·Σw.

    ``w`` (B, C) float32 non-negative, ``x01`` (B, 1) in [0, 1).  The
    total is ``lane_total``'s: the largest prefix, which can exceed the
    last lane by an ulp; the pick is clamped to the last lane.
    """
    c = lane_cumsum(w, roll)
    x = x01 * jnp.max(c, axis=-1, keepdims=True)
    idx = jnp.sum((c <= x).astype(jnp.int32), axis=-1, keepdims=True)
    return jnp.minimum(idx, w.shape[-1] - 1)
