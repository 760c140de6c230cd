"""Radix-based bias decomposition (paper Eq. 3/4, supplement §9.2).

The paper decomposes every integer bias ``w`` into its base-``B`` digits,
``B = 2^r`` (``r = 1`` is the paper's main base-2 design).  Digit position
``k`` contributes sub-bias ``digit_k(w) * B^k`` to radix group ``p_k``:

    D(w)    = { digit_k(w) * B^k | digit_k(w) != 0 }          (Eq. 3)
    W(p_k)  = sum_i digit_k(w_i) * B^k                        (Eq. 4)

For base 2 the digit is a bit, every member of a group carries the *same*
sub-bias ``2^k`` and intra-group sampling is uniform (paper §4.1).  For
larger bases members carry digits in ``1..B-1``; we sample intra-group by
digit-proportional rejection (accept with probability ``digit/(B-1)``,
expected trips < B — still O(1)), which realizes supplement §9.2 without a
second alias hierarchy (documented in DESIGN.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "digits",
    "digit_at",
    "group_weights",
    "num_groups",
    "decompose_fp",
]


def num_groups(bias_bits: int, base_log2: int) -> int:
    """Number of radix groups K needed to cover ``bias_bits``-bit biases."""
    return -(-bias_bits // base_log2)  # ceil


def digit_at(bias, k, base_log2: int = 1):
    """Base-``2^r`` digit of ``bias`` at position ``k`` (vectorized).

    ``digit_at(w, k) != 0`` iff the edge belongs to radix group ``p_k``.
    """
    mask = (1 << base_log2) - 1
    return (bias >> (k * base_log2)) & mask


def digits(bias, num_k: int, base_log2: int = 1):
    """All ``num_k`` digits of ``bias``; output shape ``bias.shape + (num_k,)``.

    ``digits(w)[..., k] * B**k`` is the paper's sub-bias D(w) component.
    """
    ks = jnp.arange(num_k, dtype=jnp.int32)
    return digit_at(bias[..., None], ks, base_log2)


def group_weights(digitsum, base_log2: int = 1):
    """W(p_k) (Eq. 4) from per-group digit sums: ``digitsum[k] * B^k``.

    Returned as float32 — these feed the inter-group alias table.  ``B^k``
    is built from its exponent bits, so it is exact on every backend
    (``exp2`` is not, on some) for the bases/bit-widths we use
    (B^k <= 2^31).
    """
    k = jax.lax.broadcasted_iota(jnp.int32, digitsum.shape, digitsum.ndim - 1)
    scale = jax.lax.bitcast_convert_type((127 + k * base_log2) << 23,
                                         jnp.float32)
    return digitsum.astype(jnp.float32) * scale


def decompose_fp(bias_fp, lam: float):
    """Split λ-scaled floating-point biases into integer + decimal parts.

    Paper §4.3: scale by the amortization factor λ, radix-decompose the
    integer part, keep the remainder in the single decimal group.  Returns
    ``(int_part int32, frac_part float32)`` with
    ``int_part + frac_part == bias_fp * lam``.
    """
    scaled = jnp.asarray(bias_fp, jnp.float32) * jnp.float32(lam)
    int_part = jnp.floor(scaled)
    frac = scaled - int_part
    return int_part.astype(jnp.int32), frac.astype(jnp.float32)
