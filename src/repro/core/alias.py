"""Vectorized alias tables (Walker/Vose) — the paper's stage-(i) structure.

BINGO keeps one *inter-group* alias table per vertex over its K radix groups
(+1 decimal group in fp mode).  K <= 33, so a table row fits in a vector
register; construction is a K-step masked small/large pairing, row-parallel over
vertices.  The same code builds the O(d)-entry tables of the KnightKing-style
alias *baseline* (core/baselines.py).

All functions are pure and shape-static.  ``build_alias`` runs ``n``
sequential steps of row-parallel work: on TPU each step is one VPU pass over
the row, so the wall-clock matches the textbook O(n) construction.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.lanes import first_lane

__all__ = ["AliasTable", "build_alias", "sample_alias", "alias_probs"]


class AliasTable(NamedTuple):
    prob: jax.Array   # (..., n) float32 — acceptance threshold per bucket
    alias: jax.Array  # (..., n) int32   — redirect target per bucket


def _row_total(w: jax.Array) -> jax.Array:
    """Row sum as explicit left-to-right lane adds (shape ``(..., n)``).

    ``jnp.sum``'s reduction order is implementation-defined and changes
    with the surrounding fusion context — alias rows must come out bit
    for bit the same from every program that builds them (the update
    paths, ``from_edges``, each shard of a mesh), so the order is spelled
    out.  n <= 33 (the K+1 inter-group lanes), so the unrolled chain is
    trivial.
    """
    total = w[..., 0]
    for j in range(1, w.shape[-1]):
        total = total + w[..., j]
    return total


def _div_rn(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a / b`` for float32 ``a >= 0``, normal ``b > 0`` and a finite
    quotient, rounded to nearest even from integer ops alone.

    The TPU's float division is not correctly rounded (alias entries
    taken straight from the quotient came out one ulp off the CPU's), so
    the same weights gave different alias rows on different backends.
    This long division of the 24-bit significands gives IEEE's quotient
    on every backend; results below the normal range flush to zero, as
    XLA's arithmetic does.
    """
    i32 = jnp.int32
    ai = jax.lax.bitcast_convert_type(a, i32)
    bi = jax.lax.bitcast_convert_type(b, i32)
    ea, eb = ai >> 23, (bi >> 23) & 0xFF
    ma = (ai & 0x7FFFFF) | 0x800000
    mb = (bi & 0x7FFFFF) | 0x800000
    lt = (ma < mb).astype(i32)          # put ma/mb in [1, 2)
    r = ma << lt
    e = ea - eb + 127 - lt              # biased exponent of the quotient
    q = jnp.zeros_like(ai)
    for _ in range(25):                 # 24 significand bits + round bit
        bit = (r >= mb).astype(i32)
        q = (q << 1) | bit
        r = (r - bit * mb) << 1
    up = (q & 1) & ((r != 0) | ((q >> 1) & 1)).astype(i32)
    q = (q >> 1) + up
    carry = q >> 24                     # rounding overflowed to 2^24
    q, e = q >> carry, e + carry
    out = jax.lax.bitcast_convert_type((e << 23) | (q & 0x7FFFFF),
                                       jnp.float32)
    return jnp.where((ea > 0) & (e > 0), out, 0.0)


def _build_rows(w: jax.Array) -> AliasTable:
    """Vose's algorithm on weight rows ``w`` (N, n) -> alias table rows.

    Row-parallel: each of the n steps retires the first "small" entry of
    every row against its first "large" one, with lane selects and
    one-hot updates only.  The per-row ``argmax`` + gather/scatter form
    of the same loop came out wrong on the TPU — rows stopped pairing
    early, and differently for different row counts — while this form
    gives the same bits on every backend and at every batch size.
    """
    N, n = w.shape
    total = _row_total(w)[:, None]
    scaled = jnp.where(total > 0, _div_rn(w * n, jnp.maximum(total, 1e-30)),
                       0.0)
    col = jax.lax.broadcasted_iota(jnp.int32, (N, n), 1)
    prob0 = jnp.ones((N, n), jnp.float32)
    done0 = jnp.zeros((N, n), bool)

    def body(_, carry):
        scaled, prob, alias, done = carry
        small = (~done) & (scaled < 1.0)
        large = (~done) & (scaled >= 1.0)
        do = (jnp.any(small, -1) & jnp.any(large, -1))[:, None]
        at_s = do & (col == first_lane(small))
        at_l = do & (col == first_lane(large))
        # the one selected lane plus zeros: exact in any order
        sval = jnp.sum(jnp.where(at_s, scaled, 0.0), -1, keepdims=True)
        # retire small s against large l
        prob = jnp.where(at_s, sval, prob)
        alias = jnp.where(at_s, first_lane(large), alias)
        scaled = jnp.where(at_l, scaled + (sval - 1.0), scaled)
        done = done | at_s
        return scaled, prob, alias, done

    _, prob, alias, _ = jax.lax.fori_loop(
        0, n, body, (scaled, prob0, col, done0))
    # Entries never retired as "small" (the final larges / near-1 smalls)
    # keep prob=1, alias=self — the textbook termination.  Zero-total rows
    # (empty vertices) degrade to prob=1 uniform; callers must not sample
    # from degree-0 vertices (walks.py masks them).
    return AliasTable(prob, alias)


def build_alias(w: jax.Array) -> AliasTable:
    """Build alias tables for a batch of weight rows ``(..., n)``."""
    w = jnp.asarray(w, jnp.float32)
    t = _build_rows(w.reshape((-1, w.shape[-1])))
    return AliasTable(
        t.prob.reshape(w.shape), t.alias.reshape(w.shape)
    )


def sample_alias(table: AliasTable, u0: jax.Array, u1: jax.Array) -> jax.Array:
    """O(1) alias sampling with two uniforms in [0, 1).

    ``table`` rows broadcast against the leading dims of ``u0``/``u1``.
    """
    n = table.prob.shape[-1]
    i = jnp.minimum((u0 * n).astype(jnp.int32), n - 1)
    p = jnp.take_along_axis(table.prob, i[..., None], axis=-1)[..., 0]
    a = jnp.take_along_axis(table.alias, i[..., None], axis=-1)[..., 0]
    return jnp.where(u1 < p, i, a)


def alias_probs(table: AliasTable) -> jax.Array:
    """Exact per-entry selection probabilities encoded by ``table``.

    Used by tests to assert the table reproduces ``w / sum(w)`` exactly:
    P(j) = (prob[j] + sum_i (1 - prob[i]) [alias[i] == j]) / n.
    """
    n = table.prob.shape[-1]
    overflow = 1.0 - table.prob  # mass redirected from bucket i to alias[i]
    redirected = jax.vmap(
        lambda a, o: jnp.zeros((n,), jnp.float32).at[a].add(o),
        in_axes=(0, 0),
    )(
        table.alias.reshape((-1, n)), overflow.reshape((-1, n))
    ).reshape(table.prob.shape)
    return (table.prob + redirected) / n
