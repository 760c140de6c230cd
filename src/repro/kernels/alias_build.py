"""Pallas kernel: batched Vose alias-table construction over K-entry rows.

This is BINGO's *update* hot spot: every insertion/deletion rebuilds the
affected vertex's K-entry inter-group alias row (paper §4.2 — the O(K)
claim).  Batched updates rebuild thousands of rows at once.

TPU adaptation: one grid step owns a (Vt, K) weight tile in VMEM and runs
Vose's small/large pairing as a K-iteration ``fori_loop`` where each
iteration retires one "small" entry *per row in parallel* (first set
lane + masked scatter across the Vt rows).  K <= 33, so the whole loop
is K VPU passes over a resident tile — no HBM traffic between steps.

VMEM budget: 5 live (Vt, K) f32/i32 tiles ≈ 20·Vt·K B; Vt=512, K=33 is
~340 KB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lanes import first_lane

__all__ = ["alias_build_pallas"]


def _kernel(w_ref, prob_ref, alias_ref):
    w = w_ref[...].astype(jnp.float32)                    # (Vt, K)
    Vt, K = w.shape
    total = w.sum(-1, keepdims=True)
    scaled = jnp.where(total > 0, w * K / jnp.maximum(total, 1e-30), 0.0)
    prob = jnp.ones((Vt, K), jnp.float32)
    alias = jnp.broadcast_to(jax.lax.broadcasted_iota(jnp.int32, (Vt, K), 1),
                             (Vt, K))
    done = jnp.zeros((Vt, K), bool)
    col = jax.lax.broadcasted_iota(jnp.int32, (Vt, K), 1)

    def body(_, carry):
        scaled, prob, alias, done = carry
        small = (~done) & (scaled < 1.0)
        large = (~done) & (scaled >= 1.0)
        do = (small.any(-1) & large.any(-1))[:, None]     # (Vt, 1)
        s = first_lane(small)                             # (Vt, 1)
        l = first_lane(large)       # both unused where ``do`` is False
        at_s = col == s
        at_l = col == l
        sval = jnp.sum(jnp.where(at_s, scaled, 0.0), -1, keepdims=True)
        prob = jnp.where(do & at_s, sval, prob)
        alias = jnp.where(do & at_s, l, alias)
        scaled = jnp.where(do & at_l, scaled + sval - 1.0, scaled)
        done = jnp.where(do & at_s, True, done)
        return scaled, prob, alias, done

    _, prob, alias, _ = jax.lax.fori_loop(
        0, K, body, (scaled, prob, alias, done))
    prob_ref[...] = prob
    alias_ref[...] = alias


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def alias_build_pallas(w, *, block_v: int = 512, interpret: bool = False):
    """(prob (V, K) f32, alias (V, K) i32) Vose tables for weight rows."""
    V, K = w.shape
    block_v = min(block_v, V)
    grid = (pl.cdiv(V, block_v),)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_v, K), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_v, K), lambda i: (i, 0)),
            pl.BlockSpec((block_v, K), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((V, K), jnp.float32),
            jax.ShapeDtypeStruct((V, K), jnp.int32),
        ],
        interpret=interpret,
    )(w)
