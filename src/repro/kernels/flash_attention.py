"""Pallas kernel: blockwise (flash) attention forward, GQA + sliding window.

LM-side hot spot for the 32k-prefill cells.  Classic streaming-softmax
tiling adapted to the TPU memory hierarchy: a (bq, D) query tile stays
VMEM-resident while (bk, D) key/value tiles stream HBM→VMEM along the
innermost (sequential) grid axis; running max/denominator/accumulator live
in VMEM scratch.  MXU-aligned tiles (bq, bk multiples of 128; D = head_dim
is 64–128 for every assigned arch).

GQA is handled in the BlockSpec index maps — query head h reads KV head
h // (H / Hkv) — so no repeated KV materialization in HBM.

NOTE (DESIGN.md §6): dry-run/roofline cells lower the jnp reference
(`ref.attention_ref`) so `cost_analysis()` sees true attention FLOPs;
this kernel is the runtime path and is validated against the reference in
interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, q_offset: int,
            block_q: int, block_k: int, num_kv_blocks: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale              # (bq, D)
    k = k_ref[0].astype(jnp.float32)                      # (bk, D)
    v = v_ref[0].astype(jnp.float32)                      # (bk, D)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
    qi = pl.program_id(1)
    qpos = (qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            + q_offset)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)            # (bq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                                # (bq, bk)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(-1, keepdims=True)
    acc_new = acc_prev * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(ki == num_kv_blocks - 1)
    def _fin():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           scale=None, block_q: int = 128,
                           block_k: int = 128, interpret: bool = False):
    """(B, H, S, D) x (B, Hkv, T, D)² -> (B, H, S, D) attention forward."""
    B, H, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    rep = H // Hkv
    scale = float(D ** -0.5) if scale is None else float(scale)
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    nq, nk = pl.cdiv(S, block_q), pl.cdiv(T, block_k)
    q_offset = T - S

    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * Hkv, T, D)
    vf = v.reshape(B * Hkv, T, D)

    def kv_map(bh, qi, ki):
        b, h = bh // H, bh % H
        return (b * Hkv + h // rep, ki, 0)

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window,
            q_offset=q_offset, block_q=block_q, block_k=block_k,
            num_kv_blocks=nk),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf)
    return out.reshape(B, H, S, D)
