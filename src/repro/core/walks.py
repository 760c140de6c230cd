"""Random-walk applications on top of the BINGO sampler (paper §2.2/§6).

The paper's four application kernels map to one scanned walker step with
per-application policies:

  * ``deepwalk``  — first-order biased walk, fixed length (default 80);
  * ``node2vec``  — second-order walk; we adopt the paper's own choice
    (§7.3): KnightKing-style static proposal from BINGO + rejection with
    the history factor f(w, v) of Eq. 1, with an *exact* second-order ITS
    fallback after a bounded number of trials (distribution unchanged);
  * ``ppr``       — geometric termination with probability 1/80 per step;
  * ``simple``    — unbiased neighbor pick (sanity/reference).

Walkers that terminate (or sit on degree-0 vertices) emit -1 and hold.
All functions are jittable; ``state``/``cfg`` are closed over per-engine.

Backend selection (DESIGN.md §7): every sample is drawn through the
``SamplerBackend`` named by ``cfg.backend`` — ``"reference"`` (pure-jnp
hierarchical sampler), ``"pallas"`` (fused kernels), or ``"auto"``
(pallas on TPU, reference elsewhere; the default).  deepwalk/ppr/simple
dispatch *whole-walk* (DESIGN.md §8): ``random_walk`` hands the entire
L-step batch to ``bk.sample_walk`` — on the pallas backend that is ONE
persistent megakernel launch (``kernels/walk_fused.py``) with walker
state resident in VMEM and per-step row DMAs double-buffered, instead of
L ``lax.scan`` iterations each paying a kernel launch plus five
HBM-materialized (B, C)/(B, K) gathers.  node2vec stays on the per-step
``scan_walk`` path: it draws KnightKing-style *proposals* through the
backend while the history-factor rejection and the exact second-order
ITS fallback stay in jnp (they need the previous-hop rows, which no
gathered-row kernel sees).  The pallas kernels fall back to an in-kernel
exact masked-ITS lane pass whenever the O(1) happy path cannot realize
Eq. 2 alone — the decimal group in fp mode, and rejected
digit-acceptance proposals for radix bases > 2 — so the sampled
distribution is identical across backends in every mode.  Pass
``backend=`` (and/or ``whole_walk=False``) explicitly to override
``cfg.backend`` for one call (benchmarks comparing the paths do this).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.backend import get_backend
from repro.core.dyngraph import BingoConfig, BingoState
from repro.core.sampler import _its_rows

__all__ = ["WalkParams", "random_walk", "scan_walk", "deepwalk",
           "node2vec", "ppr", "make_walker"]

_N2V_TRIALS = 16


class WalkParams(NamedTuple):
    kind: str = "deepwalk"     # deepwalk | node2vec | ppr | simple
    length: int = 80
    p: float = 0.5             # node2vec return parameter
    q: float = 2.0             # node2vec in-out parameter
    stop_prob: float = 0.0     # ppr termination probability per step


def _is_neighbor(state: BingoState, cfg: BingoConfig, src, cand):
    """Vectorized membership test cand ∈ N(src) — one masked row compare.

    (On GPU the paper inherits KnightKing's per-thread binary search; on TPU
    the padded row compare is a single VPU pass — DESIGN.md §2.)
    """
    row = state.nbr[src]                                   # (B, C)
    valid = (jnp.arange(cfg.capacity, dtype=jnp.int32)[None, :]
             < state.deg[src][:, None])
    return jnp.any((row == cand[:, None]) & valid, axis=-1)


def _row_neighbors(state: BingoState, cfg: BingoConfig, src, cands):
    """``cands[b, j] ∈ N(src[b])`` for (B, C) candidate rows at once.

    A binary search in ``src``'s sorted live row — O(B·C·log C), where
    ``_is_neighbor`` per candidate column would build a (B, C, C)
    compare.  Dead slots sort as INT32_MIN, which no candidate equals.
    """
    C = cfg.capacity
    row = jnp.sort(jnp.where(
        jnp.arange(C, dtype=jnp.int32)[None, :] < state.deg[src][:, None],
        state.nbr[src], jnp.iinfo(jnp.int32).min), axis=-1)
    at = jax.vmap(jnp.searchsorted)(row, cands)
    return jnp.take_along_axis(row, jnp.minimum(at, C - 1), axis=-1) == cands


def _n2v_factor(state, cfg, prev, cand, p, q):
    dist0 = cand == prev
    dist1 = _is_neighbor(state, cfg, prev, cand)
    return jnp.where(dist0, 1.0 / p, jnp.where(dist1, 1.0, 1.0 / q))


def _n2v_accept(state, cfg, prev, cur, has_prev, key, params, bk=None):
    """Second-order step: backend proposal + history-factor rejection.

    Proposals come from ``bk.sample_step`` (so the pallas backend fuses
    them too); the Eq. 1 factor test and the exact second-order ITS
    fallback are first-class jnp — they read the *previous* vertex's row.
    """
    if bk is None:
        bk = get_backend(cfg.backend)
    B = cur.shape[0]
    fmax = max(1.0 / params.p, 1.0, 1.0 / params.q)

    def cond(c):
        key, nxt, ok, t = c
        return jnp.any(~ok) & (t < _N2V_TRIALS)

    def body(c):
        key, nxt, ok, t = c
        key, k1, k2 = jax.random.split(key, 3)
        cand, _ = bk.sample_step(state, cfg, cur, k1)
        f = _n2v_factor(state, cfg, prev, cand, params.p, params.q)
        f = jnp.where(has_prev, f, 1.0)  # first hop is first-order
        accept = jax.random.uniform(k2, (B,)) * fmax < f
        nxt = jnp.where(~ok & accept, cand, nxt)
        return key, nxt, ok | accept, t + 1

    key, loop_key, fb_key = jax.random.split(key, 3)
    _, nxt, ok, _ = jax.lax.while_loop(
        cond, body, (loop_key, jnp.zeros((B,), jnp.int32),
                     jnp.zeros((B,), bool), jnp.int32(0)))

    def exact_fallback(key):
        # Exact second-order ITS over the full row: w_j * f(prev, v_j).
        valid = (jnp.arange(cfg.capacity, dtype=jnp.int32)[None, :]
                 < state.deg[cur][:, None])
        w = state.bias[cur].astype(jnp.float32) + state.frac[cur]
        nbrs = state.nbr[cur]                               # (B, C)
        d0 = nbrs == prev[:, None]
        d1 = _row_neighbors(state, cfg, prev, nbrs)
        f = jnp.where(d0, 1.0 / params.p, jnp.where(d1, 1.0, 1.0 / params.q))
        f = jnp.where(has_prev[:, None], f, 1.0)
        w = jnp.where(valid, w * f, 0.0)
        slot = _its_rows(w, jax.random.uniform(key, (B,)))
        return jnp.take_along_axis(nbrs, slot[:, None], axis=-1)[:, 0]

    nxt_fb = jax.lax.cond(jnp.any(~ok), exact_fallback,
                          lambda _: jnp.zeros((B,), jnp.int32), fb_key)
    return jnp.where(ok, nxt, nxt_fb)


def scan_walk(bk, state: BingoState, cfg: BingoConfig, starts, key,
              params: WalkParams):
    """Per-step walk: one ``lax.scan`` drawing through ``bk`` each step.

    The reference whole-walk implementation (every step gathers rows,
    launches one backend sample, and round-trips walker state through
    XLA) and the only path for node2vec.  Production deepwalk/ppr/simple
    normally go whole-walk instead — ``random_walk`` dispatches to
    ``bk.sample_walk`` (the pallas megakernel, DESIGN.md §8) when the
    backend has it; benchmarks call ``scan_walk`` directly to measure
    the per-step path side by side.
    """
    B = starts.shape[0]
    alive0 = state.deg[starts] > 0

    def step(carry, key):
        cur, prev, has_prev, alive = carry
        k1, k2 = jax.random.split(key)
        safe = jnp.maximum(cur, 0)
        if params.kind == "node2vec":
            nxt = _n2v_accept(state, cfg, prev, safe, has_prev, k1, params,
                              bk)
        elif params.kind == "simple":
            nxt, _ = bk.sample_uniform(state, cfg, safe, k1)
        else:
            nxt, _ = bk.sample_step(state, cfg, safe, k1)
        if params.kind == "ppr" and params.stop_prob > 0:
            alive = alive & (jax.random.uniform(k2, (B,)) >= params.stop_prob)
        alive = alive & (state.deg[safe] > 0)
        out = jnp.where(alive, nxt, -1)
        nxt_alive = alive & (nxt >= 0) & (state.deg[jnp.maximum(nxt, 0)] > 0)
        return (jnp.where(alive, nxt, cur), jnp.where(alive, safe, prev),
                has_prev | alive, nxt_alive), out

    keys = jax.random.split(key, params.length)
    (_, _, _, _), path = jax.lax.scan(
        step, (starts, starts, jnp.zeros((B,), bool), alive0), keys)
    return jnp.concatenate(
        [starts[:, None], jnp.swapaxes(path, 0, 1)], axis=1)


def random_walk(state: BingoState, cfg: BingoConfig, starts, key,
                params: WalkParams, backend: Optional[str] = None,
                whole_walk: Optional[bool] = None, uniforms=None):
    """Run a batch of walks; returns ``(B, length + 1)`` int32 paths.

    Column 0 holds the start vertices; terminated walkers pad with -1.
    Samples are drawn through the ``SamplerBackend`` named by
    ``backend`` (default: ``cfg.backend``) — see the module docstring
    for how each walk kind maps onto the backend interface.

    Dispatch: deepwalk/ppr/simple run *whole-walk* through
    ``bk.sample_walk`` when the backend defines it — on the pallas
    backend that is one persistent megakernel launch for all L steps
    (``kernels/walk_fused.py``, DESIGN.md §8) instead of L per-step
    launches.  node2vec always takes the per-step ``scan_walk`` path
    (its Eq. 1 rejection needs the previous hop's rows).  Force with
    ``whole_walk=True`` (raises if the backend can't) or pin the
    per-step path with ``whole_walk=False`` (benchmark comparisons).

    ``uniforms`` (L, B, 6) float32 pins the exact per-(walker, step)
    uniform stream (DESIGN.md §10): both builtin backends then draw
    identical samples — on *any* sharding, which is how the relay tests
    assert a sharded ``walk_relay`` bit-equals this single-shard call.
    Only the whole-walk kinds accept it (the per-step scan and node2vec
    draw through JAX keys).
    """
    bk = get_backend(cfg.backend if backend is None else backend)
    can_whole = hasattr(bk, "sample_walk")
    if whole_walk is True and not can_whole:
        raise ValueError(
            f"backend {bk.name!r} has no sample_walk whole-walk support")
    if uniforms is not None:
        if params.kind == "node2vec" or whole_walk is False or not can_whole:
            raise ValueError(
                "fed uniforms require the whole-walk path "
                "(deepwalk/ppr/simple through sample_walk)")
        return bk.sample_walk(state, cfg, starts, key, params, u=uniforms)
    if whole_walk is not False and can_whole and params.kind != "node2vec":
        return bk.sample_walk(state, cfg, starts, key, params)
    return scan_walk(bk, state, cfg, starts, key, params)


def deepwalk(state, cfg, starts, key, length: int = 80,
             backend: Optional[str] = None):
    return random_walk(state, cfg, starts, key,
                       WalkParams(kind="deepwalk", length=length),
                       backend=backend)


def node2vec(state, cfg, starts, key, length: int = 80,
             p: float = 0.5, q: float = 2.0,
             backend: Optional[str] = None):
    return random_walk(state, cfg, starts, key,
                       WalkParams(kind="node2vec", length=length, p=p, q=q),
                       backend=backend)


def ppr(state, cfg, starts, key, max_length: int = 400,
        stop_prob: float = 1.0 / 80.0, backend: Optional[str] = None):
    return random_walk(state, cfg, starts, key,
                       WalkParams(kind="ppr", length=max_length,
                                  stop_prob=stop_prob), backend=backend)


def make_walker(state: BingoState, cfg: BingoConfig, params: WalkParams,
                backend: Optional[str] = None,
                whole_walk: Optional[bool] = None):
    """Jitted walk closure (cfg/params/backend static) for benchmarks.

    Returns ``run(st, starts, key) -> (st, path)``: the state is donated
    (``donate_argnums=0``) and threaded through unchanged, so XLA aliases
    the full ``BingoState`` buffers input→output and repeated walk calls
    never copy them — callers rebind ``st, path = run(st, starts, key)``
    (``benchmarks/common.py:walk_rate``).
    """
    @functools.partial(jax.jit, donate_argnums=0)
    def run(st, starts, key):
        return st, random_walk(st, cfg, starts, key, params,
                               backend=backend, whole_walk=whole_walk)
    return run
