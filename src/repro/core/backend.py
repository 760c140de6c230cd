"""Pluggable engine backends — one interface, two implementations.

Every layer that touches the BINGO sampling space — drawing a sample
(the walk scan, node2vec proposals, the relay's segments, serving) or
*mutating* it (batched §5.2 update rounds) — goes through an
``EngineBackend`` looked up from ``cfg.backend`` (DESIGN.md §7/§9):

  * ``"reference"`` — the pure-jnp engine (``core/sampler.py`` sampling,
    ``core/updates.py`` updates): alias pick + materialized-group /
    dense-rejection stage (ii) with exact ITS fallbacks, and the
    whole-table insert→delete→rebuild batched update.  Portable,
    differentiably traceable, the bit-exact oracle for both halves.
  * ``"pallas"``    — the fused production engine: row gather + the
    fused two-stage sample (``kernels/walk_sample.py``), the whole-walk
    persistent megakernel (``kernels/walk_fused.py``), and the
    batched-update megakernel (``kernels/update_fused.py``) that applies
    a whole update round in one ``pallas_call`` with the state tables
    HBM-resident.  Compiled on TPU; interpret mode elsewhere.
  * ``"auto"``      — resolves to ``"pallas"`` on a TPU backend and
    ``"reference"`` everywhere else.  This is the default on
    ``BingoConfig``: production hardware gets the fused kernels without
    any caller opting in.

Both backends realize Eq. 2 exactly (Theorem 4.1) for every group type
(DENSE/ONE/SPARSE/REGULAR), fp-bias mode, and radix bases up to 2^k —
``tests/test_backend_equiv.py`` pins the sampling equivalence against
``transition_probs`` ground truth — and apply §5.2 batched updates with
identical semantics: ``tests/test_update_fused.py`` pins the pallas
update path bit-exactly against ``core/updates.py:batched_update``.

Beyond the per-step interface both builtins implement the *whole-walk*
capability (DESIGN.md §8): ``sample_walk(state, cfg, starts, key,
params, u=None)`` runs an entire L-step walk in one call — the
reference backend via the ``core/walks.py`` scan (or the fed-uniform
jnp oracle when ``u`` is given), the pallas backend via the persistent
megakernel (``kernels/walk_fused.py``) that keeps walker state in VMEM
and issues a single ``pallas_call`` for all L steps.
``core/walks.py:random_walk`` dispatches whole-walk for
deepwalk/ppr/simple whenever the resolved backend defines
``sample_walk`` (node2vec stays on the per-step proposal path — its
Eq. 1 rejection needs the previous hop's rows).

Both builtins also implement the *resumable segment* capability
(DESIGN.md §10): ``sample_walk_segment(state, cfg, starts, t0, seed,
params, u=None)`` runs one bulk-synchronous relay round — each walker
enters at its own step ``t0``, draws the counter-based ``(seed,
walker, t)`` uniform stream, and exits with a ``(vertex, step)``
frontier record when it samples a remote (``-(g+2)``-encoded)
neighbor.  The reference implementation is the windowed jnp scan
(``kernels/ref.py:walk_segment_ref``), the pallas one the megakernel's
``segment=True`` entry — bit-exact against each other, which is what
lets the relay (``distributed/relay.py:make_relay``, which
``DynamicWalkEngine(mesh=...)`` runs) stitch cross-shard whole walks
that are bit-identical to the single-shard walk.

``SamplerBackend`` remains as an alias of ``EngineBackend`` for callers
that only consume the sampling half of the protocol.

Registering a new backend:

    @register_backend
    class MyBackend:
        name = "mine"
        def sample_step(self, state, cfg, u, key): ...
        def sample_uniform(self, state, cfg, u, key): ...
        def apply_updates(self, state, cfg, is_insert, u, v, w,
                          active=None): ...
        # optional whole-walk / resumable-segment capabilities:
        def sample_walk(self, state, cfg, starts, key, params,
                        u=None): ...
        def sample_walk_segment(self, state, cfg, starts, t0, seed,
                                params, u=None, wid=None): ...
"""

from __future__ import annotations

from typing import Dict, Protocol, Tuple, runtime_checkable

import jax

from repro.core.dyngraph import BingoConfig, BingoState

__all__ = ["EngineBackend", "SamplerBackend", "register_backend",
           "get_backend", "available_backends", "PallasBackend"]


@runtime_checkable
class EngineBackend(Protocol):
    """One BINGO engine: per-walker sampling plus batched graph updates.

    Sampling half (all methods jit-traceable):

    ``sample_step``    — biased hierarchical sample: ``(state, cfg,
    u (B,) int32 vertices, key) -> (next_vertex (B,), slot (B,))``.
    ``sample_uniform`` — unbiased neighbor pick with the same signature
    (the ``simple`` walk kind and degree-normalized baselines).
    Callers must mask walkers sitting on degree-0 vertices.

    Update half:

    ``apply_updates``  — one batched §5.2 round: ``(state, cfg,
    is_insert (B,) bool, u (B,) int32, v (B,) int32, w (B,) bias,
    active (B,) bool | None) -> (new_state, UpdateStats)`` with the
    reference ``core/updates.py:batched_update`` semantics (inserts
    before deletes, earliest-version-first duplicate deletion, one
    rebuild per affected vertex).  Implementations must be bit-exact
    against the reference — serving interleaves backends freely.

    Backends may additionally implement the whole-walk capability
    ``sample_walk(state, cfg, starts (B,) int32, key, params:
    WalkParams, u=None) -> (B, length+1) int32 path`` (column 0 =
    starts, terminated walkers pad -1 — the ``random_walk`` contract;
    ``u`` (L, B, 6) optionally pins the exact uniform stream), and the
    resumable-segment capability ``sample_walk_segment(state, cfg,
    starts, t0, seed (1,) int32, params, u=None, wid=None) ->
    (path (B, L+1), frontier (B, 2), steps)`` — one relay round over
    per-walker windows [t0, exit) with the counter-based PRNG contract,
    keyed by the slot→wid map ``wid`` so compacted slot layouts draw
    the walker's own stream (DESIGN.md §10); ``steps`` (tiles,) int32
    counts the step-loop iterations each tile of the launch ran.
    ``random_walk`` prefers ``sample_walk`` over the per-step scan for
    deepwalk/ppr/simple when present; the distributed relay requires
    ``sample_walk_segment``.
    """

    name: str

    def sample_step(self, state: BingoState, cfg: BingoConfig, u, key
                    ) -> Tuple[jax.Array, jax.Array]: ...

    def sample_uniform(self, state: BingoState, cfg: BingoConfig, u, key
                       ) -> Tuple[jax.Array, jax.Array]: ...

    def apply_updates(self, state: BingoState, cfg: BingoConfig,
                      is_insert, u, v, w, active=None): ...


# The sampling-only view predates the update half; every registered
# backend satisfies the full protocol, so the alias is exact.
SamplerBackend = EngineBackend

_REGISTRY: Dict[str, EngineBackend] = {}


def register_backend(cls):
    """Class decorator: instantiate and register under ``cls.name``."""
    _REGISTRY[cls.name] = cls()
    return cls


def available_backends() -> Tuple[str, ...]:
    _ensure_builtin()
    return tuple(sorted(_REGISTRY)) + ("auto",)


def get_backend(name: str) -> EngineBackend:
    """Resolve a backend by name; ``"auto"`` picks pallas on TPU."""
    _ensure_builtin()
    if name == "auto":
        name = "pallas" if jax.default_backend() == "tpu" else "reference"
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown engine backend {name!r}; "
            f"available: {available_backends()}") from None


def _ensure_builtin():
    # The reference backend lives in core/sampler.py (which imports this
    # module for the decorator); import lazily to avoid the cycle.
    if "reference" not in _REGISTRY:
        import repro.core.sampler  # noqa: F401  (registers "reference")


@register_backend
class PallasBackend:
    """Fused production engine: sampling and updates in resident kernels.

    Sampling stage (i)+(ii) run inside ``kernels/walk_sample.py`` on
    per-walker rows staged into VMEM; group membership is recomputed
    in-register from the bias row, so DENSE/materialized parity is free.
    Bases > 2 use digit-proportional acceptance with an in-kernel exact
    masked-ITS fallback; fp mode samples the decimal group via a
    frac-row ITS lane pass (DESIGN.md §7) — the distribution is exactly
    Eq. 2 in all modes.

    Whole walks skip the per-step path entirely: ``sample_walk`` hands
    the full ``BingoState`` tables to the persistent megakernel
    (``kernels/walk_fused.py``, DESIGN.md §8), which runs all L steps in
    one ``pallas_call`` with walker state resident in VMEM and only the
    current walkers' rows DMA'd per step — no (B, C) gather ever
    materializes in HBM.

    Batched updates take the same shape (``kernels/update_fused.py``,
    DESIGN.md §9): one ``pallas_call`` per round, tables HBM-resident
    and aliased in-place, per-affected-vertex rows DMA'd through
    double-buffered VMEM for the insert → two-phase delete → rebuild
    staging — bit-identical to the reference ``batched_update``.
    """

    name = "pallas"

    def _rows(self, state, u):
        return (state.itable.prob[u], state.itable.alias[u],
                state.bias[u], state.nbr[u], state.deg[u])

    def sample_step(self, state, cfg, u, key):
        from repro.kernels import ops
        B = u.shape[0]
        prob, alias, bias, nbr, deg = self._rows(state, u)
        extended = cfg.fp_bias or cfg.base_log2 > 1
        uu = jax.random.uniform(key, (B, 5 if extended else 3))
        frac = state.frac[u] if cfg.fp_bias else None
        return ops.walk_sample(prob, alias, bias, nbr, deg, uu, frac,
                               base_log2=cfg.base_log2)

    def sample_uniform(self, state, cfg, u, key):
        from repro.kernels import ops
        # Degree-based pick in-kernel (one lane compare against deg) —
        # no dummy all-ones bias/alias rows, no prob/alias/bias gathers.
        uu = jax.random.uniform(key, (u.shape[0], 1))
        return ops.walk_sample_uniform(state.nbr[u], state.deg[u], uu)

    def sample_walk(self, state, cfg, starts, key, params, u=None):
        from repro.core import walks
        if params.kind == "node2vec":
            # Second-order rejection reads the previous hop's rows — stays
            # on the per-step proposal path (DESIGN.md §8).
            return walks.scan_walk(self, state, cfg, starts, key, params)
        from repro.kernels import ops
        stop = float(params.stop_prob) if params.kind == "ppr" else 0.0
        return ops.walk_fused(
            state.itable.prob, state.itable.alias, state.bias, state.nbr,
            state.deg, state.frac if cfg.fp_bias else None, starts, key, u,
            length=params.length, base_log2=cfg.base_log2, stop_prob=stop,
            uniform=params.kind == "simple", cohorts=cfg.cohorts)

    def sample_walk_segment(self, state, cfg, starts, t0, seed, params,
                            u=None, wid=None):
        """One relay round through the megakernel's resumable entry
        (DESIGN.md §10).  ``seed`` is the raw (1,) int32 PRNG seed
        (``ops.seed_from_key``) shared across shards and rounds; ``wid``
        is the compacted relay's slot→wid map (PRNG keys by global
        walker id, not by lane)."""
        if params.kind == "node2vec":
            raise ValueError(
                "node2vec has no segment path (per-step only, DESIGN.md §8)")
        from repro.kernels import ops
        stop = float(params.stop_prob) if params.kind == "ppr" else 0.0
        return ops.walk_segment(
            state.itable.prob, state.itable.alias, state.bias, state.nbr,
            state.deg, state.frac if cfg.fp_bias else None, starts, t0,
            seed, u, wid, length=params.length, base_log2=cfg.base_log2,
            stop_prob=stop, uniform=params.kind == "simple",
            cohorts=cfg.cohorts)

    def apply_updates(self, state, cfg, is_insert, u, v, w, active=None):
        from repro.kernels import ops
        return ops.update_fused(state, cfg, is_insert, u, v, w, active)
