"""Walk throughput: whole-walk fused vs per-step pallas vs reference,
plus the cohort-interleave sweep (K=1/2/4) and the sharded relay.

The perf baseline for the megakernel work (DESIGN.md §8/§10):
steps/second for each walk kind × sampling path, at laptop-scale shapes.
Two measurement modes, selected by ``run.py``:

  * default (interpret): every path is measured identically — on this
    CPU container the pallas paths run in interpret mode, so absolute
    numbers are a correctness-weighted smoke rather than a perf claim,
    but the K=1/2/4 rows really do emulate the three kernel programs.
  * ``--compiled``: only XLA-compiled programs are timed, and the JSON
    snapshot is stamped ``interpret: false``.  On TPU that is the real
    Mosaic megakernel at each K; on CPU (where pallas is interpret-only)
    the fused rows route through the jnp megawalk oracle — which is
    cohort-invariant by construction, so the K rows bracket measurement
    noise rather than a kernel difference (the CI guard compares them
    with tolerance for exactly this reason) — the interpret-only paths
    (pallas-step, pallas-fused legacy row) are pruned, while the relay
    rows switch to the XLA-compiled reference segment so the bulk vs
    overlapped comparison (``round_ms`` / ``overlap_efficiency``
    extras, gated by ``guard.py --mode relay``) is always measured on
    compiled programs.

The sweep threads ONE donated ``BingoState`` copy through every timed
case (``common.walk_rate``'s ``donated=`` contract) so the tables are
materialized once per run, not once per row.  The ``relay`` case runs
the exact cross-shard walk over however many host devices exist (1
here; the walk-relay CI job fakes 8) — its gap to ``pallas-fused`` is
the price of resumability + routing.
"""

from __future__ import annotations

import functools
import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks import common
from benchmarks.common import (build_dataset, build_state, record,
                               record_sizing, walk_rate)
from repro.core import walks

SCALE = 9
CAPACITY = 128
WALKERS = 256
LENGTH = 16

# --micro (CI compiled snapshot): dry-run-scale so the whole sweep is
# seconds, stamped into sizing so it can never be diffed against FULL.
MICRO_SCALE = 6
MICRO_CAPACITY = 16
MICRO_WALKERS = 64
MICRO_LENGTH = 8

COHORTS = (1, 2, 4)

KINDS = {
    "deepwalk": walks.WalkParams(kind="deepwalk", length=LENGTH),
    "ppr": walks.WalkParams(kind="ppr", length=LENGTH, stop_prob=1 / 20),
    "simple": walks.WalkParams(kind="simple", length=LENGTH),
}

# path -> (backend, whole_walk): the three production-relevant routes
# through random_walk.  "pallas-fused" is the megakernel (one launch per
# walk batch); "pallas-step" pins the same sampler to the per-step scan.
PATHS = {
    "reference": ("reference", False),
    "pallas-step": ("pallas", False),
    "pallas-fused": ("pallas", True),
}


def fused_rate(state, cfg, params, starts, *, cohorts: int = 1,
               seed: int = 0, reps: int = 3, donated=None):
    """Steps/second of the fused whole-walk entry at one cohort count.

    Calls ``ops.walk_fused`` directly — the exact op the pallas
    backend's ``sample_walk`` dispatches — with the state donated and
    threaded like ``common.walk_rate``.  In compiled mode off-TPU it
    flips ``force_ref`` so the timed program is the XLA-compiled jnp
    megawalk oracle instead of the (uncompilable-on-CPU) pallas kernel.
    Returns ``(rate, threaded_state)``.
    """
    from repro.kernels import ops
    stop = float(params.stop_prob) if params.kind == "ppr" else 0.0
    force_ref = common.COMPILED and not ops.on_tpu()

    @functools.partial(jax.jit, donate_argnums=0)
    def run(st, starts_, key):
        path = ops.walk_fused(
            st.itable.prob, st.itable.alias, st.bias, st.nbr, st.deg,
            st.frac if cfg.fp_bias else None, starts_, key,
            length=params.length, base_log2=cfg.base_log2, stop_prob=stop,
            uniform=params.kind == "simple", force_ref=force_ref,
            cohorts=cohorts)
        return st, path

    key = jax.random.key(seed)
    st = donated if donated is not None else jax.tree.map(jnp.copy, state)
    st, _ = jax.block_until_ready(run(st, starts, key))   # warmup/compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        st, path = run(st, starts, key)
        jax.block_until_ready(path)
        ts.append(time.perf_counter() - t0)
    secs = float(np.median(ts))
    return starts.shape[0] * params.length / max(secs, 1e-9), st


def _relay_backend():
    """Backend for the relay rows: the pallas megakernel on TPU (or in
    interpret mode), the XLA-compiled jnp segment on compiled CPU —
    bit-identical outputs either way, so compiled CPU snapshots get
    real relay rows instead of a pruned hole (the ``--mode relay``
    guard gates on them)."""
    from repro.kernels.ops import on_tpu
    return "reference" if common.COMPILED and not on_tpu() else "pallas"


def relay_rate(state, cfg, params, starts, *, seed: int = 0,
               reps: int = 3, overlap: bool = False):
    """Steps/second of the sharded ``walk_relay`` path (DESIGN.md §10)
    over all local devices — bit-identical output to ``pallas-fused``,
    measured with the same jitted-call protocol.  Also returns the
    relay's ``rounds_to_completion``, the peak per-shard slot occupancy
    (the allocator-pressure diagnostics: a ping-pong graph or a
    regressed free-list shows up here as a rounds/occupancy jump long
    before it is visible in wall-clock), and the median per-round
    device time in ms.  ``overlap=True`` times the overlapped schedule
    — per-ROUND time is the number that isolates its win, because the
    overlap trades 2 extra rounds of crossing latency for collectives
    off the critical path (round counts differ by design)."""
    from repro.core.backend import get_backend
    from repro.distributed.relay import make_relay
    from repro.kernels.ops import seed_from_key

    S = len(jax.devices())
    if cfg.num_vertices % S or starts.shape[0] % S:
        S = 1
    mesh = jax.make_mesh((S,), ("data",))
    relay = make_relay(get_backend(_relay_backend()), cfg, params, mesh,
                       diagnostics=True, overlap=overlap)
    f = jax.jit(lambda st, wk, sd: relay(st, wk, sd))
    sd = seed_from_key(jax.random.key(seed))
    out = jax.block_until_ready(f(state, starts, sd))   # warmup/compile
    _, rounds, _, peak = out
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(state, starts, sd))
        ts.append(time.perf_counter() - t0)
    secs = float(np.median(ts))
    rate = starts.shape[0] * params.length / max(secs, 1e-9)
    round_ms = secs * 1e3 / max(int(rounds), 1)
    return rate, int(rounds), int(peak), round_ms


def relay_phase_times(state, cfg, params, starts, *, seed: int = 0,
                      reps: int = 5):
    """Host-driver capture of per-phase relay device time (ms).

    Compiles the two round phases as standalone programs at the relay's
    exact shapes — one resumable segment launch over the Wl compacted
    slots per shard, and one round's walker + path-record all_to_alls —
    and times each under the jitted-call protocol.  segment_ms vs
    exchange_ms is the number that says how much a round COULD gain
    from overlapping them (perfect overlap hides min(seg, exch)); the
    measured ``round_ms`` ratio says how much it DID."""
    from jax.sharding import PartitionSpec as P
    from repro.core.backend import get_backend
    from repro.distributed.relay import relay_view, slot_count
    from repro.distributed.walker_exchange import exchange_walkers
    from repro.kernels.ops import seed_from_key

    S = len(jax.devices())
    W = starts.shape[0]
    if cfg.num_vertices % S or W % S:
        S = 1
    mesh = jax.make_mesh((S,), ("data",))
    shard_size = cfg.num_vertices // S
    Wl = slot_count(W, S)
    L = params.length
    bk = get_backend(_relay_backend())
    import dataclasses as _dc
    lcfg = _dc.replace(cfg, num_vertices=shard_size)

    def seg_local(st, sd):
        sidx = jax.lax.axis_index("data")
        view = relay_view(st, sidx * shard_size, shard_size)
        slot_cur = jnp.arange(Wl, dtype=jnp.int32) % shard_size
        slot_wid = jnp.arange(Wl, dtype=jnp.int32) + sidx * Wl
        paths, frontier = bk.sample_walk_segment(
            view, lcfg, slot_cur, jnp.zeros((Wl,), jnp.int32), sd,
            params, wid=slot_wid)
        return paths, frontier

    def exch_local(wpay, ppay):
        a_w, l_w, o_w = exchange_walkers(wpay, shard_size, S, "data")
        a_p, l_p, o_p = exchange_walkers(ppay, shard_size, S, "data")
        return a_w, a_p, o_w + o_p

    sspec = jax.tree.map(lambda _: P("data"), state,
                         is_leaf=lambda x: hasattr(x, "ndim"))
    seg = jax.jit(jax.shard_map(seg_local, mesh=mesh,
                                in_specs=(sspec, P()), out_specs=P("data"),
                                check_vma=False))
    exch = jax.jit(jax.shard_map(exch_local, mesh=mesh,
                                 in_specs=(P("data"), P("data")),
                                 out_specs=(P("data"), P("data"), P()),
                                 check_vma=False))

    sd = seed_from_key(jax.random.key(seed))
    wpay = jnp.stack([starts % cfg.num_vertices,
                      jnp.zeros((W,), jnp.int32),
                      jnp.arange(W, dtype=jnp.int32)], axis=-1)
    ppay = jnp.full((S * Wl, L + 4), 1, jnp.int32).at[:, 0].set(
        jnp.arange(S * Wl, dtype=jnp.int32) % cfg.num_vertices)

    def _time(fn, *args):
        jax.block_until_ready(fn(*args))          # warmup/compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)) * 1e3

    return _time(seg, state, sd), _time(exch, wpay, ppay)


def main():
    from repro.kernels.ops import on_tpu
    scale = MICRO_SCALE if common.MICRO else SCALE
    capacity = MICRO_CAPACITY if common.MICRO else CAPACITY
    walkers = MICRO_WALKERS if common.MICRO else WALKERS
    length = MICRO_LENGTH if common.MICRO else LENGTH
    kinds = {k: p._replace(length=length) for k, p in KINDS.items()}

    V, src, dst, w = build_dataset(scale)
    st, cfg = build_state(V, src, dst, w, capacity=capacity)
    starts = jnp.arange(walkers, dtype=jnp.int32) % V
    record_sizing("walks", walkers=walkers, num_vertices=V,
                  walk_length=length, capacity=capacity,
                  kin=cfg.num_inter, cohorts=list(COHORTS))
    # interpret-emulated paths are meaningless under --compiled on CPU
    prune_interpret = common.COMPILED and not on_tpu()
    donated = jax.tree.map(jnp.copy, st)   # ONE copy for the whole sweep
    for kind, params in kinds.items():
        for path, (backend, whole) in PATHS.items():
            if prune_interpret and backend == "pallas":
                continue
            rate, donated = walk_rate(st, cfg, params, starts,
                                      backend=backend, whole_walk=whole,
                                      donated=donated, return_state=True)
            record("walks", f"{kind}-{path}", "steps_per_sec", rate)
        for K in COHORTS:
            rate, donated = fused_rate(st, cfg, params, starts, cohorts=K,
                                       donated=donated)
            record("walks", f"{kind}-pallas-fused-K{K}", "steps_per_sec",
                   rate)
        # relay rows run in EVERY mode: on compiled CPU they route
        # through the XLA-compiled reference segment (_relay_backend)
        # instead of being pruned, so the --mode relay guard always has
        # a snapshot to gate.  Per-kind bulk + overlapped rows, plus the
        # per-phase host-driver capture and the overlap_efficiency
        # extra = bulk_round_ms / overlap_round_ms (the tentpole's win,
        # measured per ROUND — overlap trades extra crossing-latency
        # rounds for collectives off the critical path, so steps/s at
        # micro scale would mis-score it).
        S_here = len(jax.devices())
        rate, rounds, peak, round_ms = relay_rate(st, cfg, params, starts)
        record("walks", f"{kind}-relay", "steps_per_sec", rate)
        record("walks", f"{kind}-relay", "rounds_to_completion", rounds)
        record("walks", f"{kind}-relay", "peak_slot_occupancy", peak)
        record("walks", f"{kind}-relay", "round_ms", round_ms)
        record("walks", f"{kind}-relay", "mesh_sv", S_here)
        record("walks", f"{kind}-relay", "mesh_sw", 1)
        o_rate, o_rounds, _, o_round_ms = relay_rate(
            st, cfg, params, starts, overlap=True)
        record("walks", f"{kind}-relay-overlap", "steps_per_sec", o_rate)
        record("walks", f"{kind}-relay-overlap", "rounds_to_completion",
               o_rounds)
        record("walks", f"{kind}-relay-overlap", "round_ms", o_round_ms)
        record("walks", f"{kind}-relay-overlap", "overlap_efficiency",
               round_ms / max(o_round_ms, 1e-9))
        record("walks", f"{kind}-relay-overlap", "mesh_sv", S_here)
        record("walks", f"{kind}-relay-overlap", "mesh_sw", 1)
        seg_ms, exch_ms = relay_phase_times(st, cfg, params, starts)
        record("walks", f"{kind}-relay", "segment_ms", seg_ms)
        record("walks", f"{kind}-relay", "exchange_ms", exch_ms)


if __name__ == "__main__":
    main()
