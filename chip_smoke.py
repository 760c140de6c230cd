#!/usr/bin/env python3
"""Smoke run of the dynamic-walk engine's main path on TPU.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # four chips: sharded engine only

One chip: an R-MAT graph (Graph500 quadrants, scale 17, edge factor 16,
degree-derived biases) is built into a ``BingoState`` on the chip at
C=1024, bias_bits=16 with the pallas backend; a guarded
``DynamicWalkEngine`` ingests three mixed rounds of 102,400 updates and
serves W=16,384 walks of L=80 steps per kind (deepwalk, ppr, simple)
between rounds, plus one node2vec batch; then a few walk requests and
updates go through ``ServingScheduler``.  Checks, all on the chip:

  * the first update round is bit-identical to the jnp reference
    ``batched_update`` on every affected vertex, leaves every other
    vertex untouched, and reports the same applied counts;
  * the megakernel walks are bit-identical to the jnp oracle
    (``ops.walk_fused(..., force_ref=True)``) on a subset of walkers;
  * every hop of every path is a live edge of the state it walked;
  * the scheduler and the guard conserve every request.

``--chips 4`` runs only the sharded phase: the same graph and stream
through ``DynamicWalkEngine(mesh=...)`` with relay walks, built across
the mesh, compared bit for bit with a one-chip engine in this process.

Times printed are wall times of this one smoke run (compiles included
where marked), not benchmark numbers.  The last stdout line is the JSON
result.  Without a TPU, without this repo's sources beside the script,
or when any phase or check fails, the script exits nonzero and prints no
result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent

SCALE = 17              # V = 2**17 = 131,072
EDGE_FACTOR = 16        # Graph500's
CAPACITY = 1024
BIAS_BITS = 16
ROUNDS = 3
BATCH = 102_400         # the paper's update batch
WALKERS = 16_384
LENGTH = 80
PPR_STOP = 1.0 / 80.0
ORACLE_WALKERS = 512    # walkers re-run through the jnp oracle
REF_CHUNK = 4096        # affected vertices per reference sub-state
REF_LANES = 16_384      # update lanes per reference sub-state (hubs
                        # with more get a sub-state of their own)
SCHED_REQUESTS = 4      # walk requests sent through the scheduler
SCHED_STARTS = 512      # start vertices per request
SEED = 0


class SmokeFailure(Exception):
    """A check that did not hold."""


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)
    print(f"[check] {what}: ok", flush=True)


class Clock:
    """Prints labelled wall times of this single run."""

    def __init__(self):
        self.times = {}

    def __call__(self, label, t0):
        dt = time.perf_counter() - t0
        self.times[label] = dt
        print(f"[time] {label}: {dt:.3f} s (single smoke run, not a "
              "benchmark)", flush=True)


def make_workload(seed):
    """R-MAT graph + mixed update stream (one spare round feeds the
    scheduler phase).  Host numpy, made from ``seed``."""
    from repro.graph.rmat import degree_bias, rmat_edges
    from repro.graph.streams import make_update_stream
    V = 1 << SCALE
    src, dst = rmat_edges(SCALE, EDGE_FACTOR, seed=seed)
    w = degree_bias(src, dst, V, bias_bits=BIAS_BITS)
    stream = make_update_stream(src, dst, w, batch_size=BATCH,
                                rounds=ROUNDS + 1, mode="mixed", seed=seed,
                                num_vertices=V)
    return V, stream


def walk_params():
    from repro.core.walks import WalkParams
    return {"deepwalk": WalkParams("deepwalk", LENGTH),
            "ppr": WalkParams("ppr", LENGTH, stop_prob=PPR_STOP),
            "simple": WalkParams("simple", LENGTH),
            "node2vec": WalkParams("node2vec", LENGTH)}


@functools.partial(jax.jit, static_argnames=("cfg", "params"))
def walk(state, starts, key, *, cfg, params):
    """``random_walk`` on the engine's state, which it only reads (no
    donation: the engine keeps owning it)."""
    from repro.core.walks import random_walk
    return random_walk(state, cfg, starts, key, params)


@functools.partial(jax.jit, static_argnames=("cfg", "params"))
def oracle_walk(state, starts, key, *, cfg, params):
    """The jnp oracle of the whole-walk megakernel on the same tables."""
    from repro.kernels import ops
    return ops.walk_fused(
        state.itable.prob, state.itable.alias, state.bias, state.nbr,
        state.deg, state.frac if cfg.fp_bias else None, starts, key,
        length=params.length, base_log2=cfg.base_log2,
        stop_prob=params.stop_prob if params.kind == "ppr" else 0.0,
        uniform=params.kind == "simple", force_ref=True)


@jax.jit
def row_digest(state):
    """(V,) uint32 digest of every row of every table."""
    V = state.deg.shape[0]
    out = jnp.zeros((V,), jnp.uint32)
    for i, leaf in enumerate(jax.tree.leaves(state)):
        x = leaf.reshape(V, -1)
        if x.dtype.itemsize == 4:
            x = jax.lax.bitcast_convert_type(x, jnp.uint32)
        else:
            x = x.astype(jnp.uint32)
        mul = jnp.arange(x.shape[1], dtype=jnp.uint32) * 2 + 1
        out = out * jnp.uint32(0x9E3779B1) + jnp.sum(x * mul, axis=1) \
            + jnp.uint32(i)
    return out


@jax.jit
def bad_hops(nbr, deg, paths, starts):
    """Count hops that are not live edges, and malformed paths.

    A hop ``p[t] -> p[t+1]`` (``p[t+1] >= 0``) must find ``p[t+1]`` in
    the first ``deg[p[t]]`` slots of ``nbr[p[t]]``; column 0 must be the
    start, and a path that ended (-1) must stay ended."""
    W, L1 = paths.shape
    C = nbr.shape[1]
    n = 256 if W % 256 == 0 else W
    cur = paths[:, :-1].reshape(W // n, n, L1 - 1)
    nxt = paths[:, 1:].reshape(W // n, n, L1 - 1)

    def chunk(args):
        c, x = args
        safe = jnp.maximum(c, 0)
        rows = nbr[safe]                                   # (n, L, C)
        live = jnp.arange(C)[None, None, :] < deg[safe][..., None]
        hit = jnp.any((rows == x[..., None]) & live, axis=-1)
        return jnp.sum((x >= 0) & ~(hit & (c >= 0)))

    hops = jnp.sum(jax.lax.map(chunk, (cur, nxt)))
    ended = jnp.sum((paths[:, :-1] < 0) & (paths[:, 1:] >= 0))
    wrong_start = jnp.sum(paths[:, 0] != starts)
    return hops + ended + wrong_start


def live_starts(state, rng, n):
    deg = np.asarray(state.deg)
    pool = np.flatnonzero(deg > 0)
    return rng.choice(pool, size=n).astype(np.int32)


# --------------------------------------------------------------------------
# first update round vs the jnp reference
# --------------------------------------------------------------------------

def snapshot_affected(engine, ins, u, v, w):
    """Before the round: the guard's lane mask (the engine applies only
    these lanes), and host copies of every affected vertex's rows, in
    sub-states of at most REF_CHUNK rows and (bar single hubs) REF_LANES
    lanes, with their lanes re-based.

    The reference ``batched_update`` treats each vertex on its own rows
    and its own lanes (kept in stream order), so a sub-state holding one
    chunk of affected vertices and only their lanes reproduces exactly
    what the full round does to them."""
    from repro.core.updates import R_OK
    reasons = engine.guard.classify(engine.state, ins, u, v, w)
    ok = np.asarray(reasons == R_OK)
    un, vn, wn, insn = (np.asarray(x) for x in (u, v, w, ins))
    affected, per_vertex = np.unique(un[ok], return_counts=True)
    groups, lo, n = [], 0, 0
    for i, c in enumerate(per_vertex):
        if i > lo and (i - lo == REF_CHUNK or n + c > REF_LANES):
            groups.append(affected[lo:i])
            lo, n = i, 0
        n += c
    groups.append(affected[lo:])
    chunks = []
    lanes_max = 0
    for ids in groups:
        pad = np.full(REF_CHUNK, ids[0], np.int32)
        pad[:len(ids)] = ids
        rows = jax.device_get(jax.tree.map(lambda t: t[jnp.asarray(pad)],
                                           engine.state))
        sel = np.flatnonzero(ok & np.isin(un, ids))
        chunks.append((ids, pad, rows, sel))
        lanes_max = max(lanes_max, len(sel))
    lanes = -(-lanes_max // 1024) * 1024
    return affected, chunks, lanes, (insn, un, vn, wn)


def check_round_vs_reference(engine, cfg, stats, snap, digest_before):
    from repro.core.updates import batched_update
    affected, chunks, lanes, (insn, un, vn, wn) = snap
    sub_cfg = dataclasses.replace(cfg, num_vertices=REF_CHUNK,
                                  backend="reference")
    ref = jax.jit(lambda st, i, uu, vv, ww, act: batched_update(
        st, sub_cfg, i, uu, vv, ww, active=act))

    @jax.jit
    def mismatched_rows(state, idx, sub, n):
        got = jax.tree.map(lambda t: t[idx], state)
        bad = jnp.zeros((REF_CHUNK,), bool)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(sub)):
            if a.dtype.itemsize == 4:
                a = jax.lax.bitcast_convert_type(a, jnp.int32)
                b = jax.lax.bitcast_convert_type(b, jnp.int32)
            d = (a != b).reshape(REF_CHUNK, -1)
            bad = bad | jnp.any(d, axis=1)
        return jnp.sum(bad & (jnp.arange(REF_CHUNK) < n))

    ins_sum = del_sum = 0
    trans = np.zeros((5, 5), np.int64)
    bad_rows = 0
    for ids, pad, rows, sel in chunks:
        k = len(sel)
        uu = np.zeros(lanes, np.int32)
        vv = np.zeros(lanes, np.int32)
        ww = np.ones(lanes, wn.dtype)
        ii = np.zeros(lanes, bool)
        act = np.zeros(lanes, bool)
        uu[:k] = np.searchsorted(ids, un[sel])
        vv[:k], ww[:k], ii[:k], act[:k] = vn[sel], wn[sel], insn[sel], True
        sub, st = ref(jax.device_put(rows), ii, uu, vv, ww, act)
        ins_sum += int(st.ins_applied)
        del_sum += int(st.del_applied)
        trans += np.asarray(st.transitions)
        bad_rows += int(mismatched_rows(engine.state, jnp.asarray(pad), sub,
                                        len(ids)))
    check(bad_rows == 0,
          f"round 1: all {len(affected)} affected vertices bit-identical to "
          f"the reference batched_update ({bad_rows} rows differ)")
    after = np.asarray(row_digest(engine.state))
    untouched = np.ones(after.shape[0], bool)
    untouched[affected] = False
    moved = int(np.sum(after[untouched] != digest_before[untouched]))
    check(moved == 0, f"round 1: {int(untouched.sum())} unaffected vertices "
          f"unchanged ({moved} differ)")
    got = (int(stats.ins_applied), int(stats.del_applied))
    check(got == (ins_sum, del_sum)
          and np.array_equal(np.asarray(stats.transitions), trans),
          f"round 1: applied inserts/deletes {got} and group-type "
          f"transitions match the reference ({ins_sum}, {del_sum})")


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def assert_compiled_kernels(cfg, state, n_upd):
    """The backend resolved to pallas and the programs the engine runs
    hold compiled Mosaic kernels (``tpu_custom_call``) — interpret mode
    would leave none."""
    from repro.core.backend import get_backend
    from repro.core.updates import make_updater
    from repro.core.walks import make_walker
    from repro.kernels import ops
    check(get_backend(cfg.backend).name == "pallas" and ops.on_tpu(),
          "backend resolved to pallas, kernels not interpreted")
    sds = jax.ShapeDtypeStruct
    st = jax.tree.map(lambda t: sds(t.shape, t.dtype), state)
    lanes = [sds((n_upd,), jnp.bool_), sds((n_upd,), jnp.int32),
             sds((n_upd,), jnp.int32), sds((n_upd,), jnp.int32),
             sds((n_upd,), jnp.bool_)]
    text = make_updater(cfg, with_active=True).lower(st, *lanes).as_text()
    check("tpu_custom_call" in text, "update program holds the Mosaic "
          "update megakernel")
    for kind, p in walk_params().items():
        text = make_walker(state, cfg, p).lower(
            st, sds((WALKERS,), jnp.int32), jax.random.key(0)).as_text()
        check("tpu_custom_call" in text,
              f"{kind} walk program holds a Mosaic kernel")


def check_walks(state, cfg, paths, starts, key, params):
    kind = params.kind
    paths = jnp.asarray(paths)
    bad = int(bad_hops(state.nbr, state.deg, paths, jnp.asarray(starts)))
    check(bad == 0, f"{kind}: every hop of {paths.shape[0]} paths is a "
          f"live edge ({bad} bad)")
    if kind == "node2vec":      # per-step path: no megakernel to compare
        return
    n = ORACLE_WALKERS
    ref = oracle_walk(state, jnp.asarray(starts[:n]), key, cfg=cfg,
                      params=params)
    check(bool(jnp.array_equal(ref, paths[:n])),
          f"{kind}: megakernel paths bit-identical to the jnp oracle on "
          f"{n} walkers")


def one_chip(seed):
    from repro.core.dyngraph import BingoConfig, from_edges
    from repro.serve.dynwalk import DynamicWalkEngine
    from repro.serve.scheduler import SchedulerConfig, ServingScheduler

    clock = Clock()
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    V, stream = make_workload(seed)
    clock("host workload generation", t0)
    cfg = BingoConfig(num_vertices=V, capacity=CAPACITY, bias_bits=BIAS_BITS,
                      backend="pallas")
    deg0 = np.bincount(stream.init_src, minlength=V)
    dropped = int(np.maximum(deg0 - CAPACITY, 0).sum())
    print(f"[graph] V={V} initial edges={len(stream.init_src)} "
          f"max out-degree={int(deg0.max())}; the cap C={CAPACITY} drops "
          f"{dropped} edges of {int((deg0 > CAPACITY).sum())} vertices",
          flush=True)

    t0 = time.perf_counter()
    state = jax.jit(lambda s, d, w: from_edges(cfg, s, d, w))(
        stream.init_src, stream.init_dst, stream.init_w)
    jax.block_until_ready(state)
    clock("state build on chip (compile included)", t0)
    check(int(jnp.sum(state.deg)) == len(stream.init_src) - dropped,
          "state holds every edge the cap keeps")
    assert_compiled_kernels(cfg, state, BATCH)

    params = walk_params()
    engine = DynamicWalkEngine(state, cfg, params["deepwalk"], guard=True,
                               defer_guard=True, walk_buckets=(WALKERS,),
                               seed=seed)
    del state

    for r in range(ROUNDS):
        ins, u, v, w = (jnp.asarray(x[r]) for x in (
            stream.is_insert, stream.u, stream.v, stream.w))
        if r == 0:
            snap = snapshot_affected(engine, ins, u, v, w)
            digest = np.asarray(row_digest(engine.state))
        t0 = time.perf_counter()
        stats = engine.ingest(ins, u, v, w)
        jax.block_until_ready(engine.state)
        clock(f"round {r + 1} ingest of {BATCH} updates"
              + (" (compile included)" if r == 0 else ""), t0)
        print(f"[round {r + 1}] inserts applied={int(stats.ins_applied)} "
              f"deletes applied={int(stats.del_applied)} rejected by "
              f"reason={np.asarray(stats.rejected).tolist()}", flush=True)
        if r == 0:
            check_round_vs_reference(engine, cfg, stats, snap, digest)
            del snap
        engine.drain_guard()

        starts = live_starts(engine.state, rng, WALKERS)
        key = jax.random.key(seed * 1000 + r)
        for kind, p in params.items():
            if kind == "node2vec" and r:
                continue
            t0 = time.perf_counter()
            if kind == "deepwalk":
                paths = engine.walk(starts, key)
            else:
                paths = walk(engine.state, jnp.asarray(starts), key,
                             cfg=cfg, params=p)
            jax.block_until_ready(paths)
            clock(f"round {r + 1} {kind} walk W={WALKERS} L={LENGTH}"
                  + (" (compile included)" if r == 0 else ""), t0)
            check_walks(engine.state, cfg, paths, starts, key, p)

    # serving front end: a few walk requests and one spare round of
    # updates through the scheduler, then drain
    t0 = time.perf_counter()
    sched = ServingScheduler(engine, SchedulerConfig(
        update_lanes=BATCH, max_update_delay=2, max_walk_queue=WALKERS,
        max_update_queue=BATCH))
    spare = ROUNDS
    half = BATCH // 2
    rids = []
    for i in range(SCHED_REQUESTS):
        if i < 2:
            sl = slice(i * half, (i + 1) * half)
            check(sched.submit_update(stream.is_insert[spare, sl],
                                      stream.u[spare, sl],
                                      stream.v[spare, sl],
                                      stream.w[spare, sl]),
                  f"scheduler admits update batch {i + 1}")
        starts = live_starts(engine.state, rng, SCHED_STARTS)
        rid = sched.submit_walk(starts)
        check(rid is not None, f"scheduler admits walk request {i + 1}")
        rids.append((rid, starts))
        sched.tick()
    results = {res.rid: res for res in sched.drain()}
    sched.check_conservation()
    sched.close()
    engine.guard.check_conservation()
    clock("scheduler phase", t0)
    gens = [results[rid].generation for rid, _ in rids]
    check(all(results[rid].paths.shape == (SCHED_STARTS, LENGTH + 1)
              and np.array_equal(results[rid].paths[:, 0], s)
              for rid, s in rids)
          and gens == sorted(gens),
          f"scheduler served {len(rids)} requests, generations {gens}")
    check(all(v == 0 for v in engine.audit().values()),
          "final state passes the device invariant audit")
    return clock.times


def four_chips(seed):
    from repro.core.backend import get_backend
    from repro.core.dyngraph import BingoConfig, from_edges
    from repro.distributed.relay import make_relay
    from repro.kernels.ops import seed_from_key
    from repro.serve.dynwalk import DynamicWalkEngine, sharded_from_edges

    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, found "
                           f"{len(jax.devices())}")
    clock = Clock()
    rng = np.random.default_rng(seed)
    V, stream = make_workload(seed)
    cfg = BingoConfig(num_vertices=V, capacity=CAPACITY, bias_bits=BIAS_BITS,
                      backend="pallas")
    # Auto axes: the engine's jnp code leaves the partitioning to GSPMD
    mesh = jax.make_mesh((4,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    edges = (stream.init_src, stream.init_dst, stream.init_w)

    t0 = time.perf_counter()
    sharded = sharded_from_edges(cfg, *edges, mesh)
    jax.block_until_ready(sharded)
    clock("sharded state build across 4 chips (compile included)", t0)
    per_dev = {s.device.id: s.data.shape[0]
               for s in sharded.nbr.addressable_shards}
    check(sorted(per_dev.values()) == [V // 4] * 4,
          f"state built as 4 shards of {V // 4} rows, one per chip")
    single = jax.jit(lambda s, d, w: from_edges(cfg, s, d, w))(*edges)

    params = walk_params()
    # no guard here: the one-chip phase covers it, and both engines
    # reject-and-count the same lanes without it
    eng1 = DynamicWalkEngine(single, cfg, params["deepwalk"], seed=seed)
    engS = DynamicWalkEngine(sharded, cfg, params["deepwalk"], seed=seed,
                             mesh=mesh)
    del single, sharded
    bk = get_backend(cfg.backend)
    relays = {k: jax.jit(make_relay(bk, cfg, params[k], mesh, overlap=True))
              for k in ("ppr", "simple")}

    for r in range(ROUNDS):
        lanes = [jnp.asarray(x[r]) for x in (stream.is_insert, stream.u,
                                             stream.v, stream.w)]
        t0 = time.perf_counter()
        s1 = eng1.ingest(*lanes)
        sS = engS.ingest(*lanes)
        jax.block_until_ready((eng1.state, engS.state))
        clock(f"round {r + 1} ingest, both engines", t0)
        check((int(s1.ins_applied), int(s1.del_applied))
              == (int(sS.ins_applied), int(sS.del_applied))
              and all(np.array_equal(np.asarray(a), np.asarray(b))
                      for a, b in ((s1.transitions, sS.transitions),
                                   (s1.rejected, sS.rejected))),
              f"round {r + 1}: sharded and one-chip update stats agree")
        d1 = np.asarray(row_digest(eng1.state))
        dS = np.asarray(row_digest(engS.state))
        check(np.array_equal(d1, dS),
              f"round {r + 1}: every sharded row equals the one-chip row")
        starts = live_starts(eng1.state, rng, WALKERS)
        key = jax.random.key(seed * 1000 + r)
        t0 = time.perf_counter()
        p1 = eng1.walk(starts, key)
        pS = engS.walk(starts, key)
        jax.block_until_ready((p1, pS))
        clock(f"round {r + 1} deepwalk, both engines", t0)
        check(bool(jnp.array_equal(p1, pS)),
              f"round {r + 1} deepwalk: relay paths bit-identical to the "
              "one-chip engine")
        for kind in ("ppr", "simple"):
            t0 = time.perf_counter()
            p1 = walk(eng1.state, jnp.asarray(starts), key, cfg=cfg,
                      params=params[kind])
            pS = relays[kind](engS.state, jnp.asarray(starts),
                              seed_from_key(key))[0]
            jax.block_until_ready((p1, pS))
            clock(f"round {r + 1} {kind}, both engines", t0)
            check(bool(jnp.array_equal(p1, pS)),
                  f"round {r + 1} {kind}: relay paths bit-identical to "
                  "the one-chip engine")
    return clock.times


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repo's src/repro is not beside this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    print(f"[setup] compile cache: {use_compile_cache(ROOT)}", flush=True)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); this "
              "smoke run does not fall back to the CPU", file=sys.stderr)
        return 1
    print(f"[setup] {len(devs)} x {devs[0].device_kind}", flush=True)
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(f"[time] whole smoke run: {time.perf_counter() - t0:.3f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
