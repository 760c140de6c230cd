"""Benchmark harness entry: one module per paper table/figure.

``python -m benchmarks.run [--only table3,...]`` prints CSV rows
``bench,case,metric,value`` (captured into bench_output.txt for the
final deliverable) and writes experiments/bench_results.csv, plus two
repo-root JSON baselines future PRs diff against: BENCH_walks.json
(steps/s per kind × sampling path, incl. the whole-walk fused
megakernel) and BENCH_updates.json (updates/s per §6.1 workload mode ×
EngineBackend — reference jnp pipeline vs the pallas update
megakernel).
"""

from __future__ import annotations

import argparse
import csv
import json
import os
import time
import traceback
from pathlib import Path

from benchmarks import (bench_batched, bench_complexity, bench_fp_bias,
                        bench_group_adapt, bench_piecewise, bench_serving,
                        bench_sweeps, bench_table3, bench_updates,
                        bench_walks)
from benchmarks.common import ROWS

MODULES = {
    "walks": bench_walks,            # whole-walk fused vs per-step paths
    "updates": bench_updates,        # batched updates: ref vs megakernel
    "serving": bench_serving,        # continuous scheduler vs serial calls
    "table3": bench_table3,          # paper Table 3
    "complexity": bench_complexity,  # paper Table 1
    "group_adapt": bench_group_adapt,  # paper Fig. 11 + 13
    "batched": bench_batched,        # paper Fig. 12
    "fp_bias": bench_fp_bias,        # paper Fig. 14
    "sweeps": bench_sweeps,          # paper Fig. 15
    "piecewise": bench_piecewise,    # paper Fig. 16
}

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench_env() -> dict:
    """The stamp that makes snapshots comparable: numbers taken on a
    different platform/device count — or in interpret mode, where the
    pallas paths emulate the kernel program instruction by instruction
    and predictably lose to plain XLA — must never be diffed as a perf
    trajectory.  (The CPU-CI snapshots showing pallas-fused behind
    reference are exactly that artifact.)

    ``interpret`` is false whenever only compiled programs were timed:
    on TPU always; elsewhere under ``--compiled``, which routes every
    timed case through XLA (``benchmarks/common.COMPILED``)."""
    import jax
    from benchmarks import common
    from repro.kernels.ops import on_tpu
    return {
        "platform": jax.default_backend(),
        "device_count": jax.device_count(),
        "interpret": not (on_tpu() or common.COMPILED),
        "jax": jax.__version__,
    }


def _snap_key(snap: dict):
    """The identity of one snapshot: env stamp + sizing.  Two runs with
    the same key are re-measurements of the same experiment (the newer
    wins); any difference — platform, interpret mode, device count, or
    problem sizing — makes them distinct experiments that must coexist
    in the file instead of clobbering each other."""
    return (json.dumps(snap.get("env", {}), sort_keys=True),
            json.dumps(snap.get("sizing", {}), sort_keys=True))


def _write_bench_json(path: str, bench: str, metric: str) -> None:
    """Persist one bench's rows as a {case: value} JSON snapshot under
    ``snapshots``, *merged by (env, sizing) stamp* with whatever the
    file already holds — so a compiled run lands next to the interpret
    baseline rather than overwriting it.  Pre-existing single-snapshot
    files (the PR-5 format: ``cases`` at top level) are converted to
    one snapshot on first merge.  Secondary metrics (e.g. the relay's
    rounds_to_completion / peak_slot_occupancy) ride along in
    ``extras``."""
    from benchmarks.common import SIZING
    rows = {r["case"]: r["value"] for r in ROWS
            if r["bench"] == bench and r["metric"] == metric}
    if not rows:
        return
    extras = {f"{r['case']}.{r['metric']}": r["value"] for r in ROWS
              if r["bench"] == bench and r["metric"] != metric}
    snap = {"env": _bench_env(), "sizing": SIZING.get(bench, {}),
            "cases": rows}
    if extras:
        snap["extras"] = extras

    snapshots = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
        except ValueError:
            old = {}
        if "snapshots" in old:
            snapshots = list(old["snapshots"])
        elif "cases" in old:                 # PR-5 single-snapshot format
            snapshots = [{k: old[k] for k in ("env", "sizing", "cases",
                                              "extras") if k in old}]
    snapshots = [s for s in snapshots if _snap_key(s) != _snap_key(snap)]
    snapshots.append(snap)
    doc = {"bench": bench, "metric": metric, "snapshots": snapshots}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path} ({len(snapshots)} snapshot(s))", flush=True)


def _dry_fused_smoke() -> None:
    """Compile-and-run the megakernel path once at toy scale (interpret
    mode) so CPU-only CI exercises the whole-walk entry end to end."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import walks
    from repro.core.dyngraph import BingoConfig, from_edges

    V = 16
    src = np.arange(V, dtype=np.int32)
    dst = (src + 1) % V
    cfg = BingoConfig(num_vertices=V, capacity=4, bias_bits=3,
                      backend="pallas")
    st = from_edges(cfg, src, dst, np.ones(V, np.int32) * 3)
    p = walks.random_walk(st, cfg, jnp.zeros((8,), jnp.int32),
                          jax.random.key(0),
                          walks.WalkParams(kind="deepwalk", length=5),
                          whole_walk=True)
    assert p.shape == (8, 6), p.shape
    assert (np.asarray(p) >= 0).all()
    print("# dry: pallas whole-walk megakernel smoke ok (interpret mode)")


def _dry_relay_smoke() -> None:
    """Run the sharded walk_relay path once at toy scale over however
    many host devices exist (1 on plain CI, 8 in the walk-relay job)
    and assert it is BIT-IDENTICAL to the single-shard whole walk —
    the DESIGN.md §10 exactness contract, end to end."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import walks
    from repro.core.backend import get_backend
    from repro.core.dyngraph import BingoConfig, from_edges
    from repro.distributed.relay import make_relay
    from repro.kernels.ops import seed_from_key

    S = len(jax.devices())
    V = 16 * S
    src = np.arange(V, dtype=np.int32)
    dst = (src + 1) % V                    # ring: crosses every boundary
    cfg = BingoConfig(num_vertices=V, capacity=4, bias_bits=3)
    st = from_edges(cfg, src, dst, np.ones(V, np.int32) * 3)
    B, L = 8 * S, 5
    starts = jnp.arange(B, dtype=jnp.int32) % V
    key = jax.random.key(0)
    params = walks.WalkParams(kind="deepwalk", length=L)
    single = walks.random_walk(st, cfg, starts, key, params,
                               backend="pallas", whole_walk=True)

    mesh = jax.make_mesh((S,), ("data",))
    relay = make_relay(get_backend("pallas"), cfg, params, mesh)
    paths, rounds, ovf = relay(st, starts, seed_from_key(key))
    assert np.array_equal(np.asarray(paths), np.asarray(single)), \
        "relay != single-shard walk"
    assert (np.asarray(paths) >= 0).all()   # ring never terminates
    print(f"# dry: walk_relay bit-identical to single-shard walk "
          f"({S} shard(s), {int(rounds)} round(s), overflow {int(ovf)})")


def _dry_update_smoke() -> None:
    """Run one batched round through BOTH EngineBackends at toy scale and
    assert bit-identical states — the update megakernel path end to end
    (interpret mode) on CPU-only CI."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core.backend import get_backend
    from repro.core.dyngraph import BingoConfig, from_edges

    V = 16
    src = np.arange(V, dtype=np.int32)
    dst = (src + 1) % V
    cfg = BingoConfig(num_vertices=V, capacity=4, bias_bits=3)
    st = from_edges(cfg, src, dst, np.ones(V, np.int32) * 3)
    ins = jnp.array([True, True, False, False])
    uu = jnp.array([0, 1, 2, 3], jnp.int32)
    vv = jnp.array([5, 6, 3, 9], jnp.int32)
    ww = jnp.array([2, 5, 1, 1], jnp.int32)
    outs = {b: get_backend(b).apply_updates(st, cfg, ins, uu, vv, ww)
            for b in ("reference", "pallas")}
    (st_r, stats_r), (st_p, stats_p) = outs["reference"], outs["pallas"]
    for a, b in zip(jax.tree.leaves((st_r, stats_r)),
                    jax.tree.leaves((st_p, stats_p))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(stats_r.ins_applied) == 2 and int(stats_r.del_applied) == 1
    print("# dry: pallas update megakernel bit-exact vs reference "
          "(interpret mode)")


def _dry_serving_smoke() -> None:
    """Run the continuous scheduler once at toy scale — mixed stream,
    guard on — and assert the §12 staleness contract end to end: the
    overlapped output is BIT-IDENTICAL to a serial replay of the
    recorded admission trace, and the backpressure counters conserve."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core.dyngraph import BingoConfig, from_edges
    from repro.core.walks import WalkParams
    from repro.serve.dynwalk import DynamicWalkEngine
    from repro.serve.scheduler import (SchedulerConfig, ServingScheduler,
                                       WalkOp, replay_admission_trace)

    V, C = 32, 8
    rng = np.random.default_rng(0)
    src = np.arange(V, dtype=np.int32)
    dst = (src + 1) % V
    w = np.full(V, 3, np.int32)
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=4)

    def mk():
        return DynamicWalkEngine(
            from_edges(cfg, src, dst, w), cfg,
            WalkParams(kind="deepwalk", length=5), seed=3, guard=True,
            walk_buckets=(8, 16))
    eng = mk()
    sched = ServingScheduler(eng, SchedulerConfig(update_lanes=4,
                                                  max_update_delay=2))
    for i in range(12):
        if i % 3 == 0:
            assert sched.submit_update(
                np.ones(2, bool), rng.integers(0, V, 2).astype(np.int32),
                rng.integers(0, V, 2).astype(np.int32),
                np.full(2, 2, np.int32))
        else:
            assert sched.submit_walk(
                rng.integers(0, V, int(rng.integers(1, 7)))
                .astype(np.int32)) is not None
        sched.tick()
    done = {r.rid: r for r in sched.drain()}
    sched.check_conservation()
    replayed = iter(replay_admission_trace(mk(), sched.trace))
    for op in sched.trace:
        if isinstance(op, WalkOp):
            rep = next(replayed)
            off = np.cumsum([0] + list(op.sizes))
            for j, rid in enumerate(op.rids):
                assert np.array_equal(done[rid].paths,
                                      rep[off[j]:off[j + 1]])
    gens = [done[r].generation for r in sorted(done)]
    assert gens == sorted(gens)
    print(f"# dry: scheduler replay bit-identical ({len(done)} walks, "
          f"{sched.generation} generations, guard on)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--dry", action="store_true",
                    help="import-check every bench module, run the fused "
                         "whole-walk smoke, and exit without timing "
                         "anything (CI smoke)")
    ap.add_argument("--compiled", action="store_true",
                    help="time XLA-compiled programs only and stamp the "
                         "snapshots interpret=false: real Mosaic kernels "
                         "on TPU; on CPU the fused rows route through the "
                         "jnp megawalk oracle and interpret-emulated "
                         "paths are pruned (benchmarks/bench_walks.py)")
    ap.add_argument("--micro", action="store_true",
                    help="dry-run-scale sizing (seconds, for CI compiled "
                         "snapshots); stamped into sizing so it can never "
                         "be diffed against a full-scale snapshot")
    args = ap.parse_args()
    only = [s for s in args.only.split(",") if s]
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache(Path(__file__).resolve().parent.parent)
    from benchmarks import common as _common
    _common.set_mode(compiled=args.compiled, micro=args.micro)

    if args.dry:
        from repro.core.backend import available_backends
        for name, mod in MODULES.items():
            assert callable(mod.main), name
            print(f"# dry: {name} -> {mod.__name__}.main")
        print(f"# dry: engine backends {available_backends()}")
        _dry_fused_smoke()
        _dry_update_smoke()
        _dry_relay_smoke()
        _dry_serving_smoke()
        return

    print("bench,case,metric,value")
    failed = []
    for name, mod in MODULES.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            mod.main()
        except Exception:
            failed.append(name)
            traceback.print_exc()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)

    out = os.path.join(os.path.dirname(__file__), "..", "experiments")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "bench_results.csv"), "w", newline="") as f:
        wr = csv.DictWriter(f, fieldnames=["bench", "case", "metric",
                                           "value"])
        wr.writeheader()
        wr.writerows(ROWS)
    _write_bench_json(os.path.join(REPO_ROOT, "BENCH_walks.json"),
                      "walks", "steps_per_sec")
    _write_bench_json(os.path.join(REPO_ROOT, "BENCH_updates.json"),
                      "updates", "updates_per_s")
    _write_bench_json(os.path.join(REPO_ROOT, "BENCH_serving.json"),
                      "serving", "walks_per_s")
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == '__main__':
    main()
