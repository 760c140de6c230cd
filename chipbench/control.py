#!/usr/bin/env python3
"""The control at a cell's own size: the plain reference in the
program's place, its biases cut to 8 bits per row (``int8_biases``),
the nearest precision below the configuration's 16-bit integers.

    python3 chipbench/control.py --workload <cell> --seeds 11 12 13 \
        [--seconds 10]

For each seed it drives the cell through ``run.run_cell``, so through
the cell's own driver, traffic and comparison, with ``int8_control()``
in force, and prints the numbers the run compares and ``correct``, one
JSON line per seed.  A limit sits between the program's largest reading
over its seeds and the control's smallest.  Needs the chips the cell
asks for; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if sys.path and Path(sys.path[0] or ".").resolve() == HERE:
    sys.path.pop(0)

import numpy as np  # noqa: E402


@contextlib.contextmanager
def int8_control():
    """Put the control in the program's place for the runs inside:

    * the state is built from the graph with every bias cut to 8 bits
      of its row (the row's largest bias over the edge universe);
    * each update lane's bias is cut the same way before the program's
      ``ingest`` applies it, and the lanes are applied to a host model;
    * walks are drawn by the reference walker on that model, at the
      cut biases, in place of the program's ``walk``.
    """
    import jax.numpy as jnp
    from chipbench import gen, harness
    from chipbench.reference import Reference, int8_biases
    from repro.serve.dynwalk import DynamicWalkEngine

    model = {}
    saved = (gen.make_graph, harness.build_state, DynamicWalkEngine.ingest,
             DynamicWalkEngine.walk)
    make_graph, build_state, ingest, _ = saved

    def cut_graph(cfg, seed, holdout=0.0):
        g = make_graph(cfg, seed, holdout)
        ref = Reference(g)
        model.update(graph=g, ref=ref, live=g.live.copy(), calls=0,
                     seed=seed, w8=int8_biases(ref, np.ones_like(g.live)))
        return g

    def cut_build(run, graph, mesh=None):
        w8 = model["w8"].astype(np.int32)
        return build_state(run, graph._replace(w=w8), mesh)

    def cut_ingest(self, is_insert, u, v, w, *, n_valid=None):
        ins, uu, vv = (np.asarray(x) for x in (is_insert, u, v))
        ids = model["ref"].edge_ids(uu.astype(np.int64), vv)
        w8 = np.where(ids >= 0, model["w8"][np.maximum(ids, 0)],
                      np.asarray(w)).astype(np.int32)
        n = len(ids) if n_valid is None else int(n_valid)
        known = ids[:n] >= 0
        gen.apply_lanes(model["live"], ins[:n][known], ids[:n][known])
        return ingest(self, is_insert, u, v, jnp.asarray(w8),
                      n_valid=n_valid)

    def reference_walk(self, starts, key=None):
        model["calls"] += 1
        rng = gen.rng_for(model["seed"], 1000 + model["calls"])
        p = self.params
        paths = model["ref"].walk(model["live"], np.asarray(starts),
                                  p.length, p.stop_prob, rng, model["w8"])
        return jnp.asarray(paths.astype(np.int32))

    gen.make_graph, harness.build_state = cut_graph, cut_build
    DynamicWalkEngine.ingest = cut_ingest
    DynamicWalkEngine.walk = reference_walk
    try:
        yield model
    finally:
        (gen.make_graph, harness.build_state, DynamicWalkEngine.ingest,
         DynamicWalkEngine.walk) = saved


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import manifest, run
    bench = manifest.Manifest(ROOT)
    cell = bench.cell(args.workload)
    run.use_compile_cache(ROOT)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("control: needs the chips the cell asks for", file=sys.stderr)
        return 1
    seconds = args.seconds or bench.data["run_seconds"]
    for seed in args.seeds:
        with int8_control():
            out = run.run_cell(ROOT, args.workload, seed, seconds, 0,
                               devices, t_process=time.perf_counter())
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": out["correct"],
            "control": {k: c["value"] for k, c in out["checks"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
