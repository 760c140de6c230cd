#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest query rate at which the
walk p95 meets the mix's ``latency_limit_ms`` with no growing backlog.

    python3 chipbench/sweep.py --workload g500-s17.serve --seed 5 \
        --seconds 10 --rates 50 100 200 400

Runs the cell's driver once per rate in one process, with the mix's
``rate_qps`` replaced, and prints one JSON line per rate: the walk p95,
the update-visible p95, the queries refused, and how long after the last
due time the last query was served (a backlog that grows through the
run shows as a long tail).  The cell then runs at about 4/5 of the knee,
fixed in its mix file.  Needs the chips the cell asks for.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
if sys.path and Path(sys.path[0] or ".").resolve() == HERE:
    sys.path.pop(0)
for p in (str(HERE.parent / "src"), str(HERE.parent)):
    sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench import harness, manifest
    from chipbench.drivers import serve
    from chipbench.run import use_compile_cache
    use_compile_cache(HERE.parent)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    bench = manifest.Manifest(HERE.parent)
    cell = bench.cell(args.workload)
    for rate in args.rates:
        mix = dict(bench.mix(cell), rate_qps=rate)
        r = harness.Run(root=HERE.parent, cell=cell,
                        config=bench.config(cell), mix=mix, seed=args.seed,
                        seconds=args.seconds, trace=False,
                        devices=jax.devices()[:cell["chips"]],
                        t_process=time.perf_counter(),
                        log=lambda *a: print(*a, file=sys.stderr))
        serve.run(r)
        c = r.counters
        print(json.dumps({
            "rate_qps": rate, "walk_p95_ms": r.e2e["walk_p95_ms"],
            "update_visible_p95_ms": r.e2e["update_visible_p95_ms"],
            "queries": c["queries"], "failed": r.failed,
            "served_after_s": c["served_after_s"],
            "gen_lag_p95_ms": c["gen_lag_p95_ms"],
            "correct": r.correct}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
