#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout.  The cell (``BENCHMARK.json``) names a
configuration (``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/mixes/<traffic>.json``), whose ``driver`` is one of
``chipbench/drivers``.  The run makes the graph and the traffic from
``--seed`` on the host, builds the program's state on the chip, warms
up the cell's programs, measures for ``--seconds``, compares the
window's outputs with the plain reference (``chipbench/reference.py``)
and prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones, read by
``chipbench/layers/<metric>.py`` from the trace), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: each compared number
beside its limit, which also close standard error.

Without a TPU, with fewer chips than the cell asks for, or without the
program's sources (``src/repro``) the run exits nonzero and prints no
result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if sys.path and Path(sys.path[0] or ".").resolve() == HERE:
    sys.path.pop(0)      # import this directory only as ``chipbench``


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (``JAX_COMPILATION_CACHE_DIR`` wins where set), every program kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def run_cell(root, workload, seed, seconds, trace, devices,
             t_process=T_PROCESS):
    """Drive one cell on ``devices``; returns the result object."""
    import importlib
    from chipbench import harness, manifest
    bench = manifest.Manifest(root)
    cell = bench.cell(workload)
    mix = bench.mix(cell)
    r = harness.Run(root=Path(root), cell=cell, config=bench.config(cell),
                    mix=mix, seed=seed, seconds=seconds, trace=bool(trace),
                    devices=devices[:cell["chips"]], t_process=t_process,
                    log=log)
    driver = importlib.import_module(f"chipbench.drivers.{mix['driver']}")
    driver.run(r)
    d0 = devices[0]
    out = {"correct": r.correct, "attempted": int(r.attempted),
           "failed": int(r.failed)}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": r.memory_peak_bytes}
    if trace:
        from chipbench import xplane as tr
        spans, devs = tr.load_events(tr.find_xplane(r.trace_dir))
        red = tr.reduce_trace(spans, devs)
        facts = {"trace": red, "host": dict(r.host), "counters": r.counters,
                 "peaks": tr.peaks(d0.device_kind), "mix": mix,
                 "config": r.config, "window_s": r.window_s,
                 "chips": cell["chips"]}
        metrics = {}
        for m in bench.per_layer(cell):
            v = bench.reader(m["name"])(facts)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    else:
        out["metrics"] = {m["name"]: {"value": r.e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in bench.end_to_end(cell)}
        out["device"] = device
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in r.checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log("chipbench: the program's sources (src/repro) are not in this "
            "checkout")
        return 2
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import manifest
    cell = manifest.Manifest(ROOT).cell(args.workload)
    log(f"chipbench: compile cache {use_compile_cache(ROOT)}")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"chipbench: no TPU (JAX found {devices[0].platform}); the "
            "benchmark does not run on anything else")
        return 1
    if len(devices) < cell["chips"]:
        log(f"chipbench: {args.workload} needs {cell['chips']} chips, "
            f"JAX found {len(devices)}")
        return 1
    from chipbench.xplane import peaks
    peaks(devices[0].device_kind)      # an unknown device is an error
    out = run_cell(ROOT, args.workload, args.seed, args.seconds, args.trace,
                   devices)
    for k, c in out["checks"].items():
        log(f"check {k} = {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
