"""Helper of ``test_cb_relay``: the tiny four-shard corpus run of
``x4_run`` on four virtual CPU devices, traced, with the relay's
per-layer metrics listed for the tiny cell.  Prints the run's result
object."""

import json
import os
import sys
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

from chipbench import run, xplane  # noqa: E402
from chipbench.tests import tiny  # noqa: E402

CELL = "tiny.tiny-corpus"


def main(path):
    root = tiny.make_root(path, {"tiny-corpus": tiny.MIXES["tiny-corpus"]})
    m = json.loads((root / "BENCHMARK.json").read_text())
    for w in m["workloads"]:
        if w["name"] == CELL:
            w["chips"] = 4
    for x in m["per_layer"]:
        if "g500-s19x4.corpus" in x.get("workloads", ()):
            x["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    peaks = xplane.peaks
    xplane.peaks = lambda kind: peaks("TPU v5e")     # a CPU has none
    out = run.run_cell(root, CELL, 11, 1.0, 1, jax.devices(),
                       t_process=time.perf_counter())
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1])
