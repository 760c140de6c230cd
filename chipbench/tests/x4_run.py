"""Helper of ``test_cb_x4``: one tiny four-shard corpus run on four
virtual CPU devices, optionally with the relay's exchange left out.
Prints the run's result object."""

import json
import os
import sys
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import run  # noqa: E402
from chipbench.tests import tiny  # noqa: E402


def main(path, fault):
    root = tiny.make_root(path, {"tiny-corpus": tiny.MIXES["tiny-corpus"]})
    m = json.loads((root / "BENCHMARK.json").read_text())
    for w in m["workloads"]:
        if w["name"] == "tiny.tiny-corpus":
            w["chips"] = 4
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    if fault == "no_exchange":
        from repro.distributed import relay

        def exchange_walkers(payload, shard_size, num_shards, axis,
                             cap=None):
            cap = payload.shape[0] // num_shards if cap is None else cap
            shape = (num_shards * cap,) + payload.shape[1:]
            return (jnp.full(shape, -1, payload.dtype),
                    jnp.full(payload.shape, -1, payload.dtype),
                    jnp.zeros((), jnp.int32))
        relay.exchange_walkers = exchange_walkers
    out = run.run_cell(root, "tiny.tiny-corpus", 11, 1.0, 0, jax.devices(),
                       t_process=time.perf_counter())
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
