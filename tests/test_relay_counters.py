"""The relay's counters on four virtual CPU devices: the walker records
it delivers across shards, its rounds against ``round_bound``, the
segment kernel's loop steps, and the sharded engine's ``relay_stats()``
totals over several walks, for the bulk and the overlapped schedule.

A walker record crosses shards once per hop whose two vertices lie on
different shards, so the delivered count of each shard must equal the
hops into it that numpy counts from the returned paths.  The checks run
in one child process that asks XLA for four host devices before JAX
starts; the tests read its report.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
SCHEDULES = ["bulk", "overlap"]

_CHILD = """
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import walks
from repro.core.backend import get_backend
from repro.core.dyngraph import BingoConfig, from_edges
from repro.distributed.relay import make_relay, round_bound, slot_count
from repro.kernels.ops import seed_from_key
from repro.serve.dynwalk import DynamicWalkEngine
from tests.conftest import random_graph

S, V, C, W, L = 4, 64, 8, 32, 8
src, dst, w = random_graph(V, C, max_bias=15, seed=5)
cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=4,
                  backend="reference")
params = walks.WalkParams(kind="deepwalk", length=L)
mesh = jax.make_mesh((S,), ("v",), axis_types=(jax.sharding.AxisType.Auto,))
bk = get_backend("reference")
starts = jnp.arange(W, dtype=jnp.int32) * 7 % V


def state():
    return from_edges(cfg, src, dst, w)


def crossings(paths):
    # hops into each shard from another one, counted from the paths
    p = np.asarray(paths)
    a, b = p[:, :-1], p[:, 1:]
    hop = (a >= 0) & (b >= 0) & (a // (V // S) != b // (V // S))
    return np.bincount(b[hop] // (V // S), minlength=S).tolist()


out = {}
key0 = jax.random.key(3)
single = np.asarray(walks.random_walk(state(), cfg, starts, key0, params,
                                      backend="pallas"))
for sched in ("bulk", "overlap"):
    overlap = sched == "overlap"
    rep = {}
    for cap in (None, 1):
        relay = jax.jit(make_relay(bk, cfg, params, mesh, overlap=overlap,
                                   mailbox_cap=cap, counters=True))
        paths, rounds, ovf, dlv, _ = relay(state(), starts,
                                           seed_from_key(key0))
        rep[str(cap)] = {
            "exact": bool(np.array_equal(np.asarray(paths), single)),
            "rounds": int(rounds), "overflow": int(ovf),
            "bound": round_bound(W, L, S, mailbox_cap=cap, overlap=overlap),
            "delivered": np.asarray(dlv).tolist(),
            "numpy": crossings(paths)}
    eng = DynamicWalkEngine(state(), cfg, params, mesh=mesh,
                            relay_overlap=overlap, seed=11)
    relay = jax.jit(make_relay(bk, cfg, params, mesh, overlap=overlap,
                               counters=True))
    want = {"rounds": 0, "overflow": 0, "segment_steps": 0,
            "delivered": [0] * S}
    programs = []
    for b in range(3):
        key = jax.random.key(100 + b)
        paths = eng.walk(jnp.roll(starts, 5 * b), key=key)
        _, r, o, d, g = relay(state(), jnp.roll(starts, 5 * b),
                              seed_from_key(key))
        want["rounds"] += int(r)
        want["overflow"] += int(o)
        want["segment_steps"] += int(g)
        want["delivered"] = [x + y for x, y in
                             zip(want["delivered"], crossings(paths))]
        programs.append(eng.walk_cache_size())
    rep["engine"] = {"stats": eng.relay_stats(), "want": want,
                     "programs": programs}
    # the totals carry past one 30-bit limb without wrapping
    eng2 = DynamicWalkEngine(state(), cfg, params, mesh=mesh,
                             relay_overlap=overlap, seed=11)
    low = (1 << 30) - 1
    eng2._relay_tally = jax.device_put(
        jnp.zeros((2, 3 + S), jnp.int32).at[1].set(low),
        NamedSharding(mesh, P()))
    paths = eng2.walk(starts, key=jax.random.key(100))
    rep["carry"] = {"stats": eng2.relay_stats(), "low": low,
                    "numpy": crossings(paths)}
    out[sched] = rep
# the pallas segment kernel's own step counts, on the overlapped relay
peng = DynamicWalkEngine(state(), cfg, params, mesh=mesh, seed=11,
                         backend="pallas")
prelay = jax.jit(make_relay(get_backend("pallas"), cfg, params, mesh,
                            overlap=True, counters=True))
per = []
for b in range(2):
    key = jax.random.key(200 + b)
    peng.walk(jnp.roll(starts, 3 * b), key=key)
    _, r, _, _, g = prelay(state(), jnp.roll(starts, 3 * b),
                           seed_from_key(key))
    per.append([int(r), int(g)])
Wl = slot_count(W, S)
out["pallas"] = {"stats": peng.relay_stats(), "per_batch": per,
                 "tiles": -(-Wl // min(256, Wl))}
try:
    DynamicWalkEngine(state(), cfg, params).relay_stats()
    out["single"] = "no error"
except ValueError as e:
    out["single"] = str(e)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(REPO / "src"), str(REPO)]), JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", _CHILD], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cap", ["None", "1"])
@pytest.mark.parametrize("schedule", SCHEDULES)
def test_delivered_equals_cross_shard_hops(report, schedule, cap):
    """Each shard's delivered count is the paths' hops into it from
    another shard; with a one-record mailbox (spills resent every
    round) a record still counts once, when it lands."""
    got = report[schedule][cap]
    assert got["exact"]
    assert got["delivered"] == got["numpy"]
    assert sum(got["delivered"]) > 0
    if cap == "1":
        assert got["overflow"] > 0


@pytest.mark.parametrize("cap", ["None", "1"])
@pytest.mark.parametrize("schedule", SCHEDULES)
def test_rounds_within_round_bound(report, schedule, cap):
    got = report[schedule][cap]
    assert 1 <= got["rounds"] <= got["bound"]


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_relay_stats_sums_the_walks(report, schedule):
    """``relay_stats()`` is the sum of each walk's relay counts, read
    once; the carried totals add no walk program from walk to walk."""
    got = report[schedule]["engine"]
    stats, want = got["stats"], got["want"]
    assert stats["batches"] == 3
    assert stats["rounds"] == want["rounds"]
    assert stats["overflow"] == want["overflow"]
    assert stats["segment_steps"] == want["segment_steps"]
    assert stats["delivered_per_shard"] == want["delivered"]
    assert stats["delivered"] == sum(want["delivered"])
    assert got["programs"][-1] == got["programs"][-2]


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_relay_stats_carry_past_one_limb(report, schedule):
    got = report[schedule]["carry"]
    per = [got["low"] + n for n in got["numpy"]]
    assert got["stats"]["delivered_per_shard"] == per
    assert got["stats"]["delivered"] == sum(per)
    assert got["stats"]["rounds"] > got["low"]
    assert got["stats"]["overflow"] >= got["low"]
    assert got["stats"]["segment_steps"] > got["low"]


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_relay_stats_segment_steps(report, backend):
    """``segment_steps`` sums each walk's segment-kernel loop steps, and
    is at most ``rounds × shards × tiles × L``: the reference scan runs
    every step of its one tile; the kernel's tiles run only the steps
    their walkers occupy, so it falls short."""
    if backend == "reference":
        got = report["overlap"]["engine"]
        stats, want = got["stats"], got["want"]["segment_steps"]
        tiles = 1
    else:
        got = report["pallas"]
        stats, tiles = got["stats"], got["tiles"]
        want = sum(g for _, g in got["per_batch"])
        assert stats["rounds"] == sum(r for r, _ in got["per_batch"])
    assert stats["segment_steps"] == want
    bound = stats["rounds"] * 4 * tiles * 8
    assert 0 < stats["segment_steps"] <= bound
    if backend == "reference":
        assert stats["segment_steps"] == bound
    else:
        assert stats["segment_steps"] < bound


def test_relay_stats_needs_a_mesh(report):
    assert "mesh=" in report["single"]
