"""Program spans, the engine's and scheduler's counters, and the named
device scopes a profiler trace reads (``serve/spans.py``).

Spans: parent links, request ids shared by a request's spans, the ring
bound, ``summary()``.  Host syncs: every device-to-host read the engine
makes is one ``engine.read`` span (the same reads the deferred-ingest
tripwire of ``test_scheduler.py`` sees).  Counters: the engine's
pad-lane counters are bucket or window size minus the real lanes, and
the scheduler counts its cohorts and deadline flushes.  Scopes: the program names and scope
names appear in the lowered programs' debug locations, on one device
and in the relay on four.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.dyngraph import BingoConfig, from_edges
from repro.core.updates import make_updater
from repro.core.walks import WalkParams, make_walker
from repro.serve import dynwalk as dynwalk_mod
from repro.serve import spans
from repro.serve.dynwalk import DynamicWalkEngine
from repro.serve.guard import make_classifier
from repro.serve.scheduler import (SchedulerConfig, ServingScheduler,
                                   UpdateOp, WalkOp)
from tests.conftest import random_graph

V, C = 64, 8
REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _fresh_log():
    spans.reset()
    yield
    spans.reset()


def _engine(**kw):
    src, dst, w = random_graph(V, C, max_bias=15, seed=3)
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=4)
    return DynamicWalkEngine(from_edges(cfg, src, dst, w), cfg,
                             WalkParams(kind="deepwalk", length=6), seed=7,
                             **kw)


def _rounds(n, lanes=4, seed=3):
    rng = np.random.default_rng(seed)
    out = [(jnp.asarray(rng.random(lanes) < 0.7),
            jnp.asarray(rng.integers(-2, V, lanes), jnp.int32),
            jnp.asarray(rng.integers(0, V, lanes), jnp.int32),
            jnp.full((lanes,), 2, jnp.int32)) for _ in range(n)]
    jax.block_until_ready(out)
    return out


def test_span_log_links_parents_and_shares_rids():
    log = spans.SpanLog()
    with log.span("outer"):
        with log.span("inner", rid=5):
            pass
        with log.span("inner"):
            pass
    log.add("queued", 1.0, 1.5, rid=5)
    by = {}
    for r in log.records():
        by.setdefault(r.name, []).append(r)
    (outer,) = by["outer"]
    assert outer.parent is None
    assert [r.parent for r in by["inner"]] == [outer.sid, outer.sid]
    assert all(outer.t0 <= r.t0 <= r.t1 <= outer.t1 for r in by["inner"])
    assert {r.rid for r in log.records() if r.rid is not None} == {5}
    assert by["queued"][0].parent is None
    s = log.summary()
    assert s["inner"]["count"] == 2 and s["outer"]["count"] == 1
    assert s["queued"] == {"count": 1, "total_s": 0.5, "max_s": 0.5}
    assert s["outer"]["total_s"] >= s["inner"]["total_s"]


def test_span_ring_is_bounded_and_summary_outlives_it():
    log = spans.SpanLog(capacity=4)
    for i in range(10):
        with log.span("s", rid=i):
            pass
    recs = log.records()
    assert [r.rid for r in recs] == [6, 7, 8, 9]
    assert log.summary()["s"]["count"] == 10
    log.reset()
    assert log.records() == [] and log.summary() == {}


def test_span_closes_on_error():
    log = spans.SpanLog()
    with pytest.raises(ValueError):
        with log.span("outer"):
            with log.span("inner"):
                raise ValueError
    with log.span("after"):
        pass
    assert [r.name for r in log.records()] == ["inner", "outer", "after"]
    assert log.records()[-1].parent is None


def test_engine_spans_nest_under_ingest_and_walk():
    eng = _engine(guard=True, defer_guard=True, walk_buckets=(8, 16))
    for r in _rounds(3):
        eng.ingest(*r)
    eng.drain_guard()
    eng.walk(np.arange(5, dtype=np.int32))
    recs = spans.records()
    names = {r.sid: r.name for r in recs}
    parent_of = {}
    for r in recs:
        parent_of.setdefault(r.name, set()).add(names.get(r.parent))
    assert parent_of["engine.ingest"] == {None}
    for child in ("engine.classify", "engine.update", "engine.tally"):
        assert parent_of[child] == {"engine.ingest"}
    assert parent_of["guard.account"] == {"engine.drain_guard"}
    assert parent_of["engine.key"] == {"engine.walk"}
    s = spans.summary()
    assert s["engine.ingest"]["count"] == 3
    assert s["engine.walk"]["count"] == 1


def test_engine_counts_pad_lanes():
    eng = _engine(walk_buckets=(8, 16))
    for n_valid, r in zip((4, 1, 3), _rounds(3)):
        eng.ingest(*r, n_valid=n_valid)
    assert eng.pad_lanes == 0 + 3 + 1
    for n in (5, 8, 9):
        eng.walk(np.arange(n, dtype=np.int32))
    assert eng.walk_pad_lanes == (8 - 5) + 0 + (16 - 9)
    assert eng.walks_served == 22


def _reads():
    return spans.summary().get("engine.read", {"count": 0})["count"]


def test_host_syncs_match_the_tripwire():
    """Every device-to-host read the engine makes goes through
    ``np.asarray`` of a device array and is one ``engine.read`` span:
    none on the deferred ingest path, five arrays and the delete count
    per round at the drain, all inside ``guard.account``."""
    eng = _engine(guard=True, defer_guard=True)
    rounds = _rounds(5)
    real = dynwalk_mod.np.asarray
    seen = [0]

    def tripwire(x, *a, **k):
        if isinstance(x, jax.Array):
            seen[0] += 1
        return real(x, *a, **k)

    dynwalk_mod.np.asarray = tripwire
    try:
        for r in rounds:
            eng.ingest(*r)
        assert seen[0] == _reads() == 0
        assert eng.drain_guard() == 5
    finally:
        dynwalk_mod.np.asarray = real
    assert _reads() == seen[0] >= 5 * 6
    recs = spans.records()
    account = {r.sid for r in recs if r.name == "guard.account"}
    assert all(r.parent in account for r in recs if r.name == "engine.read")


def test_round_mode_host_syncs_match_the_tripwire():
    eng = _engine(guard=True)
    real = dynwalk_mod.np.asarray
    seen = [0]

    def tripwire(x, *a, **k):
        if isinstance(x, jax.Array):
            seen[0] += 1
        return real(x, *a, **k)

    dynwalk_mod.np.asarray = tripwire
    try:
        for r in _rounds(3):
            eng.ingest(*r)
        eng.audit()
        eng.max_fill()
    finally:
        dynwalk_mod.np.asarray = real
    assert _reads() == seen[0] >= 3 * 6 + 2


def test_scheduler_pad_counters_and_request_spans():
    eng = _engine(guard=True, walk_buckets=(8, 16, 32))
    sched = ServingScheduler(eng, SchedulerConfig(update_lanes=16,
                                                  max_update_delay=2))
    rng = np.random.default_rng(0)
    rids = []
    for i in range(30):
        if i % 3 == 0:
            assert sched.submit_update(
                rng.random(5) < 0.7, rng.integers(0, V, 5).astype(np.int32),
                rng.integers(0, V, 5).astype(np.int32),
                np.full(5, 2, np.int32))
        else:
            rids.append(sched.submit_walk(
                rng.integers(0, V, int(rng.integers(1, 12))).astype(
                    np.int32)))
        sched.tick()
    sched.drain()
    st = sched.stats()
    walks = [op for op in sched.trace if isinstance(op, WalkOp)]
    upds = [op for op in sched.trace if isinstance(op, UpdateOp)]
    buckets = [min(b for b in (8, 16, 32) if b >= len(op.starts))
               for op in walks]
    assert st["cohorts"] == len(walks) > 0
    assert eng.walk_pad_lanes == sum(
        b - len(op.starts) for b, op in zip(buckets, walks)) > 0
    assert eng.pad_lanes == sum(len(op.u) - op.n_valid for op in upds) > 0
    assert eng.rounds_ingested == len(upds)
    assert 0 < st["deadline_flushes"] <= len(upds)
    recs = spans.records()
    for name in ("sched.queue", "sched.inflight"):
        got = sorted(r.rid for r in recs if r.name == name)
        assert got == sorted(rids)
    ticks = {r.sid for r in recs if r.name == "sched.tick"}
    assert len(ticks) == 30
    for name in ("sched.flush", "sched.dispatch", "sched.harvest"):
        assert any(r.parent in ticks for r in recs if r.name == name)
    engine_ops = [r for r in recs if r.name in ("engine.ingest",
                                                 "engine.walk")]
    parents = {r.sid: r.name for r in recs}
    assert {parents.get(r.parent) for r in engine_ops} <= {
        "sched.flush", "sched.dispatch"}


def _lowered(program):
    src, dst, w = random_graph(V, C, max_bias=15, seed=3)
    cfg = BingoConfig(num_vertices=V, capacity=C, bias_bits=4,
                      backend="pallas")
    st = from_edges(cfg, src, dst, w)
    B = 16
    lanes = (jnp.ones(B, bool), jnp.zeros(B, jnp.int32),
             jnp.ones(B, jnp.int32), jnp.full(B, 2, jnp.int32))
    if program == "engine_update":
        low = make_updater(cfg, with_active=True).lower(
            st, *lanes, jnp.ones(B, bool))
    elif program == "engine_walk":
        low = make_walker(st, cfg, WalkParams(kind="deepwalk", length=4),
                          whole_walk=True).lower(
            st, jnp.arange(8, dtype=jnp.int32), jax.random.key(0))
    else:
        low = make_classifier(cfg).lower(st, *lanes)
    return low.as_text(debug_info=True)


@pytest.mark.parametrize("program, scopes", [
    ("engine_update", ["update.prepass", "update.relayout", "update.kernel",
                       "update_round", "update.epilogue", "alias_rows",
                       "writeback"]),
    ("engine_walk", ["walk.relayout", "walk.kernel", "walk_whole"]),
    ("classify", ["guard.classify"]),
])
def test_scope_names_in_lowered_program(program, scopes):
    text = _lowered(program)
    assert re.search(rf"module @jit_{program}\b", text)
    for scope in scopes:
        assert re.search(rf'"[^"]*\b{re.escape(scope)}/', text), scope


_RELAY = """
import json, re
import numpy as np
import jax, jax.numpy as jnp
from repro.core.dyngraph import BingoConfig, from_edges
from repro.core.walks import WalkParams
from repro.serve.dynwalk import DynamicWalkEngine
from tests.conftest import random_graph
src, dst, w = random_graph(64, 8, max_bias=15, seed=3)
cfg = BingoConfig(num_vertices=64, capacity=8, bias_bits=4,
                  backend="pallas")
mesh = jax.make_mesh((4,), ("v",), axis_types=(jax.sharding.AxisType.Auto,))
eng = DynamicWalkEngine(from_edges(cfg, src, dst, w), cfg,
                        WalkParams(kind="deepwalk", length=4), mesh=mesh)
walk = eng._walk.lower(eng.state, eng._relay_tally,
                       jnp.arange(8, dtype=jnp.int32),
                       jax.random.key(0)).as_text(debug_info=True)
b = jnp.ones(8, bool)
upd = eng._update.lower(eng.state, b, jnp.zeros(8, jnp.int32),
                        jnp.ones(8, jnp.int32), jnp.full(8, 2, jnp.int32),
                        b).as_text(debug_info=True)
pat = r"(?:relay|walk|update)\\.[a-z]+|walk_segment"
print(json.dumps({"devices": len(jax.devices()),
                  "walk_module": re.search(r"module @(\\w+)", walk)[1],
                  "update_module": re.search(r"module @(\\w+)", upd)[1],
                  "walk": sorted(set(re.findall(pat, walk))),
                  "update": sorted(set(re.findall(pat, upd)))}))
"""


def test_relay_scope_names_on_four_devices():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(REPO / "src"), str(REPO)]), JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", _RELAY], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["walk_module"] == "jit_engine_walk"
    assert out["update_module"] == "jit_engine_update"
    for scope in ("relay.segment", "relay.exchange", "relay.merge",
                  "walk.relayout", "walk.kernel", "walk_segment"):
        assert scope in out["walk"], scope
    for scope in ("update.prepass", "update.kernel", "update.epilogue"):
        assert scope in out["update"], scope
