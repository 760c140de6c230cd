"""Scope reduction (``scopes.py``): self times and dispatch waits on a
made-up trace, and on small traces recorded on a TPU v5e — two from
before the program named its scopes, one after — that self times add
up to busy time, that the classifier's scope holds its program's device
time, that a layout copy of the update kernel's output is the update's
relayout, and that the existing reduction (``xplane.reduce_trace``)
still reads as it did."""

import json
import os
import shutil
from pathlib import Path

import pytest

from chipbench import manifest, scopes, xplane
from chipbench.tests import tiny

DATA = Path(__file__).parent / "data"
TRACES = ["tiny-corpus", "tiny-ingest", "tiny-scoped-ingest"]
US = 1000


def _path(name):
    return DATA / f"{name}.xplane.pb"


def test_self_times_and_scopes_of_a_made_up_trace():
    ops = [
        (0, 100 * US, "jit(engine_update)/update.prepass/while/body/x:"),
        (10 * US, 30 * US, "jit(engine_update)/update.prepass/while/body/y"),
        (40 * US, 60 * US, "jit(engine_update)/update.prepass/while/body/z"),
        (100 * US, 150 * US,
         "jit(engine_update)/update.epilogue/alias_rows/mul"),
        (150 * US, 160 * US, "jit(add)/add"),
        (300 * US, 400 * US,
         "jit(engine_walk)/relay.segment/walk.kernel/walk_segment/pc")]
    assert scopes.self_times(ops) == [60 * US, 20 * US, 20 * US, 50 * US,
                                      10 * US, 100 * US]
    spans = [("cb.window", 0, 1000 * US), ("cb.dispatch", 0, 500 * US),
             ("engine.walk", 200 * US, 290 * US),
             ("sched.tick", 900 * US, 950 * US)]
    waits = [(210 * US, 250 * US), (240 * US, 260 * US),
             (280 * US, 320 * US)]
    dev = {"ops": ops, "modules": [("jit_engine_update(3)", 0, 150 * US),
                                   ("jit_add(4)", 150 * US, 160 * US),
                                   ("jit_engine_walk(5)", 300 * US,
                                    400 * US)]}
    red = scopes.reduce(spans, {"/device:TPU:0": dev}, waits)
    assert red["busy_s"] == pytest.approx(260e-6)
    assert red["self_s"] == pytest.approx(260e-6)
    assert red["scope_s"] == pytest.approx({
        "update.prepass": 100e-6, "update.epilogue": 50e-6,
        "walk.kernel": 100e-6, "none": 10e-6})
    assert red["under_s"]["relay.segment"] == pytest.approx(100e-6)
    assert red["under_s"]["alias_rows"] == pytest.approx(50e-6)
    assert red["under_s"]["jit(engine_update)"] == pytest.approx(150e-6)
    assert red["launches"] == {"jit_engine_update": 1, "jit_add": 1,
                               "jit_engine_walk": 1}
    # the walk span waited 210..260 and 280..290 for a dispatch slot
    assert red["spans"] == {"engine.walk": (1, pytest.approx(90e-6),
                                            pytest.approx(90e-6),
                                            pytest.approx(60e-6)),
                            "sched.tick": (1, pytest.approx(50e-6),
                                           pytest.approx(50e-6), 0.0)}
    # each idle gap goes to the innermost span at its middle
    assert red["idle_gaps"] == pytest.approx({
        "engine.walk": 140e-6, "none": 600e-6})     # 160..300, 400..1000


def test_path_of_drops_the_operation():
    assert scopes.path_of("jit(classify)/jit(argsort)/iota:") == [
        "jit(classify)", "jit(argsort)"]
    assert scopes.path_of("") == []


@pytest.mark.parametrize("name", TRACES)
def test_recorded_self_times_add_up_to_busy_time(name):
    red = scopes.reduce(*scopes.load(_path(name)))
    old = xplane.reduce_trace(*xplane.load_events(str(_path(name))))
    assert red["window_s"] == pytest.approx(old["window_s"], abs=1e-6)
    assert red["busy_s"] == pytest.approx(old["busy_s"], abs=1e-6)
    assert 0 < red["self_s"] == pytest.approx(red["busy_s"], rel=0.02)
    assert sum(red["scope_s"].values()) == pytest.approx(red["self_s"])


@pytest.mark.parametrize("name", ["tiny-ingest", "tiny-scoped-ingest"])
def test_classifier_scope_holds_its_program(name):
    """The self time of operations under ``jit(classify)`` is the busy
    time inside the ``jit_classify`` programs, and close to their
    length (which also counts the gaps between their operations)."""
    spans, devices, _ = scopes.load(_path(name))
    red = scopes.reduce(spans, devices)
    (dev,) = devices.values()
    t0, t1 = next((s, e) for n, s, e in spans if n == "cb.window")
    mods = xplane._union([(max(s, t0), min(e, t1))
                          for n, s, e in dev["modules"]
                          if n.startswith("jit_classify(") and e > t0
                          and s < t1])
    busy = xplane._union([(max(s, t0), min(e, t1))
                          for s, e, _ in dev["ops"] if e > t0 and s < t1])
    inside = xplane._length(xplane._subtract(
        mods, xplane._subtract(mods, busy)))
    assert red["under_s"]["jit(classify)"] == pytest.approx(inside / 1e9,
                                                            abs=1e-8)
    assert red["under_s"]["jit(classify)"] == pytest.approx(
        xplane._length(mods) / 1e9, rel=0.05)


def test_reduce_trace_reads_as_before():
    pinned = json.loads((DATA / "reduce_trace.json").read_text())
    for name, want in pinned.items():
        got = xplane.reduce_trace(*xplane.load_events(str(_path(name))))
        assert json.dumps(got, sort_keys=True) == json.dumps(
            want, sort_keys=True)


def test_scoped_trace_names_the_layers():
    """Rounds recorded with the program's names: the layers of the
    update path have their scopes (the relayout in is a free bitcast at
    this size; the relayout out is XLA's nameless copies of the
    kernel's outputs), the programs have their names, the update
    kernel's scope is its custom call, and the engine's spans are on
    the trace's clock, with the runtime's dispatch waits beside them."""
    path = _path("tiny-scoped-ingest")
    spans, devices, waits = scopes.load(path)
    red = scopes.reduce(spans, devices, waits)
    old = xplane.reduce_trace(*xplane.load_events(str(path)))
    for scope in ("guard.classify", "update.prepass", "update.kernel",
                  "update.epilogue", "update.relayout"):
        assert red["scope_s"].get(scope, 0) > 0, scope
    (dev,) = devices.values()
    t0, t1 = next((s, e) for n, s, e in spans if n == "cb.window")
    ops = [op for op in dev["ops"] if op[1] > t0 and op[0] < t1]
    copies = [p for p, (_, _, tf) in zip(scopes.paths(ops, dev["modules"]),
                                         ops)
              if tf.endswith("update.relayout/copy")]
    assert copies and all("update.relayout" in p for p in copies)
    assert waits and all(s <= e for s, e in waits)
    count, total, _, waited = red["spans"]["engine.ingest"]
    assert 0 < waited < total
    assert red["under_s"]["alias_rows"] > 0
    assert red["under_s"]["writeback"] > 0
    assert "jit_run" not in red["launches"]
    assert red["launches"]["jit_engine_update"] > 0
    (dev,) = old["devices"].values()
    assert red["scope_s"]["update.kernel"] == pytest.approx(
        dev["custom_call_ns"] / 1e9, rel=0.01)
    assert "custom-call:update_round" in dict(old["device_ops"])
    for name in ("engine.ingest", "engine.classify", "engine.update",
                 "engine.tally", "engine.drain_guard", "guard.account",
                 "engine.read"):
        assert red["spans"][name][0] > 0, name


@pytest.mark.parametrize("name, has_scopes", [
    ("tiny-ingest", False), ("tiny-scoped-ingest", True)])
def test_readers_find_the_runs_trace(tmp_path, name, has_scopes):
    """The readers find the trace their run wrote under the checkout
    (the newest); on a trace without the program's names they read
    nothing and raise nothing."""
    root = tiny.make_root(tmp_path / "root")
    bench = manifest.Manifest(root)
    cell = bench.cell("g500-s17.ingest")
    # an older run's trace of another cell lies beside this run's
    other = root / ".chipbench_trace" / "g500-s17.corpus" / "plugins" / "p"
    other.mkdir(parents=True)
    shutil.copy(_path("tiny-corpus"), other / "host.xplane.pb")
    os.utime(other / "host.xplane.pb", (1e9, 1e9))
    dst = root / ".chipbench_trace" / cell["name"] / "plugins" / "p" / "1"
    dst.mkdir(parents=True)
    shutil.copy(_path(name), dst / "host.xplane.pb")
    old = xplane.reduce_trace(*xplane.load_events(str(_path(name))))
    facts = {"trace": old, "config": bench.config(cell),
             "mix": bench.mix(cell), "counters": {"rounds": 20},
             "host": {}, "window_s": old["window_s"], "chips": 1}
    got = {m: bench.reader(m)(facts) for m in (
        "update_prepass_ms", "update_relayout_ms", "update_epilogue_ms",
        "ingest_host_ms", "ingest_host_syncs")}
    if has_scopes:
        assert all(v > 0 for v in got.values()), got
    else:
        assert got == dict.fromkeys(got)
    # another run's window: the file is not this run's trace
    stale = dict(facts, trace=dict(old, window_s=old["window_s"] + 1))
    assert bench.reader("update_prepass_ms")(stale) is None
