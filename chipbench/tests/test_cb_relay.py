"""The relay's readers on made-up reductions: the roofline of a chip's
share of the hops, collective time a kernel hides, self time nested
under ``relay.segment``, segment-kernel launches (custom calls, not the
copies that carry the kernel's name), and a trace with no relay in it,
which they read as nothing; and the tiny four-device relay run traced.

A CPU trace has no device planes (XLA's CPU backend records its
operations as host thread events, without name stacks), so it gives
none of the relay's per-layer metrics, which read the device planes:
the traced run shows that their readers raise nothing and leave them
out, as ``run.py`` does for a metric with nothing to read."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import manifest, relay_trace, scopes, xplane
from chipbench.tests import tiny

TRACE = Path(__file__).parent / "data" / "tiny-corpus.xplane.pb"
US = 1000
BENCH = manifest.Manifest(tiny.REPO)
WALK = "jit(engine_walk)/jit(shmap_body)/while/body"
RELAY_METRICS = ["relay_segment_ms", "relay_exchange_ms", "relay_merge_ms",
                 "relay_exposed_collective_ms", "relay_rounds",
                 "relay_kernel_roofline", "device_idle.relay"]


def _kernel(s, e):
    return ("%walk_segment.3 = (s32[8,81]{1,0}, s32[8,2]{1,0}) "
            "custom-call(s32[1]{0} %p)", s, e, {})


def _coll(kind, s, e):
    return (f"%{kind}.1 = s32[16,3]{{1,0}} {kind}(s32[16,3]{{1,0}} %x)",
            s, e, {})


def _facts(devices, **counters):
    spans = [("cb.window", 0, 1000 * US)]
    red = xplane.reduce_trace(spans, {
        f"/device:TPU:{i}": {"ops": ops, "modules": []}
        for i, ops in enumerate(devices)})
    return {"trace": red, "counters": dict(counters), "chips": len(devices),
            "peaks": xplane.peaks("TPU v5e"), "host": {}}


def test_roofline_counts_a_chips_share_of_the_hops():
    """Four chips that each walk a quarter of the hops in the time one
    chip walks them all read the one chip's roofline."""
    read = BENCH.reader("relay_kernel_roofline")
    one = _facts([[_kernel(0, 400 * US)]], hops=1000, alias_entries=16)
    four = _facts([[_kernel(0, 400 * US)]] * 4, hops=4000,
                  alias_entries=16)
    want = 100 * 1000 * (8 * 16 + 12) / 819e9 / 400e-6
    assert read(one) == pytest.approx(want)
    assert read(four) == pytest.approx(want)
    assert BENCH.reader("relay_kernel_roofline")(
        _facts([[_coll("all-to-all", 0, 10 * US)]], hops=1000,
               alias_entries=16)) is None


def test_exposed_collective_leaves_out_what_a_kernel_hides():
    """An ``all_to_all`` half under the segment kernel exposes its other
    half; a ``psum`` with nothing beside it is exposed whole; averaged
    over the chips, per batch."""
    read = BENCH.reader("relay_exposed_collective_ms")
    chip = [_kernel(0, 100 * US), _coll("all-to-all", 50 * US, 150 * US),
            _coll("all-reduce", 200 * US, 210 * US)]
    quiet = [_kernel(0, 100 * US), _coll("all-to-all", 10 * US, 90 * US)]
    assert read(_facts([chip], batches=2)) == pytest.approx(0.030)
    assert read(_facts([chip, quiet], batches=1)) == pytest.approx(0.030)
    assert read(_facts([quiet], batches=1)) == 0.0
    assert read(_facts([[_kernel(0, 100 * US)]], batches=1)) is None


def _relay_ops(r):
    """One relay round at ``r`` us: its ``while`` body holds the
    placement, the relayout and the segment kernel nested under
    ``relay.segment``, the exchange and the merge."""
    t = r * US
    return [
        (t, t + 100 * US, ""),                                 # the loop
        (t, t + 10 * US, f"{WALK}/relay.merge/argsort"),
        (t + 10 * US, t + 15 * US,
         f"{WALK}/relay.segment/walk.relayout/concatenate"),
        (t + 15 * US, t + 55 * US,
         f"{WALK}/relay.segment/walk.kernel/walk_segment/pallas_call:"),
        (t + 55 * US, t + 85 * US, f"{WALK}/relay.exchange/all_to_all"),
        (t + 85 * US, t + 95 * US, f"{WALK}/relay.merge/scatter")]


def test_segment_time_holds_the_nested_kernel(monkeypatch):
    spans = [("cb.window", 0, 1000 * US)]
    dev = {"ops": _relay_ops(0) + _relay_ops(200), "modules": [
        ("jit_engine_walk(7)", 0, 300 * US)]}
    red = scopes.reduce(spans, {"/device:TPU:0": dev, "/device:TPU:1": dev})
    assert red["scope_s"]["walk.kernel"] == pytest.approx(80e-6)
    monkeypatch.setattr(scopes, "of_run", lambda f, root: red)
    facts = {"trace": {}, "counters": {"batches": 2}}
    got = {m: BENCH.reader(m)(facts) for m in (
        "relay_segment_ms", "relay_exchange_ms", "relay_merge_ms")}
    assert got == pytest.approx({"relay_segment_ms": 0.045,
                                 "relay_exchange_ms": 0.030,
                                 "relay_merge_ms": 0.020})



def test_launches_count_the_kernels_custom_calls():
    """Rounds are the segment kernel's custom calls: a layout copy of
    its output that XLA gives the kernel's name stack is no launch, nor
    is a launch outside the window; averaged over the chips."""
    def round_ops(t):
        return [_kernel(t, t + 40 * US),
                ("%copy.260 = s32[6144,81]{1,0} copy(s32[6144,81]{1,0} "
                 "%get-tuple-element.1026)", t + 40 * US, t + 41 * US, {}),
                _coll("all-to-all", t + 50 * US, t + 60 * US)]
    dev = {"ops": round_ops(0) + round_ops(200 * US), "modules": []}
    spans = [("cb.window", 0, 1000 * US)]
    assert relay_trace.launches(spans, {"a": dev, "b": dev},
                                "walk_segment") == 2.0
    late = [("cb.window", 0, 150 * US)]
    assert relay_trace.launches(late, {"a": dev}, "walk_segment") == 1.0
    assert relay_trace.launches(spans, {"a": dev}, "walk_whole") == 0.0


def test_readers_read_nothing_of_a_run_without_the_relay(tmp_path):
    """A one-chip walk trace (no relay scopes, no collectives, no
    segment kernel): the relay's readers give None and raise
    nothing."""
    root = tiny.make_root(tmp_path / "root")
    bench = manifest.Manifest(root)
    cell = bench.cell("g500-s19x4.corpus")
    dst = root / ".chipbench_trace" / cell["name"] / "plugins" / "p" / "1"
    dst.mkdir(parents=True)
    shutil.copy(TRACE, dst / "host.xplane.pb")
    old = xplane.reduce_trace(*xplane.load_events(str(TRACE)))
    facts = {"trace": old, "config": bench.config(cell),
             "mix": bench.mix(cell), "counters": {"batches": 4, "hops": 0},
             "host": {}, "window_s": old["window_s"], "chips": 4}
    for m in ("relay_segment_ms", "relay_exchange_ms", "relay_merge_ms",
              "relay_exposed_collective_ms", "relay_rounds",
              "relay_kernel_roofline"):
        assert bench.reader(m)(facts) is None, m
    assert bench.reader("relay_rounds")({"counters": {"batches": 4}}) is None


def test_traced_relay_run_on_four_cpu_devices(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(tiny.REPO / "src"), str(tiny.REPO)]))
    p = subprocess.run([sys.executable, "-m", "chipbench.tests.x4_traced",
                        str(tmp_path / "root")], cwd=tiny.REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"], out["checks"]
    assert out["device"]["window_s"] > 0
    assert "state_build_s" in out["metrics"]
    assert not set(RELAY_METRICS) & set(out["metrics"])
