"""Trace reduction: interval arithmetic on a made-up trace, and the
whole reduction on small traces recorded on a TPU v5e."""

from pathlib import Path

import pytest

from chipbench import xplane

DATA = Path(__file__).parent / "data"


def test_reduction_of_a_made_up_trace():
    us = 1000
    spans = [("cb.window", 0, 1000 * us), ("cb.fetch", 400 * us, 600 * us),
             ("cb.wait", 300 * us, 1000 * us)]       # fetch is innermost
    dev = {"ops": [
        ("%walk_fused_pallas.1 = s32[8,81]{1,0} custom-call(s32[1]{0} %a)",
         100 * us, 300 * us, {}),
        ("%all-to-all.2 = (s32[4]{0}, s32[4]{0}) all-to-all(s32[4]{0} %b)",
         250 * us, 350 * us, {}),
        ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %all-to-all.2)",
         700 * us, 800 * us, {}),
        ("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %c)",
         1200 * us, 1300 * us, {})]}                       # after window
    dev["modules"] = [("jit_run(7)", 100 * us, 350 * us, {}),
                      ("jit_classify(8)", 700 * us, 800 * us, {})]
    red = xplane.reduce_trace(spans, {"/device:TPU:0": dev})
    d = red["devices"]["/device:TPU:0"]
    assert d["busy_ns"] == 350 * us
    assert red["busy_s"] == pytest.approx(350e-6)
    assert red["window_s"] == pytest.approx(1e-3)
    assert d["collective_ns"] == 100 * us
    assert d["exposed_collective_ns"] == 50 * us
    assert d["custom_calls"] == 1 and d["custom_call_ns"] == 200 * us
    assert d["programs_ns"] == {"jit_run": 250 * us, "jit_classify": 100 * us}
    gaps = dict(red["idle_gaps"])
    assert gaps["cb.fetch"] == pytest.approx(350e-6)     # 350..700 us
    assert gaps["none"] == pytest.approx(100e-6)         # 0..100 us
    assert gaps["cb.wait"] == pytest.approx(200e-6)      # 800..1000 us
    assert red["device_ops"][0] == ("custom-call:walk_fused_pallas",
                                    pytest.approx(200e-6))
    assert dict(red["device_ops"])["fusion:fusion"] == pytest.approx(100e-6)


def test_subtract_and_union():
    assert xplane._union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    assert xplane._subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]


@pytest.mark.parametrize("name, programs", [
    ("tiny-corpus", 1), ("tiny-ingest", 1)])
def test_recorded_trace(name, programs):
    path = DATA / f"{name}.xplane.pb"
    if not path.exists():
        pytest.skip("no recorded trace")
    spans, devices = xplane.load_events(str(path))
    red = xplane.reduce_trace(spans, devices)
    assert list(red["devices"]) == ["/device:TPU:0"]
    d = red["devices"]["/device:TPU:0"]
    assert 0 < red["busy_s"] <= red["window_s"]
    assert d["custom_calls"] >= programs and d["custom_call_ns"] > 0
    assert d["custom_call_ns"] <= d["busy_ns"]
    assert sum(t for _, t in red["idle_gaps"]) <= red["window_s"]
    kernel = {"tiny-corpus": "walk_fused_pallas",
              "tiny-ingest": "update_fused_pallas"}[name]
    assert f"custom-call:{kernel}" in dict(red["device_ops"])
