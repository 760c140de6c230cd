"""BENCHMARK.json against the contract, and discovery by name."""

import copy
import json

import pytest

from chipbench import manifest
from chipbench.tests import tiny

REPO = tiny.REPO


def load():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_benchmark_json_meets_the_contract():
    m = manifest.Manifest(REPO)
    assert m.data["command"] == ["python3", "chipbench/run.py"]
    for cell in m.cells.values():
        got = {x["name"] for x in m.end_to_end(cell)}
        assert "setup_s" in got and len(got) >= 2
        for x in m.per_layer(cell):        # every arrow lands in the cell
            assert x["moves"] in got


@pytest.mark.parametrize("where, bad", [
    ("metric name", "walk steps"), ("metric name", "walk/steps"),
    ("metric name", "µs_metric"), ("unit", "tokens per second"),
    ("unit", "µs"), ("cell name", "a,b"), ("bound", 0.3),
    ("moves", "no_such_metric"), ("extra key", "why"),
])
def test_manifest_refuses(where, bad):
    m = load()
    if where == "metric name":
        m["per_layer"][0]["name"] = bad
    elif where == "unit":
        m["end_to_end"][0]["unit"] = bad
    elif where == "cell name":
        m["workloads"][0]["name"] = bad
    elif where == "bound":
        m["end_to_end"][0]["bound"] = bad
    elif where == "moves":
        m["per_layer"][0]["moves"] = bad
    else:
        m["per_layer"][0][bad] = "a reason"
    with pytest.raises(manifest.ManifestError):
        manifest.validate(m, REPO)


def test_a_per_layer_metric_must_move_what_its_cells_report():
    m = load()
    walk = next(x for x in m["per_layer"] if x["name"] == "walk_kernel_ms")
    walk["workloads"] = ["g500-s17.ingest"]      # reports no walk rate
    with pytest.raises(manifest.ManifestError, match="moves"):
        manifest.validate(m, REPO)


def test_configs_mixes_and_readers_found_by_name():
    m = manifest.Manifest(REPO)
    for cell in m.cells.values():
        cfg = m.config(cell)
        assert cfg["name"] == cell["config"]
        assert m.mix(cell)["driver"] in ("walks", "ingest", "serve")
        for x in m.per_layer(cell):
            assert callable(m.reader(x["name"]))


def test_a_new_config_and_mix_need_only_new_files(tmp_path):
    before = {p.relative_to(REPO / "chipbench"): p.read_bytes()
              for p in (REPO / "chipbench").rglob("*")
              if p.is_file() and "tests" not in p.parts
              and "__pycache__" not in p.parts}
    root = tiny.make_root(tmp_path)
    m = manifest.Manifest(root)
    for name in tiny.MIXES:
        cell = m.cell(f"tiny.{name}")
        assert m.config(cell)["scale"] == tiny.CONFIG["scale"]
        assert m.mix(cell) == tiny.MIXES[name]
    for rel, data in before.items():       # no existing file was edited
        assert (root / "chipbench" / rel).read_bytes() == data
    old = load()
    new = json.loads((root / "BENCHMARK.json").read_text())
    assert new["workloads"][:len(old["workloads"])] == old["workloads"]
    assert new["configs"][:len(old["configs"])] == old["configs"]


def test_peaks_are_keyed_by_device_kind():
    from chipbench.xplane import peaks
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


def test_reader_returns_nothing_without_its_input():
    m = manifest.Manifest(REPO)
    empty = {"trace": {"devices": {"/device:TPU:0": {
        "custom_calls": 0, "custom_call_ns": 0, "collective_ns": 0,
        "exposed_collective_ns": 0, "programs_ns": {}}},
        "busy_s": 0.0, "window_s": 1.0},
        "host": {}, "counters": {}, "peaks": {"hbm_bytes_per_s": 819e9}}
    for x in m.data["per_layer"]:
        assert m.reader(x["name"])(copy.deepcopy(empty)) is None, x["name"]
