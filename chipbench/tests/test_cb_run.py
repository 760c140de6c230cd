"""Whole runs on the CPU at tiny sizes: the refusals, sound runs, and
runs with the timed path broken underneath, which must come out not
correct.  The look for a chip is skipped by calling ``run_cell``."""

import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run
from chipbench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


def drive(root, mix, seed=2**31 + 3):
    return run.run_cell(root, f"tiny.{mix}", seed, 1.0, 0, jax.devices(),
                        t_process=time.perf_counter())


def test_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    rc = run.main(["--workload", "g500-s17.corpus", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0 and capsys.readouterr().out == ""


def test_refuses_without_the_program(tmp_path):
    root = tiny.make_root(tmp_path)          # no src/ beside it
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "g500-s17.corpus", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=root, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


@pytest.mark.parametrize("mix", ["tiny-corpus", "tiny-ingest", "tiny-serve"])
def test_sound_run_is_correct(root, mix):
    out = drive(root, mix)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks" and out["failed"] == 0
    e2e = {"tiny-corpus": "walk_steps_per_s", "tiny-ingest": "updates_per_s",
           "tiny-serve": "walk_p95_ms"}[mix]
    assert out["metrics"][e2e]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    if mix == "tiny-serve":
        assert out["metrics"]["update_visible_p95_ms"]["value"] > 0
    json.dumps(out)


def _walk_fault(kind):
    from repro.serve.dynwalk import DynamicWalkEngine
    orig = DynamicWalkEngine.walk
    last = {}

    def walk(self, starts, key=None):
        paths = orig(self, starts, key)
        if kind == "altered":            # one hop moved off its edge
            return paths.at[0, 1].set((paths[0, 1] + 1) % self.cfg.num_vertices)
        if kind == "half":               # half the walks never ran
            h = paths.shape[0] // 2
            return paths.at[h:, 1:].set(-1)
        prev = last.get("paths", paths)  # state unchanged: the last answer
        last["paths"] = paths
        return prev
    return walk


def _ingest_fault(kind):
    from repro.serve.dynwalk import DynamicWalkEngine
    orig = DynamicWalkEngine.ingest

    def ingest(self, is_insert, u, v, w, *, n_valid=None):
        n = u.shape[0] if n_valid is None else n_valid
        if kind == "unchanged":
            n = 0
        elif kind == "half":
            n = n // 2
        elif kind == "altered":          # one lane's bias altered
            w = jnp.asarray(w).at[0].add(1)
        return orig(self, is_insert, u, v, w, n_valid=n)
    return ingest


@pytest.mark.parametrize("mix, kind", [
    ("tiny-corpus", "altered"), ("tiny-corpus", "half"),
    ("tiny-corpus", "unchanged"), ("tiny-serve", "altered"),
])
def test_broken_walks_are_not_correct(root, monkeypatch, mix, kind):
    from repro.serve.dynwalk import DynamicWalkEngine
    monkeypatch.setattr(DynamicWalkEngine, "walk", _walk_fault(kind))
    out = drive(root, mix)
    assert not out["correct"]
    assert out["checks"]["bad_hops"]["value"] > 0


@pytest.mark.parametrize("mix, kind", [
    ("tiny-ingest", "unchanged"), ("tiny-ingest", "half"),
    ("tiny-ingest", "altered"), ("tiny-serve", "unchanged"),
])
def test_broken_updates_are_not_correct(root, monkeypatch, mix, kind):
    from repro.serve.dynwalk import DynamicWalkEngine
    monkeypatch.setattr(DynamicWalkEngine, "ingest", _ingest_fault(kind))
    out = drive(root, mix)
    assert not out["correct"]
    assert out["checks"]["rows_bad"]["value"] > 0


def test_int8_reference_in_the_programs_place_is_not_correct(tmp_path):
    """The control: the plain reference, with its biases cut to 8 bits,
    serves the corpus walks; the comparison must refuse it."""
    from chipbench.control import int8_control
    cfg = dict(tiny.CONFIG, scale=15, capacity=1024, max_out_degree=1024)
    mix = dict(tiny.MIXES["tiny-corpus"], walkers=8192, length=40)
    root = tiny.make_root(tmp_path, {"tiny-corpus": mix})
    (root / "chipbench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    with int8_control():
        out = drive(root, "tiny-corpus")
    assert not out["correct"]
    assert out["checks"]["bad_hops"]["value"] == 0
    assert out["checks"]["transition_z"]["value"] > \
        out["checks"]["transition_z"]["limit"]


@pytest.mark.parametrize("mix", ["tiny-ingest", "tiny-serve"])
def test_int8_control_fails_through_the_drivers_checks(root, mix):
    """The control's state and update lanes carry 8-bit biases, and its
    walks follow them: the drivers' own row comparison refuses it."""
    from chipbench.control import int8_control
    with int8_control() as model:
        out = drive(root, mix)
    assert not out["correct"]
    assert out["checks"]["rows_bad"]["value"] > 0
    if mix == "tiny-serve":
        assert model["calls"] > 0 and out["checks"]["bad_hops"]["value"] == 0
