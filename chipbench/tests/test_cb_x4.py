"""The four-chip corpus path on four virtual CPU devices: a sound relay
run is correct, and one with the exchange between shards left out is
not.  Each runs in a process of its own, which asks XLA for four host
devices before JAX starts."""

import json
import os
import subprocess
import sys

import pytest

from chipbench.tests import tiny


@pytest.mark.parametrize("fault", ["none", "no_exchange"])
def test_relay_corpus(tmp_path, fault):
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(tiny.REPO / "src"), str(tiny.REPO)]))
    p = subprocess.run([sys.executable, "-m", "chipbench.tests.x4_run",
                        str(tmp_path / "root"), fault], cwd=tiny.REPO,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    if fault == "none":
        assert out["correct"], out["checks"]
    else:
        assert not out["correct"]
        assert out["checks"]["bad_hops"]["value"] > 0
