"""A throwaway checkout for the CPU tests: this benchmark's files with
tiny configurations and mixes added beside the real ones, as a later
change would add them, and a ``BENCHMARK.json`` that lists them."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

CONFIG = {
    "name": "tiny", "generator": "kronecker", "scale": 9, "edge_factor": 16,
    "a": 0.57, "b": 0.19, "c": 0.19, "bias": "dst_in_degree",
    "bias_bits": 16, "fp_bias": False, "capacity": 64,
    "max_out_degree": 64, "chips": 1,
    "reduced": {"scale": "test size"}, "assumed": {}}

MIXES = {
    "tiny-corpus": {"driver": "walks", "kind": "deepwalk", "walkers": 512,
                    "length": 16, "stop_prob": 0.0, "inflight": 1,
                    "warm_batches": 1, "check_batches": 3,
                    "limits": {"bad_hops": 0, "transition_z": 10.0}},
    "tiny-ingest": {"driver": "ingest", "holdout": 0.1, "lanes": 512,
                    "insert_share": 0.5, "drain_every": 2, "inflight": 2,
                    "warm_rounds": 1, "check_vertices": 64,
                    "limits": {"rows_bad": 0, "stats_gap": 0, "guard_gap": 0,
                               "space_bad": 0, "alias_gap": 1e-4}},
    "tiny-serve": {"driver": "serve", "holdout": 0.1, "kind": "ppr",
                   "length": 16, "stop_prob": 0.15, "walks_per_query": 64,
                   "zipf_s": 1.0, "rate_qps": 80, "update_edges_per_s": 2000,
                   "update_batch": 32, "update_lanes": 128,
                   "max_update_delay": 4, "tick_ms": 5,
                   "walk_buckets": [256, 1024], "max_walk_queue": 4096,
                   "max_update_queue": 4096, "max_inflight": 8,
                   "check_queries": 64,
                   "limits": {"lost": 0, "bad_hops": 0, "transition_z": 10.0,
                              "rows_bad": 0, "guard_gap": 0}},
}


# The serving metrics, as a serving cell lists them; BENCHMARK.json has
# no serving cell yet, so a tiny serving cell brings its own.
SERVE_METRICS = {
    "end_to_end": [
        {"name": "walk_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock"},
        {"name": "update_visible_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock"}],
    "per_layer": [
        {"name": "device_idle.serve", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device",
         "moves": "walk_p95_ms"}],
}


def make_root(tmp: Path, mixes=None) -> Path:
    """A checkout at ``tmp`` holding ``BENCHMARK.json`` and the benchmark's
    directory, with the tiny configuration and ``mixes`` (default: every
    tiny mix) added as files and cells."""
    mixes = MIXES if mixes is None else mixes
    root = Path(tmp)
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    m = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "chipbench" / "configs" / "tiny.json").write_text(
        json.dumps(CONFIG))
    m["configs"].append({"name": "tiny", "source": "https://example.org/tiny",
                         "file": "chipbench/configs/tiny.json",
                         "reduced": ["scale"], "why": "test"})
    serving = [f"tiny.{n}" for n, mix in mixes.items()
               if mix["driver"] == "serve"]
    for key, entries in SERVE_METRICS.items():
        have = {x["name"] for x in m[key]}
        m[key] += [dict(x, workloads=list(serving)) for x in entries
                   if serving and x["name"] not in have]
    for name, mix in mixes.items():
        (root / "chipbench" / "mixes" / f"{name}.json").write_text(
            json.dumps(mix))
        cell = f"tiny.{name}"
        m["workloads"].append({"name": cell, "config": "tiny",
                               "traffic": name, "chips": 1, "why": "test"})
        e2e = {"walks": "walk_steps_per_s", "ingest": "updates_per_s",
               "serve": "walk_p95_ms"}[mix["driver"]]
        for x in m["end_to_end"] + m["per_layer"]:
            if "workloads" in x and cell not in x["workloads"] and (
                    x["name"] == e2e or x.get("moves") == e2e
                    and x["name"].startswith("device_idle")):
                x["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(m, indent=1))
    return root
