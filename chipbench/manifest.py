"""``BENCHMARK.json``: loading, checking against the benchmark's
contract, and finding each cell's configuration, traffic mix and
per-layer readers by name.

Nothing here knows a particular cell: a configuration is
``configs/<name>.json``, a mix ``mixes/<traffic>.json`` and a per-layer
metric's reader ``layers/<metric>.py``, so a later change adds a cell,
a mix or a metric by adding files and entries only.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}
TOP_KEYS = ["command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"]


class ManifestError(ValueError):
    """``BENCHMARK.json`` breaks the contract."""


def _line(s, what):
    if not isinstance(s, str) or not 1 <= len(s) <= 200 or "\n" in s \
            or "\t" in s:
        raise ManifestError(f"{what}: 1 to 200 characters on one line")


def _name(s, what):
    if not isinstance(s, str) or not NAME.match(s):
        raise ManifestError(f"{what} {s!r} is not a valid name")


def validate(m: dict, root: Path) -> None:
    """Raise ``ManifestError`` where ``m`` breaks the contract's rules
    on keys, names, units, bounds, cross-references and files."""
    here = bench_dir(m, root)
    if sorted(m) != sorted(TOP_KEYS):
        raise ManifestError(f"top-level keys must be exactly {TOP_KEYS}")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        raise ManifestError("run_seconds is a whole number from 1 to 51")
    cmd = m["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        raise ManifestError("command: a list of 1 to 32 strings")
    for word in cmd:
        _line(word, "command word")
    paths = m["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        raise ManifestError("paths: 1 to 16 directories")
    for p in paths:
        if not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) or p.startswith("/") \
                or ".." in p.split("/"):
            raise ManifestError(f"path {p!r}")
    configs = {c["name"]: c for c in m["configs"]}
    if not 1 <= len(m["configs"]) <= 24 or len(configs) != len(m["configs"]):
        raise ManifestError("configs: 1 to 24 with distinct names")
    for c in m["configs"]:
        if sorted(c) != ["file", "name", "reduced", "source", "why"]:
            raise ManifestError(f"config {c.get('name')}: keys")
        _name(c["name"], "config")
        _line(c["source"], f"config {c['name']} source")
        _line(c["why"], f"config {c['name']} why")
        if len(c["reduced"]) > 16:
            raise ManifestError(f"config {c['name']}: reduced > 16 keys")
        for k in c["reduced"]:
            _name(k, "reduced key")
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in paths) \
                or not (root / c["file"]).is_file():
            raise ManifestError(f"config file {c['file']} not under paths")
    cells = {w["name"]: w for w in m["workloads"]}
    if not 1 <= len(m["workloads"]) <= 24 or len(cells) != len(m["workloads"]):
        raise ManifestError("workloads: 1 to 24 with distinct names")
    pairs = set()
    for w in m["workloads"]:
        if sorted(w) != ["chips", "config", "name", "traffic", "why"]:
            raise ManifestError(f"workload {w.get('name')}: keys")
        for k in ("name", "config", "traffic"):
            _name(w[k], f"workload {k}")
        _line(w["why"], f"workload {w['name']} why")
        if w["config"] not in configs or w["chips"] not in (1, 4):
            raise ManifestError(f"workload {w['name']}: config or chips")
        if (w["config"], w["traffic"]) in pairs:
            raise ManifestError(f"workload {w['name']}: pair repeated")
        pairs.add((w["config"], w["traffic"]))
    if sum(w["chips"] == 4 for w in m["workloads"]) > max(
            1, len(m["workloads"]) // 2):
        raise ManifestError("too many four-chip cells")
    used = {w["config"] for w in m["workloads"]}
    if used != set(configs):
        raise ManifestError(f"configs used by no cell: {set(configs) - used}")
    e2e = {x["name"]: x for x in m["end_to_end"]}
    layer = {x["name"]: x for x in m["per_layer"]}
    if not 1 <= len(e2e) <= 16 or len(e2e) != len(m["end_to_end"]):
        raise ManifestError("end_to_end: 1 to 16 distinct metrics")
    if not 1 <= len(layer) <= 128 or len(layer) != len(m["per_layer"]):
        raise ManifestError("per_layer: 1 to 128 distinct metrics")
    if set(e2e) & set(layer):
        raise ManifestError("a metric is both end-to-end and per-layer")
    if "setup_s" not in e2e:
        raise ManifestError("setup_s is missing")
    for x in m["end_to_end"]:
        keys = {"name", "unit", "better", "bound", "source"}
        if not keys <= set(x) <= keys | {"workloads"}:
            raise ManifestError(f"metric {x['name']}: keys")
        if x["source"] not in SOURCES_E2E:
            raise ManifestError(f"metric {x['name']}: source")
        if not 0 < x["bound"] <= 0.25:
            raise ManifestError(f"metric {x['name']}: bound")
    for x in m["per_layer"]:
        keys = {"name", "unit", "better", "source", "layer", "moves"}
        if not keys <= set(x) <= keys | {"workloads"}:
            raise ManifestError(f"metric {x['name']}: keys")
        if x["source"] not in SOURCES:
            raise ManifestError(f"metric {x['name']}: source")
        _line(x["layer"], f"metric {x['name']} layer")
        if x["moves"] not in e2e:
            raise ManifestError(f"metric {x['name']} moves no end-to-end "
                                "metric")
        if not (here / "layers" / f"{x['name']}.py").is_file():
            raise ManifestError(f"metric {x['name']} has no reader")
    for x in m["end_to_end"] + m["per_layer"]:
        _name(x["name"], "metric")
        if not UNIT.match(x["unit"]) or x["better"] not in ("lower", "higher"):
            raise ManifestError(f"metric {x['name']}: unit or better")
        for c in x.get("workloads", []):
            if c not in cells:
                raise ManifestError(f"metric {x['name']}: unknown cell {c}")
    for c in cells:
        got = [x["name"] for x in m["end_to_end"] if c in reported(x, cells)]
        if "setup_s" not in got or len(got) < 2:
            raise ManifestError(f"cell {c}: setup_s and one more end-to-end")
        lay = [x for x in m["per_layer"] if c in reported(x, cells)]
        if not lay:
            raise ManifestError(f"cell {c}: no per-layer metric")
        for x in lay:
            if x["moves"] not in got:
                raise ManifestError(f"cell {c}: {x['name']} moves "
                                    f"{x['moves']}, which the cell lacks")
        if not (here / "mixes" / f"{cells[c]['traffic']}.json").is_file():
            raise ManifestError(f"cell {c}: no mix file")


def bench_dir(m: dict, root: Path) -> Path:
    """The benchmark's own directory: the first of ``paths``."""
    return Path(root) / m["paths"][0]


def reported(metric: dict, cells) -> list:
    """The cells in which ``metric`` is reported."""
    return metric.get("workloads", list(cells))


class Manifest:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())
        validate(self.data, self.root)
        self.here = bench_dir(self.data, self.root)
        self.cells = {w["name"]: w for w in self.data["workloads"]}
        self.configs = {c["name"]: c for c in self.data["configs"]}

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        return self.cells[name]

    def config(self, cell: dict) -> dict:
        return json.loads((self.root / self.configs[cell["config"]]["file"])
                          .read_text())

    def mix(self, cell: dict) -> dict:
        return json.loads((self.here / "mixes" / f"{cell['traffic']}.json")
                          .read_text())

    def end_to_end(self, cell: dict) -> list:
        return [x for x in self.data["end_to_end"]
                if cell["name"] in reported(x, self.cells)]

    def per_layer(self, cell: dict) -> list:
        return [x for x in self.data["per_layer"]
                if cell["name"] in reported(x, self.cells)]

    def reader(self, metric: str):
        """The ``read`` function of ``layers/<metric>.py``."""
        return _load_reader(self.here / "layers" / f"{metric}.py", metric)


def _load_reader(path: Path, metric: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_layer_{re.sub(r'[^A-Za-z0-9_]', '_', metric)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
