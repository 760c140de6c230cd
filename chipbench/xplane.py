"""Reduction of a profiler trace (``.xplane.pb``) to device time.

What it reads: the TPU device planes (``/device:TPU:<n>``), whose ``XLA
Ops`` line holds one event per operation that ran and whose ``XLA
Modules`` line holds one per program, and the host plane, where the
benchmark's own ``TraceAnnotation`` spans (names starting ``cb.``) lie
on the same clock.  Only events inside the ``cb.window`` span count.

What it gives, per device: busy time (the union of operation intervals),
time per program, per operation kind and per custom call, collective
time and the part of it during which no other operation ran (exposed);
and over all devices the idle gaps, each put down to the innermost
benchmark span that covers it.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "cb.window"
SPAN_PREFIX = "cb."
COLLECTIVE = re.compile(r"all-to-all|all-reduce|all-gather|collective-permute"
                        r"|reduce-scatter|send|recv", re.I)
GAP_MIN_NS = 20_000          # shorter idle slivers are not attributed


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind`` from ``peaks.json``; a device
    that is not in the table is an error."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" in peaks.json")
    return table["devices"][device_kind]


def find_xplane(log_dir) -> str:
    files = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _op(text: str):
    """``(name, kind)`` of an ``XLA Ops`` event, whose name is the HLO
    instruction's text (``%fusion.12 = f32[8]{0} fusion(...)``): the
    instruction's name without its instance number, and its opcode."""
    name, _, rest = text.partition(" = ")
    name = re.sub(r"[.\d]+$", "", name.lstrip("%")) or name
    if rest.startswith("("):                  # a tuple type: skip it
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    kind = rest.strip().partition("(")[0].split(" ")[-1] or "?"
    return name, kind


def _union(iv):
    """Merged, sorted copy of a list of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(iv) -> int:
    return sum(e - s for s, e in iv)


def _subtract(a, b):
    """Intervals of union ``a`` not covered by union ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def load_events(path):
    """``(host_spans, devices)`` from an xplane file: host spans as
    ``(name, start, end)`` and per device plane the op and module events
    as ``(name, start, end, stats)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            ops, mods = [], []
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": mods}.get(line.name)
                if dst is None:
                    continue
                for e in line.events:
                    dst.append((e.name, int(e.start_ns), int(e.end_ns),
                                dict(e.stats)))
            devices[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.end_ns)))
    return spans, devices


def reduce_trace(spans, devices) -> dict:
    """Per-device and whole-window device time inside ``cb.window``."""
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no cb.window span")
    t0, t1 = min(s for s, _ in win), max(e for _, e in win)
    window_ns = t1 - t0
    per_dev = {}
    busy_all = []
    for dname, ev in sorted(devices.items()):
        ops = [(n, max(s, t0), min(e, t1), st) for n, s, e, st in ev["ops"]
               if e > t0 and s < t1]
        mods = [(n, max(s, t0), min(e, t1)) for n, s, e, _ in ev["modules"]
                if e > t0 and s < t1]
        busy = _union([(s, e) for _, s, e, _ in ops])
        kinds = [_op(n) for n, _, _, _ in ops]
        coll = _union([(s, e) for (_, k), (_, s, e, _) in zip(kinds, ops)
                       if COLLECTIVE.search(k)])
        comp = _union([(s, e) for (_, k), (_, s, e, _) in zip(kinds, ops)
                       if not COLLECTIVE.search(k)])
        by_op, by_prog, custom = defaultdict(int), defaultdict(int), []
        for (name, kind), (_, s, e, _) in zip(kinds, ops):
            by_op[f"{kind}:{name}"] += e - s
            if kind == "custom-call":
                custom.append(e - s)
        for n, s, e in mods:
            by_prog[re.sub(r"\(-?\d+\)$", "", n)] += e - s
        per_dev[dname] = {
            "busy_ns": _length(busy),
            "ops_ns": dict(by_op),
            "programs_ns": dict(by_prog),
            "custom_call_ns": sum(custom),
            "custom_calls": len(custom),
            "collective_ns": _length(coll),
            "exposed_collective_ns": _length(_subtract(coll, comp)),
        }
        busy_all.append(busy)
    n_dev = max(len(per_dev), 1)
    gaps = defaultdict(int)
    for busy in busy_all:
        idle = _subtract([[t0, t1]], busy)
        for s, e in idle:
            if e - s >= GAP_MIN_NS:
                gaps[_host_span_at(spans, (s + e) // 2)] += e - s
    ops_total = defaultdict(int)
    for d in per_dev.values():
        for n, t in d["ops_ns"].items():
            ops_total[n] += t
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(d["busy_ns"] for d in per_dev.values()) / n_dev / 1e9,
        "devices": per_dev,
        "device_ops": sorted(((n, t / n_dev / 1e9)
                              for n, t in ops_total.items()),
                             key=lambda x: -x[1])[:10],
        "idle_gaps": sorted(((n, t / n_dev / 1e9) for n, t in gaps.items()),
                            key=lambda x: -x[1])[:10],
    }


def _host_span_at(spans, t) -> str:
    """Innermost benchmark span (other than the window) covering ``t``."""
    best, width = "none", None
    for n, s, e in spans:
        if n != WINDOW_SPAN and s <= t < e and (width is None
                                                or e - s < width):
            best, width = n, e - s
    return best
