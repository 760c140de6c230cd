"""Reduction of a profiler trace by the program's own names: device self
time per named scope, and the program's host spans.

``xplane.py`` keys device work by HLO instruction; this module reads
what the program names.  In the raw ``XSpace`` each device operation's
event metadata carries ``tf_op``, JAX's name stack (``jit(engine_update)
/jit(update_fused_pallas)/update.prepass/jit(searchsorted)/.../while/
body/lt``), and ``source`` (file:line); ``jax.profiler.ProfileData``
gives only the events' own stats, so the file is read with the
profiler's protobuf module.  Program scopes are the dotted components
of that path (``update.prepass``, ``walk.kernel``, ``guard.classify``,
``relay.segment``); the program's host spans are the events named
``engine.*``, ``sched.*`` and ``guard.*`` (``repro/serve/spans.py``).

A *dispatch wait* is the runtime's back-pressure on launches: PJRT
lets a device hold a bounded number of programs in flight, and a launch
beyond it waits in ``ExecutePrepare`` until an earlier program finishes
and its slot is freed (the host events ``CommonPjRtLoadedExecutable::
ExecutePrepare`` and, once the slot is free, ``Acquire semaphore``).
The time from the first to the second is the wait.  A host span that
holds one waits for the device; its own host work is the rest.

Inside the ``cb.window`` span, per device and averaged over devices:

- each operation's *self time*: its duration less the part covered by
  the operations nested in it (a ``while`` holds the events of its
  body), so self times add up to busy time;
- ``scope_s``: self time per innermost program scope on the operation's
  path, ``"none"`` for operations under no program scope;
- ``under_s``: self time under each component of the path, any kind
  (``jit(classify)``, ``alias_rows``, ``update.epilogue``);
- ``launches``: programs (``XLA Modules`` events) started in the window,
  by name;
- ``spans``: the program's host spans that start in the window, by
  name: count, total and longest seconds, and the seconds of dispatch
  wait inside them (below);
- ``idle_gaps``: device idle time put down to the innermost benchmark
  or program span covering it.
"""

from __future__ import annotations

import bisect
import functools
import importlib.util
import re
import sys
from collections import defaultdict
from pathlib import Path

from chipbench import xplane

HOST_SPANS = ("cb.", "engine.", "sched.", "guard.")
PROGRAM_SPANS = ("engine.", "sched.", "guard.")
SCOPE = re.compile(r"^[a-z][a-z0-9_]*\.[a-z][a-z0-9_.]*$")
PROTO = ("tensorflow", "tsl/profiler/protobuf/xplane_pb2.py")
STRUCTURAL = ("custom-call", "get-tuple-element", "tuple")
PREPARE = "CommonPjRtLoadedExecutable::ExecutePrepare"
SLOT = "Acquire semaphore"


@functools.lru_cache(maxsize=1)
def _xplane_pb2():
    """The profiler's ``xplane_pb2``, loaded from its file so that its
    package (TensorFlow) is not imported."""
    spec = importlib.util.find_spec(PROTO[0])
    if spec is None or not spec.submodule_search_locations:
        raise ImportError(f"no {PROTO[0]} installation holds {PROTO[1]}")
    path = Path(list(spec.submodule_search_locations)[0]) / PROTO[1]
    mspec = importlib.util.spec_from_file_location("chipbench_xplane_pb2",
                                                   path)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return mod


def _stat(stat, names):
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return names.get(stat.ref_value)
    return getattr(stat, kind) if kind else None


def load(path) -> tuple:
    """``(spans, devices, waits)`` from an xplane file: host spans of
    the benchmark and the program as ``(name, start_ns, end_ns)``, per
    TPU plane its ``ops`` as ``(start_ns, end_ns, tf_op)`` and its
    ``modules`` as ``(name, start_ns, end_ns)``, and the dispatch waits
    as ``(start_ns, end_ns)``."""
    space = _xplane_pb2().XSpace()
    space.ParseFromString(Path(path).read_bytes())
    spans, devices, waits = [], {}, []
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            tf_op = name_stacks(_hlo_graph(plane, names))
            ops, mods = [], []
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                for ev in line.events:
                    s = line.timestamp_ns + ev.offset_ps // 1000
                    e = s + ev.duration_ps // 1000
                    if line.name == "XLA Ops":
                        ops.append((s, e, tf_op.get(ev.metadata_id, "")))
                    else:
                        mods.append((plane.event_metadata[ev.metadata_id]
                                     .name, s, e))
            devices[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                prepare = []
                for ev in line.events:
                    name = plane.event_metadata[ev.metadata_id].name
                    s = line.timestamp_ns + ev.offset_ps // 1000
                    e = s + ev.duration_ps // 1000
                    if name.startswith(HOST_SPANS):
                        spans.append((name, s, e))
                    elif name == PREPARE:
                        prepare.append((s, e))
                    elif name == SLOT and prepare and \
                            prepare[-1][0] <= s <= prepare[-1][1]:
                        waits.append((prepare.pop()[0], s))
    return spans, devices, waits


def _hlo_graph(plane, names) -> dict:
    """Per operation metadata id: ``(tf_op, program_id, instruction,
    opcode, operand instructions)``, from the stats and the HLO text
    (``%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a, ...), kind=...``)."""
    graph = {}
    for mid, em in plane.event_metadata.items():
        st = {names.get(x.metadata_id): x for x in em.stats}
        if "program_id" not in st:
            continue
        inst, _, rest = em.name.partition(" = ")
        kind = xplane._op(em.name)[1]
        args = rest.partition(kind + "(")[2] if kind != "?" else ""
        depth, end = 1, 0
        for end, ch in enumerate(args):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        graph[mid] = (str(_stat(st["tf_op"], names) or "")
                      if "tf_op" in st else "",
                      _stat(st["program_id"], names), inst.lstrip("%"),
                      kind, re.findall(r"%([\w.\-]+)", args[:end]))
    return graph


def name_stacks(graph: dict) -> dict:
    """The name stack of every operation in ``graph``.  XLA leaves the
    operations it adds itself without one (the fill and the scatter a
    ``.at[].set`` becomes, a layout copy): such an operation takes the
    stack of the operations it reads and that read it, in the same
    program, with the most common program scope among them (a stack
    without a program scope only where none has one); in a program
    whose named work has one scope, every operation takes it.  A custom
    call (a kernel), the tuple elements it returns and a tuple lend
    their stack to nothing: a copy of a kernel's output is not the
    kernel.  Such a layout copy is the relayout out of the kernel's
    layer, which the program names around its reshape out: it takes
    that layer's ``.relayout`` scope (``update.kernel`` ->
    ``update.relayout``; ``_relayout_out``).  A stack of one component
    (an argument's name) counts as none."""
    by_name = {(g[1], g[2]): mid for mid, g in graph.items()}
    near = defaultdict(list)
    for mid, (_, prog, _, _, args) in graph.items():
        for a in args:
            if (prog, a) in by_name:
                near[mid].append(by_name[(prog, a)])
                near[by_name[(prog, a)]].append(mid)
    stack = {mid: g[0] for mid, g in graph.items() if path_of(g[0])}
    stack.update(_relayout_out(graph, stack))
    lender = {mid for mid in stack if graph[mid][3] not in STRUCTURAL}
    for scoped in (True, False):
        todo = [mid for mid in graph if mid not in stack]
        while todo:
            left = []
            for mid in todo:
                got = [stack[m] for m in near[mid] if m in lender]
                tops = [_scope_of(path_of(t)) for t in got]
                if scoped:
                    got = [t for t, top in zip(got, tops) if top != "none"]
                    tops = [top for top in tops if top != "none"]
                if got:
                    best = max(tops, key=tops.count)
                    stack[mid] = got[tops.index(best)]
                    lender.add(mid)
                else:
                    left.append(mid)
            if len(left) == len(todo):
                break
            todo = left
    # a program whose named work lies under one scope (the guard's
    # classifier) is that scope: its other operations go to it too
    one = defaultdict(set)
    for mid, t in stack.items():
        if _scope_of(path_of(t)) != "none":
            one[graph[mid][1]].add(t)
    for mid, g in graph.items():
        scoped = {_scope_of(path_of(t)) for t in one[g[1]]}
        if len(scoped) == 1 and \
                _scope_of(path_of(stack.get(mid, ""))) == "none":
            stack[mid] = min(one[g[1]], key=len)
    return stack


def _relayout_out(graph: dict, stack: dict) -> dict:
    """Stacks for the nameless layout copies (``copy``, ``copy-start``,
    a ``copy_*`` fusion) of a kernel's output: the kernel's stack up to
    its ``<layer>.kernel`` scope, with ``<layer>.relayout`` in its
    place.  A kernel that returns several arrays is read through tuple
    elements that run as no event; XLA names them after the kernel's
    operation (``pallas_call.2`` for ``.../update_round/pallas_call``)."""
    kernels = {}
    for mid, (_, prog, inst, kind, _) in graph.items():
        comps = path_of(stack.get(mid, ""))
        if kind == "custom-call" and _scope_of(comps).endswith(".kernel"):
            k = max(i for i, c in enumerate(comps) if SCOPE.match(c))
            out = "/".join(comps[:k] + [comps[k][:-len("kernel")]
                                        + "relayout", "copy"])
            kernels[(prog, inst)] = out
            kernels[(prog, stack[mid].rstrip(":").split("/")[-1])] = out
    got = {}
    for mid, (_, prog, inst, kind, args) in graph.items():
        if mid in stack or not (kind in ("copy", "copy-start")
                                or inst.startswith("copy")):
            continue
        for a in args:
            out = kernels.get((prog, a)) or \
                kernels.get((prog, re.sub(r"\.\d+$", "", a)))
            if out:
                got[mid] = out
                break
    return got


def _scope_of(comps) -> str:
    """The innermost program scope of a name stack, else ``"none"``."""
    dotted = [c for c in comps if SCOPE.match(c)]
    return dotted[-1] if dotted else "none"


def path_of(tf_op: str) -> list:
    """The scope components of a ``tf_op`` name stack: every component
    but the last, which names the operation."""
    return tf_op.rstrip(":").split("/")[:-1]


def _nesting(ops) -> tuple:
    """``(order, parent)``: the operations in start order (an enclosing
    one first), and the index of the operation each lies directly in
    (-1 at the top)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    parent = [-1] * len(ops)
    stack = []
    for i in order:
        s, e = ops[i][0], ops[i][1]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            parent[i] = stack[-1]
        stack.append(i)
    return order, parent


def self_times(ops) -> list:
    """Self time of each ``(start, end, ...)`` operation: its duration
    less the union of the operations directly nested in it."""
    _, parent = _nesting(ops)
    children = defaultdict(list)
    for i, p in enumerate(parent):
        if p >= 0:
            children[p].append((ops[i][0], ops[i][1]))
    return [ops[i][1] - ops[i][0]
            - xplane._length(xplane._union(children.get(i, [])))
            for i in range(len(ops))]


def paths(ops, modules) -> list:
    """The scope components of each ``(start, end, tf_op)`` operation.
    An operation the compiler added has no ``tf_op`` (a ``while`` around
    a loop body, a layout copy): it takes the components its nested
    operations share, else those of the operation it lies in, else its
    program's (``jit_classify`` -> ``jit(classify)``)."""
    order, parent = _nesting(ops)
    got = [path_of(op) if op else None for _, _, op in ops]
    kids = defaultdict(list)
    for i, p in enumerate(parent):
        if p >= 0:
            kids[p].append(i)
    for i in reversed(order):                  # nested ones first
        if got[i] is None and kids[i]:
            shared = [got[k] for k in kids[i] if got[k] is not None]
            if shared:
                got[i] = _common(shared)
    starts = sorted((s, e, n) for n, s, e in modules)
    for i in order:                            # enclosing ones first
        if got[i] is None:
            if parent[i] >= 0:
                got[i] = got[parent[i]]
            else:
                got[i] = _program(starts, ops[i][0])
    return got


def _common(seqs) -> list:
    out = []
    for parts in zip(*seqs):
        if any(p != parts[0] for p in parts):
            break
        out.append(parts[0])
    return out


def _program(modules, t) -> list:
    """``[jit(<name>)]`` for the program running at ``t``, else ``[]``."""
    k = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if k >= 0 and modules[k][0] <= t < modules[k][1]:
        name = re.sub(r"\(-?\d+\)$", "", modules[k][2])
        if name.startswith("jit_"):
            return [f"jit({name[4:]})"]
    return []


def _overlap(s, e, iv) -> int:
    """The part of ``[s, e)`` covered by the sorted, disjoint ``iv``."""
    k = max(bisect.bisect_right(iv, [s]) - 1, 0)
    got = 0
    for a, b in iv[k:]:
        if a >= e:
            break
        got += max(0, min(b, e) - max(a, s))
    return got


def reduce(spans, devices, waits=()) -> dict:
    """Scope, span and launch totals inside ``cb.window`` (seconds,
    averaged over the devices)."""
    win = [(s, e) for n, s, e in spans if n == xplane.WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no cb.window span")
    t0, t1 = min(s for s, _ in win), max(e for _, e in win)
    n_dev = max(len(devices), 1)
    scope_s, under_s = defaultdict(float), defaultdict(float)
    launches = defaultdict(int)
    busy = self_total = 0
    gaps = defaultdict(float)
    for dev in devices.values():
        ops = [(max(s, t0), min(e, t1), op) for s, e, op in dev["ops"]
               if e > t0 and s < t1]
        iv = xplane._union([(s, e) for s, e, _ in ops])
        busy += xplane._length(iv)
        for comps, t in zip(paths(ops, dev["modules"]), self_times(ops)):
            scope_s[_scope_of(comps)] += t / n_dev / 1e9
            for c in set(comps):
                under_s[c] += t / n_dev / 1e9
            self_total += t
        for name, s, _ in dev["modules"]:
            if t0 <= s < t1:
                launches[re.sub(r"\(-?\d+\)$", "", name)] += 1
        for s, e in xplane._subtract([[t0, t1]], iv):
            if e - s >= xplane.GAP_MIN_NS:
                gaps[xplane._host_span_at(spans, (s + e) // 2)] += \
                    (e - s) / n_dev / 1e9
    host = defaultdict(lambda: [0, 0.0, 0.0, 0.0])
    waited = xplane._union(waits)
    for n, s, e in spans:
        if n.startswith(PROGRAM_SPANS) and t0 <= s < t1:
            rec = host[n]
            rec[0] += 1
            rec[1] += (e - s) / 1e9
            rec[2] = max(rec[2], (e - s) / 1e9)
            rec[3] += _overlap(s, e, waited) / 1e9
    return {"window_s": (t1 - t0) / 1e9, "busy_s": busy / n_dev / 1e9,
            "self_s": self_total / n_dev / 1e9, "scope_s": dict(scope_s),
            "under_s": dict(under_s),
            "launches": {k: v / n_dev for k, v in launches.items()},
            "spans": {k: tuple(v) for k, v in host.items()},
            "idle_gaps": dict(gaps)}


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, mtime_ns: int) -> dict:
    return reduce(*load(path))


def of_run(facts: dict, root: Path):
    """The reduction of the trace that the ``--trace 1`` run described
    by a reader's ``facts`` wrote under ``root``, or None where there is
    none to read: the newest trace under ``.chipbench_trace/``, which
    counts only if its window is the one ``xplane.py`` measured."""
    if "trace" not in facts:
        return None
    found = sorted(Path(root).glob(".chipbench_trace/*/**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    if not found:
        return None
    try:
        red = _reduce_file(str(found[-1]), found[-1].stat().st_mtime_ns)
    except (OSError, ImportError) as e:
        print(f"chipbench: no scope reduction: {e}", file=sys.stderr)
        return None
    if abs(red["window_s"] - facts["trace"]["window_s"]) > 1e-5:
        return None
    return red


def scope_ms(facts: dict, root: Path, scope: str, per: str):
    """Device self time under program scope ``scope`` per unit of the
    run's counter ``per`` (``rounds``, ``batches``), in ms."""
    red = of_run(facts, root)
    n = facts["counters"].get(per)
    if red is None or not n or not red["scope_s"].get(scope):
        return None
    return 1e3 * red["scope_s"][scope] / n


def span_ms(facts: dict, root: Path, name: str):
    """Mean host time of the program span ``name`` in the window, less
    the dispatch waits inside it, in ms."""
    red = of_run(facts, root)
    if red is None or name not in red["spans"]:
        return None
    count, total, _, waited = red["spans"][name]
    return 1e3 * (total - waited) / count


def span_count(facts: dict, root: Path, name: str, per: str):
    """Program spans ``name`` in the window per unit of the run's
    counter ``per``."""
    red = of_run(facts, root)
    n = facts["counters"].get(per)
    if red is None or not n or name not in red["spans"]:
        return None
    return red["spans"][name][0] / n
