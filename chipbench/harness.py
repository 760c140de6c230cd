"""What every driver shares: host spans, the measured window, the
optional profiler trace, the state build on the chip, and the result.

A driver (``drivers/<name>.py``) gets a ``Run`` and returns nothing; it
fills ``run.e2e`` (end-to-end values), ``run.attempted`` and
``run.failed``, ``run.checks`` (each compared number with its limit)
and ``run.counters`` (what per-layer readers need besides the trace).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from collections import defaultdict
from pathlib import Path

import numpy as np

from chipbench import gen


class CheckFailed(Exception):
    """A run that cannot compare its outputs (not the same as a
    comparison that comes out false, which is reported)."""


@dataclasses.dataclass
class Run:
    root: Path
    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_process: float
    log: callable = print
    e2e: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    checks: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    host: dict = dataclasses.field(
        default_factory=lambda: defaultdict(lambda: [0, 0.0]))
    longest: dict = dataclasses.field(
        default_factory=lambda: defaultdict(float))
    memory_peak_bytes: int = 0
    trace_dir: Path | None = None
    _window: tuple | None = None

    # -- seeds --------------------------------------------------------------
    def rng(self, stream: int) -> np.random.Generator:
        return gen.rng_for(self.seed, stream)

    @property
    def jax_seed(self) -> int:
        return int(self.seed) % (2 ** 31 - 1)

    # -- spans --------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        """A benchmark span: host time recorded always, and a
        ``TraceAnnotation`` on the profiler's clock when tracing."""
        ann = contextlib.nullcontext()
        if self.trace:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
        t = time.perf_counter()
        with ann:
            yield
        dt = time.perf_counter() - t
        rec = self.host[name]
        rec[0] += 1
        rec[1] += dt
        self.longest[name] = max(self.longest[name], dt)

    @contextlib.contextmanager
    def window(self):
        """The measured window.  Set-up ends where it starts; with
        ``--trace 1`` the profiler records exactly this span.  Programs
        lowered inside it, compiled or loaded from the cache, are counted
        (``compiles_in_window``), and so are the interpreter's garbage
        collections: set-up's objects are collected and frozen first, so
        that a collection inside the window scans only the window's."""
        import jax
        gc.collect()
        gc.freeze()
        self.e2e["setup_s"] = time.perf_counter() - self.t_process
        self.host.clear()
        self.longest.clear()
        compiles = [0]
        pauses = []

        def on_gc(phase, info):
            if phase == "start":
                pauses.append(time.perf_counter())
            else:
                pauses[-1] = time.perf_counter() - pauses[-1]
        gc.callbacks.append(on_gc)

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                compiles[0] += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)
        tracer = contextlib.nullcontext()
        if self.trace:
            self.trace_dir = self.root / ".chipbench_trace" / \
                self.cell["name"]
            import shutil
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            tracer = jax.profiler.trace(str(self.trace_dir),
                                        profiler_options=opts)
        try:
            with tracer:
                with self.span("cb.window"):
                    t0 = time.perf_counter()
                    yield
                    t1 = time.perf_counter()
        finally:
            jax.monitoring.unregister_event_duration_listener(on_event)
            gc.callbacks.remove(on_gc)
            gc.unfreeze()
        self._window = (t0, t1)
        self.counters["compiles_in_window"] = compiles[0]
        spans = ", ".join(f"{k} {1e3 * v:.1f}"
                          for k, v in self.longest.items()
                          if k != "cb.window")
        self.log(f"chipbench: window {t1 - t0:.3f} s, {compiles[0]} "
                 f"programs compiled inside it; longest spans (ms): {spans}; "
                 f"{len(pauses)} collections, longest "
                 f"{1e3 * max(pauses, default=0.0):.1f} ms")

    @property
    def window_s(self) -> float:
        return self._window[1] - self._window[0]

    def read_memory_peak(self):
        peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
                 for d in self.devices if d.memory_stats()]
        self.memory_peak_bytes = int(max(peaks, default=0))

    def check(self, name: str, value, limit) -> None:
        """Record one compared number beside its limit: ``value <=
        limit`` passes, and a value of None (no reading) fails."""
        if value is not None:
            value = float(value) if isinstance(value, (float, np.floating)) \
                else int(value)
        self.checks[name] = (value, limit)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            v is not None and v <= lim for v, lim in self.checks.values())


def bingo_config(cfg: dict, num_vertices=None):
    from repro.core.dyngraph import BingoConfig
    return BingoConfig(num_vertices=num_vertices or 1 << cfg["scale"],
                       capacity=cfg["capacity"], bias_bits=cfg["bias_bits"],
                       fp_bias=cfg["fp_bias"])


def build_state(run: Run, graph: gen.Graph, mesh=None):
    """The program's state for ``graph``'s live edges, built on the chip
    (across ``mesh`` when given); ``state_build_s`` is its host time,
    ending when the state is ready."""
    import jax
    from repro.core.dyngraph import from_edges
    from repro.serve.dynwalk import sharded_from_edges
    bcfg = bingo_config(run.config)
    # a fixed edge count per configuration, whatever the seed, so that
    # the build compiles once: the Kronecker generator's edge budget,
    # padded with edges from the out-of-range vertex V, which the build
    # drops
    cap = run.config["edge_factor"] << run.config["scale"]
    live = np.flatnonzero(graph.live)
    edges = [np.full(cap, fill, np.int32) for fill in
             (graph.num_vertices, 0, 1)]
    for e, x in zip(edges, (graph.src, graph.dst, graph.w)):
        e[:len(live)] = x[live]
    t = time.perf_counter()
    with run.span("cb.state_build"):
        if mesh is None:
            state = jax.jit(lambda s, d, w: from_edges(bcfg, s, d, w))(*edges)
        else:
            state = sharded_from_edges(bcfg, *edges, mesh)
        jax.block_until_ready(state)
    run.counters["state_build_s"] = time.perf_counter() - t
    return bcfg, state


def walk_params(mix: dict):
    from repro.core.walks import WalkParams
    return WalkParams(mix["kind"], mix["length"],
                      stop_prob=mix["stop_prob"])


def make_mesh(chips: int):
    """A 1-D mesh over the cell's chips, or None on one chip."""
    if chips == 1:
        return None
    import jax
    return jax.make_mesh((chips,), ("v",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def percentile(xs, q) -> float:
    """The ``q``-th percentile (nearest rank) of ``xs``."""
    xs = np.sort(np.asarray(xs, np.float64))
    if not len(xs):
        raise CheckFailed("no samples for a percentile")
    return float(xs[min(len(xs) - 1, int(np.ceil(q / 100 * len(xs))) - 1)])
